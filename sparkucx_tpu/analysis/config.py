"""Analyzer configuration: allowlist, required surface, and pass tables.

This is the ONE place reviewed exceptions live.  Every entry is
``(file_suffix, pass_name, message_substring)`` and carries a justification
comment above it; an entry without a justification does not get merged.  The
substring pins a single construct — prefer quoting the attribute/function
name from the finding message over blanket file-wide entries.
"""

from __future__ import annotations

#: Reviewed exceptions, grouped by pass.
#:
#: private-access (migrated verbatim from scripts/lint_private_access.py):
#: - core/block.py: ``np.memmap`` exposes no public way to close its mapping —
#:   ``mm._mmap.close()`` is the canonical numpy idiom for releasing the fd
#:   eagerly (numpy/numpy#13510); guarded by try/except for numpy internals
#:   moving.
#: - daemon.py / peer.py ``._sendmsg_all``: the partial-send/IOV_MAX-safe
#:   vectored send loop lives as a ``BlockServer`` staticmethod; the store
#:   daemon's serve path and peer.py's own ``_ServerGroup`` lane senders
#:   (same file, but the pass keys on the attribute) reuse it so every wire
#:   writer handles short ``sendmsg`` returns identically.  It is a pure
#:   function of (socket, parts) — no BlockServer state — kept underscored
#:   because the iovec windowing is an implementation detail of the wire,
#:   not transport API.  Reviewed with the striped-wire PR.
#: - service/tenants.py ``._gate``: ``Tenant`` is a same-file data holder of
#:   its ``TenantRegistry`` — the registry lazily creates the per-tenant
#:   CreditGate under its own lock; exposing the slot publicly would invite
#:   unlocked construction.  Reviewed with the multi-tenant service PR.
#:
#: host-sync:
#: - "drain stage": the drain lane IS the pipeline's sanctioned host-sync
#:   point.  Submit issues ``copy_to_host_async`` / device work and returns;
#:   drain runs on the one-worker drain executor and *observes* completion
#:   (``np.asarray`` / ``block_until_ready``) without stalling the submit
#:   lane — that overlap is the whole point of RoundPipeline.  Blocking in a
#:   SUBMIT stage is the real bug this pass exists to catch, and submit-stage
#:   findings are never allowlisted wholesale.
#: - spmd.py / tpu.py ``_submit``: ``np.asarray(payload)`` sits on the
#:   host-payload branch (the ``isinstance(payload, jax.Array)`` arm above it
#:   keeps the piece on its device instead); asarray over an ndarray is a
#:   free view that strips the spill tier's ``np.memmap`` subclass, not a
#:   device sync.  tpu.py's mixed host/device round no longer pulls a
#:   device-sealed payload to the host: no ``np.asarray`` of a ``jax.Array``
#:   is left in either submit lane.
#:   (The retired per-variant engines' ``_assemble``/``_submit_quota``
#:   entries were pruned with PR 13 — the unified plan executor replaced
#:   them.)
#:
#:   (tpu.py ``_recover_and_rerun``'s entry was pruned with PR 49: the
#:   degraded re-run is a plan ``execute_plan`` interprets, its submit closure
#:   blocks on nothing and its waits — ``np.asarray`` of the size matrix and
#:   of the received prefixes — sit in its drain closure, the lane the
#:   "drain stage" note above blesses.  Nothing is left to excuse.)
#:
#: - testing/faults.py ``kill_executor``: the chaos harness's whole job is to
#:   kill an executor the way SIGKILL would — yanking the live connection
#:   cache (``._conns``/``._zombies``) out from under the transport is the
#:   fault being injected, not an API to encourage.  Test-only module (no
#:   production import path reaches it with nothing armed); reviewed with the
#:   robustness PR.  ``._chaos_killed`` is the harness's own idempotency tag
#:   stamped onto the victim (second kill = no-op) — chaos bookkeeping, not
#:   transport state, so it stays the harness's private mark.
#: - transport/tpu.py ``._single_device_array_to_np_array_did_copy``: the one
#:   place the JAX runtime says whether ``np.asarray`` of an array of a device
#:   copies (a chip) or hands out a view (the CPU backend) — the observable
#:   the received shards' landing is chosen from, asked once a cluster with a
#:   one-word array (``_d2h_copies``); no public API says it.  Guarded: a
#:   runtime without the method keeps the landing of before.  Reviewed PR 43.
#: - native/__init__.py ``_multiarray_umath`` / ``._ARRAY_API``: NumPy's data
#:   allocator hook (NEP 49, ``PyDataMem_SetHandler``) is C API only; the
#:   table NumPy publishes for extension modules is the way in from ctypes
#:   (``_numpy_set_handler``), its indices are the ABI.  Guarded: a NumPy
#:   without it, or one that does not take the handler, means no
#:   ``LandingPool`` and the allocation of before.  Reviewed PR 43.
#:
#: cache-hygiene:
#: - hbm_store.py ``out_rows``: the scatter output shape IS the staging
#:   geometry — ``out_rows`` comes from ``staging_capacity_per_executor``
#:   (fixed per store), not from data, so distinct values are bounded by
#:   distinct configs.  Bucketing it would over-allocate the HBM staging
#:   array itself rather than a transient pad.
ALLOWLIST = {
    ("testing/faults.py", "private-access", "._conns"),
    ("testing/faults.py", "private-access", "._zombies"),
    ("testing/faults.py", "private-access", "._chaos_killed"),
    ("service/tenants.py", "private-access", "._gate"),
    ("core/block.py", "private-access", "._mmap"),
    ("shuffle/daemon.py", "private-access", "._sendmsg_all"),
    ("transport/peer.py", "private-access", "._sendmsg_all"),
    ("transport/tpu.py", "private-access", "._single_device_array_to_np_array_did_copy"),
    ("native/__init__.py", "private-access", "_multiarray_umath"),
    ("native/__init__.py", "private-access", "._ARRAY_API"),
    ("store/hbm_store.py", "cache-hygiene", "'out_rows'"),
}

#: Public-surface contract: these classes must keep these methods.  Transports
#: and writers are wired to them by name across layers, and
#: the device-staging path (ISSUE 2) made several of them load-bearing surface
#: — a rename here fails the analyzer before it fails at runtime in another
#: layer.  (Migrated from scripts/lint_private_access.py.)
REQUIRED_SURFACE = {
    "store/hbm_store.py": {
        "HbmBlockStore": [
            "seal", "map_writer", "read_block", "block_staging_view",
            "region_bytes", "num_rounds", "host_staging_allocated",
        ],
    },
    "store/writer.py": {
        "MapWriter": [
            "write_partition", "write_partition_device", "write_partitions_device", "commit",
        ],
    },
    "shuffle/writer.py": {
        "DeviceMapWriter": ["write_partition", "write_partitions", "commit"],
        "TpuShuffleMapOutputWriter": [
            "get_partition_writer", "write_partition_device", "write_partitions_device",
            "commit_all_partitions",
        ],
    },
}

# ----------------------------------------------------------------------
# use-after-donate tables

#: Builders whose returned callable donates these positional args.  Donation
#: may be conditional at runtime (build_exchange only donates when
#: send_rows == recv_rows) — the pass treats may-donate as must-not-reuse,
#: which is exactly the contract callers must code to.
DONATING_BUILDERS = {
    "build_exchange": (0,),
    "build_hierarchical_exchange": (0,),
    "build_block_scatter": (4,),  # fn(starts, counts, outs, packed, dst): dst
    "build_ici_exchange": (0,),  # scheduled-ring exchange: same donation rule
    # fused send side fn(starts, counts, outs, packed, staging, sizes): staging
    "build_fused_ici_exchange": (4,),
    "build_quantized_exchange": (0,),  # tier-b twin of build_ici_exchange
    "build_quantized_fused_exchange": (4,),  # tier-b twin: staging donated
    # fused combine fn(data, sizes, accv, accc): the running accumulator is
    # consumed and re-emitted in place across quota sub-rounds
    "build_combine_exchange": (2, 3),
    "_exchange_fn": (0,),  # TpuShuffleCluster cache front-end for build_exchange
}

#: Builders returning ``(fn, ...)`` tuples where element 0 is the donating
#: callable (same positions convention).
TUPLE_DONATING_BUILDERS = {
    "_scatter_fn": (2,),  # HbmBlockStore cache front-end for build_block_scatter: fn(plan, src, dst)
}

# ----------------------------------------------------------------------
# host-sync tables

#: Root functions whose whole (module-local) call graph must stay free of
#: blocking host syncs, beyond RoundPipeline stages discovered per-module.
HOST_SYNC_ROOTS = ("_run_exchange",)

# ----------------------------------------------------------------------
# cache-hygiene tables

#: Attribute-name fragments that identify a compile cache.
CACHE_NAME_MARKERS = ("cache", "_fns")

#: Callee names that count as jit-compile builders (a cache keyed on raw
#: shapes in front of one of these is a recompile bomb).
BUILDER_PREFIXES = ("build_",)
BUILDER_NAMES = ("jit",)

#: Callee / method names that sanctify a shape value as bucketed.
#: quota_slot_rows / plan_exchange (ops/skew.py) pow2-round the quota-capped
#: slot — a plan's slot_rows is a bucket_send_rows fixed point, so shape
#: params flowing through the skew planner are bucketed by construction.
BUCKETING_MARKERS = (
    "bucket_send_rows",
    "round_up_to_next_power_of_two",
    "bit_length",
    "quota_slot_rows",
    "plan_exchange",
    "schedule_chunks",  # pow2 chunk-count clamp (ops/ici_exchange.py)
)

# ----------------------------------------------------------------------
# lock-order tables

#: Cross-object receiver resolution for the lock-order graph: a call through
#: ``self.<attr>.method(...)`` is resolved to a class when ``<attr>`` appears
#: here, so acquisitions inside that class's method become edges from every
#: lock held at the call site.  This is the wiring that actually exists in
#: the package (store/transport/service composition) — an attr missing here
#: just means the call contributes no edges, never a false cycle.
LOCK_ATTR_CLASSES = {
    "store": "HbmBlockStore",
    "_store": "HbmBlockStore",
    "tenants": "TenantRegistry",
    "eviction": "EvictionManager",
    "_eviction": "EvictionManager",
    "_credits": "CreditGate",
    "_gate": "CreditGate",
    "gate": "CreditGate",
    "_reactor": "Reactor",
    "server": "BlockServer",
    "membership": "ClusterMembership",
    # obs plane (PR 14): the registry lock is a leaf by design (providers run
    # OUTSIDE it — obs/metrics.py snapshot()); the recorder and tracer locks
    # guard only their own ring/bundle lists.  Wiring them here lets the
    # lock-order pass prove those claims instead of assuming them.
    "metrics": "MetricsRegistry",
    "_metrics": "MetricsRegistry",
    "recorder": "FlightRecorder",
    "tracer": "Tracer",
    # popularity-aware serving tier (PR 19): both locks are leaves by design
    # — the tracker computes EWMAs and the serve cache mutates its LRU map
    # with no calls out while held.  Wiring them here lets the lock-order
    # pass prove that instead of assuming it.
    "popularity": "BlockPopularity",
    "_popularity": "BlockPopularity",
    "serve_cache": "ServeCache",
    "_serve_cache": "ServeCache",
}

#: Locks that exist to SERIALIZE a blocking wire write and are therefore
#: exempt from the held-across-blocking-call check, keyed ``Class.lockname``
#: (``*`` wildcards the class).  Justifications:
#: - ``*.send_lock``: the per-connection frame-write serializer shared by a
#:   lane's serve thread and its _ServerGroup sender — control acks must
#:   interleave with chunk frames at frame granularity, so holding it across
#:   ``sendall``/``sendmsg`` IS the contract (transport/peer.py).
#: - ``_PeerConnection.lock``: the client-side twin — one frame on the wire
#:   at a time per connection; sendall under it is the serializer working.
#: - ``DaemonClient._lock``: the JVM-shim client is a synchronous
#:   request/response RPC over one socket — the lock holds the socket for
#:   the full send+recv round trip BY CONTRACT (two interleaved calls would
#:   cross-read each other's replies).  Blocking under it is the protocol.
LOCK_BLOCKING_EXEMPT = {
    "*.send_lock",
    "_PeerConnection.lock",
    "DaemonClient._lock",
}

# ----------------------------------------------------------------------
# reactor-discipline tables

#: Reactor registration methods and the lane the callback runs on.
#: ``add_listener(sock, on_accept)`` callbacks run ON the selector loop
#: thread — any block there stalls every connection the process serves.
#: ``add_connection(conn, serve_once, on_close=...)`` callbacks run on the
#: bounded worker pool — blocking frame reads are sanctioned there (the
#: reactor's documented design), but joins, untimed waits, and unbounded
#: queue puts can deadlock the pool against itself.
REACTOR_LOOP_REGISTRARS = ("add_listener",)
REACTOR_WORKER_REGISTRARS = ("add_connection",)

# ----------------------------------------------------------------------
# resource-balance tables

#: Paired acquire/release method names: a call to the key must be balanced
#: by a call to the value on every exception path (sibling try/finally or
#: except-reraise), unless the acquiring function documents an ownership
#: transfer ("released by ..." / "caller releases" / "ownership transfers"
#: in its docstring) or the call line carries a ``#: balanced by <name>``
#: annotation naming the releasing function.
RESOURCE_PAIRS = {
    "acquire": "release",        # CreditGate wire credits
    "try_acquire": "release",
    "charge": "release",         # TenantRegistry HBM quota bytes
    "_charge_tenant": "_release_tenant",  # store-side tenant admission
    "checkout": "release",       # pooled-buffer handles
}

#: Receivers whose final name contains one of these fragments are
#: synchronization primitives, not refundable resources — ``lock.acquire()``
#: is the lock-discipline passes' business, not this one's.
RESOURCE_RECEIVER_SKIP = ("lock", "cond", "sem")

# ----------------------------------------------------------------------
# wire-schema tables

#: Module defining the wire: the AmId enum and every frame/header struct.
WIRE_DEFS_MODULE = "core/definitions.py"
#: Doc the schema is cross-checked against (docs/ basename).
WIRE_DOC = "SHIM_PROTOCOL.md"

# ----------------------------------------------------------------------
# conf-knob registry tables

#: Module defining TpuShuffleConf + from_spark_conf, and the doc that must
#: carry a row per knob.
CONF_MODULE = "config.py"
CONF_DOC = "DEPLOYMENT.md"
CONF_KEY_PREFIX = "spark.shuffle.tpu"

#: Knobs handled outside the from_spark_conf (name, attr, conv) table —
#: parsed with bespoke code — mapped to the conf field they set.
SPECIAL_CONF_KNOBS = {
    "memory.preAllocateBuffers": "prealloc_buffers",
    "memory.minBufferSize": "min_buffer_size",
    "memory.minAllocationSize": "min_allocation_size",
    "listener.sockaddr": "listener_address",
}

#: The byte-identical off-path pin: every feature added since the golden
#: wire captures must DEFAULT to the value that leaves frames, store
#: behavior, and exchange results byte-for-byte identical to the
#: pre-feature build.  The conf-registry pass compares these against the
#: dataclass field defaults in config.py — flipping one here requires
#: re-capturing the golden frames, which is exactly the review this table
#: forces.
OFF_PATH_DEFAULTS = {
    "wire_streams": 1,
    "wire_checksum": False,
    "wire_compress_codec": "off",
    "quantize_mode": "off",
    "replication_factor": 0,
    "elastic": False,
    "membership_suspect_after_ms": 0,
    "replication_max_backlog_bytes": 0,
    "tenants_enabled": False,
    "server_workers": 0,
    "exchange_impl": "stock",
    "device_staging": False,
    "keep_device_recv": False,
    "use_shm_staging": False,
    "slot_quota_rows": 0,
    "planner_mode": "static",
    "planner_optimize": False,
    # adaptive-only thresholds: inert while planner_mode == "static" (the
    # off-path planner never reads them), so their defaults ARE the pinned
    # off-path values — all four planner.* knobs stay in one reviewed table
    "planner_target_padding": 0.5,
    "planner_min_quota_rows": 256,
    "host_recv_mode": "array",
    "sanitize": False,
    "fetch_hedge_ms": 0,
    "fetch_hedge_max_ms": 0,
    "breaker_failure_threshold": 0,
    "breaker_cooldown_ms": 1000,
    "store_soft_watermark": 0,
    "store_hard_watermark": 0,
    "server_accept_backlog": 0,
    "obs_trace_context": False,
    "obs_metrics_port": 0,
    "obs_ring_capacity": 8192,
    "obs_postmortem_dir": "",
    "exchange_fused_combine": False,
    # popularity-aware serving tier: threshold 0 = no tracker, no HotSetPull
    # frames, no widened replica pushes; serve_hot_replicas is hot-path-only
    # (inert while the threshold is 0) and serve_cache_bytes 0 = no decoded
    # cache, so serve behavior stays byte-identical.  compress_cache_bytes is
    # only consulted while compress.codec is on (itself pinned "off") — its
    # default preserves the historical 128 MiB pool cap.
    "serve_hot_threshold_fetches_per_sec": 0.0,
    "serve_hot_replicas": 4,
    "serve_cache_bytes": 0,
    "compress_cache_bytes": 128 << 20,
    # serve.holdersTtlMs is only consulted while serve.hotThresholdFetchesPerSec
    # is on (itself pinned 0.0 above); its default preserves the historical
    # hard-coded 250 ms advertisement TTL byte-for-byte.  The query-runner
    # knobs gate the lineage cache (sparkucx_tpu/query): off = every exchange
    # executes and is unregistered after its query, so wire/store behavior is
    # byte-identical to a cache-less runner; cacheMaxBytes is inert while the
    # cache is off.
    "serve_holders_ttl_ms": 250,
    "query_cache_enabled": False,
    "query_cache_max_bytes": 0,
}

# ----------------------------------------------------------------------
# lockstep-taint tables

#: The plan dataclass and the module defining it.  The taint pass parses the
#: dataclass fields and cross-checks the declared COLLECTIVE/SERVE_PLANE
#: split below against them, so the registry cannot drift from the code.
PLAN_MODULE = "ops/skew.py"
PLAN_CLASS = "ExchangePlan"

#: ExchangePlan fields that shape the COLLECTIVE schedule: in the SPMD
#: deployment every process compiles and submits collectives from these, so
#: they must be pure functions of conf + all-gathered geometry — a per-host
#: telemetry read steering one of them is a divergent compiled program and a
#: cluster-wide hang.  ``quantize_mode``/``quantize_block`` are here (not
#: serve-plane) because they select a DIFFERENT compiled collective
#: (``build_quantized_exchange``) — the lossy encode runs inside the kernel.
COLLECTIVE_FIELDS = (
    "slot_rows",
    "chunks_per_round",
    "single_shot",
    "round_order",
    "lowering",
    "quantize_mode",
    "quantize_block",
    "combine",
)

#: Fields local telemetry MAY steer: they shape how one host serves or
#: overlaps, never what any collective computes.  ``pipeline_depth`` is here
#: deliberately (ops/planner.py:36): depth changes WHEN stages overlap,
#: never the order collectives are submitted in, so it may vary per host.
SERVE_PLANE_FIELDS = (
    "pipeline_depth",
    "streams",
    "codec",
    "hedge_ms",
)

#: Modules the taint dataflow runs over (the plan-producing and
#: plan-consuming layers).  Fixture runs that contain none of these analyze
#: every module they were given instead.
TAINT_MODULES = (
    "ops/planner.py",
    "ops/skew.py",
    "transport/spmd.py",
    "transport/executor.py",
)

#: Callee names whose results are local telemetry (may differ per host):
#: metric registry snapshots, PlanSignals construction, health/wire/breaker
#: reads, and clocks.  Matched on the bare callee name, so both
#: ``registry.snapshot()`` and ``self.membership.snapshot()`` taint.
TAINT_SOURCE_CALLS = (
    "PlanSignals",
    "from_registry",
    "snapshot",
    "health_snapshot",
    "wire_lane_stats",
    "breaker_state",
    "breaker_allows",
    "eviction_stats",
    "monotonic",
    "monotonic_ns",
    "perf_counter",
    "perf_counter_ns",
    "process_time",
    "time",
)

#: Attribute reads that (re-)introduce taint wherever they appear:
#: ``ctx.signals`` is THE sanctioned telemetry channel into a planner, and
#: reading it back out is where serve-plane-only discipline must hold.
TAINT_SOURCE_ATTRS = ("signals",)

#: Constructor/rewrite callees whose keywords are plan/context fields — the
#: taint sinks.  A tainted value bound to a COLLECTIVE_FIELDS keyword (or a
#: collective keyword written under a telemetry-tainted branch) is a
#: finding; taint bound to a serve-plane keyword (or the ``signals``
#: channel) is absorbed there by design.
PLAN_CONSTRUCTORS = ("ExchangePlan", "PlanContext", "replace")

#: Functions whose branch conditions run BEFORE collective submission in the
#: SPMD transport (matched by name in the analyzed modules): a tainted
#: condition there can diverge which collective each process enters.
#: Branches whose body ends in ``raise`` are exempt — failing fast before a
#: collective is the sanctioned response to local bad news (membership), a
#: divergent schedule is not.
SPMD_PRECOLLECTIVE_FUNCS = ("run_exchange",)

# ----------------------------------------------------------------------
# span-discipline / metrics-naming tables

#: Doc carrying the metric family registry and the trace-point table.
TRACE_DOC = "OBSERVABILITY.md"

#: The tracer implementation itself (opens/closes spans by definition) —
#: excluded from the span-discipline walk.
TRACE_IMPL_MODULES = ("utils/trace.py",)

#: The metrics module and the exposition prefix every family rides under
#: (``<prefix>_<family>_<name>``); the pass pins the PREFIX constant and
#: checks family/name literals against the scheme and the TRACE_DOC table.
OBS_METRICS_MODULE = "obs/metrics.py"
METRIC_PREFIX = "sparkucx_tpu"

# ----------------------------------------------------------------------
# error-taxonomy tables

#: Module defining the TransportError hierarchy, and the doc whose "Failure
#: semantics" section must name every classified type.
ERROR_MODULE = "core/operation.py"
ERROR_BASE = "TransportError"
ERROR_DOC = "API.md"

#: THE machine-checked retryable/fail-fast registry (API.md "Failure
#: semantics" points here).  Every TransportError subclass in the package
#: must appear exactly once; the pass fails on an unclassified subclass AND
#: on a stale entry naming a deleted class.
#: - retryable: transient per-block conditions — another attempt (or a
#:   replica) can succeed.
#: - retryable-backoff: the third arm — the server shed load; retry after a
#:   typed backoff hint, never instantly.
#: - fail-fast: deterministic rejections and no-recovery losses — every
#:   replica gives the same answer, so a retry only burns the budget and
#:   hides the real error.
ERROR_TAXONOMY = {
    "BlockNotFoundError": "retryable",
    "BlockCorruptError": "retryable",
    "ResourceExhaustedError": "retryable-backoff",
    "UnknownTenantError": "fail-fast",
    "TenantQuotaExceededError": "fail-fast",
    "ExecutorLostError": "fail-fast",
    "SplitBlockError": "fail-fast",
}

#: Reader retry/failover functions (matched by name): statically barred from
#: catching a fail-fast type, and a base-class ``except TransportError``
#: there must carry an isinstance re-raise guard covering EVERY fail-fast
#: class — anything less silently retries a deterministic rejection.
RETRY_PATH_FUNCS = ("_retry_fetch",)

# ----------------------------------------------------------------------
# tier-vocabulary tables

#: THE plan/conf tier vocabularies, defined once.  The pass cross-checks
#: every parse/validate/literal-comparison site against these: a string
#: compared to, assigned to, or passed as a keyword named after one of these
#: fields must be in its vocabulary — tier typos become findings instead of
#: silently-dead dispatch arms.  ``lowering`` carries the union of the plan
#: tier (stock|pallas|auto) and the kernel lowering it resolves to
#: (auto|dma|xla|interpret) because both ride the same field name.
#: The bare word ``impl`` is deliberately NOT pinned: every op module uses
#: it for its own local dispatch tiers (ragged|dense|single|local|...), so
#: a global vocabulary for it would be fiction — the plan-level names
#: (``lowering``, ``exchange_impl``) are the pinned ones.
TIER_VOCAB = {
    "lowering": ("stock", "pallas", "auto", "dma", "xla", "interpret"),
    "exchange_impl": ("stock", "pallas", "auto"),
    "combine": ("off", "auto", "dense", "sorted"),
    "codec": ("off", "dict", "rle", "delta"),
    "wire_compress_codec": ("off", "dict", "rle", "delta"),
    "quantize_mode": ("off", "int8", "blockfloat"),
    "planner_mode": ("static", "adaptive"),
    "host_recv_mode": ("array", "memmap", "device"),
}

#: Conf-backed vocabulary keys whose every value must have a DEPLOYMENT.md
#: mention (operators pick these by name; an undocumented tier is
#: unreachable in practice and rots).
TIER_DOC_KEYS = (
    "exchange_impl",
    "wire_compress_codec",
    "quantize_mode",
    "planner_mode",
    "host_recv_mode",
)

# ----------------------------------------------------------------------
# tests-tree run

#: Reviewed exceptions for analyzer runs over the tests/ tree (the CI step
#: runs the private-access pass there so tests cannot quietly couple to
#: internals either).  Same entry shape and review bar as ALLOWLIST.
#:
#: Policy: private ATTRIBUTE access is sanctioned wholesale — white-box
#: tests poke instance internals (store ``._state``, wire ``._inflight``,
#: fault-injection on ``._conns``) by design, and per-attribute entries
#: would just transcribe the test suite.  Private IMPORTS stay individually
#: reviewed: copying an internal symbol across a module boundary couples
#: the test to a name the package is free to rename, so each one must
#: justify why no public seam exists.
#: - ``_StripeRx`` (transport/peer.py): the stripe reassembly unit tests
#:   drive the receiver state machine directly — no public entry point
#:   exercises mid-stripe states deterministically.
#: - ``_read_frame`` (shuffle/daemon.py): the daemon protocol tests speak
#:   raw frames on a socket; the helper IS the framing contract under test.
#: - ``_frame`` (shuffle/daemon.py): its other half — the receive-in-place
#:   tests send a frame's bytes in pieces (a body that stalls or ends
#:   half-way) and compare what ``DaemonClient`` put on the wire against the
#:   joined frame; ``scripts/gen_shim_fixtures.py`` makes the JVM fixtures
#:   from the same helper.
#: - ``_estimate`` (shuffle/external.py): spill-size estimator unit tests;
#:   the public path only exposes it through end-to-end sort memory use.
#: - ``_ici_order`` (parallel/mesh.py): ring-order derivation pinned
#:   against the documented executor ordering.
#: - ``_free_port`` (tests' own test_spmd.py helper): test-to-test import,
#:   no package coupling at all.
TESTS_ALLOWLIST = {
    ("", "private-access", "private attribute access"),
    ("", "private-access", "private import: _StripeRx"),
    ("", "private-access", "private import: _read_frame"),
    ("", "private-access", "private import: _frame"),
    ("", "private-access", "private import: _estimate"),
    ("", "private-access", "private import: _ici_order"),
    ("", "private-access", "private import: _free_port"),
}
