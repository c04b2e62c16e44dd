#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, records from ``--seed``, the manager or daemon, one verified
full-size warm-up job), then whole jobs for ``--seconds`` seconds, then one
JSON object as the last line of stdout: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  ``--trace 0`` gives the
cell's end-to-end metrics; ``--trace 1`` its per-layer metrics, the device
numbers from a ``jax.profiler`` trace of two whole jobs in the middle of the
window, of which the one that ran shorter is reduced (``jobs.TRACED_JOBS``).

Without a TPU of a kind in the peaks table, or with fewer chips than the cell
asks for, it exits 4 and prints nothing.  ``--rehearse`` is the only way it
runs without a chip: it pins ``JAX_PLATFORMS=cpu``, gives the CPU backend the
cell's number of devices, cuts the scale to the configuration's ``rehearse``
sizes and prints ``platform: cpu``; its numbers are counts and checks, never
rates.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
#: exit code for "no chip / cannot start" (2 and 3 are the chip tool's)
NO_CHIP = 4
#: the profiler's files, inside the checkout, one directory a run, removed after
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")


def say(label: str, payload: dict) -> None:
    print(f"{label}: {json.dumps(payload)}", flush=True)


class Harness:
    """The process that holds the chip: answers the job loop's control events,
    traces, and reads the counters of the manager the traffic driver stood up."""

    def __init__(self, manager, trace: bool) -> None:
        from benchmark.counters import CompileCounter

        self.cluster = manager.cluster
        self.trace = trace
        self.compiles = CompileCounter()
        self.rounds = []
        self.window_compiles = None
        self.t_window_start = None
        self.stats_before = self.stats_after = None
        self.program_events = []
        self.program_dropped = 0
        self.xplane = None
        self.trace_dir = None

    def _exchange_stats(self) -> dict:
        submit = self.cluster.stats.summary("exchange.pipeline.submit")
        drain = self.cluster.stats.summary("exchange.pipeline.drain")
        return {
            "submit_ops": submit.ops, "submit_p50_ns": submit.p50_ns,
            "drain_ops": drain.ops, "drain_p50_ns": drain.p50_ns,
            "used_rows": drain.used_rows, "padded_rows": drain.padded_rows,
        }

    def control(self, event: str, **fields) -> dict:
        from sparkucx_tpu.utils.trace import TRACER

        if event == "window_start":
            self.t_window_start = time.perf_counter()
            self._compile_mark = self.compiles.snapshot()
            self.stats_before = self._exchange_stats()
            if self.trace:
                # the program's ring holds 8,192 events and drops the oldest;
                # a job of some thousand blocks needs more
                TRACER.set_capacity(1 << 20)
                TRACER.enable()
                TRACER.clear()
        elif event == "window_end":
            self.window_compiles = self.compiles.since(self._compile_mark)
            self.stats_after = self._exchange_stats()
            if self.trace:
                self.program_events = TRACER.events
                self.program_dropped = TRACER.dropped
                TRACER.disable()
        elif event == "job_done":
            self.rounds.append(len(self.cluster.meta(fields["shuffle_id"]).recv_sizes))
        elif event == "trace_start":
            self._trace_start()
        elif event == "trace_stop":
            self._trace_stop()
        else:
            raise ValueError(f"unknown control event {event!r}")
        return {}

    def _trace_start(self) -> None:
        import jax
        from benchmark.device_trace import SYNC_NAME, SYNC_STAT

        os.makedirs(TRACE_ROOT, exist_ok=True)
        self.trace_dir = tempfile.mkdtemp(dir=TRACE_ROOT)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python calls by the thousand: not wanted
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(SYNC_NAME, **{SYNC_STAT: str(time.perf_counter_ns())}):
            pass

    def _trace_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        self.xplane = found[0] if found else None

    def lowerings_ok(self, platform: str) -> bool:
        """Only the platform's own exchange lowering may have executed."""
        ran = set(self.cluster.executed_lowerings()["exchange"])
        n = self.cluster.num_executors
        want = ("local" if n == 1 else "ragged") if platform == "tpu" else "dense"
        say("lowerings", {"exchange": sorted(ran), "expected": want})
        return ran == {want}


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip, as the backend reports it."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU form for tests: tiny scale, platform cpu, no rate means anything")
    args = ap.parse_args(argv)

    from benchmark.cells import load_cell, load_module

    cell = load_cell(args.workload, rehearse=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell.chips}"
    # before this process imports JAX: a driver's client processes set up beside it
    traffic = load_module("traffic", cell.traffic["driver"]).Traffic(cell, args)
    try:
        code, result = run_cell(cell, args, traffic)
    finally:
        traffic.close()
    if result is not None:
        # the last line, once everything the run started has stopped
        print(json.dumps(result), flush=True)
    return code


def run_cell(cell, args, traffic):
    """Set-up, window and reduction; returns the exit code and, where the run
    reached its end, the result."""
    from benchmark.cells import read_metrics

    from benchmark.peaks import PEAKS

    try:
        import jax

        from sparkucx_tpu import native
        from sparkucx_tpu.config import TpuShuffleConf

        devices = jax.devices()
    except (ImportError, RuntimeError) as e:
        print(f"benchmark: cannot start: {type(e).__name__}: {e}", file=sys.stderr)
        return NO_CHIP, None
    platform, kind = devices[0].platform, devices[0].device_kind
    on_chip = platform == "tpu" and kind in PEAKS
    if not (on_chip or args.rehearse) or len(devices) < cell.chips:
        print(
            f"benchmark: {cell.name} needs {cell.chips} TPU chip(s) of a kind in {sorted(PEAKS)}; "
            f"JAX offers {len(devices)} x {platform} ({kind!r})", file=sys.stderr,
        )
        return NO_CHIP, None
    devices = devices[: cell.chips]

    # the persistent compile cache: where the environment says, else at a
    # fixed path inside the checkout (the path is part of the cache's key)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if native.build_error() is not None:
        print(f"benchmark: native arena failed to build: {native.build_error()}", file=sys.stderr)
        return 1, None
    parts = {"imports": time.perf_counter() - T_PROCESS}
    say("benchmark", {"cell": cell.name, "platform": platform, "kind": kind, "chips": cell.chips,
                      "seed": args.seed, "trace": args.trace, "rehearse": args.rehearse})

    conf = TpuShuffleConf(**cell.config["conf"])
    trace = bool(args.trace)
    harness = Harness(traffic.start(conf, parts), trace)
    try:
        window = traffic.run(harness.control, parts)
    finally:
        lowerings_ok = harness.lowerings_ok(platform)

    setup_s = harness.t_window_start - T_PROCESS
    parts["warmup"] = window.warmup_s
    say("setup", {"setup_s": round(setup_s, 3), **{k: round(v, 3) for k, v in parts.items()}})

    from benchmark.measured import build_run

    run = build_run(cell, window, harness, kind, setup_s)
    jobs = window.jobs
    end_to_end = read_metrics("end_to_end", cell.end_to_end, run)
    say("window", {
        "jobs": len(jobs), "job_bytes": window.job_bytes, "job_blocks": window.job_blocks,
        "job_s": [round(j.seconds, 4) for j in jobs],
        "reduce_task_samples": sum(len(j.read_task_s) for j in jobs),
        "reduce_task_ms": {f"p{q}": run.read_task_ms(q / 100) for q in (50, 90, 95, 99)},
        "end_to_end": end_to_end,
        "warmup_failed_tasks": window.warmup.failed, "fetch_faults": run.fetch_faults,
        "compiles_in_window": harness.window_compiles,
        "compiles_in_run": harness.compiles.since(), "rounds_per_job": run.rounds,
        "exchange_stats": harness.stats_after, "traced_job": window.traced_job,
    })
    correct = window.sound() and lowerings_ok and harness.window_compiles["compiles"] == 0
    device = {
        "platform": platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": memory_peak_bytes(devices),
    }
    result = {"correct": correct, "attempted": sum(j.tasks for j in jobs),
              "failed": sum(j.failed for j in jobs)}
    if not trace:
        result["metrics"] = end_to_end
    else:
        result["metrics"] = read_metrics("layer_metrics", cell.per_layer, run)
        reduction = run.reduction
        device["busy_s"] = reduction.busy_s if reduction else 0.0
        device["window_s"] = reduction.window_s if reduction else 0.0
        if reduction:
            result["breakdown"] = {
                "device_ops": [list(row) for row in reduction.device_ops],
                "idle_gaps": [list(row) for row in reduction.idle_gaps],
            }
        say("trace", {
            "layout": run.trace_layout, "modules_s": reduction.module_s if reduction else None,
            "program_spans": len(run.program_spans), "program_spans_dropped": run.program_dropped,
        })
        if harness.trace_dir:
            shutil.rmtree(harness.trace_dir, ignore_errors=True)
    result["device"] = device
    return 0, result


if __name__ == "__main__":
    sys.exit(main())
