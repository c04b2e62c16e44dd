"""Tests for the typed non-executing record codec (utils/codec.py) — the
data-plane default that replaces pickle on socket-delivered block payloads."""

import pickle
import random
import struct

import numpy as np
import pytest

from sparkucx_tpu.utils.codec import (
    MAX_DEPTH,
    decode_records,
    encode_record,
    encode_records,
)


def _readonly_uint8(data: bytes) -> memoryview:
    arr = np.frombuffer(data, np.uint8).copy()
    arr.flags.writeable = False
    return memoryview(arr)


def _strided(data: bytes) -> memoryview:
    arr = np.zeros(2 * len(data), np.uint8)
    arr[::2] = np.frombuffer(data, np.uint8)
    return memoryview(arr)[::2]


#: what a caller may hand ``decode_records``: the same bytes, each way
PAYLOADS = {
    "bytes": bytes,  # the daemon's client
    "bytearray": bytearray,
    "readonly-uint8-view": _readonly_uint8,  # shuffle/reader.py's, of a pooled fetch buffer
    "signed-char-view": lambda data: memoryview(data).cast("b"),  # an index is a signed int
    "char-view": lambda data: memoryview(data).cast("c"),  # an index is a bytes
    "strided-view": _strided,  # not contiguous: copied once
}


@pytest.fixture(params=list(PAYLOADS))
def as_payload(request):
    return PAYLOADS[request.param]


def reference_decode(payload: bytes, pos: int = 0, depth: int = 0):
    """The decoder of before PR 37, kept as the reference: one recursive call
    a frame, a tag a one-byte slice, every bound a call.  ``bytes`` only."""

    def need(n):
        if pos + n > len(payload):
            raise ValueError(
                f"truncated record frame: need {n} bytes at offset {pos}, have {len(payload) - pos}"
            )

    if depth > MAX_DEPTH:
        raise ValueError(f"record nests deeper than MAX_DEPTH={MAX_DEPTH}")
    need(1)
    tag = payload[pos : pos + 1]
    pos += 1
    if tag in (b"N", b"T", b"F"):
        return {b"N": None, b"T": True, b"F": False}[tag], pos
    if tag in (b"i", b"f"):
        need(8)
        return struct.unpack_from(">q" if tag == b"i" else ">d", payload, pos)[0], pos + 8
    if tag in (b"j", b"s", b"b"):
        need(4)
        (n,) = struct.unpack_from(">I", payload, pos)
        pos += 4
        need(n)
        raw = payload[pos : pos + n]
        pos += n
        if tag == b"j":
            return int.from_bytes(raw, "big", signed=True), pos
        return (str(raw, "utf-8") if tag == b"s" else bytes(raw)), pos
    if tag in (b"t", b"l", b"m"):
        need(4)
        (n,) = struct.unpack_from(">I", payload, pos)
        pos += 4
        items = []
        for _ in range(2 * n if tag == b"m" else n):
            item, pos = reference_decode(payload, pos, depth + 1)
            items.append(item)
            if tag == b"m" and not len(items) % 2:
                try:
                    hash(items[-2])
                except TypeError:
                    raise ValueError(f"unhashable map key of type {type(items[-2]).__name__}") from None
        if tag == b"m":
            return dict(zip(items[::2], items[1::2])), pos
        return (tuple(items) if tag == b"t" else items), pos
    raise ValueError(f"unknown record tag {bytes(tag)!r} at offset {pos - 1}")


def drain(records):
    """What a decoder yielded, and the error that ended it (type and words)."""
    got = []
    try:
        for rec in records:
            got.append(rec)
    except Exception as e:  # noqa: BLE001 — the test compares whatever it was
        return got, type(e), str(e)
    return got, None, None


def reference_records(payload: bytes):
    pos = 0
    while pos < len(payload):
        rec, pos = reference_decode(payload, pos)
        yield rec


def random_value(rng: random.Random, depth: int = 0):
    """One value of the codec's value set, containers nested to depth 4."""
    kind = rng.randrange(11 if depth < 4 else 8)
    if kind == 0:
        return None
    if kind == 1:
        return rng.random() < 0.5
    if kind == 2:
        return rng.randrange(-(2**63), 2**63)
    if kind == 3:
        return rng.choice((-1, 1)) * rng.randrange(2**63, 2**200)
    if kind == 4:
        return rng.choice((0.0, -0.0, float("inf"), rng.uniform(-1e9, 1e9)))
    if kind == 5:
        return "".join(chr(rng.randrange(32, 0x3000)) for _ in range(rng.randrange(8)))
    if kind in (6, 7):
        return rng.randbytes(rng.randrange(64))
    if kind == 8:
        return tuple(random_value(rng, depth + 1) for _ in range(rng.randrange(4)))
    if kind == 9:
        return [random_value(rng, depth + 1) for _ in range(rng.randrange(4))]
    keys = (rng.randrange(100), "k%d" % rng.randrange(9), rng.randbytes(2), (1, rng.randrange(5)))
    return {rng.choice(keys): random_value(rng, depth + 1) for _ in range(rng.randrange(4))}


def assert_same(got, want):
    """Equal, of the same types all the way down — a ``bytes`` leaf is ``bytes``."""
    assert type(got) is type(want), (got, want)
    if isinstance(want, (tuple, list)):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert_same(g, w)
    elif isinstance(want, dict):
        assert len(got) == len(want)
        for (gk, gv), (wk, wv) in zip(got.items(), want.items()):  # in the order written
            assert_same(gk, wk)
            assert_same(gv, wv)
    else:
        assert got == want or (got != got and want != want)  # or NaN both


class TestRoundtrip:
    def test_scalar_shapes(self):
        vals = [
            None, True, False, 0, -1, 2**62, -(2**62), 2**100, -(2**100),
            0.0, -1.5, 3.141592653589793, float("inf"), "", "héllo ∆",
            b"", b"\x00\xff" * 100,
        ]
        for v in vals:
            got = list(decode_records(encode_record(v)))
            assert got == [v] and type(got[0]) is type(v), v

    def test_nan_roundtrip(self):
        (got,) = decode_records(encode_record(float("nan")))
        assert got != got  # NaN

    def test_containers(self):
        vals = [
            (), (1, "a", b"b"), [1, [2, [3]]], {"k": 1, 2: (3, 4)},
            ("key", {"nested": [1.5, None, True]}),
        ]
        for v in vals:
            (got,) = decode_records(encode_record(v))
            assert got == v and type(got) is type(v)

    def test_record_stream_concatenates(self):
        records = [(i, f"v{i}") for i in range(100)] + [None, (0, 0)]
        assert list(decode_records(encode_records(records))) == records

    def test_fuzz_random_kv_records(self, rng):
        for _ in range(20):
            records = [
                (int(rng.integers(-1e9, 1e9)), float(rng.normal()),
                 bytes(rng.integers(0, 256, size=int(rng.integers(0, 50)), dtype=np.uint8)))
                for _ in range(int(rng.integers(0, 40)))
            ]
            assert list(decode_records(encode_records(records))) == records

    def test_numpy_scalars_coerce(self):
        (got,) = decode_records(encode_record((np.int32(7), np.float32(0.5), np.bool_(True))))
        assert got == (7, 0.5, True)
        assert type(got[0]) is int and type(got[1]) is float and type(got[2]) is bool

    def test_empty_payload_yields_nothing(self, as_payload):
        assert list(decode_records(as_payload(b""))) == []

    @pytest.mark.parametrize("seed", range(4))
    def test_random_values_from_every_payload_type(self, seed, as_payload):
        rng = random.Random(seed)
        records = [random_value(rng) for _ in range(60)]
        got = list(decode_records(as_payload(encode_records(records))))
        assert_same(got, records)


class TestAValueOwnsItsBytes:
    """Nothing ``decode_records`` yields refers to the payload: the reader
    hands its fetch buffer back to the pool while a ``groupByKey`` consumer
    still holds every value."""

    RECORDS = [(7, b"abc" * 100), ("key", [b"x" * 50, {b"k": b"v" * 20}], 2**90), (8, b"")]

    def test_overwriting_the_source_changes_no_value(self):
        src = bytearray(encode_records(self.RECORDS))
        got = list(decode_records(src))
        src[:] = b"\xff" * len(src)
        assert_same(got, self.RECORDS)

    def test_a_value_taken_mid_stream_is_its_own_too(self):
        src = bytearray(encode_records(self.RECORDS))
        records = decode_records(src)
        first = next(records)
        cut = len(encode_record(self.RECORDS[0]))
        src[:cut] = b"\xff" * cut  # behind the decoder: the frames it has left
        assert_same(first, self.RECORDS[0])
        assert_same(list(records), self.RECORDS[1:])


class TestZeroCopyByteLikes:
    """The zero-copy ``_encode`` branches: bytes, bytearray, and memoryview
    append straight into the output buffer without an intermediate ``bytes()``
    materialization.  All decode back as bytes."""

    def test_bytearray_roundtrip(self):
        src = bytearray(b"\x00\xff" * 500)
        (got,) = decode_records(encode_record(("k", src)))
        assert got == ("k", bytes(src)) and type(got[1]) is bytes

    def test_memoryview_flat_roundtrip(self):
        src = np.arange(256, dtype=np.uint8).tobytes()
        (got,) = decode_records(encode_record(memoryview(src)))
        assert got == src

    def test_memoryview_shaped_counts_bytes_not_elements(self):
        # len() on a shaped view counts ELEMENTS; the encoder must frame by
        # nbytes or the payload is silently truncated to the first dimension
        arr = np.arange(64, dtype=np.uint32).reshape(8, 8)
        mv = memoryview(arr)
        assert len(mv) != mv.nbytes  # the trap this test pins
        (got,) = decode_records(encode_record(mv))
        assert got == arr.tobytes()

    def test_memoryview_noncontiguous_copies_once_correctly(self):
        arr = np.arange(100, dtype=np.uint8)
        mv = memoryview(arr)[::2]  # strided: NOT contiguous
        assert not mv.contiguous
        (got,) = decode_records(encode_record(mv))
        assert got == arr[::2].tobytes()

    def test_bytes_mutation_after_encode_is_isolated(self):
        # the zero-copy append must COPY out of the source buffer (iadd
        # semantics), not alias it — later mutation can't corrupt the frame
        src = bytearray(b"before-mutation!")
        frame = encode_record(src)
        src[:] = b"AFTER-MUTATION!!"
        (got,) = decode_records(frame)
        assert got == b"before-mutation!"


class TestRejection:
    """Every malformation is a ``ValueError`` with the documented words, from
    every payload type — never the ``IndexError`` or ``struct.error`` of a
    bound that is off by one."""

    def test_unknown_tag(self, as_payload):
        with pytest.raises(ValueError, match="unknown record tag"):
            list(decode_records(as_payload(b"Z")))

    @pytest.mark.parametrize("bad", [b"i\x00\x00", b"s\x00\x00\x00\x05ab", b"f", b"t\x00\x00"])
    def test_truncated_scalar_and_length(self, bad, as_payload):
        with pytest.raises(ValueError, match="truncated"):
            list(decode_records(as_payload(bad)))

    def test_truncated_container_items(self, as_payload):
        # tuple claims 3 items, carries 1
        with pytest.raises(ValueError, match="truncated"):
            list(decode_records(as_payload(b"t\x00\x00\x00\x03N")))

    def test_over_deep_nesting_bounded(self, as_payload):
        payload = b"t\x00\x00\x00\x01" * (MAX_DEPTH + 10) + b"N"
        with pytest.raises(ValueError, match="MAX_DEPTH"):
            list(decode_records(as_payload(payload)))

    def test_nesting_to_the_bound_is_accepted(self, as_payload):
        # the encoder's own bound: a value MAX_DEPTH containers deep, and an
        # empty container one deeper (it has no item past the bound)
        value = ()
        for _ in range(MAX_DEPTH):
            value = (value,)
        assert list(decode_records(as_payload(encode_record(value)))) == [value]
        with pytest.raises(ValueError, match="MAX_DEPTH"):
            encode_record((value,))
        one_deeper = b"t\x00\x00\x00\x01" * (MAX_DEPTH + 1) + b"N"
        with pytest.raises(ValueError, match="MAX_DEPTH"):
            list(decode_records(as_payload(one_deeper)))

    def test_unencodable_type_raises(self):
        with pytest.raises(TypeError, match="safe codec"):
            encode_record(object())

    def test_unhashable_map_key_is_valueerror(self, as_payload):
        # crafted frame: map of 1 entry whose key is an (empty) list — the
        # error contract promises ValueError, never a leaked TypeError
        with pytest.raises(ValueError, match="unhashable"):
            list(decode_records(as_payload(b"m\x00\x00\x00\x01l\x00\x00\x00\x00N")))

    def test_pickle_payload_never_executes(self, tmp_path, as_payload):
        """The canonical attack: a pickle whose deserialization has a side
        effect.  The default codec must raise, not execute."""
        canary = tmp_path / "owned"

        class Evil:
            def __reduce__(self):
                return (open, (str(canary), "w"))

        payload = pickle.dumps(Evil())
        with pytest.raises(ValueError):
            list(decode_records(as_payload(payload)))
        assert not canary.exists(), "decoding socket bytes executed code"

    #: three records that between them carry every tag
    MIXED = [
        (7, b"bytes-value", "str\u00e9 \u2206"),
        [None, True, False, 1.5, -(2**80)],
        {"k": (1, [b"x"]), 2: {}},
    ]

    def test_every_proper_prefix_is_a_prefix_of_the_records_or_truncated(self, as_payload):
        payload = encode_records(self.MIXED)
        ends = [len(encode_records(self.MIXED[: k + 1])) for k in range(len(self.MIXED))]
        for cut in range(len(payload)):
            got, error, words = drain(decode_records(as_payload(payload[:cut])))
            whole = sum(end <= cut for end in ends)
            assert_same(got, self.MIXED[:whole])  # the records that lie before the cut, then ...
            if cut in (0, *ends):
                assert error is None  # ... a shorter stream, where the cut falls between two records
            else:
                assert error is ValueError and "truncated" in words, (cut, error, words)

    @pytest.mark.parametrize("seed", range(4))
    def test_damaged_frames_end_as_the_reference_decoder_ends_them(self, seed, as_payload):
        """Records yielded, the error's type and its words, on frames cut
        short and on frames with a few bytes overwritten, tags among them."""
        rng = random.Random(1000 + seed)
        for _ in range(40):
            payload = encode_records([random_value(rng) for _ in range(rng.randrange(1, 4))])
            frames = [payload[: rng.randrange(len(payload))] for _ in range(4)]
            for _ in range(12):
                damaged = bytearray(payload)
                for _ in range(rng.randrange(1, 4)):
                    damaged[rng.randrange(len(damaged))] = rng.choice((rng.randrange(256), rng.choice(b"NTFijfsbtlm")))
                frames.append(bytes(damaged))
            for frame in frames:
                got, error, words = drain(decode_records(as_payload(frame)))
                want, want_error, want_words = drain(reference_records(frame))
                assert (error, words) == (want_error, want_words), frame
                assert error is None or issubclass(error, ValueError)
                assert_same(got, want)


class TestReaderWiring:
    def test_default_deserializer_is_the_safe_codec(self, tmp_path):
        from sparkucx_tpu.shuffle.reader import default_deserializer, serialize_records

        records = [("k1", 1), ("k2", [2, 3])]
        assert list(default_deserializer(serialize_records(records))) == records
        # and it rejects pickle bytes rather than loading them
        canary = tmp_path / "owned"

        class Evil:
            def __reduce__(self):
                return (open, (str(canary), "w"))

        with pytest.raises(ValueError):
            list(default_deserializer(pickle.dumps(Evil())))
        assert not canary.exists()

    def test_pickle_optin_still_available(self):
        from sparkucx_tpu.shuffle.reader import (
            pickle_deserializer,
            pickle_serialize_records,
        )

        # sets are outside the safe codec's value set — the opt-in pickle
        # path is for exactly these arbitrary-object needs on trusted hosts
        recs = [{1, 2, 3}, frozenset({"a"})]
        assert list(pickle_deserializer(pickle_serialize_records(recs))) == recs
