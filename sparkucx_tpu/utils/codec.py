"""Typed, non-executing record codec — the data plane's default serializer.

The shuffle data plane delivers peer-produced bytes into the reduce-side
record pipeline (shuffle/reader.py).  Spark's default ``JavaSerializer``
deserializes attacker-controllable streams with full object construction;
this build's control plane explicitly bans that (parallel/bootstrap.py: "must
not execute peer-controlled bytes"), and the same rule applies here: the
default codec decodes a closed set of value shapes with explicit type tags
and bounds checks, and nothing else.  ``pickle`` remains available as an
explicit opt-in for trusted single-host runs (see shuffle/reader.py's
``pickle_deserializer``).

Wire format, per record (records concatenate back-to-back; each is
self-delimiting):

    N                      None
    T / F                  True / False
    i <int64 be>           int fitting 64 bits
    j <u32 len> <bytes>    arbitrary-precision int (two's complement, be)
    f <float64 be>         float
    s <u32 len> <utf8>     str
    b <u32 len> <bytes>    bytes
    t <u32 count> <items>  tuple
    l <u32 count> <items>  list
    m <u32 count> <k v>*   dict

Anything else — unknown tags, truncated frames, nesting deeper than
``MAX_DEPTH`` — raises ``ValueError``.  Decoding allocates only containers
and scalars; there is no code path to object construction or callables.

``decode_records`` takes any payload with the buffer protocol — ``bytes``,
``bytearray``, a ``memoryview`` of any format (shuffle/reader.py serves a
read-only one of its pooled fetch buffer); one that is not C-contiguous is
copied once — and hands out nothing that refers to it: a ``b`` value is always
a ``bytes`` that owns its memory, never a view, because the reader gives the
buffer back to its pool while a consumer may still hold every value.  That
copy is the one pass a decoded byte pays.
"""

from __future__ import annotations

import struct
from typing import Any, Iterable, Iterator

import numpy as np

#: Container-nesting bound: a crafted frame of a million nested tuples would
#: otherwise turn the recursive decoder into a stack-overflow primitive.
MAX_DEPTH = 100

_U32 = struct.Struct(">I")
_I64 = struct.Struct(">q")
_F64 = struct.Struct(">d")


def _encode(obj: Any, out: bytearray, depth: int = 0) -> None:
    if depth > MAX_DEPTH:
        raise ValueError(f"record nests deeper than MAX_DEPTH={MAX_DEPTH}")
    if obj is None:
        out += b"N"
    elif obj is True:
        out += b"T"
    elif obj is False:
        out += b"F"
    elif isinstance(obj, (bool, np.bool_)):  # np.bool_ is not `is True`
        out += b"T" if bool(obj) else b"F"
    elif isinstance(obj, (int, np.integer)):
        v = int(obj)
        if -(2**63) <= v < 2**63:
            out += b"i"
            out += _I64.pack(v)
        else:
            raw = v.to_bytes((v.bit_length() + 8) // 8, "big", signed=True)
            out += b"j"
            out += _U32.pack(len(raw))
            out += raw
    elif isinstance(obj, (float, np.floating)):
        out += b"f"
        out += _F64.pack(float(obj))
    elif isinstance(obj, str):
        raw = obj.encode("utf-8")
        out += b"s"
        out += _U32.pack(len(raw))
        out += raw
    elif isinstance(obj, (bytes, bytearray)):
        # zero-copy append: bytearray.__iadd__ copies straight out of the
        # source buffer — an intermediate bytes() would double the allocation
        # on the map-side hot path
        out += b"b"
        out += _U32.pack(len(obj))
        out += obj
    elif isinstance(obj, memoryview):
        # len() counts ELEMENTS, not bytes, on shaped views — use nbytes and
        # flatten to a byte view; only a non-contiguous view pays a copy
        mv = obj if obj.contiguous else memoryview(obj.tobytes())
        out += b"b"
        out += _U32.pack(mv.nbytes)
        out += mv.cast("B")
    elif isinstance(obj, tuple):
        out += b"t"
        out += _U32.pack(len(obj))
        for item in obj:
            _encode(item, out, depth + 1)
    elif isinstance(obj, list):
        out += b"l"
        out += _U32.pack(len(obj))
        for item in obj:
            _encode(item, out, depth + 1)
    elif isinstance(obj, dict):
        out += b"m"
        out += _U32.pack(len(obj))
        for k, v in obj.items():
            _encode(k, out, depth + 1)
            _encode(v, out, depth + 1)
    else:
        raise TypeError(
            f"type {type(obj).__name__} is outside the safe codec's value set "
            "(None/bool/int/float/str/bytes/tuple/list/dict); pass an explicit "
            "pickle serializer for trusted single-host runs"
        )


def encode_record(obj: Any) -> bytes:
    """Encode one record into the typed wire format."""
    out = bytearray()
    _encode(obj, out)
    return bytes(out)


def encode_records(records: Iterable[Any]) -> bytes:
    """Encode a record stream (back-to-back self-delimiting frames)."""
    out = bytearray()
    for rec in records:
        _encode(rec, out)
    return bytes(out)


#: the tags as the ints that indexing a byte buffer yields
_NONE, _TRUE, _FALSE, _INT, _BIGINT, _FLOAT, _STR, _BYTES, _TUPLE, _LIST, _MAP = (
    b"NTFijfsbtlm"
)

_u32_at = _U32.unpack_from
_i64_at = _I64.unpack_from
_f64_at = _F64.unpack_from


def _truncated(pos: int, need: int, n: int) -> ValueError:
    return ValueError(
        f"truncated record frame: need {need} bytes at offset {pos}, "
        f"have {n - pos}"
    )


def _byte_view(payload) -> memoryview:
    """``payload`` as a 1-D ``B``-format view: its index is an int, its slice
    copies nothing, and ``unpack_from`` reads it in place.  A view of another
    format or shape is cast; only one that is not C-contiguous pays a copy
    (``_encode`` does the same for its side)."""
    view = memoryview(payload)
    if not view.c_contiguous:
        view = memoryview(view.tobytes())
    if view.format != "B" or view.ndim != 1:
        view = view.cast("B")
    return view


def decode_records(payload) -> Iterator[Any]:
    """Decode a stream of records; raises ``ValueError`` on any malformation
    (unknown tag, truncation, over-deep nesting) — never executes anything.
    ``payload`` is any object with the buffer protocol: ``bytes``,
    ``bytearray``, or a ``memoryview`` of any format (the fetch iterator of
    shuffle/reader.py serves a read-only one of its pooled buffer).  Nothing
    yielded refers to ``payload``: a ``b`` value is a ``bytes`` of its own.

    One loop, one frame an iteration, for every record shape: a tag is an int
    compared in the order record streams carry them, a bound is one comparison
    with the payload's length, and an open container is an entry of ``stack``
    (so nesting costs no call and ``MAX_DEPTH`` is the stack's height)."""
    buf = _byte_view(payload)
    n = len(buf)
    pos = 0
    # the innermost open container: its tag, the items it still lacks (a map
    # counts keys and values) and those decoded so far; ``stack`` holds the
    # same three of every container round it
    kind, left, items = 0, 0, None
    stack: list = []
    while True:
        if pos >= n:
            if items is None:
                return
            raise _truncated(pos, 1, n)
        tag = buf[pos]
        pos += 1
        if tag == _TUPLE or tag == _LIST or tag == _MAP:
            end = pos + 4
            if end > n:
                raise _truncated(pos, 4, n)
            count = _u32_at(buf, pos)[0]
            pos = end
            if count:
                if len(stack) >= MAX_DEPTH:
                    raise ValueError(
                        f"record nests deeper than MAX_DEPTH={MAX_DEPTH}"
                    )
                stack.append((kind, left, items))
                kind, left, items = tag, (2 * count if tag == _MAP else count), []
                continue
            val = () if tag == _TUPLE else [] if tag == _LIST else {}
        elif tag == _INT:
            end = pos + 8
            if end > n:
                raise _truncated(pos, 8, n)
            val = _i64_at(buf, pos)[0]
            pos = end
        elif tag == _BYTES or tag == _STR or tag == _BIGINT:
            end = pos + 4
            if end > n:
                raise _truncated(pos, 4, n)
            size = _u32_at(buf, pos)[0]
            pos = end
            end += size
            if end > n:
                raise _truncated(pos, size, n)
            if tag == _BYTES:
                val = bytes(buf[pos:end])  # the one copy: the value owns its bytes
            elif tag == _STR:
                val = str(buf[pos:end], "utf-8")
            else:
                val = int.from_bytes(buf[pos:end], "big", signed=True)
            pos = end
        elif tag == _FLOAT:
            end = pos + 8
            if end > n:
                raise _truncated(pos, 8, n)
            val = _f64_at(buf, pos)[0]
            pos = end
        elif tag == _NONE:
            val = None
        elif tag == _TRUE:
            val = True
        elif tag == _FALSE:
            val = False
        else:
            raise ValueError(
                f"unknown record tag {bytes((tag,))!r} at offset {pos - 1}"
            )
        # hand ``val`` to the container it belongs to, closing every container
        # it completes; at the top level it is a record
        while items is not None:
            if kind == _MAP and left & 1:  # a map's value: its key came before
                try:
                    hash(items[-1])
                except TypeError:
                    # container-typed key in a crafted frame: keep the
                    # documented ValueError error contract
                    raise ValueError(
                        f"unhashable map key of type {type(items[-1]).__name__}"
                    ) from None
            items.append(val)
            left -= 1
            if left:
                break
            if kind == _TUPLE:
                val = tuple(items)
            elif kind == _LIST:
                val = items
            else:
                pairs = iter(items)
                val = dict(zip(pairs, pairs))
            kind, left, items = stack.pop()
        else:
            yield val
