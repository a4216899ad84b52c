"""The name-scope path of each device op of a traced run.

Each op of a jitted program carries, in its HLO metadata, the path of
``jax.named_scope``s it was traced under.  A TPU trace keeps it in the
``tf_op`` stat of the op's event metadata (e.g.
``jit(paged_decode_step_device)/unembed/dot_general:``), which
``jax.profiler.ProfileData`` does not expose: its events carry their own
stats only, and their names are the op's HLO text without metadata.  So
the device planes' event metadata is read from the ``XSpace`` protobuf
itself (``_metadata``: the few fields needed, by their numbers in tsl's
``xplane.proto``), as a map from an op's name, the name
``bench/trace.py``'s reduction keeps, to its path.
"""
from __future__ import annotations

import glob
from pathlib import Path
from typing import Dict, Iterator, Tuple

from bench.trace import _DEVICE


def paths(trace_dir: Path) -> Dict[str, Dict[str, str]]:
    """Per device plane, the scope path of each op name that has one, in
    the newest trace under ``trace_dir``; empty if there is none."""
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        return {}
    with open(files[-1], "rb") as f:
        return _metadata(f.read())


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is one of the components of ``path``."""
    return scope in path.split("/")


# ---------------------------------------------------------------------------
# event metadata from the XSpace protobuf (wire format)
# ---------------------------------------------------------------------------

# XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5
# (maps: key 1, value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .str_value 5,
# .ref_value 7 (the id of a stat metadata whose name is the string)
_Span = Tuple[int, int]
SCOPE_STAT = "tf_op"


def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b, span: _Span) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a span
    of ``b`` for a length-delimited field; fixed-width ones skipped."""
    i, end = span
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")


def _text(b, span: _Span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(b, entries) -> Iterator[_Span]:
    for entry in entries:
        for num, v in _fields(b, entry):
            if num == 2:
                yield v


def _metadata(raw: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, the ``tf_op`` path of each event metadata's name
    that has one."""
    b = memoryview(raw)
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(b, (0, len(b))):
        if num != 1:
            continue
        fields = list(_fields(b, plane))
        name = next((_text(b, v) for n, v in fields if n == 2), "")
        if not _DEVICE.match(name):
            continue
        stat_names = {}
        for meta in _map_values(b, [v for n, v in fields if n == 5]):
            f = dict(_fields(b, meta))
            stat_names[f.get(1)] = _text(b, f[2]) if 2 in f else ""
        paths: Dict[str, str] = {}
        for meta in _map_values(b, [v for n, v in fields if n == 4]):
            ev_name, path = "", ""
            for fn, v in _fields(b, meta):
                if fn == 2:
                    ev_name = _text(b, v)
                elif fn == 5:
                    st = dict(_fields(b, v))
                    if stat_names.get(st.get(1)) != SCOPE_STAT:
                        continue
                    path = (_text(b, st[5]) if 5 in st
                            else stat_names.get(st.get(7), ""))
            if path:
                paths[ev_name] = path
        out[name] = paths
    return out
