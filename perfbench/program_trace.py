"""The program's own spans and scopes in a ``--trace 1`` run's trace.

The program records two kinds of marks that share the device trace's clock:

- host spans (``jax.profiler.TraceAnnotation`` names starting with
  ``repro.``): one pool attempt of one chunk (``repro.pool.attempt``) and,
  nested in it on the same thread, the chunk's steps
  (``repro.chunk.inputs``, ``.dispatch``, ``.wait``, ``.fetch``);
- device scopes (``jax.named_scope`` names starting with ``ants.``): each
  phase of the ants tick. A compiled operation carries the scope in its
  ``op_name`` metadata, which the profiler writes as the ``tf_op`` stat of
  the operation's event metadata on the device plane (a TPU v5e's trace
  holds it there and not on the events, so ``jax.profiler.ProfileData``
  does not show it and this module reads the file's protobuf itself); an
  operation's scope is the innermost ``ants.*`` component of that path.

``trace_reduce.Trace`` keeps neither, so the readers of the metrics built on
them read the same ``.xplane.pb`` once more: ``of(view)`` finds the file
whose ``bench.window`` span is the view's window and reduces it to a
``Marks``. A trace of a program without these marks gives empty ones, and
the readers then return ``None``.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from trace_reduce import SPAN_PREFIX, short_op

PREFIX = "repro."
SCOPE = re.compile(r"(?<![\w.])ants\.[A-Za-z_]+")
# the stat of an operation's event metadata that holds its op_name path
OP_NAME_STAT = "tf_op"
LOOP_OPS = ("%while", "%conditional")


class Span(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    line: str            # the host line (thread) that recorded it

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


class Marks(NamedTuple):
    spans: List[Span]                   # the program's host spans, by start
    scopes: Dict[str, Dict[str, str]]   # plane -> operation -> ants.* scope


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``ants.*`` component of an op_name path:
    ``jit(f)/while/body/vmap(ants.sense)/gather`` -> ``ants.sense``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


# --- the XSpace protobuf (tsl/profiler/protobuf/xplane.proto), read as
# plain wire format: only the fields below are decoded, the rest skipped ---
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry: key 1,
# value 2), .stat_metadata = 5 (same); XEventMetadata.name = 2, .stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
# .ref_value = 7 (the id of a stat metadata whose name is the value).


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of a message: an int for a varint or fixed
    field, the bytes for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield field, value


def _map_values(entries: List[bytes]) -> List[bytes]:
    return [v for e in entries for f, v in _fields(e) if f == 2]


def op_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """plane -> {operation: scope} for every device plane of a serialized
    XSpace: each operation's event metadata ``name`` (shortened as
    ``trace_reduce`` shortens it) with the scope of its ``tf_op`` stat.
    An operation name that two programs give different scopes is left
    out."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, events, stats = "", [], []
        for g, v in _fields(plane):
            if g == 2:
                name = v.decode()
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for m in _map_values(stats):
            fields = dict(_fields(m))
            stat_names[fields.get(1, 0)] = fields.get(2, b"").decode()
        tf_op = [k for k, v in stat_names.items() if v == OP_NAME_STAT]
        scopes: Dict[str, str] = {}
        clashes = set()
        for m in _map_values(events):
            op, path = None, None
            for g, v in _fields(m):
                if g == 2:
                    op = short_op(v.decode())
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        path = (stat[5].decode() if 5 in stat
                                else stat_names.get(stat.get(7), ""))
            scope = scope_of(path) if op and path else None
            if scope is None:
                continue
            if scopes.setdefault(op, scope) != scope:
                clashes.add(op)
        out[name] = {k: v for k, v in scopes.items() if k not in clashes}
    return out


def from_xplane(path: str) -> Tuple[Marks, Optional[Tuple[float, float]]]:
    """The program's marks in one trace file, and its ``bench.window``
    span as (start, end) (``None`` where it has none)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        xspace = f.read()
    data = ProfileData.from_serialized_xspace(xspace)
    spans: List[Span] = []
    window = None
    host = data.find_plane_with_name("/host:CPU")
    for i, ln in enumerate(host.lines if host is not None else ()):
        line = f"{i}:{ln.name}"        # thread names need not differ
        for e in ln.events:
            if e.name.startswith(PREFIX):
                spans.append(Span(e.name, e.start_ns, e.duration_ns, line))
            elif e.name == SPAN_PREFIX + "window" and window is None:
                window = (e.start_ns, e.start_ns + e.duration_ns)
    spans.sort(key=lambda s: s.start_ns)
    return Marks(spans, op_scopes(xspace)), window


_CACHE: Dict[Tuple[str, float], Tuple[Marks, Optional[tuple]]] = {}


def _read(path: str):
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE[key] = from_xplane(path)
    return _CACHE[key]


def of(view, search: Optional[str] = None) -> Optional[Marks]:
    """The marks of the trace the view was read from: the newest trace file
    under ``search`` (the harness's output directory) whose ``bench.window``
    span is the view's window. ``None`` where no such file is found."""
    if hasattr(view, "marks"):          # a view built with its marks
        return view.marks
    if search is None:
        import harness
        search = harness.OUT
    found = glob.glob(os.path.join(search, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        marks, window = _read(path)
        if window == (view.lo, view.hi):
            return marks
    return None


def nested(outer: Span, spans: Sequence[Span], name: str) -> List[Span]:
    """The spans called ``name`` that lie inside ``outer`` on its thread."""
    return [s for s in spans if s.name == name and s.line == outer.line
            and s.start_ns >= outer.start_ns and s.end_ns <= outer.end_ns]


def host_ms_per_attempt(spans: Sequence[Span], lo: float, hi: float
                        ) -> Optional[float]:
    """Median over the ``repro.pool.attempt`` spans that started in [lo, hi)
    of the span less the ``repro.chunk.wait`` nested in it (ms)."""
    own = []
    for s in spans:
        if s.name == PREFIX + "pool.attempt" and lo <= s.start_ns < hi:
            wait = sum(w.duration_ns for w in nested(
                s, spans, PREFIX + "chunk.wait"))
            own.append((s.duration_ns - wait) / 1e6)
    return statistics.median(own) if own else None


def scoped_ns(ops, scopes: Dict[str, str], scope: str) -> Tuple[float, bool]:
    """(device time of the operations in ``scope``, whether any of ``ops``
    has a scope at all); loop operations, which span their bodies, are left
    out."""
    total, any_scoped = 0.0, False
    for n, _, d in ops:
        if n.startswith(LOOP_OPS):
            continue
        s = scopes.get(n)
        if s is not None:
            any_scoped = True
            if s == scope:
                total += d
    return total, any_scoped
