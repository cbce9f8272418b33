"""The island superstep's device scopes in a ``--trace 1`` run's trace.

The program scopes the stages of an island epoch with ``jax.named_scope``:
``islands.init_eval`` (the initial evaluation, the epoch-0 branch of
``make_recorded_evolve``'s ``lax.cond``), ``islands.select`` (ranks,
crowding, offspring and the (mu + lambda) truncation of a GA step),
``islands.merge`` and ``islands.reseed``. As with the ``ants.*`` scopes
(``program_trace.py``), an operation carries its scope in the ``tf_op``
stat of its event metadata on the device plane; its island scope is the
``islands.*`` component of that path (the ``ants.*`` scopes of the ticks
nest inside ``islands.init_eval``).

``of(view)`` reads the trace file whose ``bench.window`` span is the
view's window and returns plane -> {operation: scope}; ``None`` where no
such file is found. A program without these scopes gives empty maps.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Optional

import program_trace as pt
from trace_reduce import short_op

SCOPE = re.compile(r"(?<![\w.])islands\.[A-Za-z_]+")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``islands.*`` component of an op_name path."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def op_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """plane -> {operation: islands scope} for every TPU plane of a
    serialized XSpace (the walk of ``program_trace.op_scopes``, with this
    module's scope); an operation two programs scope differently is left
    out."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in pt._fields(xspace):
        if f != 1:
            continue
        fields = list(pt._fields(plane))
        name = next((v.decode() for g, v in fields if g == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for m in pt._map_values([v for g, v in fields if g == 5]):
            meta = dict(pt._fields(m))
            stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        tf_op = {k for k, v in stat_names.items() if v == pt.OP_NAME_STAT}
        scopes: Dict[str, str] = {}
        clashes = set()
        for m in pt._map_values([v for g, v in fields if g == 4]):
            op, path = None, None
            for g, v in pt._fields(m):
                if g == 2:
                    op = short_op(v.decode())
                elif g == 5:
                    stat = dict(pt._fields(v))
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


_CACHE: Dict[str, Dict[str, Dict[str, str]]] = {}


def of(view, search: Optional[str] = None
       ) -> Optional[Dict[str, Dict[str, str]]]:
    """The island scopes of the trace the view was read from."""
    if hasattr(view, "island_scopes"):      # a view built with its scopes
        return view.island_scopes
    if search is None:
        import harness
        search = harness.OUT
    found = glob.glob(os.path.join(search, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        if pt._read(path)[1] == (view.lo, view.hi):
            if path not in _CACHE:
                with open(path, "rb") as f:
                    _CACHE[path] = op_scopes(f.read())
            return _CACHE[path]
    return None


def scoped_ns_per_program(view, scopes_wanted) -> Optional[tuple]:
    """(device ns of the operations in ``scopes_wanted``, device ns, count)
    of the superstep programs (``facts["ants_module"]``) that started once
    the window had opened, loop operations left out of the scoped time;
    ``None`` where there is no such program or no operation of them
    carries an island scope."""
    f = view.facts
    scopes = of(view) if "ants_module" in f else None
    if scopes is None:
        return None
    scoped, total, count, any_scoped = 0.0, 0.0, 0, False
    for plane in view.planes:
        mods = view.modules(plane, f["ants_module"])
        ops = view.ops_in(plane, mods)
        for scope in scopes_wanted:
            t, s = pt.scoped_ns(ops, scopes.get(plane, {}), scope)
            scoped += t
            any_scoped = any_scoped or s
        total += sum(d for _, _, d in mods)
        count += len(mods)
    if not count or not any_scoped:
        return None
    return scoped, total, count
