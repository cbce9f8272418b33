"""Device time of the ants tick's sensing phase per lane and tick (ns): the
operations scoped ``ants.sense`` (the neighbour positions, the gathers of
the chemical field and the nest-distance table at each ant's 8
neighbours, the scores and their argmax) inside the ants evaluation
programs that started once the window had opened, loop operations left
out, over those programs' lanes times ticks. ``None`` where no operation of
those programs carries a scope."""
import program_trace


def read(view):
    f = view.facts
    marks = program_trace.of(view) if "ants_module" in f else None
    if marks is None:
        return None
    total, count, scoped = 0.0, 0, False
    for plane in view.planes:
        mods = view.modules(plane, f["ants_module"])
        t, s = program_trace.scoped_ns(view.ops_in(plane, mods),
                                       marks.scopes.get(plane, {}),
                                       "ants.sense")
        total += t
        count += len(mods)
        scoped = scoped or s
    if not count or not scoped or total <= 0:
        return None
    return total / (count * f["lanes_per_program"] * f["ticks"])
