"""Device time of the ants evaluation programs per lane and tick (ns):
every execution of the chunk program that started once the window had opened,
over its lanes times ticks."""


def read(view):
    f = view.facts
    if "ants_module" not in f:
        return None
    total, count = 0.0, 0
    for plane in view.planes:
        mods = view.modules(plane, f["ants_module"])
        total += sum(d for _, _, d in mods)
        count += len(mods)
    if not count:
        return None
    return total / (count * f["lanes_per_program"] * f["ticks"])
