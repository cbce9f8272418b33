"""Median device gap between consecutive ants evaluation programs on a
chip (ms), inside the window: how long a chip waits for its next chunk."""
from trace_reduce import gaps_between, median


def read(view):
    f = view.facts
    if "ants_module" not in f:
        return None
    gaps = []
    for plane in view.planes:
        gaps.extend(gaps_between(view.modules(plane, f["ants_module"])))
    m = median(gaps)
    return None if m is None else m / 1e6
