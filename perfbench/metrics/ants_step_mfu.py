"""The ants tick's share of the chip's peak (%): the least time the
operations and bytes a tick needs (``costs.py``, from the model's shapes)
could take on this chip, over the device time per lane and tick of the
chunk programs that started once the window had opened."""
import costs


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
    lane_ticks = count * f["lanes_per_program"] * f["ticks"]
    return costs.roofline_share(f["tick_flops"] * lane_ticks,
                                f["tick_bytes"] * lane_ticks, total / 1e9,
                                view.device_kind)
