"""The Pallas diffusion kernel's share of its HBM bound (%): the bytes its
calls must move (``costs.diffusion_bytes``: every simulated lane's field
read and written once) at the chip's peak bandwidth, over the kernel's
device time inside the chunk programs that started once the window had
opened."""
import costs
from trace_reduce import by_name


def read(view):
    f = view.facts
    if "kernel" not in f:
        return None
    total, calls = 0.0, 0
    for plane in view.planes:
        ops = view.ops_in(plane, view.modules(plane, f["ants_module"]))
        t, n = by_name(ops, f["kernel"])
        total += t
        calls += n
    if not calls or total <= 0:
        return None
    return costs.roofline_share(0.0, calls * f["kernel_bytes_per_call"],
                                total / 1e9, view.device_kind)
