"""Run the tiny streamed cell on four host CPU devices, one pool member
each, and print its result line; with ``exchange`` the work sent to every
chip but the first is left out (those chips answer with the rows of chunk
0 instead of the chunk they were sent).

    python3 perfbench/tests/four_chips.py [exchange] OUT_DIR
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

import tiny_cells  # noqa: E402


def leave_out_the_exchange():
    import jax
    from repro.core.environment import DeviceEnvironment
    from repro.core.prototype import Context
    real = DeviceEnvironment.run_attempt
    first = jax.devices()[0]

    def run_attempt(self, task, context, **kw):
        if first not in self.devices:
            context = Context(chunk=0, size=int(context["size"]))
        return real(self, task, context, **kw)
    DeviceEnvironment.run_attempt = run_attempt


if __name__ == "__main__":
    *fault, out = sys.argv[1:]
    if fault == ["exchange"]:
        leave_out_the_exchange()
    cell = tiny_cells.cell("egi_init.chunk64")
    cell.chips = 4                      # one pool member per device
    r = tiny_cells.run(cell, out, seconds=1.0)
    print(json.dumps({k: r[k] for k in ("correct", "attempted", "checks")}))
