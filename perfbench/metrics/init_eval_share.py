"""The island superstep's device time spent evaluating the islands'
initial populations (%): operations scoped ``islands.init_eval``, loop
operations left out, over the device time of the superstep programs that
started once the window had opened. Under ``vmap`` the epoch-0
``lax.cond`` runs both branches, so every epoch pays it."""
import island_scopes


def read(view):
    found = island_scopes.scoped_ns_per_program(view, ("islands.init_eval",))
    if found is None:
        return None
    scoped, total, _ = found
    return 100.0 * scoped / total if total > 0 else None
