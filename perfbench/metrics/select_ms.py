"""Device time of the island GA's selection per superstep (ms): the
operations scoped ``islands.select`` (ranks, crowding, offspring and the
(mu + lambda) truncation), ``islands.merge`` (the islands' top-k and the
archive merge) and ``islands.reseed``, loop operations left out, in the
superstep programs that started once the window had opened, over their
number."""
import island_scopes


def read(view):
    found = island_scopes.scoped_ns_per_program(
        view, ("islands.select", "islands.merge", "islands.reseed"))
    if found is None:
        return None
    scoped, _, count = found
    return scoped / count / 1e6
