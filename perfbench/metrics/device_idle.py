"""Share of the window in which no operation ran on a chip (%), averaged
over the cell's chips."""


def read(view):
    if view.window_s <= 0:
        return None
    return 100.0 * (1.0 - view.busy_s() / view.window_s)
