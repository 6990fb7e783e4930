"""Share of the window in which no operation ran on the device: 1 minus the
union of the device's operation intervals over the window, from the trace
(averaged over the cell's chips)."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
