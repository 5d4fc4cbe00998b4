"""Share (%) of the traced window in which the device is idle while the
host is inside the simulator's spans (``repro.sim.*``: trace lookup,
launch, fetch, results)."""


def read(red, rec, ctx):
    s = red.idle_under("repro.sim.")
    return None if s is None else 100.0 * s / red.window_s
