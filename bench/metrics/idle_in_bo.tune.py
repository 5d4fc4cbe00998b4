"""Share (%) of the traced window in which the device is idle while the
host is inside the BO tuner's spans (``repro.bo.*``: ask, fit, pool,
acquisition, tell)."""


def read(red, rec, ctx):
    s = red.idle_under("repro.bo.")
    return None if s is None else 100.0 * s / red.window_s
