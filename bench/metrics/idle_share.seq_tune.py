"""Share (%) of the traced window in which no operation ran on the device."""


def read(red, rec, ctx):
    return 100.0 * red.idle_share if red.window_s > 0 else None
