"""Device time (ms) of the fused decode program (``jit__decode``: append,
paged attention, read recording) per decode step of the traced window."""


def read(red, rec, ctx):
    t = red.module_s.get("jit__decode", 0.0)
    return 1e3 * t / rec["steps"] if t > 0 and rec["steps"] else None
