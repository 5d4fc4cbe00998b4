"""Device time (ms) of the compiled epoch-loop programs (``jit_run``) per
candidate evaluated in the traced window."""


def read(red, rec, ctx):
    t = red.module_s.get("jit_run", 0.0)
    n = len(rec["evals"])
    return 1e3 * t / n if t > 0 and n else None
