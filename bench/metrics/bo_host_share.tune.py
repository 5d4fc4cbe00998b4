"""Share (%) of the traced window that the BO tuner's ask and tell took on
the host, from each round's ``round_times`` of ``Study.tune``."""


def read(red, rec, ctx):
    host = sum(r["ask_s"] + r["tell_s"] for s in rec["studies"]
               for r in s["round_times"])
    return 100.0 * host / red.window_s if red.window_s > 0 else None
