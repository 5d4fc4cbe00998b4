"""Host wall (ms) of one engine epoch, ``step_engine``, which ends at its
sync on the number of pages moved; mean over the traced window's epochs."""


def read(red, rec, ctx):
    s = rec["engine_s"]
    return 1e3 * sum(s) / len(s) if s else None
