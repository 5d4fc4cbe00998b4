"""Host milliseconds of one fit of the BO surrogate, ``repro.bo.fit``:
self time per call in the traced window."""


def read(red, rec, ctx):
    return red.self_ms_per_call("repro.bo.fit")
