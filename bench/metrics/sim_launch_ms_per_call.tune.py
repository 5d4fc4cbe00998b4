"""Host milliseconds of one simulator launch, ``repro.sim.launch``: the
compiled loop's lookup, the knobs and carry, and the jitted call's
dispatch, while the device waits.  Self time per call in the traced
window."""


def read(red, rec, ctx):
    return red.self_ms_per_call("repro.sim.launch")
