"""Roofline share (%) of the ``select_topk`` kernel inside the epoch loop:
every evaluated candidate runs one selection row per epoch over all pages."""


def read(red, rec, ctx):
    t = red.kernel_s("select_topk", module="jit_run")
    rows = len(rec["evals"]) * rec["n_epochs"]
    w = ctx["work"]("select_topk", rows=rows, n=rec["n_pages"])
    return ctx["roofline_share"](w, t, ctx["peaks"]) if t > 0 else None
