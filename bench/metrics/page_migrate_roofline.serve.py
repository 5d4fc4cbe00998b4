"""Roofline share (%) of the ``page_migrate`` kernel: each page the engine
moved in the traced window is read once and written once, K and V."""


def read(red, rec, ctx):
    t = red.kernel_s("page_migrate")
    g = rec["geometry"]
    page = 2 * g["L"] * g["pt"] * g["KV"] * g["D"] * rec["itemsize"]
    w = ctx["work"]("page_migrate", pages=sum(rec["moved"]), page_bytes=page)
    return ctx["roofline_share"](w, t, ctx["peaks"]) if t > 0 else None
