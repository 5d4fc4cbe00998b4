"""Roofline share (%) of the ``paged_attention`` kernel: the least work is
the attended layer's K and V of the fast-tier tokens each step reads, plus
queries and outputs."""


def read(red, rec, ctx):
    t = red.kernel_s("paged_attention")
    g = rec["geometry"]
    w = ctx["work"]("paged_attention", tokens=sum(rec["tokens"]),
                    steps=rec["steps"], batch=g["B"], heads=g["heads"],
                    kv_heads=g["KV"], head_dim=g["D"],
                    itemsize=rec["itemsize"])
    return ctx["roofline_share"](w, t, ctx["peaks"]) if t > 0 else None
