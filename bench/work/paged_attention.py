"""Least work of the paged decode-attention kernel's calls.

One call serves one decode step of a batch: every query head attends the
fast-tier tokens of its sequence through its KV head.  The least it must
read is each attended token's K and V row of the attended layer once, and
the queries; it writes one output row per query head.  Tokens that are not
in the fast tier, and unused block-table entries, are not counted.
"""


def work(tokens: int, steps: int, batch: int, heads: int, kv_heads: int,
         head_dim: int, itemsize: int) -> dict:
    """``tokens``: attended fast-tier tokens summed over sequences and
    steps; ``steps``: kernel calls."""
    kv = 2 * tokens * kv_heads * head_dim * itemsize
    q_out = 2 * steps * batch * heads * head_dim * itemsize
    flops = 4 * tokens * heads * head_dim          # q.k and p.v
    return {"flops": float(flops), "bytes": float(kv + q_out)}
