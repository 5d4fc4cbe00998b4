"""Least work of the top-k selection kernel's calls.

Each call takes, for every row of ``n`` pages, a packed 32-bit key per page
and side (promote, demote) and writes one 32-bit selection flag per page
and side.  The least a selection can do is read each key once, compare it
once and write each flag once, however many passes the kernel makes.
"""


def work(rows: int, n: int) -> dict:
    """``rows``: selection rows over all calls (a call over a batch of B
    rows counts B); ``n``: pages per row."""
    keys = 2 * rows * n
    return {"flops": float(keys), "bytes": float(keys * (4 + 4))}
