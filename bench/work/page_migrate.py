"""Least work of the page-migration kernel's calls: each moved page is read
once from one tier and written once to the other."""


def work(pages: int, page_bytes: int) -> dict:
    """``pages``: pages moved; ``page_bytes``: bytes of one page (K and V
    together where both move)."""
    return {"flops": 0.0, "bytes": float(2 * pages * page_bytes)}
