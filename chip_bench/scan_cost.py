"""Operations and bytes of one index search, from the corpus and the batch.

The least work an exact top-k search of a padded batch can do: read
every live document's int8 codes and its norm once, read the batch of
float32 queries, and multiply-accumulate each query with each code.
Counted from shapes alone, never from what the program does, so the
count is the same whatever implements the scan (bit-planes, int8
matmul, a kernel) and the least time it gives bounds every one of them.
Checked against a hand count in tests/chip_bench/test_cb_quora.py.
"""
from __future__ import annotations


def search_work(n_docs: int, dim: int, batch: int) -> tuple:
    """(ops, bytes) of one search of `batch` queries over `n_docs` codes.

    ops: one multiply and one add per query, document and dimension.
    bytes: n_docs * dim int8 codes, a float32 norm per document, and
    batch * dim float32 queries."""
    ops = 2 * batch * n_docs * dim
    nbytes = n_docs * dim + 4 * n_docs + 4 * batch * dim
    return ops, nbytes


def least_seconds(work: tuple, peak_ops: float, peak_bw: float) -> float:
    """The least time of `work` (ops, bytes) on a chip: the larger of ops
    over the int8 peak and bytes over HBM bandwidth."""
    ops, nbytes = work
    return max(ops / peak_ops, nbytes / peak_bw)
