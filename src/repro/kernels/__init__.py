"""repro.kernels — Pallas TPU kernels for the DIRC-RAG hot paths.

  dirc_mac      bit-serial bit-plane MAC (paper-faithful digital CIM math)
  score_matmul  MXU-path INT8 score matmul (+fused cosine) — beyond-paper
  topk_select   exact per-row top-k by selection, no sort (each macro's
                comparator in ShardedDircIndex)
  paged_attend  fused paged-attention decode: flash-decoding split-KV over
                the block table, new-token scatter folded into the launch

ops.py = jit'd public wrappers; ref.py = pure-jnp oracles. All kernels are
validated in interpret mode on CPU and compile through Mosaic on a TPU;
_env.py picks between the two from the default backend.
"""
from . import ops, paged_attend, ref  # noqa: F401
