# Pallas TPU kernels for the compute hot-spots the paper optimizes:
#   fused_rank    — adjusted-score ranking (the <50 ms online hot path)
#   rank_audited  — rank + in-VMEM audit: one kernel emits the complete
#                   RankingOutput (perm/utility/exposure/compliance) with
#                   zero post-kernel reads of u/a
#   predict_rank_audited — the whole online stage (λ̂ = f(X), rank,
#                   audit) as one device program: affine predictors fold
#                   into the rank kernel's prologue, KNN fuses its
#                   weighting into the db sweep's flush, MLP joins the
#                   same executable as XLA matmuls
#   knn_topk / knn_lambda — lambda-predictor KNN over the train-user
#                   database (top-k pairs / fused λ̂ emission)
# Each has a pure-jnp oracle in ref.py; ops.py wraps with padding +
# XLA fallbacks. Their results are checked with interpret=True on CPU
# (tests/test_kernels.py, tests/test_rank_audited.py,
# tests/test_predict_rank.py); the main-path kernels are compiled for a
# described v5e in tests/test_tpu_compile.py and served on a chip by
# chip_smoke.py.
from repro.kernels import ref
from repro.kernels.ops import (
    fused_rank,
    knn_lambda,
    knn_predict_kernel,
    knn_topk,
    predict_rank_audited,
    rank_audited,
)
