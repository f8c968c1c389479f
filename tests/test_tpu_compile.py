"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached, at a real serving bucket.

Interpret mode (every other kernel test) accepts programs that Mosaic,
the TPU kernel compiler, refuses: batched contractions inside a kernel,
blocks that break the (8, 128) tiling. These tests lower each route of
the ``kernels.ops`` dispatchers with ``interpret=False`` through the
TPU compiler and check that the executable holds exactly one kernel
(``tpu_custom_call``) — one launch per micro-batch, as
``kernel_launch_count`` charges it. Nothing runs: shapes only.

The bucket is the one ``chip_smoke.py`` serves: B = 32 requests,
m1 = 1024 candidates, K = 8 constraint rows (5 predicted, bucket-padded),
m2 = 64 slots, d_cov = 50 covariates, a 65,536-user KNN database.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.predictors import LinearLambdaPredictor
from repro.kernels import ops
from repro.kernels.common import DB_SLAB

B, M1, K, K_PRED, M2, D_COV, N_DB, KNN_K = 32, 1024, 8, 5, 64, 50, 65_536, 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The described chip, with the persistent compilation cache off:
    an executable compiled for a device that is not attached cannot be
    read back from it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _rank_audited(u, a, b, gamma, lam):
    return ops.rank_audited(u, a, b, lam, gamma, m2=M2, interpret=False)


def _linear(u, a, b, gamma, X, W, c):
    return ops.predict_rank_audited(X, LinearLambdaPredictor(W=W, c=c),
                                    u, a, b, gamma, m2=M2, interpret=False)


def _knn(u, a, b, gamma, X, X_db, lam_db):
    return ops.knn_rank_audited(X, X_db, lam_db, u, a, b, gamma, k=KNN_K,
                                m2=M2, interpret=False)


def _knn_int8(u, a, b, gamma, X, X_db, lam_db, X_q, q_scale, y2_q):
    return ops.knn_rank_audited(X, X_db, lam_db, u, a, b, gamma, k=KNN_K,
                                m2=M2, interpret=False, quant="int8",
                                X_q=X_q, q_scale=q_scale, y2_q=y2_q)


# per-request inputs (u, a, b, gamma), then each route's own
_REQUEST = [(B, M1), (B, K, M1), (B, K), (B, M2)]
_KNN_DB = [(B, D_COV), (N_DB, D_COV), (N_DB, K_PRED)]       # X, X_db, lam_db
ROUTES = {
    # the kernel the executable must hold -> (dispatcher, argument shapes)
    "rank_audited_pallas": (_rank_audited, _REQUEST + [(B, K)]),
    "linear_rank_audited_pallas": (
        _linear, _REQUEST + [(B, D_COV), (K_PRED, D_COV), (K_PRED,)]),
    "knn_rank_audited_pallas": (_knn, _REQUEST + _KNN_DB),
    "knn_rank_audited_quant_pallas": (
        _knn_int8, _REQUEST + _KNN_DB + [((N_DB, D_COV), jnp.int8),
                                         (N_DB // DB_SLAB, 1), (N_DB, 1)]),
}


@pytest.mark.parametrize("kernel", list(ROUTES))
def test_main_path_kernel_compiles_for_v5e(kernel, one_chip):
    fn, shapes = ROUTES[kernel]

    def struct(spec):
        shape, dtype = spec if isinstance(spec[0], tuple) else (
            spec, jnp.float32)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(fn).lower(
        *map(struct, shapes)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1, kernel
