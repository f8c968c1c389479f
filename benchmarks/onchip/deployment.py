"""Set-up of one configuration: backbone, offline stage, request pool,
engine.

The offline stage (Algorithm 1's dual solve over the offline users and
the KNN fit) depends only on the configuration, not on ``--seed``. It
is ``repro.launch.serve.build_deployment``'s loop, run on the program's
backbone, dual solver and KNN fit, with the offline users' seeds kept
here (``OFFLINE_SEED``), so the reference can rebuild any database
row's LP after a run and check the row. The first run of a
configuration in a checkout writes the λ-database (X_db, λ_db, as the
fitted predictor holds them) and the offline compliance to
``.cache/offline/``; later runs load it, as a server loads its
λ-database. The cache key hashes the configuration's offline part and
the source files of the offline stage, so an edit to any of them is
never served from a stale database.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import numpy as np

from benchmarks.onchip.layout import HERE, ROOT

CACHE = HERE / ".cache"

# what the offline stage's result depends on, besides the configuration
OFFLINE_SOURCES = (
    "src/repro/core/dual_solver.py",
    "src/repro/core/predictors.py",
    "src/repro/core/assignment.py",
    "src/repro/core/constraints.py",
    "src/repro/launch/serve.py",
    "src/repro/models/recsys.py",
    "src/repro/models/layers.py",
    "src/repro/data/batches.py",
    "src/repro/configs/recsys_family.py",
)

# the configuration's keys the λ-database depends on
OFFLINE_KEYS = ("arch", "widths", "offline_users", "offline_candidates",
                "knn_k", "K", "m2")

# the backbone's weights, the offline users and the request pool come
# from fixed keys: --seed moves the traffic, never the deployment
POOL_SEED = 40_000
# offline users, OFFLINE_CHUNK to a program, chunk c from key
# OFFLINE_SEED + c; the solver's iterations (as build_deployment)
OFFLINE_SEED = 10_000
OFFLINE_CHUNK = 8192
OFFLINE_ITERS = 300


def offline_sources(cfg: dict) -> list[str]:
    return [*OFFLINE_SOURCES, f"src/repro/configs/{cfg['arch']}.py"]


def offline_key(cfg: dict, root: Path = ROOT) -> str:
    """Hash of everything the λ-database depends on."""
    h = hashlib.sha256()
    h.update(json.dumps({k: cfg[k] for k in OFFLINE_KEYS},
                        sort_keys=True).encode())
    for rel in offline_sources(cfg):
        h.update(rel.encode())
        h.update((Path(root) / rel).read_bytes())
    return h.hexdigest()[:20]


def offline_path(cfg: dict, root: Path = ROOT, cache: Path = CACHE) -> Path:
    return Path(cache) / "offline" / f"{cfg['name']}-{offline_key(cfg, root)}.npz"


def serve_args(cfg: dict):
    """The serve driver's arguments for this configuration."""
    from repro.launch import serve

    srv = cfg["serve"]
    argv = ["--arch", cfg["arch"],
            "--candidates", str(cfg["offline_candidates"]),
            "--offline-users", str(cfg["offline_users"]),
            "--constraints", str(cfg["K"]), "--m2", str(cfg["m2"]),
            "--train-steps", "0",
            "--max-batch", str(srv["max_batch"]),
            "--max-wait-ms", str(srv["max_wait_ms"]),
            "--pipeline-depth", str(srv["pipeline_depth"]),
            "--budget-ms", str(srv["budget_ms"])]
    if srv["admission"]:
        argv.append("--admission")
    return serve.parse_args(argv)


def model_config(cfg: dict):
    from repro.configs import get_arch

    return dataclasses.replace(get_arch(cfg["arch"]).make_config(full=True),
                               **cfg["widths"])


@dataclass
class Setup:
    cfg: dict
    args: Any                 # serve-driver namespace
    dep: Any                  # repro.launch.serve.Deployment
    X_db: np.ndarray          # (n_db, d_cov) the λ-database, host copy
    lam_db: np.ndarray        # (n_db, K)
    offline_hit: bool
    seconds: dict


def offline_surface(cfg: dict):
    """(gamma, topics, b) of the offline users' candidate set."""
    from repro.launch import serve

    return serve.surface(int(cfg["offline_candidates"]), int(cfg["m2"]),
                         int(cfg["K"]))


def offline_chunks(cfg: dict) -> list[tuple[int, int]]:
    """(first row, users) of each offline program."""
    n = int(cfg["offline_users"])
    c = min(n, OFFLINE_CHUNK)
    return [(start, min(c, n - start)) for start in range(0, n, c)]


def offline_users(dep, cfg: dict, chunk: int):
    """(u (n, offline_candidates), X (n, d_cov)) of offline chunk
    ``chunk``, on the device: the backbone's scores of its users."""
    import jax.numpy as jnp

    from repro.launch import serve

    _, n = offline_chunks(cfg)[chunk]
    return dep.score(dep.params,
                     serve._request_batch(dep.cfg, n, OFFLINE_SEED + chunk),
                     jnp.arange(int(cfg["offline_candidates"])))


def solve_offline(dep, cfg: dict):
    """Algorithm 1's offline stage: (X_off, λ_off, compliance) of every
    offline user, the duals by the program's solver."""
    import jax.numpy as jnp

    from repro.core.dual_solver import solve_dual_batch

    gamma, topics, b = offline_surface(cfg)
    X_parts, lam_parts, comp_parts = [], [], []
    for c in range(len(offline_chunks(cfg))):
        u, X = offline_users(dep, cfg, c)
        sol = solve_dual_batch(u, jnp.asarray(topics), jnp.asarray(b),
                               jnp.asarray(gamma), m2=int(cfg["m2"]),
                               num_iters=OFFLINE_ITERS)
        X_parts.append(X)
        lam_parts.append(sol.lam)
        comp_parts.append(sol.compliant)
    return (jnp.concatenate(X_parts), jnp.concatenate(lam_parts),
            float(jnp.concatenate(comp_parts).mean()))


def offline_rows(setup: Setup, rng: np.random.Generator, n: int):
    """A seeded sample of λ-database rows, all from one offline chunk,
    with their users' LP inputs rebuilt from the chunk's fixed key:
    (rows (n,), u (n, offline_candidates), X (n, d_cov)), host arrays."""
    chunks = offline_chunks(setup.cfg)
    c = int(rng.integers(len(chunks)))
    start, size = chunks[c]
    local = np.sort(rng.choice(size, min(n, size), replace=False))
    u, X = offline_users(setup.dep, setup.cfg, c)
    return (start + local, np.asarray(u, np.float32)[local],
            np.asarray(X, np.float32)[local])


def build(cfg: dict, *, root: Path = ROOT, cache: Path = CACHE) -> Setup:
    """Backbone weights on the device in one jitted call from the
    deployment's fixed key, then the offline stage from the cache (or
    solved on a miss)."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from repro.core.predictors import KNNLambdaPredictor
    from repro.launch import serve
    from repro.models.recsys import RECSYS_REGISTRY

    seconds = {}
    args = serve_args(cfg)
    mcfg = model_config(cfg)
    t = time.perf_counter()
    model = RECSYS_REGISTRY[mcfg.kind](mcfg)
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(0)))
    seconds["weights"] = time.perf_counter() - t

    t = time.perf_counter()
    dep = serve.Deployment(
        cfg=mcfg, params=params,
        score=jax.jit(partial(serve._score, model, mcfg.kind)),
        knn=None, mean=None, n_cand=int(cfg["candidates"]), m2=int(cfg["m2"]),
        K=int(cfg["K"]), offline_compliance=float("nan"), seconds=seconds)
    path = offline_path(cfg, root, cache)
    hit = path.is_file()
    if hit:
        with np.load(path) as z:
            X_off, lam_off = jnp.asarray(z["X_db"]), jnp.asarray(z["lam_db"])
            compliance = float(z["compliance"])
    else:
        X_off, lam_off, compliance = solve_offline(dep, cfg)
    # the predictor as it is served; the database is what it holds
    dep.knn = KNNLambdaPredictor.fit(X_off, lam_off, k=int(cfg["knn_k"]))
    dep.offline_compliance = compliance
    X_db = np.asarray(dep.knn.X_db, np.float32)
    lam_db = np.asarray(dep.knn.lam_db, np.float32)
    if not hit:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp.npz")
        np.savez(tmp, X_db=X_db, lam_db=lam_db, compliance=compliance)
        tmp.replace(path)
    seconds["offline"] = time.perf_counter() - t
    return Setup(cfg=cfg, args=args, dep=dep, X_db=X_db, lam_db=lam_db,
                 offline_hit=hit, seconds=seconds)


@dataclass
class Pool:
    """Users scored once by the backbone over the serving candidate set,
    and the candidate set's surface (slot discounts, topic attributes,
    exposure floors)."""

    U: np.ndarray             # (n_pool, n_cand) utilities
    X: np.ndarray             # (n_pool, d_cov) covariates
    gamma: np.ndarray         # (m2,)
    topics: np.ndarray        # (K, n_cand)
    b: np.ndarray             # (K,)


def score_pool(setup: Setup, n_pool: int, *, chunk: int = 64) -> Pool:
    import jax.numpy as jnp

    from repro.launch import serve

    dep = setup.dep
    gamma, topics, b = serve.surface(dep.n_cand, dep.m2, dep.K)
    cand = jnp.arange(dep.n_cand)
    U = np.empty((n_pool, dep.n_cand), np.float32)
    X = np.empty((n_pool, dep.d_cov), np.float32)
    for c, start in enumerate(range(0, n_pool, chunk)):
        u, x = dep.score(dep.params, serve._request_batch(
            dep.cfg, chunk, POOL_SEED + c), cand)
        n = min(chunk, n_pool - start)
        U[start:start + n] = np.asarray(u)[:n]
        X[start:start + n] = np.asarray(x)[:n]
    if not (np.isfinite(U).all() and np.isfinite(X).all()):
        raise ValueError(f"{setup.cfg['name']}: the backbone scored "
                         f"non-finite utilities or covariates for the pool")
    return Pool(U=U, X=X, gamma=np.asarray(gamma, np.float32),
                topics=topics, b=b)


def predictor_tag(setup: Setup, mix: dict) -> str:
    """The engine's tag of the mix's predictor: the KNN predictor, the
    one a deployment's engine serves by its arch's name."""
    if mix["predictor"] != "knn":
        raise ValueError(f"traffic {mix['name']!r}: unknown predictor "
                         f"{mix['predictor']!r} (have ['knn'])")
    return setup.cfg["arch"]


def make_engine(setup: Setup, metrics=None):
    """``repro.launch.serve.make_engine``'s engine for this
    configuration; the ``dist`` executor gets a (data 1, model chips)
    mesh over the first ``chips`` devices, the axes
    ``rank_input_shardings`` splits candidates over."""
    import jax

    from repro.launch import serve

    executor = setup.cfg["serve"]["executor"]
    mesh = None
    if executor == "dist":
        chips = int(setup.cfg["chips"])
        mesh = jax.make_mesh((1, chips), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2,
                             devices=jax.devices()[:chips])
    eng = serve.make_engine(setup.args, setup.dep, executor=executor,
                            mesh=mesh)
    if metrics is not None:
        eng.metrics = metrics
    return eng


def warm_m1s(mix: dict) -> list[int]:
    """One candidate count per bucket the mix can reach: every
    power-of-two m1 corner between the mix's bounds, and the bounds
    themselves."""
    lo, hi = (int(v) for v in mix["m1"])
    m1s = {lo, hi}
    p = 1
    while p <= hi:
        if p >= lo:
            m1s.add(p)
        p *= 2
    return sorted(m1s)
