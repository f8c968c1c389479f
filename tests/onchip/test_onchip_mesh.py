"""The four-chip cell on four virtual CPU devices, at smoke widths: the
harness serves it through the ``dist`` executor on a (data 1, model 4)
mesh and ``run_cell`` comes out correct; with the cross-chip merge left
out (each chip's local top-m2 served as the page) it fails ``page_err``.

Run in a subprocess, so this process keeps its one CPU device."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

_PROG = textwrap.dedent("""
    import copy, json, os, sys
    from pathlib import Path

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax

    from benchmarks.onchip import deployment, layout, run

    cfg = copy.deepcopy(layout.load_config("mind-retrieval-4chip"))
    cfg["widths"] = {"n_items": 2048, "embed_dim": 16, "seq_len": 12,
                     "n_interests": 2, "capsule_iters": 2}
    cfg.update(offline_users=2048, offline_candidates=256,
               candidates=1024, check_sample=64)
    mix = dict(layout.load_traffic("retrieval_steady_4chip"))
    mix.update(rate_rps=200, m1=[300, 1024], pool=32, lead_in_s=0.2)
    cell = {"name": "mind-retrieval-4chip.steady", "chips": 4}

    meshes = []
    make_engine = deployment.make_engine

    def spy(setup, metrics=None):
        eng = make_engine(setup, metrics)
        meshes.append(dict(eng.mesh.shape))
        return eng

    deployment.make_engine = spy

    def cell_run():
        res = run.run_cell(layout.load_benchmark(), cell, cfg, mix,
                           seed=2**31 + 5, seconds=0.5, trace=False,
                           devices=jax.devices(), cache=Path(sys.argv[2]),
                           peaks=layout.peaks_for("TPU v5 lite"),
                           log=lambda msg: None)
        return {"correct": res["correct"], "checks": res["checks"],
                "attempted": res["attempted"], "meshes": meshes[-1:]}

    out = {"devices": len(jax.devices()), "sound": cell_run()}

    # the merge left out: each chip's local top-m2 with its global
    # indices, never gathered; the page is one chip's
    import repro.core.serving_dist as serving_dist
    from repro.distributed.topk import _take_last

    def local_top_k(scores, k, axis_name, global_offset=None,
                    payload=None):
        n_local = scores.shape[1]
        v, i = jax.lax.top_k(scores, min(k, n_local))
        sel = jax.tree.map(lambda p: _take_last(p, i), payload)
        return v, i + jax.lax.axis_index(axis_name) * n_local, sel

    serving_dist.distributed_top_k = local_top_k
    out["no_merge"] = cell_run()
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _PROG, str(ROOT),
         str(tmp_path_factory.mktemp("offline"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_four_chip_cell_is_correct_on_a_mesh(runs):
    assert runs["devices"] == 4
    sound = runs["sound"]
    assert sound["meshes"] == [{"data": 1, "model": 4}]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] == 100


def test_the_four_chip_cell_without_the_merge_fails_page_err(runs):
    res = runs["no_merge"]
    assert res["meshes"] == [{"data": 1, "model": 4}]
    assert not res["correct"]
    page = res["checks"]["page_err"]
    assert page["value"] > page["limit"]
    lam = res["checks"]["lam_err"]
    assert lam["value"] <= lam["limit"]      # the merge, not λ̂
