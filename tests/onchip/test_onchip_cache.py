"""The offline λ-database cache is keyed on everything it depends on, so
a stale database is never read."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import shutil

import pytest

from benchmarks.onchip import deployment, layout


@pytest.fixture
def tree(tmp_path):
    cfg = layout.load_config("sasrec-feed")
    for rel in deployment.offline_sources(cfg):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(layout.ROOT / rel, dst)
    return cfg, tmp_path


def test_key_is_stable(tree):
    cfg, root = tree
    assert (deployment.offline_key(cfg, root)
            == deployment.offline_key(dict(cfg), root))


@pytest.mark.parametrize("rel", deployment.OFFLINE_SOURCES
                         + ("src/repro/configs/sasrec.py",))
def test_key_changes_with_each_offline_source(tree, rel):
    cfg, root = tree
    before = deployment.offline_key(cfg, root)
    with open(root / rel, "a") as f:
        f.write("\n# changed\n")
    assert deployment.offline_key(cfg, root) != before
    assert (deployment.offline_path(cfg, root, cache=root / "c")
            != deployment.offline_path({**cfg}, layout.ROOT,
                                       cache=root / "c"))


@pytest.mark.parametrize("key,value", [
    ("offline_users", 4096), ("offline_candidates", 512), ("knn_k", 5),
    ("K", 4), ("m2", 20), ("widths", {"embed_dim": 32})])
def test_key_changes_with_the_offline_configuration(tree, key, value):
    cfg, root = tree
    assert (deployment.offline_key({**cfg, key: value}, root)
            != deployment.offline_key(cfg, root))


def test_serving_only_keys_do_not_invalidate(tree):
    cfg, root = tree
    srv = {**cfg["serve"], "max_wait_ms": 5.0}
    assert (deployment.offline_key({**cfg, "serve": srv,
                                    "check_sample": 7}, root)
            == deployment.offline_key(cfg, root))
