"""Each work/ count against a bucket worked by hand."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import shutil

import pytest

from benchmarks.onchip import layout
from benchmarks.onchip.record import least_time

# sasrec-feed's larger bucket: 32 rows of 1,024 candidates, 64 slots,
# K tier 8, over a 65,536 x 50 database of 5 prices, 10 neighbours
SHAPE = {"B": 32, "m1": 1024, "m2": 64, "K": 8, "d": 50, "n_db": 65536,
         "k": 10, "k_pred": 5}


def test_knn_rank_audited_by_hand():
    w = layout.work_counter("knn_rank_audited")({**SHAPE,
                                                 "predictor": "knn"})
    # distances 209,715,200 + |y|^2 6,553,600 + |x|^2 3,200 + d2 assembly
    # 6,291,456 + weights 6,080 + scores 557,056 + audit 36,864
    assert w["flops"] == 223_163_456
    # f32 elements: db 3,276,800 + prices 327,680 + u 32,768 + a 262,144
    # + X 1,600 + b 256 + gamma 2,048 + page 4,096 + 64 + 512
    assert w["bytes"] == 4 * 3_907_968 == 15_631_872
    peaks = layout.peaks_for("TPU v5 lite")
    assert least_time(w, peaks) == pytest.approx(15_631_872 / 819e9)


def test_step_follows_the_predictor_not_the_executor():
    shape = {**SHAPE, "predictor": "knn"}
    assert (layout.work_counter("step")(shape)
            == layout.work_counter("knn_rank_audited")(shape))
    with pytest.raises(KeyError):
        layout.work_counter("step")({**SHAPE, "predictor": "mean"})


def test_step_sums_the_counts_the_configuration_lists(tmp_path):
    shape = {**SHAPE, "predictor": "knn", "widths": {"embed_dim": 10}}
    here = tmp_path / "onchip"
    shutil.copytree(layout.HERE / "work", here / "work",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (here / "work" / "tower.py").write_text(
        "def count(shape):\n"
        "    w = shape['widths']['embed_dim']\n"
        "    return {'flops': 2 * shape['B'] * shape['m1'] * w,\n"
        "            'bytes': 4 * w}\n")
    knn = layout.work_counter("knn_rank_audited")(shape)
    both = layout.step_counter({"step": ["knn_rank_audited", "tower"]},
                               here)(shape)
    assert both == {"flops": knn["flops"] + 2 * 32 * 1024 * 10,
                    "bytes": knn["bytes"] + 40}
    # without the key, work/step.py's routes: the predictor's kernel
    assert layout.step_counter({}, here)(shape) == knn
