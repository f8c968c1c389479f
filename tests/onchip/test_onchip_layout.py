"""Discovery by name: every cell, configuration, traffic mix and metric of
BENCHMARK.json resolves to its own file, a new one is found without an
edit, and the entry point refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import pytest

from benchmarks.onchip import layout

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_resolves():
    for cell in BENCH["workloads"]:
        cfg = layout.load_config(cell["config"])
        mix = layout.load_traffic(cell["traffic"])
        assert cfg["chips"] == cell["chips"]
        assert mix["rate_rps"] > 0
        for kind in ("end_to_end", "per_layer"):
            for m in layout.cell_metrics(BENCH, cell["name"], kind):
                assert callable(layout.metric_reader(m["name"]))
    for entry in BENCH["configs"]:
        cfg = layout.load_config(entry["name"])
        assert (ROOT / entry["file"]).resolve() == (
            layout.HERE / "configs" / f"{entry['name']}.json")
        assert entry["reduced"] == cfg["reduced"]


def test_every_cell_reports_the_contract_minimum():
    for cell in BENCH["workloads"]:
        e2e = {m["name"] for m in
               layout.cell_metrics(BENCH, cell["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert layout.cell_metrics(BENCH, cell["name"], "per_layer")
        for m in layout.cell_metrics(BENCH, cell["name"], "per_layer"):
            assert m["moves"] in e2e, (cell["name"], m["name"])


def test_new_files_are_found_without_an_edit(tmp_path):
    here = tmp_path / "onchip"
    shutil.copytree(layout.HERE, here,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = json.loads((here / "configs" / "sasrec-feed.json").read_text())
    cfg["name"] = "sasrec-notif"
    (here / "configs" / "sasrec-notif.json").write_text(json.dumps(cfg))
    (here / "traffic" / "notif_steady.json").write_text(json.dumps(
        {"name": "notif_steady", "arrivals": "poisson", "rate_rps": 100,
         "m1": [120, 120], "predictor": "knn", "pool": 64,
         "lead_in_s": 1.0}))
    (here / "metrics" / "served_share.py").write_text(
        "def read(rec):\n    return 100.0 * rec['served'] / "
        "rec['attempted']\n")
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "sasrec-notif.steady",
                           "config": "sasrec-notif",
                           "traffic": "notif_steady", "chips": 1,
                           "why": "a test"}]
    bench["per_layer"] = BENCH["per_layer"] + [
        {"name": "served_share", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "client", "moves": "p99_ms",
         "workloads": ["sasrec-notif.steady"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = layout.load_benchmark(tmp_path)
    cell = layout.find_cell(got, "sasrec-notif.steady")
    assert layout.load_config(cell["config"], here)["name"] == "sasrec-notif"
    assert layout.load_traffic(cell["traffic"], here)["m1"] == [120, 120]
    names = [m["name"] for m in
             layout.cell_metrics(got, cell["name"], "per_layer")]
    assert "served_share" in names
    read = layout.metric_reader("served_share", here)
    assert read({"served": 3, "attempted": 4}) == 75.0
    for p, data in before.items():       # nothing that was there changed
        assert p.read_bytes() == data


def test_a_missing_file_is_an_error(tmp_path):
    with pytest.raises(layout.LayoutError):
        layout.load_config("no-such-config")
    with pytest.raises(layout.LayoutError):
        layout.metric_reader("no_such_metric")


@pytest.mark.parametrize("key,value,find", [
    ("requests", "no_such_requests", layout.request_module),
    ("step", ["knn_rank_audited", "no_such_count"], layout.step_counter)])
def test_an_absent_request_module_or_step_count_is_an_error(key, value,
                                                            find):
    cfg = {**layout.load_config("mind-retrieval"), key: value}
    with pytest.raises(layout.LayoutError):
        find(cfg)


def test_more_than_one_chip_needs_the_dist_executor(tmp_path):
    here = tmp_path / "onchip"
    (here / "configs").mkdir(parents=True)
    cfg = layout.load_config("mind-retrieval-4chip")
    assert (cfg["chips"], cfg["serve"]["executor"]) == (4, "dist")
    for name, chips, executor in (("four-fused", 4, "fused"),
                                  ("two-dist", 2, "dist")):
        bad = {**cfg, "name": name, "chips": chips,
               "serve": {**cfg["serve"], "executor": executor}}
        (here / "configs" / f"{name}.json").write_text(json.dumps(bad))
        with pytest.raises(layout.LayoutError):
            layout.load_config(name, here)


def test_peaks_are_keyed_by_device_kind():
    v5e = layout.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(layout.LayoutError):
        layout.peaks_for("TPU v4")
    with pytest.raises(layout.LayoutError):
        layout.peaks_for("cpu")


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    cell = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell,
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns(".cache",
                                                      "__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
