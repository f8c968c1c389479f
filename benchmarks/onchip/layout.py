"""Where the benchmark's pieces live, and how they are found by name.

Everything that belongs to one configuration, traffic mix, metric or
work count is a file of its own, named after the entry in
``BENCHMARK.json`` that uses it:

  configs/<config>.json    sizes, serve-driver arguments, chips, the
                           correctness limits of the deployment; two
                           optional keys: ``"requests"`` names its
                           requests/ module (``scored`` without it),
                           ``"step"`` lists the work/ counts summed into
                           its whole step (work/step.py's ``ROUTES``
                           without it)
  traffic/<mix>.json       arrival process, rate, m1 range, predictor
                           tag, pool size
  requests/<name>.py       ``make(setup, pool, mix, users, m1s, *,
                           rid0)``: the window's and the warm-up's
                           RankRequests; ``inputs(setup, pool, reqs)``:
                           each request's plain inputs for the reference
                           (u, a, b, gamma, X, m2, m1)
  metrics/<metric>.py      ``read(rec) -> float | None``: one number
                           from the run's record (and reduced trace)
  work/<kernel>.py         ``count(shape) -> {"flops", "bytes"}``: the
                           algorithm's work per batch from bucket shapes
                           and the configuration's ``widths``
  peaks.json               per-``device_kind`` peaks with their source

A configuration on more than one chip serves through the ``dist``
executor (candidates split over the chips, top-m2 merged across them);
chips are 1 or 4.

A later change adds a cell by adding files and entries; nothing here
lists them.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class LayoutError(ValueError):
    """A name in BENCHMARK.json that has no file, or a malformed file."""


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise LayoutError(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {[c['name'] for c in bench['workloads']]})")


def _json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise LayoutError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


CHIPS = (1, 4)


def load_config(name: str, here: Path = HERE) -> dict:
    cfg = _json(Path(here) / "configs" / f"{name}.json", f"config {name!r}")
    if cfg.get("name") != name:
        raise LayoutError(f"configs/{name}.json names itself "
                          f"{cfg.get('name')!r}")
    if cfg.get("chips") not in CHIPS:
        raise LayoutError(f"configs/{name}.json: chips {cfg.get('chips')!r}"
                          f" (have {CHIPS})")
    if cfg["chips"] > 1 and cfg["serve"]["executor"] != "dist":
        raise LayoutError(f"configs/{name}.json: {cfg['chips']} chips need "
                          f"the dist executor, not "
                          f"{cfg['serve']['executor']!r}")
    return cfg


def load_traffic(name: str, here: Path = HERE) -> dict:
    mix = _json(Path(here) / "traffic" / f"{name}.json", f"traffic {name!r}")
    if mix.get("name") != name:
        raise LayoutError(f"traffic/{name}.json names itself "
                          f"{mix.get('name')!r}")
    return mix


def _module(path: Path, what: str):
    if not path.is_file():
        raise LayoutError(f"{what}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"onchip_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE):
    """``metrics/<name>.py``'s ``read`` function."""
    mod = _module(Path(here) / "metrics" / f"{name}.py", f"metric {name!r}")
    if not callable(getattr(mod, "read", None)):
        raise LayoutError(f"metrics/{name}.py has no read(rec)")
    return mod.read


def work_module(name: str, here: Path = HERE):
    """``work/<name>.py``, which has ``count(shape)``."""
    mod = _module(Path(here) / "work" / f"{name}.py", f"work {name!r}")
    if not callable(getattr(mod, "count", None)):
        raise LayoutError(f"work/{name}.py has no count(shape)")
    return mod


def work_counter(name: str, here: Path = HERE):
    """``work/<name>.py``'s ``count`` function."""
    return work_module(name, here).count


def step_counter(cfg: dict, here: Path = HERE):
    """The whole step's ``count``: the sum of the work counts the
    configuration's ``"step"`` lists, or work/step.py's."""
    names = cfg.get("step")
    if names is None:
        return work_counter("step", here)
    counts = [work_counter(n, here) for n in names]

    def count(shape: dict) -> dict:
        ws = [c(shape) for c in counts]
        return {"flops": sum(w["flops"] for w in ws),
                "bytes": sum(w["bytes"] for w in ws)}

    return count


def request_module(cfg: dict, here: Path = HERE):
    """``requests/<name>.py`` of the configuration's ``"requests"`` key,
    ``scored`` without it; it has ``make`` and ``inputs``."""
    name = cfg.get("requests", "scored")
    mod = _module(Path(here) / "requests" / f"{name}.py",
                  f"requests {name!r}")
    for fn in ("make", "inputs"):
        if not callable(getattr(mod, fn, None)):
            raise LayoutError(f"requests/{name}.py has no {fn}()")
    return mod


def peaks_for(device_kind: str, here: Path = HERE) -> dict:
    """The peaks of one chip kind; an unknown kind is an error, never a
    default (a wrong peak would make every share meaningless)."""
    table = _json(Path(here) / "peaks.json", "peaks")
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise LayoutError(f"no peaks for device_kind {device_kind!r} in "
                          f"peaks.json (have {sorted(kinds)})")
    return kinds[device_kind]


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The metric entries of ``kind`` ('end_to_end' or 'per_layer') that
    this cell reports: those listing it under ``workloads``; without
    that key, an end-to-end metric is in every cell and a per-layer one
    in every cell that reports the end-to-end metric it ``moves``."""
    e2e = {m["name"] for m in cell_metrics_e2e(bench, cell_name)}
    if kind == "end_to_end":
        return [m for m in bench[kind] if m["name"] in e2e]
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def cell_metrics_e2e(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]
