"""Roofline report: read dry-run artifacts -> the §Roofline table.

Per (arch x cell x mesh): the three terms in seconds, the dominant
bottleneck, MODEL_FLOPS/HLO_FLOPs usefulness ratio (LM cells), and a
one-line lever on the dominant term. Emits markdown to
experiments/roofline.md and CSV records for benchmarks.run.

The default run reads the CHECKED-IN `experiments/dryrun` artifacts
and emits the full table. `--refresh-dryrun` regenerates the artifacts
first (`python -m repro.launch.dryrun`, both meshes — minutes of XLA
lowering, meant for a machine with headroom) and then reports. When
artifacts are absent for a mesh the report does not fail or silently
truncate: it emits a clearly-labeled partial table naming that mesh
and the command that fills it (documented in docs/benchmarks.md).

    python -m benchmarks.roofline_report [--refresh-dryrun] [--json OUT]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

from benchmarks.common import Record, write_bench_json
from repro.launch.roofline import PEAK_FLOPS, terms_from_artifact

DRYRUN_DIR = "experiments/dryrun"
OUT_MD = "experiments/roofline.md"

LEVERS = {
    ("lm", "compute"): "more per-chip batch or lower remat recompute",
    ("lm", "memory"): "shard/fuse MoE dispatch buffers; bf16 end-to-end; "
                      "larger microbatch raises arithmetic intensity",
    ("lm", "collective"): "reduce FSDP gather volume (group layers, "
                          "bigger per-chip batch) or cut TP degree",
    ("recsys", "memory"): "fuse lookup+pool into one gather-sum; "
                          "row-shard tables to cut gather footprint",
    ("recsys", "collective"): "distributed top-k (k per shard, not full "
                              "gather); batch-parallel lookups",
    ("gnn", "memory"): "cast messages bf16; fuse edge-MLP chain",
    ("gnn", "collective"): "edge-cut partitioning to shrink halo gathers",
    ("paper", "collective"): "shard_map distributed top-k over the "
                             "database axis (k*shards, not n_db)",
    ("paper", "memory"): "fused_rank kernel: adjusted scores stay in VMEM",
}


def model_flops_for(rec: dict) -> float | None:
    """6*N(_active)*D for LM train cells; 2*N*D for prefill; 2*N*B decode."""
    if rec.get("kind") not in ("train", "prefill", "decode"):
        return None
    try:
        from repro.configs import get_arch
        spec = get_arch(rec["arch"])
        if spec.family != "lm":
            return None
        cfg = spec.make_config(True)
        tokens = rec["cell_params"]["seq_len"] * rec["cell_params"]["global_batch"]
        n_act = cfg.active_params_per_token
        if rec["kind"] == "train":
            return 6.0 * n_act * tokens
        if rec["kind"] == "prefill":
            return 2.0 * n_act * tokens
        return 2.0 * n_act * rec["cell_params"]["global_batch"]
    except Exception:
        return None


def load_records(mesh: str = "single") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, mesh, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def family_of(arch: str) -> str:
    from repro.configs import get_arch
    try:
        return get_arch(arch).family
    except Exception:
        return "?"


def build_table(mesh: str = "single"):
    rows = []
    for rec in load_records(mesh):
        if rec.get("status") != "ok":
            rows.append({"arch": rec["arch"], "cell": rec["cell"],
                         "status": "FAIL", "error": rec.get("error")})
            continue
        t = terms_from_artifact(rec)
        mf = model_flops_for(rec)
        useful = (mf / (t.flops * t.chips)) if (mf and t.flops) else None
        fam = family_of(rec["arch"])
        rows.append({
            "arch": rec["arch"], "cell": rec["cell"], "status": "ok",
            "family": fam,
            "compute_s": t.compute_s, "memory_s": t.memory_s,
            "collective_s": t.collective_s, "dominant": t.dominant,
            "bound_s": t.bound_s,
            "compute_fraction": t.compute_fraction,
            "useful_flops_ratio": useful,
            "lever": LEVERS.get((fam, t.dominant), "raise per-chip work"),
            "per_device_gb": rec.get("per_device_bytes", 0) / 1e9
            if rec.get("per_device_bytes") else None,
        })
    return rows


def to_markdown(rows, mesh: str) -> str:
    lines = [
        f"## Roofline — mesh `{mesh}` "
        f"(v5e: 197 TF/s bf16, 819 GB/s HBM, 50 GB/s/link)",
        "",
        "| arch | cell | compute s | memory s | collective s | dominant | "
        "compute-frac | useful-FLOPs | lever |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['cell']} | FAIL | | | | | | "
                         f"{r.get('error','')[:60]} |")
            continue
        uf = (f"{r['useful_flops_ratio']:.2f}"
              if r["useful_flops_ratio"] else "—")
        lines.append(
            f"| {r['arch']} | {r['cell']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"**{r['dominant']}** | {r['compute_fraction']:.3f} | {uf} | "
            f"{r['lever']} |")
    return "\n".join(lines)


def records(rows, mesh):
    out = []
    for r in rows:
        if r["status"] != "ok":
            continue
        out.append(Record(
            name=f"roofline/{mesh}/{r['arch']}/{r['cell']}",
            us_per_call=r["bound_s"] * 1e6,
            derived={"dominant": r["dominant"],
                     "compute_frac": round(r["compute_fraction"], 4)}))
    return out


def missing_section(mesh: str) -> str:
    """Explicit placeholder for a mesh with no dry-run artifacts."""
    return "\n".join([
        f"## Roofline — mesh `{mesh}` — PARTIAL: no dry-run artifacts",
        "",
        f"No artifacts under `{DRYRUN_DIR}/{mesh}/`. This table is a",
        "placeholder, not a truncation: regenerate the artifacts on a",
        "machine with headroom (the 512-device dry-run is too heavy for",
        "the 2-core CI container) and re-run this report:",
        "",
        "```bash",
        f"PYTHONPATH=src python -m repro.launch.dryrun   # fills {DRYRUN_DIR}/",
        "PYTHONPATH=src python -m benchmarks.roofline_report",
        "```",
    ])


def refresh_dryrun() -> None:
    """Regenerate the dry-run artifacts in a subprocess (same
    interpreter, PYTHONPATH inherited). Raises on a failed run — a
    half-refreshed artifact tree is worse than a stale one."""
    subprocess.run([sys.executable, "-m", "repro.launch.dryrun",
                    "--force"], check=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--refresh-dryrun", action="store_true",
                    help="regenerate experiments/dryrun artifacts first "
                         "(python -m repro.launch.dryrun --force; "
                         "minutes of XLA lowering)")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="write BENCH_roofline.json to OUT (a directory,"
                         " or an explicit *.json path)")
    args = ap.parse_args()
    if args.refresh_dryrun:
        refresh_dryrun()
    md = []
    all_records = []
    missing = []
    for mesh in ("single", "multi"):
        rows = build_table(mesh)
        if not rows:
            missing.append(mesh)
            md.append(missing_section(mesh))
            continue
        md.append(to_markdown(rows, mesh))
        all_records += records(rows, mesh)
    os.makedirs(os.path.dirname(OUT_MD), exist_ok=True)
    with open(OUT_MD, "w") as f:
        f.write("\n\n".join(md) + "\n")
    for rec in all_records:
        print(rec.csv())
    if missing:
        print(f"# PARTIAL report: no dry-run artifacts for mesh(es) "
              f"{', '.join(missing)} under {DRYRUN_DIR}/ — "
              f"run `python -m repro.launch.dryrun` to fill them "
              f"(see docs/benchmarks.md)")
    if args.json:
        write_bench_json(args.json, "roofline", all_records,
                         meta={"missing_meshes": missing})
    print(f"# wrote {OUT_MD}")


if __name__ == "__main__":
    main()
