import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above run BEFORE any other import (jax locks the platform and
the device count at first init). They pin this host-device lowering
tool to the CPU, so it never takes an accelerator that another process
holds. Do NOT replicate them anywhere global — smoke tests and
benchmarks must see the single real device.

Per cell:
  with mesh:
      lowered = jax.jit(step, donate_argnums=...).lower(*abstract_args)
      compiled = lowered.compile()
      memory_analysis / cost_analysis / collective-bytes(HLO)

and a JSON artifact lands in experiments/dryrun/<mesh>/<arch>__<cell>.json
for the roofline report. Failures are recorded (and are bugs to fix).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch kimi-k2-1t-a32b \
      --cell train_4k --mesh multi [--smoke] [--force]
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import all_archs, get_arch
from repro.distributed.sharding import use_mesh_rules
from repro.launch.hlo_analysis import (
    collective_bytes,
    cost_analysis_dict,
    memory_analysis_dict,
    op_census,
)
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.launch.roofline import terms_from_artifact

DEFAULT_OUT = "experiments/dryrun"


def run_cell(arch_name: str, cell_name: str, mesh, mesh_tag: str,
             *, smoke: bool = False, variant: str | None = None) -> dict:
    spec = get_arch(arch_name)
    cell = spec.cell(cell_name)
    if smoke:
        # reduced cells keep the same names
        from repro.configs.gnn_family import GNN_SMOKE_CELLS
        from repro.configs.lm_family import LM_SMOKE_CELLS
        from repro.configs.paper import PAPER_SMOKE_CELLS
        from repro.configs.recsys_family import RECSYS_SMOKE_CELLS
        table = {"lm": LM_SMOKE_CELLS, "gnn": GNN_SMOKE_CELLS,
                 "recsys": RECSYS_SMOKE_CELLS, "paper": PAPER_SMOKE_CELLS}
        cell = next(c for c in table[spec.family] if c.name == cell_name)
    if variant:
        cfg = spec.variants[variant]()
    else:
        cfg = spec.make_config(not smoke)

    record = {
        "arch": arch_name, "cell": cell_name, "kind": cell.kind,
        "mesh": mesh_tag, "mesh_shape": list(mesh.devices.shape),
        "mesh_devices": mesh.devices.size, "smoke": smoke,
        "variant": variant,
        "cell_params": {k: (list(v) if isinstance(v, tuple) else v)
                        for k, v in cell.params.items()},
        "status": "error",
    }
    t0 = time.perf_counter()
    low = spec.build(cfg, cell, mesh)
    with use_mesh_rules(mesh, low.rules):
        jitted = jax.jit(low.fn, donate_argnums=low.donate)
        lowered = jitted.lower(*low.args)
        record["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        record["compile_s"] = round(time.perf_counter() - t1, 2)

    record["memory_analysis"] = memory_analysis_dict(compiled)
    # XLA's cost_analysis counts while bodies ONCE (verified; see
    # launch/hlo_cost.py) — kept for reference only. The roofline reads
    # hlo_cost: the trip-count-aware per-device walk.
    record["xla_cost_analysis_raw"] = {
        k: v for k, v in cost_analysis_dict(compiled).items()
        if not k.startswith("operand ")
    }
    hlo = compiled.as_text()
    from repro.launch.hlo_cost import hlo_cost
    record["hlo_cost"] = hlo_cost(hlo)
    record["collective_bytes"] = record["hlo_cost"]["collectives"]
    record["collective_bytes_static"] = collective_bytes(hlo)
    record["op_census"] = op_census(hlo)
    record["hlo_lines"] = hlo.count("\n")
    ma = record["memory_analysis"]
    if ma:
        per_dev = (ma.get("argument_size_in_bytes", 0)
                   + ma.get("temp_size_in_bytes", 0)
                   + ma.get("output_size_in_bytes", 0)
                   - ma.get("alias_size_in_bytes", 0))
        record["per_device_bytes"] = int(per_dev)
    record["roofline"] = terms_from_artifact(record).as_dict()
    record["status"] = "ok"
    return record


def save_record(record: dict, out_dir: str):
    d = os.path.join(out_dir, record["mesh"])
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{record['arch']}__{record['cell']}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--cell", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs + 8-device meshes (plumbing test)")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have artifacts")
    ap.add_argument("--variant", default=None,
                    help="named optimized config variant (§Perf hillclimb)")
    args = ap.parse_args()

    archs = all_archs() if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for multi in meshes:
        mesh_tag = ("smoke_" if args.smoke else "") + ("multi" if multi else "single")
        if args.variant:
            mesh_tag = f"{mesh_tag}@{args.variant}"
        mesh = (make_smoke_mesh(multi_pod=multi) if args.smoke
                else make_production_mesh(multi_pod=multi))
        for arch in archs:
            spec = get_arch(arch)
            cells = ([c.name for c in spec.cells] if args.cell == "all"
                     else args.cell.split(","))
            for cell in cells:
                if cell not in [c.name for c in spec.cells]:
                    continue
                path = os.path.join(args.out, mesh_tag, f"{arch}__{cell}.json")
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        if json.load(f).get("status") == "ok":
                            n_skip += 1
                            continue
                try:
                    rec = run_cell(arch, cell, mesh, mesh_tag,
                                   smoke=args.smoke, variant=args.variant)
                    n_ok += 1
                except Exception as e:
                    rec = {
                        "arch": arch, "cell": cell, "mesh": mesh_tag,
                        "mesh_devices": mesh.devices.size,
                        "smoke": args.smoke, "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-3000:],
                    }
                    n_fail += 1
                save_record(rec, args.out)
                jax.clear_caches()  # bound compile-cache memory across cells
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"[{mesh_tag}] {arch}/{cell}: OK "
                          f"lower {rec['lower_s']}s compile {rec['compile_s']}s "
                          f"| compute {r['compute_s']:.3e}s "
                          f"memory {r['memory_s']:.3e}s "
                          f"coll {r['collective_s']:.3e}s "
                          f"-> {r['dominant']}", flush=True)
                else:
                    print(f"[{mesh_tag}] {arch}/{cell}: FAIL {rec['error']}",
                          flush=True)
    print(f"dry-run done: {n_ok} ok, {n_fail} failed, {n_skip} cached")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
