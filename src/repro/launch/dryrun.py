import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init).  512 placeholder CPU devices back the production
meshes: 16x16 (single pod) and 2x16x16 (two pods).

For each applicable cell this script:
  1. builds the step function (train_step / prefill_step / serve_step)
     with the default sharding policy,
  2. ``.lower().compile()`` against ShapeDtypeStruct inputs (no allocation),
  3. records ``memory_analysis()`` (per-device bytes -> proves it fits),
     ``cost_analysis()`` (raw XLA flops/bytes; NOTE: scan bodies counted
     once — see repro.roofline for trip-count-corrected terms),
  4. runs the collective census over the partitioned HLO,
  5. appends the record to ``results/dryrun.json`` incrementally.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both]
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from .. import configs                     # noqa: E402
from ..roofline.hlo import collective_census  # noqa: E402
from . import policies, shapes, steps      # noqa: E402
from .mesh import make_production_mesh    # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results"


def build_bundle(arch_name: str, cell: shapes.ShapeCell, mesh,
                 scfg=None) -> steps.StepBundle:
    cfg = policies.arch_for_cell(configs.get(arch_name), cell)
    scfg = scfg or policies.default_sharding(cfg, cell)
    if cell.kind == "train":
        batch = shapes.batch_specs_for(cfg, cell)
        return steps.make_train_step(cfg, scfg, mesh,
                                     policies.default_opt(cfg), batch)
    if cell.kind == "prefill":
        batch = shapes.batch_specs_for(cfg, cell)
        return steps.make_prefill_step(cfg, scfg, mesh, batch,
                                       max_len=cell.seq_len)
    return steps.make_serve_step(cfg, scfg, mesh, cell.global_batch,
                                 cell.seq_len)


def run_cell(arch_name: str, cell: shapes.ShapeCell, mesh_name: str,
             scfg=None, keep_hlo: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=mesh_name == "multi")
    rec: dict = {"arch": arch_name, "cell": cell.name, "mesh": mesh_name,
                 "n_devices": mesh.devices.size}
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            bundle = build_bundle(arch_name, cell, mesh, scfg)
            lowered = bundle.lower()
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            txt = compiled.as_text()
            census = collective_census(txt)
            rec.update({
                "ok": True,
                "lower_s": round(t_lower - t0, 1),
                "compile_s": round(t_compile - t_lower, 1),
                "memory": {
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "alias_bytes": ma.alias_size_in_bytes,
                    "peak_per_device_gb": round(
                        (ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes
                         - ma.alias_size_in_bytes) / 2**30, 3),
                },
                "cost_analysis": {
                    "flops": ca.get("flops", 0.0),
                    "bytes_accessed": ca.get("bytes accessed", 0.0),
                },
                "collectives": census,
            })
            if keep_hlo:
                rec["hlo_path"] = str(RESULTS / "hlo" /
                                      f"{arch_name}_{cell.name}_{mesh_name}.txt")
                Path(rec["hlo_path"]).parent.mkdir(parents=True, exist_ok=True)
                Path(rec["hlo_path"]).write_text(txt)
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
    return rec


def append_result(rec: dict, path: Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    existing = []
    if path.exists():
        existing = json.loads(path.read_text())
    existing = [r for r in existing
                if not (r["arch"] == rec["arch"] and r["cell"] == rec["cell"]
                        and r["mesh"] == rec["mesh"])]
    existing.append(rec)
    path.write_text(json.dumps(existing, indent=1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=configs.ARCH_NAMES)
    ap.add_argument("--shape", default=None, choices=list(shapes.SHAPE_CELLS))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--out", default=str(RESULTS / "dryrun.json"))
    args = ap.parse_args()

    archs = list(configs.ARCH_NAMES) if (args.all or not args.arch) \
        else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    out = Path(args.out)

    n_ok = n_fail = n_skip = 0
    for arch_name in archs:
        cfg = configs.get(arch_name)
        for cell in shapes.SHAPE_CELLS.values():
            if args.shape and cell.name != args.shape:
                continue
            ok, reason = shapes.applicable(cfg, cell)
            if not ok:
                print(f"SKIP  {arch_name} x {cell.name}: {reason}")
                n_skip += 1
                continue
            for mesh_name in meshes:
                rec = run_cell(arch_name, cell, mesh_name,
                               keep_hlo=args.keep_hlo)
                append_result(rec, out)
                if rec["ok"]:
                    n_ok += 1
                    print(f"OK    {arch_name} x {cell.name} x {mesh_name}: "
                          f"lower {rec['lower_s']}s compile {rec['compile_s']}s "
                          f"peak/dev {rec['memory']['peak_per_device_gb']} GiB "
                          f"flops {rec['cost_analysis']['flops']:.3e}")
                else:
                    n_fail += 1
                    print(f"FAIL  {arch_name} x {cell.name} x {mesh_name}: "
                          f"{rec['error']}")
    print(f"\ndone: {n_ok} ok, {n_fail} failed, {n_skip} skipped "
          f"-> {out}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
