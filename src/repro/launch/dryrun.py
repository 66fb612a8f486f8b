import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax -----------------------------------
import argparse       # noqa: E402
import json           # noqa: E402
import math           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402

from repro.configs.base import ARCHS, SHAPES, get_config    # noqa: E402
from repro.launch.mesh import make_production_mesh, HW      # noqa: E402
from repro.launch.specs import build_cell, cell_is_supported # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo            # noqa: E402
from repro.launch.roofline import roofline_terms, model_flops # noqa: E402

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, print memory/cost analysis, and persist
the roofline inputs to artifacts/dryrun/.

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out artifacts/dryrun

A cell *passes* when .lower().compile() succeeds; bytes-per-device,
FLOPs and the collective schedule land in the JSON artifact that
EXPERIMENTS.md §Dry-run / §Roofline read."""


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, save_hlo: bool = False,
             overrides: dict | None = None, tag: str = "",
             micro: int = 0) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = get_config(arch)
    if overrides:
        import dataclasses as _dc
        # config overrides for §Perf A/B runs, e.g. remat=full
        typed = {}
        for k, v in overrides.items():
            fld = {f.name: f for f in _dc.fields(cfg)}[k]
            typed[k] = (fld.type in ("int", int) and int(v)) or                        (v in ("True", "False") and v == "True") or v
            if fld.type in ("int", int):
                typed[k] = int(v)
            elif str(fld.type) in ("bool", "<class 'bool'>"):
                typed[k] = v in (True, "True", "true", "1")
        cfg = _dc.replace(cfg, **typed)
    shape = SHAPES[shape_name]
    ok, why = cell_is_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "skipped", "why": why}
    if not ok:
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    step, args, in_sh, out_sh, donate, meta = build_cell(
        arch, shape_name, mesh, cfg=cfg, n_microbatches=micro)
    jit_kwargs = dict(in_shardings=in_sh, donate_argnums=donate)
    if out_sh is not None:
        jit_kwargs["out_shardings"] = out_sh
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, **jit_kwargs).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    print(f"[{arch} x {shape_name} x {mesh_name}] memory_analysis:", ma)
    ca = compiled.cost_analysis() or {}
    print(f"[{arch} x {shape_name} x {mesh_name}] cost_analysis flops:",
          ca.get("flops"), "bytes:", ca.get("bytes accessed"))

    hlo_text = compiled.as_text()
    costs = analyze_hlo(hlo_text)
    n_chips = math.prod(mesh.devices.shape)
    terms = roofline_terms(costs, hw=HW)
    mf = model_flops(cfg, shape)

    rec.update({
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device_bytes": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
        },
        "xla_cost_analysis": {"flops": ca.get("flops"),
                              "bytes_accessed": ca.get("bytes accessed")},
        "hlo_costs": costs.to_dict(),
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flop_ratio": (mf / n_chips) / max(costs.flops, 1.0),
    })
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{arch}__{shape_name}__{mesh_name}{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if save_hlo:
        with open(path.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo_text)
    print(f"[{arch} x {shape_name} x {mesh_name}] OK  "
          f"compile={t_compile:.1f}s  "
          f"peak/dev={rec['memory']['peak_per_device_bytes']/2**30:.2f}GiB  "
          f"terms(ms): C={terms['compute_s']*1e3:.2f} "
          f"M={terms['memory_s']*1e3:.2f} X={terms['collective_s']*1e3:.2f} "
          f"dominant={terms['dominant']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override k=v (repeatable) — §Perf A/B")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--micro", type=int, default=0,
                    help="override train microbatch count (0 = heuristic)")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.override)

    archs = ARCHS if args.arch == "all" else [args.arch.replace("-", "_")]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_cell(arch, shape, multi_pod=mp,
                                            out_dir=args.out,
                                            save_hlo=args.save_hlo,
                                            overrides=overrides,
                                            tag=args.tag,
                                            micro=args.micro))
                except Exception:
                    failures += 1
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "multi" if mp else "single",
                                    "status": "FAILED"})
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\n== dry-run summary: {n_ok} ok, {n_skip} skipped "
          f"(documented), {failures} FAILED ==")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
