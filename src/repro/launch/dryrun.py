"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 'data' x 'model'; --multi-pod adds
     the 'pod' axis: 2 x 16 x 16 = 512 chips),
  2. assembles the step function + ShapeDtypeStruct inputs + shardings
     from repro.launch.specs,
  3. ``jax.jit(step, in_shardings, out_shardings).lower(*args).compile()``,
  4. prints ``compiled.memory_analysis()`` (proves the cell fits) and
     ``cost_analysis()`` FLOPs/bytes, and parses the HLO for collective
     bytes (all-gather / all-reduce / reduce-scatter / all-to-all /
     collective-permute) — the three roofline terms' raw inputs,
  5. appends a JSON record to --out for benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results/dryrun.jsonl
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, get_config
from repro.distributed import shard as shard_lib
from repro.launch.mesh import make_production_mesh, mesh_data_axes
from repro.launch.specs import SHAPES, build_cell, shape_skips
from repro.perfmodel.hlo import collective_bytes_from_text


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             verbose: bool = True, kv_quant: bool = False) -> dict:
    import dataclasses
    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    skip = shape_skips(cfg, shape)
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "skip", "reason": skip,
        "kv_quant": kv_quant,
    }
    if skip:
        if verbose:
            print(f"[skip] {arch} x {shape}: {skip}")
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with shard_lib.use_mesh(mesh, mesh_data_axes(mesh)):
        cell = build_cell(cfg, shape, mesh)
        # donate params/opt (train) or caches (decode): the production step
        # reuses those buffers in place, and memory_analysis should reflect it
        donate = (0, 1) if cell.kind == "train" else ()
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=donate,
        )
        with mesh:
            lowered = jitted.lower(*cell.args)
            compiled = lowered.compile()
            # collectives only exist post-SPMD-partitioning: parse the
            # compiled module, not the lowered one
            coll = collective_bytes_from_text(compiled.as_text())
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()

    elapsed = time.time() - t0
    n_dev = mesh.devices.size
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    rec.update(
        status="ok",
        devices=int(n_dev),
        lower_compile_s=round(elapsed, 1),
        flops_total=flops,
        bytes_total=bytes_acc,
        collective_bytes=coll,
        memory=_mem_dict(mem),
    )
    if verbose:
        per_dev_gb = rec["memory"].get("per_device_total_gb", float("nan"))
        print(f"[ok] {arch} x {shape} ({rec['mesh']}): "
              f"{flops/1e12:.1f} TFLOP, {bytes_acc/1e9:.1f} GB accessed, "
              f"coll={coll['total']/1e9:.2f} GB, "
              f"mem/dev={per_dev_gb:.2f} GiB, {elapsed:.0f}s")
        print(f"  memory_analysis: {rec['memory']}")
    return rec


def _mem_dict(mem) -> dict:
    out = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    total = (out.get("argument_size_in_bytes", 0)
             + out.get("output_size_in_bytes", 0)
             + out.get("temp_size_in_bytes", 0)
             - out.get("alias_size_in_bytes", 0))
    out["per_device_total_gb"] = round(total / 2**30, 3)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (see configs/)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode cells (perf variant)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()
    # 512 placeholder host devices let jax.make_mesh build the production
    # 16x16 single-pod and 2x16x16 multi-pod meshes; set here, before the
    # first backend initializes, and never at import
    jax.config.update("jax_num_cpu_devices", 512)

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, multi_pod=mp, kv_quant=args.kv_quant)
            except Exception as e:  # a failing cell is a bug in the system
                failures += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "error", "error": repr(e)}
                print(f"[FAIL] {arch} x {shape}: {e}")
                traceback.print_exc()
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
