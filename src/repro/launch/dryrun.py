import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init), which is why they precede the module docstring's
friends below.  Run as:

    PYTHONPATH=src python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Artifacts (memory_analysis, cost_analysis, collective bytes, op census) are
written to artifacts/dryrun/<arch>__<shape>__<mesh>.json; the roofline
benchmark (benchmarks/roofline.py) reads them.
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

from repro.configs import registry
from repro.launch import hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.models.types import SHAPES, cell_supported
from repro.sharding.rules import MeshRules

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_overrides: dict | None = None,
             tag: str = "", cfg_overrides: dict | None = None) -> dict:
    cfg = registry.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ok, reason = cell_supported(cfg, shape)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "tag": tag,
    }
    if not ok:
        record.update(status="skipped", reason=reason)
        return record

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = MeshRules(mesh, multi_pod=multi_pod, **(rules_overrides or {}))
    built = build_step(cfg, shape, rules)
    compiled = built.fn.lower(*built.args_abs).compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()

    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    per_dev = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
    }
    loop_aware = hlo.analyze(text)
    record.update(
        status="ok",
        chips=n_chips,
        compile_seconds=round(time.time() - t0, 1),
        memory_analysis=per_dev,
        peak_device_bytes=(mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes
                           + mem.temp_size_in_bytes),
        # raw XLA cost analysis counts each while body ONCE; the loop-aware
        # numbers multiply through known_trip_count (launch/hlo.py)
        xla_flops_raw=cost.get("flops", 0.0),
        xla_bytes_raw=cost.get("bytes accessed", 0.0),
        flops=loop_aware["flops"],
        bytes_min=loop_aware["bytes_min"],
        bytes_max=loop_aware["bytes_max"],
        collectives=loop_aware["collectives"],
        collectives_raw=hlo.collective_bytes(text),
        op_census={k: v for k, v in sorted(
            hlo.op_census(text).items(), key=lambda kv: -kv[1])[:40]},
    )
    return record


def save(record: dict) -> pathlib.Path:
    ART_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"__{record['tag']}" if record.get("tag") else ""
    path = ART_DIR / f"{record['arch']}__{record['shape']}__{record['mesh']}{tag}.json"
    path.write_text(json.dumps(record, indent=2, default=str))
    return path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=registry.list_archs())
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--tag", default="", help="artifact suffix (perf variants)")
    ap.add_argument("--sequence-parallel", action="store_true")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "blocked", "triangular"])
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (decode cells)")
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--accum", type=int, default=None)
    args = ap.parse_args()

    archs = registry.list_archs() if args.all or not args.arch else [args.arch]
    shapes = sorted(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    overrides = {}
    if args.sequence_parallel:
        overrides["sequence_parallel"] = True
    cfg_overrides = {}
    if args.attn_impl:
        cfg_overrides["attn_impl"] = args.attn_impl
    if args.kv_quant:
        cfg_overrides["kv_quant"] = True
    if args.capacity_factor is not None:
        cfg_overrides["capacity_factor"] = args.capacity_factor
    if args.accum is not None:
        cfg_overrides["accum_steps"] = args.accum

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, mp, overrides, args.tag,
                                   cfg_overrides)
                except Exception as e:  # noqa: BLE001 - report and continue
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "pod2x16x16" if mp else "pod16x16",
                           "status": "error", "tag": args.tag,
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                path = save(rec)
                if rec["status"] == "ok":
                    gb = rec["peak_device_bytes"] / 2**30
                    print(f"OK   {label}: {gb:.2f} GiB/dev, "
                          f"{rec['flops']/1e12:.1f} TF, "
                          f"{rec['compile_seconds']}s -> {path.name}", flush=True)
                elif rec["status"] == "skipped":
                    print(f"SKIP {label}: {rec['reason']}", flush=True)
                else:
                    print(f"FAIL {label}: {rec['error']}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
