"""End-to-end training driver: a ~100M-param qwen2-family model for a few
hundred steps on the host mesh, with the full production stack — sharded
train step, runahead data loader, async checkpointing, straggler watchdog,
crash recovery.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  PYTHONPATH=src python examples/train_lm.py --steps 300 --arch qwen2-1.5b

  # published widths on a TPU host (4 chips: a (data 2, model 2) mesh)
  PYTHONPATH=src python examples/train_lm.py --no-reduced --seq 1024

(Defaults are sized for CPU smoke: a reduced-width model, 200 steps.  The
params and AdamW moments are created already sharded over the mesh, so the
full-width state never sits on one device.)
"""
import argparse
import dataclasses
import tempfile
import time

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import registry
from repro.data.pipeline import RunaheadLoader, synthetic_batch
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_train_step, init_train_state
from repro.models.types import ShapeConfig
from repro.runtime.fault_tolerance import StragglerWatchdog, TrainDriver
from repro.sharding.rules import MeshRules


def build_100m_config(arch: str, reduced: bool):
    cfg = registry.get(arch)
    if reduced:
        # ~100M params: 12L x 768 with the arch's own family structure
        cfg = dataclasses.replace(
            cfg, name=cfg.name + "-100m", n_layers=12, d_model=768,
            n_heads=12, n_kv_heads=4, d_head=64, d_ff=2048,
            vocab_size=32_000, accum_steps=1)
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=registry.list_archs())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="~100M-param reduced width (--no-reduced: the "
                         "published widths)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = build_100m_config(args.arch, args.reduced)
    shape = ShapeConfig("train_custom", "train", args.seq, args.batch)
    n_dev = len(jax.devices())
    mesh = make_host_mesh(min(2, n_dev), max(1, n_dev // 2)) \
        if n_dev > 1 else make_host_mesh(1, 1)
    rules = MeshRules(mesh, sequence_parallel=False)
    built = build_train_step(cfg, shape, rules)

    state = init_train_state(cfg, rules, jax.random.key(0))
    n_params = sum(int(x.size) for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M devices={n_dev} "
          f"mesh={dict(mesh.shape)}")

    loader = RunaheadLoader(
        lambda step: synthetic_batch(cfg, shape, seed=0, step=step), depth=2)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    ck = Checkpointer(ckpt_dir)
    wd = StragglerWatchdog(on_straggler=lambda s, t, m: print(
        f"  [watchdog] step {s}: {t:.2f}s vs median {m:.2f}s"))

    driver = TrainDriver(built.fn, loader.get, ck, checkpoint_every=50,
                         watchdog=wd)
    t0 = time.time()
    state, hist = driver.run(state, args.steps)
    dt = time.time() - t0
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"steps={len(hist)} loss {first:.3f} -> {last:.3f} "
          f"({dt/len(hist)*1e3:.0f} ms/step) ckpts={ck.all_steps()} "
          f"dir={ckpt_dir}")
    assert last < first, "loss did not decrease"
    loader.close()


if __name__ == "__main__":
    main()
