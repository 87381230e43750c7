#!/usr/bin/env python3
"""Chip smoke test: drive the main path once on a TPU and check what comes out.

One process, phases in order; any failed phase makes the script exit
non-zero, and only a run in which every phase passed prints the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

1. device — the backend must be a TPU.  There is no CPU fallback: any other
   platform exits non-zero before any model work, naming what was found.
2. serve — qwen2-1.5b at its published widths (random weights from a seed)
   through the serving entry point of ``examples/serve_lm.py``: the
   ``ServeEngine`` over the paged KV cache, ``attn_read="gather"``.  12
   requests (more than the 8 slots, so queued requests are admitted as
   others retire), prompts of 64-1024 seeded random tokens, 32 new tokens
   each, half greedy and half at temperature 0.8.  Checks: every request
   finished with exactly its budget, no page leaked, and each greedy
   request's final prefill-chunk logits match ``api.prefill`` (the training
   forward, independent of the paged cache) over the whole prompt within
   ``LOGIT_TOL``.
3. gather — the runahead gather kernel, compiled (never interpreted), on the
   model's own embedding table (151936 x 1536 bf16): 65,536 Zipf-skewed
   indices at depth 1, 2 and 4, bitwise equal to ``jnp.take``; and
   ``gather_bag`` at fan-in 16 against its reference within one bf16 ulp.

``--chips 4`` runs only the sharded train step and what it is compared with,
on a (data 2, model 2) mesh: the same step at 4 layers on one device and on
the mesh (loss and grad norm within ``TRAIN_TOL``), then qwen2-1.5b at full
width for 3 steps at batch 8 x 1024 with the AdamW state created sharded
(finite loss).

Compile seconds, peak device bytes and each phase's wall time are printed
as information, not as metrics.  JAX's persistent compilation cache is on
(``repro.launch.compile_cache``).

Usage:
  python3 chip_smoke.py              # one chip
  python3 chip_smoke.py --chips 4    # one host with four chips
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

SEED = 0
MODEL = "qwen2-1.5b"
SERVE = dict(slots=8, max_len=2048, page_size=16, prefill_chunk=256,
             n_requests=12, new_tokens=32, prompt_len=(64, 1024))
# greedy prompts share this many distinct lengths, so the reference
# forward compiles once per length rather than once per request
GREEDY_LENGTHS = 3
# engine vs api.prefill: relative L2 error of the logits.  Both run bf16 with
# float32 softmax, but the chunked paged path and the training forward round
# activations differently, and the gap grows with depth (CPU rehearsal at
# full width: 2 layers 0.4%, 8 layers 1.3%).  A misplaced page or position
# makes the logits unrelated (error near 140%)
LOGIT_TOL = 0.1
GATHER = dict(n_idx=65_536, zipf_a=1.2, depths=(1, 2, 4), bag_rows=4096,
              fanin=16)
TRAIN = dict(batch=8, seq=1024, steps=3, compare_layers=4, compare_batch=4,
             compare_seq=512)
TRAIN_TOL = 1e-2        # relative, loss and grad norm: one bf16 ulp is 2^-7


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    """A result check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(msg)


def check_device(n_chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"no TPU: JAX found platform {d.platform!r} "
             f"({d.device_kind}); this script runs only on a TPU")
    if len(devices) != n_chips:
        fail(f"expected {n_chips} TPU chip(s), JAX found {len(devices)}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class CompileLog:
    """Backend compile seconds per jitted function (a persistent-cache hit
    counts its retrieval time), from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds: dict[str, float] = collections.defaultdict(float)
        self.cache_hits = 0

        def on_duration(name, secs, fun_name="?", **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds[fun_name] += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def report(self, since: dict) -> str:
        new = {k: v - since.get(k, 0.0) for k, v in self.seconds.items()
               if v - since.get(k, 0.0) > 0}
        top = sorted(new.items(), key=lambda kv: -kv[1])[:6]
        return (f"compile {sum(new.values()):.2f}s "
                + " ".join(f"{k}={v:.2f}s" for k, v in top))


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phase 2: serving at full width
# ---------------------------------------------------------------------------

def prompt_lengths(n: int, lo: int, hi: int, seed: int) -> list[int]:
    """Seeded prompt lengths in [lo, hi]; the greedy (even) requests cycle
    through ``GREEDY_LENGTHS`` shared lengths."""
    import numpy as np
    rng = np.random.default_rng(seed)
    shared = rng.integers(lo, hi + 1, GREEDY_LENGTHS)
    return [int(shared[(i // 2) % GREEDY_LENGTHS]) if i % 2 == 0
            else int(rng.integers(lo, hi + 1)) for i in range(n)]


def serve_phase(cfg, params, *, slots, max_len, page_size, prefill_chunk,
                n_requests, new_tokens, prompt_len, seed=SEED,
                logit_tol=LOGIT_TOL) -> None:
    import jax
    import numpy as np
    from examples import serve_lm
    from repro.models import api
    from repro.serve.scheduler import RequestState

    eng = serve_lm.build_engine(cfg, params, slots=slots, max_len=max_len,
                                page_size=page_size,
                                prefill_chunk=prefill_chunk,
                                capture_logits=True)
    check(eng.steps.meta["attn_read"] == "gather", str(eng.steps.meta))
    lens = prompt_lengths(n_requests, *prompt_len, seed)
    reqs = serve_lm.submit_requests(eng, lens, new_tokens=new_tokens,
                                    seed=seed)
    secs = serve_lm.serve(eng)              # asserts every page came back
    m = eng.metrics.summary()
    print(f"  serve: {len(reqs)} requests on {slots} slots, prompts "
          f"{min(lens)}-{max(lens)} tokens, {m['tokens_sampled']} tokens in "
          f"{secs:.2f}s wall, {m['steps']} steps ({m['prefill_chunks']} "
          f"prefill chunks), peak in flight {m['peak_in_flight']}, "
          f"leaked pages {eng.pool.used_pages}", flush=True)
    bad = [(r.rid, r.state.value, len(r.out_tokens)) for r in reqs
           if r.state is not RequestState.FINISHED
           or len(r.out_tokens) != new_tokens]
    check(not bad, f"requests without exactly {new_tokens} tokens: {bad}")

    @jax.jit
    def prefill_ref(params, batch):
        return api.prefill(params, batch, cfg)

    by_len = collections.defaultdict(list)
    for r in reqs:
        if r.params.temperature == 0.0:
            by_len[len(r.prompt)].append(r)
    worst = 0.0
    for n, group in sorted(by_len.items()):
        toks = np.asarray([r.prompt for r in group], np.int32)
        ref = np.asarray(prefill_ref(params, {"tokens": toks}))  # [B, V]
        got = np.stack([r.logits_log[0] for r in group])        # final chunk
        check(np.all(np.isfinite(got)), "non-finite engine logits")
        rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        err = float(np.max(np.abs(got - ref)))
        agree = int(np.sum(got.argmax(-1) == ref.argmax(-1)))
        print(f"  prefill logits, prompt {n}: |engine - api.prefill| / "
              f"|api.prefill| = {rel:.4g}, max abs error {err:.4g} (max "
              f"|logit| {float(np.max(np.abs(ref))):.4g}), argmax agrees "
              f"{agree}/{len(group)}", flush=True)
        worst = max(worst, rel)
    check(worst <= logit_tol,
          f"prefill logits off by {worst:.4g} (relative L2) > {logit_tol}")


# ---------------------------------------------------------------------------
# phase 3: the runahead gather kernel on the embedding table
# ---------------------------------------------------------------------------

def zipf_indices(n_rows: int, shape, a: float, seed: int):
    """Zipf-skewed row ids; the hot ranks land on random rows."""
    import numpy as np
    rng = np.random.default_rng(seed)
    hot = rng.permutation(n_rows)
    ranks = np.minimum(rng.zipf(a, size=shape) - 1, n_rows - 1)
    return hot[ranks].astype(np.int32)


def gather_phase(table, *, n_idx, zipf_a, depths, bag_rows, fanin,
                 seed=SEED) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import interpret_mode
    from repro.kernels.gather_runahead import ops, ref

    check(not interpret_mode(), "kernels would run interpreted")
    words = {2: jnp.uint16, 4: jnp.uint32}[table.dtype.itemsize]

    @jax.jit
    def bitwise_equal(a, b):
        return jnp.array_equal(jax.lax.bitcast_convert_type(a, words),
                               jax.lax.bitcast_convert_type(b, words))

    idx = jnp.asarray(zipf_indices(table.shape[0], (n_idx,), zipf_a, seed))
    want = jax.jit(ref.gather_ref)(table, idx)
    print(f"  gather: table {tuple(table.shape)} {table.dtype}, {n_idx} "
          f"indices, {len(np.unique(np.asarray(idx)))} distinct rows",
          flush=True)
    for depth in depths:
        gather = ops.gather.lower(table, idx, impl="runahead",
                                  depth=depth).compile()
        check("tpu_custom_call" in gather.as_text(), "not a Mosaic kernel")
        t0 = time.perf_counter()
        got = jax.block_until_ready(gather(table, idx))
        secs = time.perf_counter() - t0
        same = bool(bitwise_equal(got, want))
        print(f"  runahead gather depth {depth}: bitwise equal to jnp.take "
              f"= {same} (first call {secs * 1e3:.2f} ms wall)", flush=True)
        check(same, f"runahead gather (depth {depth}) differs from jnp.take")

    bag_idx = jnp.asarray(zipf_indices(table.shape[0], (bag_rows, fanin),
                                       zipf_a, seed + 1))
    w = jax.random.normal(jax.random.key(seed), (bag_rows, fanin),
                          jnp.float32)
    got = np.asarray(ops.gather_bag(table, bag_idx, w), np.float32)
    want = np.asarray(jax.jit(ref.gather_bag_ref)(table, bag_idx, w),
                      np.float32)
    # both sum exact bf16 products in float32 (in different orders), then
    # round to bf16: one ulp at the value, or at the output's scale near 0
    ulp = 2.0 ** -7
    rms = float(np.sqrt(np.mean(want ** 2)))
    err = np.abs(got - want)
    within = bool(np.all(err <= ulp * np.abs(want) + ulp * rms))
    print(f"  gather_bag fan-in {fanin}, {bag_rows} rows: max err "
          f"{float(err.max()):.4g} (rms {rms:.4g}), within one bf16 ulp = "
          f"{within}", flush=True)
    check(bool(np.all(np.isfinite(got))) and within, "gather_bag differs")


# ---------------------------------------------------------------------------
# --chips 4: the sharded train step
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch, seq, steps, compare_layers, compare_batch,
                compare_seq, seed=SEED, tol=TRAIN_TOL) -> None:
    import jax
    from repro.data.pipeline import synthetic_batch
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_train_step, init_train_state
    from repro.models.types import ShapeConfig
    from repro.sharding.rules import MeshRules

    def run(cfg, rules, shape, n_steps):
        built = build_train_step(cfg, shape, rules)
        state = init_train_state(cfg, rules, jax.random.key(seed))
        out = []
        for step in range(n_steps):
            state, m = built.fn(state, synthetic_batch(cfg, shape, seed, step))
            out.append({k: float(v) for k, v in m.items()})
        del state
        return out

    mesh = make_host_mesh(2, 2)
    rules = MeshRules(mesh, sequence_parallel=False)
    small = dataclasses.replace(cfg, n_layers=compare_layers,
                                name=f"{cfg.name}-{compare_layers}l")
    shape = ShapeConfig("chip_smoke_compare", "train", compare_seq,
                        compare_batch)
    one = run(small, MeshRules(make_host_mesh(1, 1), sequence_parallel=False),
              shape, 1)[0]
    four = run(small, rules, shape, 1)[0]
    print(f"  {small.name} one device: loss {one['loss']:.6f} grad norm "
          f"{one['grad_norm']:.6f}; mesh {dict(mesh.shape)}: loss "
          f"{four['loss']:.6f} grad norm {four['grad_norm']:.6f}", flush=True)
    for k in ("loss", "grad_norm"):
        rel = abs(four[k] - one[k]) / max(abs(one[k]), 1e-12)
        check(rel <= tol, f"sharded {k} off by {rel:.3g} > {tol}")

    shape = ShapeConfig("chip_smoke_train", "train", seq, batch)
    hist = run(cfg, rules, shape, steps)
    print(f"  {cfg.name} full width on {dict(mesh.shape)}, batch {batch} x "
          f"{seq}: losses " + ", ".join(f"{h['loss']:.4f}" for h in hist),
          flush=True)
    check(all(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"])
              for h in hist), f"non-finite training metrics: {hist}")


# ---------------------------------------------------------------------------

def run_phase(name: str, fn, compiles: CompileLog) -> bool:
    import jax
    since = dict(compiles.seconds)
    print(f"[{name}]", flush=True)
    t0 = time.perf_counter()
    try:
        jax.block_until_ready(fn())
        ok = True
    except Exception:  # noqa: BLE001 - report the phase, run the rest
        traceback.print_exc()
        ok = False
    secs = time.perf_counter() - t0
    print(f"[{name}] {'passed' if ok else 'FAILED'}: wall {secs:.1f}s, "
          f"{compiles.report(since)}, peak_bytes_in_use {peak_bytes()}",
          flush=True)
    return ok


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step on a 2x2 mesh")
    args = ap.parse_args()

    device = check_device(args.chips)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.configs import registry
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    compiles = CompileLog()
    cfg = registry.get(MODEL)

    if args.chips == 4:
        phases = [("train", lambda: train_phase(cfg, **TRAIN))]
    else:
        from examples import serve_lm
        params = {}

        def init():
            params.update(serve_lm.init_params(cfg, SEED))
            return params

        phases = [
            ("init", init),
            ("serve", lambda: serve_phase(cfg, params, **SERVE)),
            ("gather", lambda: gather_phase(params["embed"], **GATHER)),
        ]
    ok = True
    for name, fn in phases:
        ok = run_phase(name, fn, compiles) and ok
    print(f"compile cache hits: {compiles.cache_hits}", flush=True)
    if not ok:
        fail("a phase failed")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
