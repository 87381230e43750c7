"""Serving example: the continuous-batching engine over a paged KV cache.

Serves a model at its published widths (qwen2-1.5b by default) with random
weights from a seed.  Mixed-length requests go to
:class:`repro.serve.ServeEngine` — chunked prefill, slot-batched decode,
per-request sampling temperatures, streamed tokens — and the script prints
each request's stream plus the engine metrics.  ``--smoke`` serves the
reduced same-family config (``d_model=64``) instead, small enough for the
CPU.  ``--legacy`` keeps the old lockstep batch loop (every sequence the
same length, one shared position) for comparison.

``chip_smoke.py`` drives the same functions (:func:`build_engine`,
:func:`submit_requests`, :func:`serve`) on the chip.

Usage:
  PYTHONPATH=src python examples/serve_lm.py                  # on a TPU
  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_lm.py --smoke
  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_lm.py --smoke --legacy
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import compile_cache
from repro.models import api


def init_params(cfg, seed: int = 0):
    """Random weights from ``seed``, created on the device in one jit."""
    def init_params(key):
        return api.init_params(key, cfg)
    return jax.jit(init_params)(jax.random.key(seed))


def build_engine(cfg, params, *, slots: int, max_len: int,
                 prefill_chunk: int, page_size: int = 16,
                 backend: str = "paged", capture_logits: bool = False):
    from repro.serve import ServeEngine

    ok, why = api.serve_supported(cfg)
    if not ok:
        raise SystemExit(f"{cfg.name}: {why} (use --legacy)")
    return ServeEngine(cfg, params, slots=slots, max_len=max_len,
                       page_size=page_size, prefill_chunk=prefill_chunk,
                       backend=backend, capture_logits=capture_logits)


def submit_requests(eng, prompt_lens, *, new_tokens: int, seed: int = 0,
                    stream: bool = False):
    """One request per prompt length, with seeded random prompt tokens;
    even-numbered requests are greedy, odd-numbered ones sample at
    temperature 0.8."""
    rng = np.random.default_rng(seed)
    return [eng.submit(
        rng.integers(0, eng.cfg.vocab_size, int(plen)).tolist(),
        max_new_tokens=new_tokens,
        temperature=0.8 if i % 2 else 0.0, seed=i,
        stream_cb=(lambda tok, r: print(f"  r{r.rid} -> {tok}", flush=True))
        if stream else None)
        for i, plen in enumerate(prompt_lens)]


def serve(eng) -> float:
    """Run every submitted request to completion; returns the wall seconds.
    The engine must hand every page back."""
    t0 = time.perf_counter()
    eng.run()
    jax.block_until_ready(eng.cache)
    dt = time.perf_counter() - t0
    eng.assert_no_leaks()
    return dt


def run_engine(args, cfg):
    params = init_params(cfg)
    eng = build_engine(cfg, params, slots=args.batch, max_len=args.cache_len,
                       prefill_chunk=args.prefill_chunk, backend=args.backend)
    lo, hi = args.prompt_len
    lens = np.random.default_rng(1).integers(lo, hi + 1, args.batch + 2)
    reqs = submit_requests(eng, lens, new_tokens=args.tokens,
                           stream=args.stream)    # more requests than slots
    dt = serve(eng)
    for r in reqs:
        print(f"r{r.rid}: prompt[{len(r.prompt)}] -> {r.out_tokens[:10]}"
              f"{'...' if len(r.out_tokens) > 10 else ''} "
              f"({r.done_reason()}, ttft {r.metrics.ttft * 1e3:.0f} ms)")
    m = eng.metrics.summary()
    print(f"arch={cfg.name} backend={args.backend} "
          f"{m['tokens_sampled']} tokens in {dt:.1f}s "
          f"({m['tokens_sampled'] / dt:.0f} tok/s), "
          f"occupancy {m['occupancy_mean']:.0%}, "
          f"steps {m['steps']} ({m['prefill_chunks']} prefill chunks)")


def run_legacy(args, cfg):
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_serve_step
    from repro.models.types import ShapeConfig
    from repro.sharding.rules import MeshRules

    shape = ShapeConfig("serve_custom", "decode", args.cache_len, args.batch)
    n_dev = len(jax.devices())
    mesh = make_host_mesh(min(2, n_dev), max(1, n_dev // 2)) \
        if n_dev > 1 else make_host_mesh(1, 1)
    rules = MeshRules(mesh)
    built = build_serve_step(cfg, shape, rules)

    params = jax.device_put(init_params(cfg),
                            rules.named(rules.param_specs(
                                api.abstract_params(cfg))))
    cache = api.init_cache(cfg, args.batch, args.cache_len)
    cache = jax.device_put(
        cache, rules.named(rules.cache_specs(cache, args.batch)))

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (args.batch, 1)),
                         jnp.int32)
    generated = [tokens]
    t0 = time.time()
    for _ in range(args.tokens):
        logits, cache = built.fn(params, tokens, cache)
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        generated.append(tokens)
    dt = time.time() - t0
    seqs = np.concatenate([np.asarray(t) for t in generated], axis=1)
    print(f"arch={cfg.name} batch={args.batch} generated {args.tokens} "
          f"tokens/seq in {dt:.1f}s ({dt/args.tokens*1e3:.0f} ms/token)")
    print("first sequence:", seqs[0][:16], "...")
    assert seqs.shape == (args.batch, args.tokens + 1)
    assert int(cache["pos"] if "pos" in cache else 0) == args.tokens


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=registry.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (d_model=64) for the CPU")
    ap.add_argument("--batch", type=int, default=8,
                    help="decode slots (engine) / batch size (--legacy)")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=None,
                    help="max tokens per slot (default 2048; 256 with "
                         "--smoke)")
    ap.add_argument("--backend", default="paged", choices=("paged", "dense"))
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they stream (engine mode)")
    ap.add_argument("--legacy", action="store_true",
                    help="old lockstep batch loop instead of the engine")
    args = ap.parse_args()
    sizes = (dict(cache_len=256, prompt_len=(2, 24), prefill_chunk=16)
             if args.smoke else
             dict(cache_len=2048, prompt_len=(64, 1024), prefill_chunk=256))
    if args.cache_len is not None:
        sizes["cache_len"] = args.cache_len
    vars(args).update(sizes)
    compile_cache.enable()
    cfg = registry.smoke(args.arch) if args.smoke else registry.get(args.arch)
    if args.legacy:
        run_legacy(args, cfg)
    else:
        run_engine(args, cfg)


if __name__ == "__main__":
    main()
