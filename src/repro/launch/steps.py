"""Train / serve step functions + their jit/sharding assembly.

``build_train_step`` / ``build_serve_step`` return (jitted_fn, abstract
inputs, shardings) so the same assembly serves the real launcher, the
integration tests (host meshes) and the dry-run (512 placeholder devices).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro import sharding as shard_ctx
from repro.models import api, lm
from repro.models.types import ModelConfig, ShapeConfig
from repro.optim import adamw
from repro.sharding.rules import MeshRules


def make_optimizer(cfg: ModelConfig, lr: float = 3e-4) -> adamw.AdamWConfig:
    return adamw.AdamWConfig(lr=lr, moment_dtype=cfg.adam_dtype)


def train_step(state: dict, batch: dict, cfg: ModelConfig,
               opt: adamw.AdamWConfig, transform=None):
    """Loss + grads + AdamW update; returns (new_state, metrics).

    ``cfg.accum_steps > 1`` runs gradient accumulation: the global batch is
    split into microbatches scanned sequentially, shrinking every transient
    activation proportionally (how the 100B+ train cells fit HBM)."""
    accum = max(1, cfg.accum_steps)
    if accum == 1:
        loss, grads = jax.value_and_grad(
            lambda p: api.train_loss(p, batch, cfg)
        )(state["params"])
    else:
        micro = jax.tree.map(
            lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
            batch)
        params = state["params"]

        def mb_step(acc, mb):
            g_acc, l_acc = acc
            # barrier: stops XLA hoisting the (loop-invariant) FSDP weight
            # all-gathers out of the accumulation loop, which would leave
            # every layer's full weights live simultaneously
            l, g = jax.value_and_grad(
                lambda p: api.train_loss(lm.grad_safe_barrier(p), mb, cfg))(params)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + l), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), _ = jax.lax.scan(mb_step, (zeros, jnp.float32(0.0)),
                                        micro)
        grads = jax.tree.map(lambda g: g / accum, grads)
        loss = loss / accum
    new_state = adamw.apply_updates(state, grads, cfg=opt, transform=transform)
    metrics = {"loss": loss, "grad_norm": adamw.global_norm(grads)}
    return new_state, metrics


def serve_step(params, tokens, cache, cfg: ModelConfig):
    logits, new_cache = api.decode(params, tokens, cache, cfg)
    return logits, new_cache


def prefill_step(params, batch, cfg: ModelConfig):
    return api.prefill(params, batch, cfg)


@dataclasses.dataclass
class BuiltStep:
    fn: Any                   # jitted
    args_abs: tuple           # abstract example args (ShapeDtypeStructs)
    in_shardings: tuple
    rules: MeshRules


def abstract_state(cfg: ModelConfig, opt: adamw.AdamWConfig):
    params_abs = api.abstract_params(cfg)
    return jax.eval_shape(lambda: adamw.init_state(params_abs, opt))


def init_train_state(cfg: ModelConfig, rules: MeshRules, key,
                     opt: adamw.AdamWConfig | None = None) -> dict:
    """Random params and zero AdamW moments, created already sharded: one jit
    whose ``out_shardings`` are ``rules.state_specs``, so no device ever
    holds the whole state (full-width qwen2-1.5b state is ~15 GB)."""
    opt = opt or make_optimizer(cfg)
    state_sh = rules.named(rules.state_specs(abstract_state(cfg, opt)))
    def init_train_state(key):
        return adamw.init_state(api.init_params(key, cfg), opt)

    return jax.jit(init_train_state, out_shardings=state_sh)(key)


def build_train_step(cfg: ModelConfig, shape: ShapeConfig, rules: MeshRules,
                     transform=None) -> BuiltStep:
    opt = make_optimizer(cfg)
    state_abs = abstract_state(cfg, opt)
    batch_abs = api.input_specs(cfg, shape)
    state_sh = rules.named(rules.state_specs(state_abs))
    batch_sh = rules.named(rules.batch_specs(batch_abs))

    def fn(state, batch):
        with shard_ctx.constrainer(rules.constrain_fn()):
            return train_step(state, batch, cfg, opt, transform)

    # out_shardings pins the new state to the input specs so the state's
    # sharding cannot drift across steps / checkpoint-restore cycles
    metrics_sh = {"loss": None, "grad_norm": None}
    jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metrics_sh),
                     donate_argnums=(0,))
    return BuiltStep(jitted, (state_abs, batch_abs), (state_sh, batch_sh), rules)


def build_serve_step(cfg: ModelConfig, shape: ShapeConfig,
                     rules: MeshRules) -> BuiltStep:
    params_abs = api.abstract_params(cfg)
    cache_abs = api.abstract_cache(cfg, shape)
    tokens_abs = api.input_specs(cfg, shape)["tokens"]
    params_sh = rules.named(rules.param_specs(params_abs))
    cache_sh = rules.named(rules.cache_specs(cache_abs, shape.global_batch))
    tokens_sh = rules.named(rules.batch_specs({"tokens": tokens_abs}))["tokens"]

    def fn(params, tokens, cache):
        with shard_ctx.constrainer(rules.constrain_fn()):
            return serve_step(params, tokens, cache, cfg)

    jitted = jax.jit(fn, in_shardings=(params_sh, tokens_sh, cache_sh),
                     donate_argnums=(2,))
    return BuiltStep(jitted, (params_abs, tokens_abs, cache_abs),
                     (params_sh, tokens_sh, cache_sh), rules)


def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                       rules: MeshRules) -> BuiltStep:
    params_abs = api.abstract_params(cfg)
    batch_abs = api.input_specs(cfg, shape)
    params_sh = rules.named(rules.param_specs(params_abs))
    batch_sh = rules.named(rules.batch_specs(batch_abs))

    def fn(params, batch):
        with shard_ctx.constrainer(rules.constrain_fn()):
            return prefill_step(params, batch, cfg)

    jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
    return BuiltStep(jitted, (params_abs, batch_abs), (params_sh, batch_sh),
                     rules)


@dataclasses.dataclass
class ServeSteps:
    """Jitted step pair + cache factory for the continuous-batching engine.

    ``decode(params, tokens, active, temps, key_data, cache)`` and
    ``prefill(params, tokens, n_valid, slot, temp, key_data, cache)`` both
    donate the cache argument, so the page pools are updated in place
    across engine steps.  Shapes are fixed at build time (slot count,
    padded cache length, prefill chunk), so each step compiles exactly
    once no matter how the batch composition churns.
    """

    decode: Any
    prefill: Any
    init_cache: Any          # () -> concrete serve-cache pytree
    cache_abs: Any
    meta: dict


def build_serve_engine_steps(cfg: ModelConfig, *, slots: int, max_len: int,
                             backend: str = "paged", page_size: int = 16,
                             n_pages: int | None = None,
                             attn_read: str = "gather",
                             sampling: bool = True,
                             return_logits: bool = False,
                             rules: MeshRules | None = None) -> ServeSteps:
    """Assemble the continuous-batching serve steps (paged or dense cache).

    With ``rules`` the model's activation constraints are installed (the
    engine then runs under that mesh); without, the steps are plain jits
    for single-process serving and tests.
    """
    import contextlib

    def ctx():
        return (shard_ctx.constrainer(rules.constrain_fn()) if rules
                else contextlib.nullcontext())

    def make_cache():
        return api.init_serve_cache(cfg, slots=slots, max_len=max_len,
                                    backend=backend, page_size=page_size,
                                    n_pages=n_pages)

    def decode_fn(params, tokens, active, temps, key_data, cache):
        with ctx():
            return api.serve_decode(params, tokens, active, temps, key_data,
                                    cache, cfg, attn_read=attn_read,
                                    sampling=sampling,
                                    return_logits=return_logits)

    def prefill_fn(params, tokens, n_valid, slot, temp, key_data, cache):
        with ctx():
            return api.serve_prefill(params, tokens, n_valid, slot, temp,
                                     key_data, cache, cfg, sampling=sampling,
                                     return_logits=return_logits)

    return ServeSteps(
        decode=jax.jit(decode_fn, donate_argnums=(5,)),
        prefill=jax.jit(prefill_fn, donate_argnums=(6,)),
        init_cache=jax.jit(make_cache),
        cache_abs=jax.eval_shape(make_cache),
        meta=dict(slots=slots, max_len=max_len, backend=backend,
                  page_size=page_size, n_pages=n_pages, attn_read=attn_read),
    )


def build_step(cfg: ModelConfig, shape: ShapeConfig, rules: MeshRules) -> BuiltStep:
    if shape.kind == "train":
        return build_train_step(cfg, shape, rules)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, rules)
    return build_serve_step(cfg, shape, rules)
