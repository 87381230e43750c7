"""Slot-batched serving model steps over a paged (or dense) KV cache.

This is the model half of the continuous-batching serving engine
(:mod:`repro.serve`): where :func:`repro.models.lm.decode_step` advances a
whole batch in lockstep from one shared scalar position, the steps here
advance a *slot batch* — every slot is an independent sequence at its own
depth, slots join and leave between steps, and the KV cache behind them is
either

* ``paged`` — one physical page pool per pattern entry, stacked over the
  ``G`` layer groups (``k_pages``/``v_pages``: ``[G, n_pages, page, Hkv,
  Dh]``) and indirected through a per-slot ``page_table`` ``[B,
  pages_per_seq]`` plus per-slot ``lengths`` ``[B]``; a layer's pool
  ``[g]`` is exactly the :mod:`repro.kernels.paged_attention` operand
  layout, so the attention read can run through that kernel
  (``attn_read="kernel"``); or
* ``dense`` — per-slot contiguous KV ``[G, B, Hkv, S+1, Dh]`` (slot ``S``
  is a write-diversion scratch row), the oracle the paged path is tested
  bit-identical against.

The stacked caches ride in the layer scan's carry, next to the
activations, and each group indexes them at its own ``g``: the token's
K/V is scattered into ``[g, page, offset]`` of the carried buffer and the
read gathers ``[g, page_table]`` from it directly.  So a step updates the
donated pool in place; passing the pools as the scan's ``xs``/``ys``
instead would slice each layer's pool out, write it back into a second,
freshly allocated stack and copy that stack into the result.

Both backends run the *same* projection / RoPE / attention / FFN code with
the same shapes; only where K/V bytes live differs.  Stale bytes in reused
pages (and the zeros vs garbage difference between the backends) sit
strictly behind the position mask of :func:`repro.models.layers
.cache_attention`, where softmax weights are exactly 0.0 — which is what
makes paged-vs-dense outputs bitwise equal, not merely close
(tests/test_serve_engine.py pins this).

Masked writes keep every step jit-compiled at a fixed shape: inactive
decode slots and prefill padding divert their write to the reserved null
page 0 (paged; rewriting the value already there) or the scratch row S
(dense), so no step ever recompiles as the batch composition changes.

Both steps name their parts with ``jax.named_scope`` (metadata only; the
compiled arithmetic is the same), so a device trace can split a step's
time: ``qkv``, ``kv_write``, ``kv_read``, ``attention``, ``o_proj``,
``mlp``, ``lm_head`` and ``sample``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import sharding
from . import layers, moe
from .lm import _lm_head
from .types import ModelConfig

NULL_PAGE = 0  # physical page 0 is reserved: idle page-table entries point here


# ---------------------------------------------------------------------------
# support / geometry
# ---------------------------------------------------------------------------

def serve_supported(cfg: ModelConfig) -> tuple[bool, str]:
    """Whether the continuous-batching serve path covers this arch."""
    if any(spec.mixer != "attn" for spec in cfg.pattern()):
        return False, "paged serving covers attention mixers only (SSM/hybrid state is slot-resident, not paged)"
    if cfg.family == "encdec":
        return False, "encoder-decoder serving needs a cross-attention cache"
    if cfg.attention_kind != "full":
        return False, "sliding-window ring caches do not page"
    if cfg.kv_quant:
        return False, "int8 KV paging (scale pages) not implemented"
    return True, ""


def serve_geometry(max_len: int, page_size: int) -> tuple[int, int]:
    """(pages_per_seq, padded_cache_len) for a max sequence length."""
    pages_per_seq = -(-max_len // page_size)
    return pages_per_seq, pages_per_seq * page_size


def init_serve_cache(cfg: ModelConfig, *, slots: int, max_len: int,
                     backend: str = "paged", page_size: int = 16,
                     n_pages: int | None = None) -> dict:
    """Serve-cache pytree.  ``paged`` pools default to full provisioning
    (every slot can hold ``max_len``) plus the null page; pass a smaller
    ``n_pages`` to create page pressure (preemption testing / memory caps)."""
    ok, why = serve_supported(cfg)
    if not ok:
        raise ValueError(f"{cfg.name}: {why}")
    dims = layers.attn_dims(cfg)
    g = cfg.n_groups
    dt = jnp.dtype(cfg.dtype)
    pages_per_seq, s_pad = serve_geometry(max_len, page_size)
    cache: dict = {"lengths": jnp.zeros((slots,), jnp.int32)}
    if backend == "paged":
        n_pages = n_pages if n_pages is not None else 1 + slots * pages_per_seq
        assert n_pages >= 2, "need at least the null page plus one real page"
        cache["page_table"] = jnp.zeros((slots, pages_per_seq), jnp.int32)
        cache["layers"] = tuple(
            {"k_pages": jnp.zeros((g, n_pages, page_size, dims.n_kv,
                                   dims.d_head), dt),
             "v_pages": jnp.zeros((g, n_pages, page_size, dims.n_kv,
                                   dims.d_head), dt)}
            for _ in cfg.pattern()
        )
    elif backend == "dense":
        cache["layers"] = tuple(
            {"k": jnp.zeros((g, slots, dims.n_kv, s_pad + 1, dims.d_head), dt),
             "v": jnp.zeros((g, slots, dims.n_kv, s_pad + 1, dims.d_head), dt)}
            for _ in cfg.pattern()
        )
    else:
        raise ValueError(f"unknown serve-cache backend {backend!r}")
    return cache


def cache_backend(cache: dict) -> str:
    return "paged" if "page_table" in cache else "dense"


def cache_seq_len(cache: dict) -> int:
    """Padded logical sequence capacity S of a serve cache."""
    layer0 = cache["layers"][0]
    if "k_pages" in layer0:
        return cache["page_table"].shape[1] * layer0["k_pages"].shape[2]
    return layer0["k"].shape[3] - 1


# ---------------------------------------------------------------------------
# sampling (device-side: the host only ever sees sampled token ids)
# ---------------------------------------------------------------------------

def _sample(logits, temps, key_data):
    """logits [..., V]; temps [...] (0 = greedy); key_data uint32 [..., 2].

    Temperature slots draw categorically from their own PRNG stream (the
    engine derives ``key_data`` from (request seed, token index), so a
    request's sampled continuation is reproducible across preemption /
    re-batching); temperature-0 slots take the argmax."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(kd, lg, t):
        key = jax.random.wrap_key_data(kd)
        return jax.random.categorical(key, lg / jnp.maximum(t, 1e-6))

    for _ in range(logits.ndim - 1):
        draw = jax.vmap(draw)
    sampled = draw(key_data, logits, temps).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


# ---------------------------------------------------------------------------
# per-layer attention with serve-cache read/write
# ---------------------------------------------------------------------------

def _write_paged(pool, g, pid, off, vals, mask):
    """Masked scatter of per-token rows into group ``g`` of a stacked pool.

    pool [G, N, page, Hkv, Dh]; pid/off [T]; vals [T, Hkv, Dh]; mask [T].
    Masked-out rows are diverted to the null page by the caller and write
    back the value already there — colliding diverted writes therefore all
    carry identical data, keeping the scatter deterministic."""
    with jax.named_scope("kv_write"):
        cur = pool[g, pid, off]
        return pool.at[g, pid, off].set(
            jnp.where(mask[:, None, None], vals, cur))


def _qkv(p, x, positions, cfg: ModelConfig):
    """Projections and RoPE: x [B,T,D] at ``positions`` [B|1,1,T]."""
    with jax.named_scope("qkv"):
        q, k, v = layers._project_qkv(p, x, x, layers.attn_dims(cfg))
        if cfg.rope_theta > 0:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
        return q, k, v


def _read_pages(pool, g, table, b, dims):
    """Gather the pages of ``table`` [b, P] (or [P] for b = 1) from group
    ``g`` of the stacked ``pool`` as [b,Hkv,S,Dh], in one gather: no layer
    slice of the pool is made first."""
    with jax.named_scope("kv_read"):
        pages = pool[g, table]                           # [b,P,page,Hkv,Dh]
        return pages.reshape(b, -1, dims.n_kv,
                             dims.d_head).transpose(0, 2, 1, 3)


def _attend(p, q, k_read, v_read, q_positions):
    """Attention over the read cache, then the output projection."""
    s_len = k_read.shape[2]
    with jax.named_scope("attention"):
        y = layers.cache_attention(q, k_read, v_read,
                                   jnp.arange(s_len)[None, :], q_positions)
    with jax.named_scope("o_proj"):
        return layers._merge_heads(p, y)


def _attn_decode(p, x, c, g, cache, active, cfg: ModelConfig,
                 attn_read: str):
    """One decode token per slot: x [B,1,D] -> (y [B,1,D], new cache ``c``).

    ``c`` is this pattern entry's cache stacked over all groups (the scan's
    carry); the layer reads and writes it at group ``g``.  ``cache``
    provides the shared ``lengths`` / ``page_table``."""
    dims = layers.attn_dims(cfg)
    lengths = cache["lengths"]
    b = x.shape[0]
    q, k, v = _qkv(p, x, lengths[:, None, None], cfg)
    k_tok = k[:, :, 0, :]                                # [B,Hkv,Dh]
    v_tok = v[:, :, 0, :]
    b_ids = jnp.arange(b)
    if "k_pages" in c:
        kp, vp = c["k_pages"], c["v_pages"]
        page = kp.shape[2]
        table = cache["page_table"]
        lp = jnp.clip(lengths // page, 0, table.shape[1] - 1)
        pid = jnp.where(active, table[b_ids, lp], NULL_PAGE)
        off = jnp.where(active, lengths % page, 0)
        kp = _write_paged(kp, g, pid, off, k_tok, active)
        vp = _write_paged(vp, g, pid, off, v_tok, active)
        new_c = {"k_pages": kp, "v_pages": vp}
        if attn_read == "kernel":
            # the Pallas paged-attention call path: repeat KV pages to the
            # query head count (GQA: kv head = q head // rep, matching the
            # repeat layout), lengths+1 counts the token just written.
            # The repeat and the kernel, which reads every page of each
            # slot's row and attends in the same pass, are the KV read of
            # this path, as the gather is of the other
            from repro.kernels.paged_attention import ops as paged_ops
            rep = dims.rep
            with jax.named_scope("kv_read"):
                kpf, vpf = kp[g], vp[g]
                if rep > 1:
                    kpf = jnp.repeat(kpf, rep, axis=2)
                    vpf = jnp.repeat(vpf, rep, axis=2)
                y = paged_ops.paged_attention(q[:, :, 0, :], kpf, vpf, table,
                                              lengths + 1)[:, :, None, :]
            with jax.named_scope("o_proj"):
                return layers._merge_heads(p, y), new_c
        k_read = _read_pages(kp, g, table, b, dims)
        v_read = _read_pages(vp, g, table, b, dims)
    else:
        kc, vc = c["k"], c["v"]                          # [G,B,Hkv,S+1,Dh]
        s_pad = kc.shape[3] - 1
        s_idx = jnp.where(active, jnp.clip(lengths, 0, s_pad - 1), s_pad)
        with jax.named_scope("kv_write"):
            kc = kc.at[g, b_ids, :, s_idx, :].set(k_tok)
            vc = vc.at[g, b_ids, :, s_idx, :].set(v_tok)
        new_c = {"k": kc, "v": vc}
        with jax.named_scope("kv_read"):
            k_read, v_read = kc[g, :, :, :s_pad, :], vc[g, :, :, :s_pad, :]
    return _attend(p, q, k_read, v_read, lengths[:, None]), new_c


def _attn_prefill(p, x, c, g, cache, slot, positions, write_mask,
                  cfg: ModelConfig):
    """Prefill chunk for one slot: x [1,C,D] -> (y [1,C,D], new cache ``c``).

    Writes the chunk's K/V into the slot's cache region, then attends the
    chunk queries over the slot's full cache (earlier chunks included), so
    chunked prefill is exact — not an approximation of whole-prompt
    prefill.  ``c`` and ``g`` as in :func:`_attn_decode`."""
    dims = layers.attn_dims(cfg)
    chunk = x.shape[1]
    q, k, v = _qkv(p, x, positions[None, None, :], cfg)
    k_tok = k[0].transpose(1, 0, 2)                      # [C,Hkv,Dh]
    v_tok = v[0].transpose(1, 0, 2)
    if "k_pages" in c:
        kp, vp = c["k_pages"], c["v_pages"]
        page = kp.shape[2]
        table_row = cache["page_table"][slot]            # [P]
        lp = jnp.clip(positions // page, 0, table_row.shape[0] - 1)
        pid = jnp.where(write_mask, table_row[lp], NULL_PAGE)
        off = jnp.where(write_mask, positions % page, jnp.arange(chunk) % page)
        kp = _write_paged(kp, g, pid, off, k_tok, write_mask)
        vp = _write_paged(vp, g, pid, off, v_tok, write_mask)
        new_c = {"k_pages": kp, "v_pages": vp}
        k_read = _read_pages(kp, g, table_row, 1, dims)
        v_read = _read_pages(vp, g, table_row, 1, dims)
    else:
        kc, vc = c["k"], c["v"]                          # [G,B,Hkv,S+1,Dh]
        s_pad = kc.shape[3] - 1
        pos_w = jnp.where(write_mask, jnp.clip(positions, 0, s_pad - 1), s_pad)
        with jax.named_scope("kv_write"):
            kc = kc.at[g, slot, :, pos_w, :].set(k_tok)
            vc = vc.at[g, slot, :, pos_w, :].set(v_tok)
        new_c = {"k": kc, "v": vc}
        with jax.named_scope("kv_read"):
            k_read = kc[g, slot, :, :s_pad, :][None]
            v_read = vc[g, slot, :, :s_pad, :][None]
    return _attend(p, q, k_read, v_read, positions[None, :]), new_c


# ---------------------------------------------------------------------------
# engine steps
# ---------------------------------------------------------------------------

def _block(p, x, c, attn_fn, cfg: ModelConfig, spec):
    h = layers.apply_norm(p["mixer_norm"], x, cfg)
    h, new_c = attn_fn(p["attn"], h, c)
    x = x + h
    if spec.ffn != "none":
        h = layers.apply_norm(p["ffn_norm"], x, cfg)
        with jax.named_scope("mlp"):
            if spec.ffn == "moe":
                h, _ = moe.apply_moe(p["moe"], h, cfg)
            else:
                h = layers.apply_mlp(p["mlp"], h)
        x = x + h
    return x, new_c


def _scan_groups(params, x, caches, attn_fn, cfg: ModelConfig):
    """Run the layer groups over ``x`` -> (x, new ``caches``).

    The weights are the scan's ``xs``, with each group's index ``g``; the
    stacked caches (one per pattern entry) ride in its carry, so every
    layer updates them in place through ``attn_fn(p, h, c, g)``."""
    pattern = cfg.pattern()

    def group_body(carry, inp):
        x, caches = carry
        group_params, g = inp
        caches = list(caches)
        for p_idx, spec in enumerate(pattern):
            x, caches[p_idx] = _block(
                group_params[p_idx], x, caches[p_idx],
                lambda pa, h, c: attn_fn(pa, h, c, g), cfg, spec)
        return (x, tuple(caches)), None

    (x, caches), _ = jax.lax.scan(
        group_body, (x, caches),
        (params["groups"], jnp.arange(cfg.n_groups, dtype=jnp.int32)))
    return x, caches


def serve_decode_step(params, tokens, active, temps, key_data, cache,
                      cfg: ModelConfig, *, attn_read: str = "gather",
                      sampling: bool = True, return_logits: bool = False):
    """One continuous-batching decode step.

    tokens i32 [B] (each slot's pending input token), active bool [B],
    temps f32 [B], key_data uint32 [B,2].  Active slots append their
    token's K/V at position ``lengths[b]`` and advance; inactive slots are
    write-diverted and their outputs are garbage the host ignores.
    Returns ``(next_tokens [B], logits [B,V] | None, new cache)``.
    """
    x = jnp.take(params["embed"], tokens[:, None], axis=0)     # [B,1,D]

    def attn_fn(pa, h, c, g):
        return _attn_decode(pa, h, c, g, cache, active, cfg, attn_read)

    x, new_layers = _scan_groups(params, x, cache["layers"], attn_fn, cfg)
    x = layers.apply_norm(params["final_norm"], x, cfg)
    with jax.named_scope("lm_head"):
        logits = (x[:, 0, :] @ _lm_head(params, cfg)).astype(jnp.float32)
        logits = sharding.constrain(logits, "decode_logits")
    with jax.named_scope("sample"):
        if sampling:
            next_tokens = _sample(logits, temps, key_data)
        else:
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["lengths"] = cache["lengths"] + active.astype(jnp.int32)
    return next_tokens, (logits if return_logits else None), new_cache


def serve_prefill_chunk(params, tokens, n_valid, slot, temp, key_data, cache,
                        cfg: ModelConfig, *, sampling: bool = True,
                        return_logits: bool = False):
    """Prefill ``n_valid`` prompt tokens (padded to the fixed chunk length
    ``C = tokens.shape[0]``) for one slot.

    Runs a full forward over the chunk, appending K/V for valid positions
    starting at ``lengths[slot]`` — chunk k > 0 attends to the slot's
    earlier chunks through the cache, so any chunking of a prompt yields
    the same cache state.  Returns ``(sampled_token, logits [V] | None,
    new cache)`` where the sample is drawn from the last valid position's
    logits (only meaningful on the final chunk of a prompt).
    """
    chunk = tokens.shape[0]
    lengths = cache["lengths"]
    start = lengths[slot]
    positions = start + jnp.arange(chunk, dtype=jnp.int32)
    write_mask = jnp.arange(chunk) < n_valid
    x = jnp.take(params["embed"], tokens[None, :], axis=0)     # [1,C,D]

    def attn_fn(pa, h, c, g):
        return _attn_prefill(pa, h, c, g, cache, slot, positions, write_mask,
                             cfg)

    x, new_layers = _scan_groups(params, x, cache["layers"], attn_fn, cfg)
    x = layers.apply_norm(params["final_norm"], x, cfg)
    last = jax.lax.dynamic_index_in_dim(
        x[0], jnp.clip(n_valid - 1, 0, chunk - 1), 0, keepdims=False)
    with jax.named_scope("lm_head"):
        logits = (last @ _lm_head(params, cfg)).astype(jnp.float32)
    with jax.named_scope("sample"):
        if sampling:
            token = _sample(logits, temp, key_data)
        else:
            token = jnp.argmax(logits).astype(jnp.int32)
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["lengths"] = lengths.at[slot].add(
        jnp.asarray(n_valid, jnp.int32))
    return token, (logits if return_logits else None), new_cache
