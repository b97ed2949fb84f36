"""Decoder-only LM backbone (covers 7 of the 10 assigned archs).

Layers are *stacked* (leading L dim) and executed with jax.lax.scan so the
HLO is O(1) in depth — essential for compiling 60-layer MoE models in the
multi-pod dry-run.  Per-layer heterogeneity (gemma2 local/global alternation)
is threaded through the scan as data (a per-layer window array), not as
Python branching.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist import act_sharding as act
from repro.models import flags
from repro.models import layers as L
from repro.models import moe as M

Params = dict[str, Any]
_NO_WINDOW = jnp.iinfo(jnp.int32).max


def _layer_windows(cfg: ArchConfig, n_layers: int) -> jax.Array:
    """(L,) int32: sliding-window size per layer (INT32_MAX = global)."""
    if not cfg.local_window or not cfg.local_global_period:
        return jnp.full((n_layers,), _NO_WINDOW, jnp.int32)
    idx = jnp.arange(n_layers)
    is_local = (idx % cfg.local_global_period) == 0  # even layers local
    return jnp.where(is_local, cfg.local_window, _NO_WINDOW).astype(
        jnp.int32)


def init_block(key, cfg: ArchConfig, dtype) -> Params:
    ka, km, = jax.random.split(key, 2)
    p: Params = {"ln1": jnp.zeros((cfg.d_model,), dtype),
                 "ln2": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.softcap_attn is not None:  # gemma2-style post-norms
        p["ln1_post"] = jnp.zeros((cfg.d_model,), dtype)
        p["ln2_post"] = jnp.zeros((cfg.d_model,), dtype)
    p["attn"] = (L.init_mla(ka, cfg, dtype) if cfg.attn == "mla"
                 else L.init_gqa(ka, cfg, dtype))
    p["mlp"] = (M.init_moe(km, cfg, dtype) if cfg.moe
                else L.init_mlp(km, cfg, cfg.d_ff, dtype))
    return p


def init_decoder(cfg: ArchConfig, key) -> Params:
    dtype = cfg.dtype
    k_e, k_b, k_h = jax.random.split(key, 3)
    blocks = [init_block(k, cfg, dtype)
              for k in jax.random.split(k_b, cfg.n_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    p: Params = {
        "embed": (jax.random.normal(k_e, (cfg.padded_vocab, cfg.d_model),
                                    jnp.float32)
                  / math.sqrt(cfg.d_model)).astype(dtype),
        "blocks": stacked,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(k_h, cfg.d_model, cfg.padded_vocab,
                                    dtype)
    return p


def _block_apply(p: Params, cfg: ArchConfig, x: jax.Array,
                 positions: jax.Array, window: jax.Array) -> jax.Array:
    x = act.residual(x)
    h = L.rms_norm(x, p["ln1"])
    if cfg.attn == "mla":
        a = L.apply_mla(p["attn"], cfg, h, positions)
    else:
        a = L.apply_gqa(p["attn"], cfg, h, positions, window=window)
    if "ln1_post" in p:
        a = L.rms_norm(a, p["ln1_post"])
    x = x + a
    h = L.rms_norm(x, p["ln2"])
    f = (M.apply_moe(p["mlp"], cfg, h) if cfg.moe
         else L.apply_mlp(p["mlp"], cfg, h))
    if "ln2_post" in p:
        f = L.rms_norm(f, p["ln2_post"])
    return act.residual(x + f)


# Serving step programs name their parts with these leaf scopes
# (``jax.named_scope``; op metadata only, the compiled program is the
# same): ``embed``, ``attn_in`` (ln1, q/k/v projections, rope, qk-norm),
# ``kv_write`` (cache write addresses and the pool scatter),
# ``attention`` (the paged attention op), ``attn_out`` (output
# projection, post-norm, residual), ``mlp`` (ln2, MLP/MoE, post-norm,
# residual), ``head`` (final norm, head matmul, softcap, vocab mask) and
# ``sample``.  The layer scan itself is left unscoped; it carries the
# page pool in place (``_layer_scan``) and moves no pool bytes of its
# own, so an op that carries none of these scopes is the compiler's.

def _embed(params: Params, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        return params["embed"][tokens] * jnp.asarray(
            math.sqrt(cfg.d_model), params["embed"].dtype)


def _head(params: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Final norm, head matmul, softcap, vocab mask -> float32 logits."""
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"])
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        return L.mask_vocab(
            L.softcap((x @ head).astype(jnp.float32), cfg.softcap_logits),
            cfg.vocab)


def _attn_out_mlp(blk: Params, cfg: ArchConfig, x: jax.Array,
                  a: jax.Array) -> jax.Array:
    """A layer's tail after its output projection: post-norm and residual
    (``attn_out``), then the MLP/MoE with its norms and residual
    (``mlp``)."""
    with jax.named_scope("attn_out"):
        if "ln1_post" in blk:
            a = L.rms_norm(a, blk["ln1_post"])
        x = x + a
    with jax.named_scope("mlp"):
        h = L.rms_norm(x, blk["ln2"])
        f = (M.apply_moe(blk["mlp"], cfg, h) if cfg.moe
             else L.apply_mlp(blk["mlp"], cfg, h))
        if "ln2_post" in blk:
            f = L.rms_norm(f, blk["ln2_post"])
        return x + f


def forward_decoder(params: Params, cfg: ArchConfig, tokens: jax.Array, *,
                    remat: bool = True) -> jax.Array:
    """tokens (B, S) -> logits (B, S, V)."""
    b, s = tokens.shape
    x = params["embed"][tokens] * jnp.asarray(
        math.sqrt(cfg.d_model), params["embed"].dtype)
    x = act.batch_seq(x)
    positions = jnp.arange(s)
    windows = _layer_windows(cfg, cfg.n_layers)

    def body(x, inp):
        blk, window = inp
        return _block_apply(blk, cfg, x, positions, window), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (params["blocks"], windows),
                        unroll=flags.scan_unroll(cfg.n_layers))
    x = L.rms_norm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = act.constrain(x @ head, "dp", None, "model")
    return L.mask_vocab(
        L.softcap(logits.astype(jnp.float32), cfg.softcap_logits),
        cfg.vocab)


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def cache_spec_decoder(cfg: ArchConfig, batch: int, max_seq: int
                       ) -> dict[str, jax.ShapeDtypeStruct]:
    dt = cfg.dtype
    lyr = cfg.n_layers
    if cfg.attn == "mla":
        # head-free latent leaves (kv_lora + qk_rope bytes per position,
        # vs 2*H*dh for dense KV) — see layers.mla_latents for why no
        # singleton head dim may appear here.
        m = cfg.mla
        return {
            "c_kv": jax.ShapeDtypeStruct((lyr, batch, max_seq, m.kv_lora),
                                         dt),
            "k_rope": jax.ShapeDtypeStruct(
                (lyr, batch, max_seq, m.qk_rope), dt),
        }
    return {
        "k": jax.ShapeDtypeStruct(
            (lyr, batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": jax.ShapeDtypeStruct(
            (lyr, batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dt),
    }


def init_cache_decoder(cfg: ArchConfig, batch: int, max_seq: int) -> Params:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec_decoder(cfg, batch, max_seq))


def prefill_decoder(params: Params, cfg: ArchConfig, tokens: jax.Array,
                    max_seq: int) -> tuple[jax.Array, Params, jax.Array]:
    """Full forward over the prompt, returning (last_logits, cache, lengths).

    The cache holds the prompt K/V (or MLA latents) padded to max_seq."""
    b, s = tokens.shape
    x = params["embed"][tokens] * jnp.asarray(
        math.sqrt(cfg.d_model), params["embed"].dtype)
    x = act.batch_seq(x)
    positions = jnp.arange(s)
    windows = _layer_windows(cfg, cfg.n_layers)
    pad = max_seq - s

    def body(x, inp):
        blk, window = inp
        h = L.rms_norm(x, blk["ln1"])
        if cfg.attn == "mla":
            c_kv, k_rope = L.mla_latents(blk["attn"], cfg, h, positions)
            a = L.apply_mla(blk["attn"], cfg, h, positions)
            ys = {"c_kv": act.constrain(
                      jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
                      "dp", "model", None),
                  "k_rope": act.constrain(
                      jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))),
                      "dp", "model", None)}
        else:
            q, kk, v = L.gqa_qkv(blk["attn"], cfg, h, positions)
            o = L.attention(q, kk, v, q_positions=positions,
                            k_positions=positions, causal=True,
                            window=window, logit_cap=cfg.softcap_attn,
                            q_chunk=cfg.q_chunk)
            a = o.reshape(b, s, -1) @ blk["attn"]["wo"]
            ys = {"k": L._kv_cache_constrain(
                      jnp.pad(kk, ((0, 0), (0, pad), (0, 0), (0, 0)))),
                  "v": L._kv_cache_constrain(
                      jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))))}
        if "ln1_post" in blk:
            a = L.rms_norm(a, blk["ln1_post"])
        x = x + a
        h = L.rms_norm(x, blk["ln2"])
        f = (M.apply_moe(blk["mlp"], cfg, h) if cfg.moe
             else L.apply_mlp(blk["mlp"], cfg, h))
        if "ln2_post" in blk:
            f = L.rms_norm(f, blk["ln2_post"])
        return act.residual(x + f), ys

    x, cache = jax.lax.scan(body, x, (params["blocks"], windows),
                            unroll=flags.scan_unroll(cfg.n_layers))
    x = L.rms_norm(x[:, -1:], params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = L.mask_vocab(
        L.softcap((x @ head).astype(jnp.float32), cfg.softcap_logits),
        cfg.vocab)
    lengths = jnp.full((b,), s, jnp.int32)
    return logits[:, 0], cache, lengths


def decode_step_decoder(params: Params, cfg: ArchConfig, tokens: jax.Array,
                        cache: Params, lengths: jax.Array
                        ) -> tuple[jax.Array, Params, jax.Array]:
    """tokens (B, 1) one new token per sequence; returns
    (logits (B, V), new_cache, new_lengths)."""
    b = tokens.shape[0]
    x = params["embed"][tokens] * jnp.asarray(
        math.sqrt(cfg.d_model), params["embed"].dtype)  # (B,1,D)
    positions = lengths  # (B,) current position of the new token
    windows = _layer_windows(cfg, cfg.n_layers)
    max_seq = (cache["c_kv"].shape[2] if cfg.attn == "mla"
               else cache["k"].shape[2])

    def body(x, inp):
        blk, window, cache_l = inp
        h = L.rms_norm(x, blk["ln1"])
        if cfg.attn == "mla":
            q_lat, q_rope = L.mla_absorbed_q(
                blk["attn"], cfg, h, positions[:, None])
            c_kv_new, k_rope_new = L.mla_latents(
                blk["attn"], cfg, h, positions[:, None])
            c_kv = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0))
            )(cache_l["c_kv"], c_kv_new, lengths)
            k_rope = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0))
            )(cache_l["k_rope"], k_rope_new, lengths)
            o_lat = L.latent_decode_attention(
                q_lat, q_rope, c_kv, k_rope, lengths=lengths + 1,
                scale=L.mla_scale(cfg))
            a = L.mla_out(blk["attn"], cfg, o_lat)
            new_cache = {"c_kv": c_kv, "k_rope": k_rope}
        else:
            q, kk, v = L.gqa_qkv(blk["attn"], cfg, h, positions[:, None])
            k_c = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
            )(cache_l["k"], kk, lengths)
            v_c = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
            )(cache_l["v"], v, lengths)
            o = L.decode_attention(q, k_c, v_c, lengths=lengths + 1,
                                   window=window,
                                   logit_cap=cfg.softcap_attn)
            a = o.reshape(b, 1, -1) @ blk["attn"]["wo"]
            new_cache = {"k": k_c, "v": v_c}
        if "ln1_post" in blk:
            a = L.rms_norm(a, blk["ln1_post"])
        x = x + a
        h = L.rms_norm(x, blk["ln2"])
        f = (M.apply_moe(blk["mlp"], cfg, h) if cfg.moe
             else L.apply_mlp(blk["mlp"], cfg, h))
        if "ln2_post" in blk:
            f = L.rms_norm(f, blk["ln2_post"])
        return x + f, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], windows, cache),
                                unroll=flags.scan_unroll(cfg.n_layers))
    x = L.rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = L.mask_vocab(
        L.softcap((x @ head).astype(jnp.float32), cfg.softcap_logits),
        cfg.vocab)
    return logits[:, 0], new_cache, lengths + 1


# ---------------------------------------------------------------------------
# Paged serving: chunked prefill + fused paged decode (repro.serve engine)
# ---------------------------------------------------------------------------

def paged_cache_leaf_specs(cfg: ArchConfig, page_size: int
                           ) -> dict[str, jax.ShapeDtypeStruct]:
    """Shape of ONE KV page, layer-stacked; repro.serve.paging.init_pool
    adds the physical-page pool dimension after the layer dim, so each
    pool leaf is (L, P, page, *feat).  The paged step programs address
    it as one (L*P, page, *feat) pool, layer l's pages at l*P + p
    (``_layer_scan``).

    Two cache families behind the same pool/block-table machinery
    (DESIGN.md §8.5): GQA pages are (L, page, Hkv, dh) per k/v leaf; MLA
    pages keep the cache COMPRESSED — head-free latent leaves c_kv
    (L, page, kv_lora) and k_rope (L, page, qk_rope), kv_lora + qk_rope
    bytes per position vs 2*Hkv*dh for dense KV."""
    lyr = cfg.n_layers
    if cfg.attn == "mla":
        m = cfg.mla
        return {"c_kv": jax.ShapeDtypeStruct((lyr, page_size, m.kv_lora),
                                             cfg.dtype),
                "k_rope": jax.ShapeDtypeStruct((lyr, page_size, m.qk_rope),
                                               cfg.dtype)}
    shape = (lyr, page_size, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, cfg.dtype),
            "v": jax.ShapeDtypeStruct(shape, cfg.dtype)}


def _layer_scan(body, params: Params, cfg: ArchConfig, x: jax.Array,
                pages: Params) -> tuple[jax.Array, Params]:
    """Run ``body(x, blk, window, pg, base) -> (x, pg)`` over the layer
    stack with the page pool as scan CARRY (``_paged_forward``'s layer
    loop).

    Each (L, P, page, *feat) pool leaf is viewed as one (L*P, page,
    *feat) pool: a bitcast, since L and P are the two major dims and
    ``dist.sharding.paged_pool_specs`` cuts neither.  Layer l's physical
    page p is page ``base + p`` with ``base = l*P``, so ``body`` adds
    ``base`` to its write page ids and block tables.  No per-layer pool
    slice enters the scan and no restacked pool leaves it, so each
    layer's scatter updates the one donated buffer in place.
    """
    n_layers, n_phys = next(iter(pages.values())).shape[:2]
    flat = {k: v.reshape(n_layers * n_phys, *v.shape[2:])
            for k, v in pages.items()}

    def step(carry, inp):
        x, pg = carry
        blk, window, layer = inp
        return body(x, blk, window, pg, layer * n_phys), None

    (x, flat), _ = jax.lax.scan(
        step, (x, flat),
        (params["blocks"], _layer_windows(cfg, n_layers),
         jnp.arange(n_layers)),
        unroll=flags.scan_unroll(n_layers))
    return x, {k: flat[k].reshape(v.shape) for k, v in pages.items()}


def _paged_forward(params: Params, cfg: ArchConfig, tokens: jax.Array,
                   q_start: jax.Array, pages: Params, tables: jax.Array,
                   write) -> tuple[jax.Array, Params]:
    """The one paged forward of every serving step program: W tokens per
    sequence through the layer scan against the page pool.

    tokens (B, W) at global positions ``q_start[b] + t``; tables
    (B, width) the block tables (possibly sliced to the live width).
    ``write(pool, new, base) -> pool`` stores one cache leaf's new
    entries, ``new`` (B, W, *feat), into the (L*P, page, *feat) pool of
    the layer whose pages start at ``base`` (``_layer_scan``): it is the
    one thing the programs do differently (prefill scatters whole pages,
    decode one row per slot with masked slots routed to the null page,
    verify a window per slot).  Each layer projects its queries and the
    new cache leaves (dense GQA ``k``/``v`` or MLA latents
    ``c_kv``/``k_rope``, by leaf name, ``paged_cache_leaf_specs``),
    writes the leaves, then attends through its cache family's one op
    (``kernels.attention.ops``), masked causally from each query's own
    position, so stale page contents never count.  Returns
    (logits (B, W, V), updated pages).
    """
    from repro.kernels.attention import ops as A

    b, w = tokens.shape
    mla = cfg.attn == "mla"
    x = _embed(params, cfg, tokens)                          # (B, W, D)
    positions = q_start[:, None] + jnp.arange(w)[None, :]   # (B, W)

    def body(x, blk, window, pg, base):
        with jax.named_scope("attention"):
            tbl = tables + base
        with jax.named_scope("attn_in"):
            h = L.rms_norm(x, blk["ln1"])
            if mla:
                new = dict(zip(("c_kv", "k_rope"),
                               L.mla_latents(blk["attn"], cfg, h, positions)))
                q = L.mla_absorbed_q(blk["attn"], cfg, h, positions)
            else:
                q, k, v = L.gqa_qkv(blk["attn"], cfg, h, positions)
                new = {"k": k, "v": v}
        pg = {name: write(pool, new[name], base)
              for name, pool in pg.items()}
        with jax.named_scope("attention"):
            if mla:
                o = A.paged_latent_attention(*q, pg["c_kv"], pg["k_rope"],
                                             tbl, q_start,
                                             scale=L.mla_scale(cfg))
            else:
                o = A.paged_attention(q, pg["k"], pg["v"], tbl, q_start,
                                      window=window,
                                      logit_cap=cfg.softcap_attn)
        with jax.named_scope("attn_out"):
            a = (L.mla_out(blk["attn"], cfg, o) if mla
                 else o.reshape(b, w, -1) @ blk["attn"]["wo"])
        return _attn_out_mlp(blk, cfg, x, a), pg

    x, pages = _layer_scan(body, params, cfg, x, pages)
    return _head(params, cfg, x), pages


def prefill_chunk_decoder(params: Params, cfg: ArchConfig,
                          tokens: jax.Array, start: jax.Array,
                          pages: Params, block_row: jax.Array
                          ) -> tuple[jax.Array, Params]:
    """One prompt chunk for ONE slot: tokens (1, C) at positions
    [start, start+C), written into the slot's pages via ``block_row``.

    Chunks are page-aligned (C a multiple of page_size, start a multiple
    of C), so each chunk writes C/page_size WHOLE pages — a scatter of
    PACO leaf tiles, no read-modify-write.  Returns (logits (C, V) for
    every chunk position, updated pages); the engine issues exactly
    ceil(prompt_len / C) of these jitted calls per admitted request.
    """
    c = tokens.shape[1]
    page = next(iter(pages.values())).shape[2]
    assert c % page == 0, (c, page)
    # pages this chunk fills: block_row[start/page : start/page + C/page]
    with jax.named_scope("kv_write"):
        page_ids = jax.lax.dynamic_slice(block_row, (start // page,),
                                         (c // page,))

    def write(pool, new, base):
        with jax.named_scope("kv_write"):
            return pool.at[page_ids + base].set(
                new.reshape(c // page, page, *new.shape[2:]))

    logits, pages = _paged_forward(params, cfg, tokens, start[None], pages,
                                   block_row[None], write)
    return logits[0], pages


def _decode_write(pages: Params, tables: jax.Array, lengths: jax.Array,
                  write_mask: jax.Array | None = None,
                  null_page: int | None = None):
    """``_paged_forward``'s ``write`` for one decode tick: each slot's new
    row lands at position ``lengths`` through its block table.
    ``write_mask`` (B,) bool routes masked-off slots' writes to each
    layer's null page — ``null_page`` as told by the pool owner
    (serve.paging ``PagePool.null_page``; the last-physical-page
    fallback matches ``init_pool``'s layout), offset to the layer's
    pages like every other page id — so their pages are untouched,
    which is how the multi-tick scan freezes slots that retire
    mid-block.  Tables may be width-sliced to the live context;
    out-of-range rows of masked-off slots clamp and are then routed to
    the null page."""
    page = next(iter(pages.values())).shape[2]
    with jax.named_scope("kv_write"):
        write_page = tables[jnp.arange(tables.shape[0]), lengths // page]
        write_off = lengths % page
        if write_mask is not None:
            if null_page is None:
                null_page = next(iter(pages.values())).shape[1] - 1
            write_page = jnp.where(write_mask, write_page, null_page)

    def write(pool, new, base):
        with jax.named_scope("kv_write"):
            return pool.at[write_page + base, write_off].set(new[:, 0])

    return write


def decode_step_paged_decoder(params: Params, cfg: ArchConfig,
                              tokens: jax.Array, pages: Params,
                              block_tables: jax.Array, lengths: jax.Array
                              ) -> tuple[jax.Array, Params]:
    """Fused decode over every slot against the shared page pool.

    tokens (B, 1); block_tables (B, pages_per_seq); lengths (B,) current
    context length per slot (the new token lands at position lengths).
    Inactive slots ride along pointed at the pool's null page — no
    per-slot Python, one compiled step per tick.  Returns
    (logits (B, V), updated pages).
    """
    write = _decode_write(pages, block_tables, lengths)
    logits, pages = _paged_forward(params, cfg, tokens, lengths, pages,
                                   block_tables, write)
    return logits[:, 0], pages


def decode_ticks_decoder(params: Params, cfg: ArchConfig,
                         tokens: jax.Array, pages: Params,
                         block_tables: jax.Array, lengths: jax.Array,
                         active: jax.Array, budget: jax.Array,
                         eos: jax.Array, keys: jax.Array, *, max_seq: int,
                         top_k: int | None = None,
                         temperature: float = 1.0,
                         null_page: int | None = None
                         ) -> tuple[jax.Array, Params]:
    """Fused MULTI-tick decode: N decode steps in one dispatch.

    A ``jax.lax.scan`` of one-token ``_paged_forward`` ticks with
    device-side sampling (``models.sampling.sample_tokens``), cache
    append, block-table advance, and per-slot retirement flags — the
    host syncs ONE small (N, slots) token block per dispatch instead of
    one logits argmax per token (DESIGN.md §8.7).

    tokens (B,) last emitted token per slot (its KV lands on the slot's
    first tick); lengths (B,) cache positions written; active (B,) bool;
    budget (B,) int32 remaining new-token budget; eos (B,) int32 per-slot
    eos id (-1 = never); keys (N, 2) uint32 per-tick PRNG keys (unused
    for greedy).  A slot whose emitted token triggers retirement —
    budget exhausted, eos, or context reaching ``max_seq`` (exactly the
    scheduler's ``_emit`` rule) — flips inactive: later ticks freeze its
    token/length and route its cache writes to the null page, so it
    rides along at zero semantic cost until the host retires it.

    Returns (toks (N, B) int32, updated pages); toks[t, s] is the token
    slot s emitted at tick t, -1 where the slot was already inactive —
    the host replays its retirement rule over the block, which agrees
    with the device flags by construction.
    """
    from repro.models.sampling import sample_tokens

    def tick(carry, key):
        toks, lens, act, bud, pg = carry
        write = _decode_write(pg, block_tables, lens, act, null_page)
        logits, pg = _paged_forward(params, cfg, toks[:, None], lens, pg,
                                    block_tables, write)
        logits = logits[:, 0]
        with jax.named_scope("sample"):
            nxt = sample_tokens(logits, key=key, top_k=top_k,
                                temperature=temperature)
            nxt = jnp.where(act, nxt, toks)    # freeze inactive lanes
            lens = lens + act                  # the old token's KV landed
            bud = bud - act
            # _emit's retirement rule on the just-emitted token: after the
            # emit, prompt+out == lens + 1 (the new token's KV is unwritten)
            done = (bud <= 0) | (nxt == eos) | (lens + 1 >= max_seq)
            out_t = jnp.where(act, nxt, -1)
        return (nxt, lens, act & ~done, bud, pg), out_t

    (_, _, _, _, pages), toks = jax.lax.scan(
        tick, (tokens, lengths, active, budget, pages), keys)
    return toks, pages


# ---------------------------------------------------------------------------
# Speculative decoding: batched paged verify of device-drafted windows
# ---------------------------------------------------------------------------

def verify_ticks_decoder(params: Params, cfg: ArchConfig,
                         tokens: jax.Array, pages: Params,
                         block_tables: jax.Array, lengths: jax.Array,
                         active: jax.Array, budget: jax.Array,
                         eos: jax.Array, history: jax.Array,
                         write_limit: jax.Array, steps: jax.Array, *,
                         max_seq: int, draft_len: int, ngram: int = 2,
                         null_page: int | None = None
                         ) -> tuple[jax.Array, jax.Array, jax.Array,
                                    Params]:
    """Fused SPECULATIVE decode: N draft->verify->accept steps in one
    dispatch, each advancing every live slot by 1..draft_len+1 tokens.

    Per step, per slot: (1) the device-side n-gram drafter
    (``models.draft.draft_ngram_propose``) proposes ``draft_len``
    continuation tokens from the slot's own token history; (2) ONE
    ``_paged_forward`` scores the W = draft_len + 1 window (last token
    + drafts) and scatters its K/V into the pool — each window row
    through the op sequence of the decode tick at its position, which
    keeps each accepted position's logits equal to the tick's up to the
    rounding of W-row against 1-row projections; (3) the
    greedy-acceptance prefix is computed on-device — drafted token t is
    accepted iff every earlier draft matched its argmax and draft[t] ==
    argmax(logits[t]) — and the emitted tokens are argmax[0 ..
    accepted], i.e. the accepted drafts plus the one correction token,
    exactly the tokens non-speculative greedy decode would emit; (4)
    the scheduler's ``_emit`` retirement rule (budget / eos / max_seq —
    the same predicate ``decode_ticks_decoder`` replicates) caps the
    emission prefix and flips exhausted slots inactive; (5) window
    positions past the emission prefix are ROLLED BACK to their
    pre-step pool contents, so rejected drafts leave no trace.

    tokens/lengths/active/budget/eos: as in ``decode_ticks_decoder``.
    history (B, H) int32: per-slot token context (prompt + generated,
    history[b, lengths[b]] == tokens[b]), updated in-scan so later
    steps draft against tokens accepted earlier in the same dispatch.
    write_limit (B,) int32: one past the last cache position the
    scheduler mapped real pages for (0 for inactive slots); window
    writes at positions >= write_limit are routed to the null page —
    their logits can only influence draft positions the emission cap
    already excludes.  steps: (N,) dummy array whose length sets the
    step count (shape-only, like ``decode_ticks``' keys).

    Returns (blocks (N, B, W) int32, accepted (N, B) int32, updated
    history, updated pages): blocks[n, b, t] is the t-th token slot b
    emitted at step n, -1 past the emission prefix; accepted[n, b] is
    how many of those emitted tokens were accepted DRAFTS (the
    scheduler's acceptance stats — it cannot be inferred from the block
    alone, because a flag-truncated window may end on an accepted draft
    rather than the correction token); history is returned so the
    scheduler can keep it DEVICE-resident across dispatches (its
    appends mirror the host replay exactly; only slot churn —
    admit/retire/preempt — forces a host re-upload).  Contract (pinned
    by tests/test_speculative.py, DESIGN.md §8.8), against running the
    fused non-speculative ``decode_ticks`` for the same number of
    emitted tokens: the same greedy tokens; accepted pool positions
    equal within rounding; rolled-back and null-routed positions
    bit-exact.
    """
    from repro.models.draft import draft_ngram_propose

    w = draft_len + 1
    b = tokens.shape[0]
    page = next(iter(pages.values())).shape[2]
    width = block_tables.shape[1]
    if null_page is None:
        null_page = next(iter(pages.values())).shape[1] - 1
    offs_w = jnp.arange(w)

    def step(carry, _):
        toks, lens, act, bud, hist, pg = carry
        with jax.named_scope("sample"):
            props = draft_ngram_propose(hist, lens + 1,
                                        draft_len=draft_len, ngram=ngram)
            win = jnp.concatenate([toks[:, None], props], axis=1)  # (B, W)
        # pool coordinates of the window; out-of-plan positions (past
        # the mapped write plan, or any position of an inactive slot)
        # are absorbed by the null page, mirroring ``_decode_write``'s
        # write_mask routing.
        with jax.named_scope("kv_write"):
            positions = lens[:, None] + offs_w[None, :]            # (B, W)
            pp = jnp.clip(positions // page, 0, width - 1)
            wp = jnp.take_along_axis(block_tables, pp, axis=1)
            in_plan = act[:, None] & (positions < write_limit[:, None])
            wp = jnp.where(in_plan, wp, null_page)
            wo = positions % page
            # pre-step window contents, for rolling back rejected writes
            old = {name: leaf[:, wp, wo] for name, leaf in pg.items()}

        def write(pool, new, base):
            with jax.named_scope("kv_write"):
                return pool.at[wp + base, wo].set(new)

        logits, pg = _paged_forward(params, cfg, win, lens, pg,
                                    block_tables, write)
        with jax.named_scope("sample"):
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # (B, W)
            ok = (props == g[:, :draft_len]).astype(jnp.int32)
            acc = jnp.cumprod(ok, axis=1).sum(axis=1)              # (B,)
            # sequential _emit replay over the window (static W,
            # unrolled): token j is emittable while the slot is alive and
            # every earlier draft was accepted; budget/eos/max_seq flip
            # the slot dead at exactly the scheduler's rule.
            alive = act
            new_toks, new_lens, new_bud = toks, lens, bud
            cols = []
            for j in range(w):
                tok_j = g[:, j]
                can = alive & (j <= acc)
                cols.append(jnp.where(can, tok_j, -1))
                new_toks = jnp.where(can, tok_j, new_toks)
                new_lens = new_lens + can
                new_bud = new_bud - can
                done = ((new_bud <= 0) | (tok_j == eos)
                        | (new_lens + 1 >= max_seq))
                alive = alive & ~(can & done)
            out = jnp.stack(cols, axis=1)                          # (B, W)
            n_emit = new_lens - lens
        # rollback: positions at window offsets >= n_emit revert to
        # their pre-step contents — the pool ends the step exactly as
        # if only the emitted tokens' KV had ever been written.
        with jax.named_scope("kv_write"):
            keep = offs_w[None, :] < n_emit[:, None]               # (B, W)
            for name in pg:
                cur = pg[name][:, wp, wo]
                k_mask = keep.reshape((1, b, w) + (1,) * (cur.ndim - 3))
                pg[name] = pg[name].at[:, wp, wo].set(
                    jnp.where(k_mask, cur, old[name]))
        # history append: emitted token j becomes context index
        # lens + 1 + j; un-emitted lanes are dropped.
        with jax.named_scope("sample"):
            hidx = jnp.where(keep, lens[:, None] + 1 + offs_w[None, :],
                             hist.shape[1])
            hist = hist.at[jnp.arange(b)[:, None], hidx].set(out,
                                                             mode="drop")
        # of the n_emit emitted tokens, min(n_emit, acc) were accepted
        # drafts (the remainder — at most one — is the correction token)
        return ((new_toks, new_lens, alive, new_bud, hist, pg),
                (out, jnp.minimum(n_emit, acc).astype(jnp.int32)))

    (_, _, _, _, history, pages), (blocks, accepted) = jax.lax.scan(
        step, (tokens, lengths, active, budget, history, pages), steps)
    return blocks, accepted, history, pages
