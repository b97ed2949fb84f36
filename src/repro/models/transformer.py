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
# ``sample``.  The layer scan itself is left unscoped, so an op that
# carries none of them was put there by the scan's slicing of the pool or
# by the compiler.

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
    adds the physical-page pool dimension.

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
    ceil(prompt_len / C) of these jitted calls per admitted request
    (the per-token teacher-forcing loop this replaces issued prompt_len).
    """
    from repro.kernels.attention import ops as A

    b, c = tokens.shape
    page = next(iter(pages.values())).shape[2]
    assert c % page == 0, (c, page)
    x = act.batch_seq(_embed(params, cfg, tokens))
    positions = start + jnp.arange(c)
    windows = _layer_windows(cfg, cfg.n_layers)
    # pages this chunk fills: block_row[start/page : start/page + C/page]
    with jax.named_scope("kv_write"):
        page_ids = jax.lax.dynamic_slice(block_row, (start // page,),
                                         (c // page,))

    def scatter(pool_l, new):
        """Write this chunk's C positions as C/page WHOLE pages (PACO
        leaf-tile scatter, no read-modify-write): new (1, C, *feat)."""
        with jax.named_scope("kv_write"):
            return pool_l.at[page_ids].set(
                new.reshape(c // page, page, *new.shape[2:]))

    def body(x, inp):
        blk, window, pg = inp
        if cfg.attn == "mla":
            with jax.named_scope("attn_in"):
                h = L.rms_norm(x, blk["ln1"])
                c_kv, k_rope = L.mla_latents(blk["attn"], cfg, h, positions)
            pg = {"c_kv": scatter(pg["c_kv"], c_kv),
                  "k_rope": scatter(pg["k_rope"], k_rope)}
            # absorbed latent attention straight off the slot's pages
            # (past pages + this chunk); stale/future page contents are
            # masked by the global causal rule inside the paged op.
            with jax.named_scope("attn_in"):
                q_lat, q_rope = L.mla_absorbed_q(blk["attn"], cfg, h,
                                                 positions)
            with jax.named_scope("attention"):
                o_lat = A.paged_latent_prefill_attention(
                    q_lat, q_rope, pg["c_kv"], pg["k_rope"], block_row,
                    start, scale=L.mla_scale(cfg), q_chunk=cfg.q_chunk)
            with jax.named_scope("attn_out"):
                a = L.mla_out(blk["attn"], cfg, o_lat)
        else:
            with jax.named_scope("attn_in"):
                h = L.rms_norm(x, blk["ln1"])
                q, kk, v = L.gqa_qkv(blk["attn"], cfg, h, positions)
            pg = {"k": scatter(pg["k"], kk), "v": scatter(pg["v"], v)}
            # paged-prefill attention over the slot's whole context (past
            # pages + this chunk); unwritten/future positions are masked
            # by the causal rule (k_pos > q_pos), stale contents included.
            # Pallas lowering: kernels.attention.paged_flash_prefill_pallas.
            with jax.named_scope("attention"):
                o = A.paged_prefill_attention(
                    q, pg["k"], pg["v"], block_row, start, window=window,
                    logit_cap=cfg.softcap_attn, q_chunk=cfg.q_chunk)
            with jax.named_scope("attn_out"):
                a = o.reshape(b, c, -1) @ blk["attn"]["wo"]
        return act.residual(_attn_out_mlp(blk, cfg, x, a)), pg

    x, new_pages = jax.lax.scan(
        body, x, (params["blocks"], windows, pages),
        unroll=flags.scan_unroll(cfg.n_layers))
    return _head(params, cfg, x)[0], new_pages


def _paged_tick(params: Params, cfg: ArchConfig, tokens: jax.Array,
                pages: Params, block_tables: jax.Array, lengths: jax.Array,
                write_mask: jax.Array | None = None,
                null_page: int | None = None
                ) -> tuple[jax.Array, Params]:
    """One fused paged decode tick over all slots (the shared body of
    ``decode_step_paged_decoder`` and ``decode_ticks_decoder``).

    tokens (B, 1); block_tables (B, pages_per_seq); lengths (B,) current
    context length per slot (the new token lands at position lengths).
    ``write_mask`` (B,) bool routes masked-off slots' cache writes to the
    pool's null page — ``null_page`` as told by the pool owner
    (serve.paging ``PagePool.null_page``; the last-physical-page
    fallback matches ``init_pool``'s layout) — their pages and lengths
    are untouched, which is how the multi-tick scan freezes slots that
    retire mid-block.  ``write_mask=None`` writes every slot, matching
    the block tables the engine builds (inactive slots' rows already
    point at the null page).  Returns (logits (B, V), updated pages).
    """
    from repro.kernels.attention import ops as A

    b = tokens.shape[0]
    page = next(iter(pages.values())).shape[2]
    x = _embed(params, cfg, tokens)  # (B,1,D)
    positions = lengths
    windows = _layer_windows(cfg, cfg.n_layers)
    # block_tables may be width-sliced to the live context (the engine
    # caps the jnp gather's materialization); out-of-range rows of
    # masked-off slots clamp and are then routed to the null page.
    with jax.named_scope("kv_write"):
        write_page = block_tables[jnp.arange(b), lengths // page]  # (B,)
        write_off = lengths % page
        if write_mask is not None:
            if null_page is None:
                null_page = next(iter(pages.values())).shape[1] - 1
            write_page = jnp.where(write_mask, write_page, null_page)

    def body(x, inp):
        blk, window, pg = inp
        if cfg.attn == "mla":
            with jax.named_scope("attn_in"):
                h = L.rms_norm(x, blk["ln1"])
                c_kv_new, k_rope_new = L.mla_latents(
                    blk["attn"], cfg, h, positions[:, None])
            with jax.named_scope("kv_write"):
                pg = {"c_kv": pg["c_kv"].at[write_page, write_off].set(
                          c_kv_new[:, 0]),
                      "k_rope": pg["k_rope"].at[write_page, write_off].set(
                          k_rope_new[:, 0])}
            with jax.named_scope("attn_in"):
                q_lat, q_rope = L.mla_absorbed_q(
                    blk["attn"], cfg, h, positions[:, None])
            with jax.named_scope("attention"):
                o_lat = A.paged_latent_decode_attention(
                    q_lat, q_rope, pg["c_kv"], pg["k_rope"], block_tables,
                    lengths + 1, scale=L.mla_scale(cfg))
            with jax.named_scope("attn_out"):
                a = L.mla_out(blk["attn"], cfg, o_lat)
        else:
            with jax.named_scope("attn_in"):
                h = L.rms_norm(x, blk["ln1"])
                q, kk, v = L.gqa_qkv(blk["attn"], cfg, h,
                                     positions[:, None])
            with jax.named_scope("kv_write"):
                pg = {"k": pg["k"].at[write_page, write_off].set(kk[:, 0]),
                      "v": pg["v"].at[write_page, write_off].set(v[:, 0])}
            with jax.named_scope("attention"):
                o = A.paged_decode_attention(
                    q, pg["k"], pg["v"], block_tables, lengths + 1,
                    window=window, logit_cap=cfg.softcap_attn)
            with jax.named_scope("attn_out"):
                a = o.reshape(b, 1, -1) @ blk["attn"]["wo"]
        return _attn_out_mlp(blk, cfg, x, a), pg

    x, new_pages = jax.lax.scan(
        body, x, (params["blocks"], windows, pages),
        unroll=flags.scan_unroll(cfg.n_layers))
    return _head(params, cfg, x)[:, 0], new_pages


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
    return _paged_tick(params, cfg, tokens, pages, block_tables, lengths)


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

    A ``jax.lax.scan`` over ``decode_step_paged``'s tick body with
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
        logits, pg = _paged_tick(params, cfg, toks[:, None], pg,
                                 block_tables, lens, write_mask=act,
                                 null_page=null_page)
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

def _verify_window(params: Params, cfg: ArchConfig, tokens: jax.Array,
                   pages: Params, block_tables: jax.Array,
                   lengths: jax.Array, write_page: jax.Array,
                   write_off: jax.Array) -> tuple[jax.Array, Params]:
    """One speculative VERIFY forward: W window tokens per slot in one
    pass (the multi-token sibling of ``_paged_tick``'s body).

    tokens (B, W): slot b's last emitted token followed by its W-1
    drafted continuation tokens, at global positions lengths[b] + t.
    write_page/write_off (B, W): per-position pool coordinates as routed
    by the caller (out-of-plan positions already point at the null
    page).  Every layer scatters the window's K/V (or MLA latents) into
    the pool, then attends through the paged VERIFY attention — the
    decode tick's exact op sequence generalized to W query positions
    (kernels/attention/ops.paged_verify_attention), which keeps each
    accepted position's logits AND residual stream equal, up to the
    rounding of W-row against 1-row reductions, to the non-speculative
    tick that would have produced them (DESIGN.md §8.8).  Returns
    (logits (B, W, V), updated pages); the caller computes greedy
    acceptance and rolls back the rejected tail
    (``verify_ticks_decoder``).
    """
    from repro.kernels.attention import ops as A

    b, w = tokens.shape
    x = _embed(params, cfg, tokens)                          # (B, W, D)
    positions = lengths[:, None] + jnp.arange(w)[None, :]   # (B, W)
    windows = _layer_windows(cfg, cfg.n_layers)

    def body(x, inp):
        blk, window, pg = inp
        if cfg.attn == "mla":
            with jax.named_scope("attn_in"):
                h = L.rms_norm(x, blk["ln1"])
                c_kv_new, k_rope_new = L.mla_latents(
                    blk["attn"], cfg, h, positions)
            with jax.named_scope("kv_write"):
                pg = {"c_kv": pg["c_kv"].at[write_page, write_off].set(
                          c_kv_new),
                      "k_rope": pg["k_rope"].at[write_page, write_off].set(
                          k_rope_new)}
            with jax.named_scope("attn_in"):
                q_lat, q_rope = L.mla_absorbed_q(blk["attn"], cfg, h,
                                                 positions)
            with jax.named_scope("attention"):
                o_lat = A.paged_latent_verify_attention(
                    q_lat, q_rope, pg["c_kv"], pg["k_rope"], block_tables,
                    lengths, scale=L.mla_scale(cfg))
            with jax.named_scope("attn_out"):
                a = L.mla_out(blk["attn"], cfg, o_lat)
        else:
            with jax.named_scope("attn_in"):
                h = L.rms_norm(x, blk["ln1"])
                q, kk, v = L.gqa_qkv(blk["attn"], cfg, h, positions)
            with jax.named_scope("kv_write"):
                pg = {"k": pg["k"].at[write_page, write_off].set(kk),
                      "v": pg["v"].at[write_page, write_off].set(v)}
            with jax.named_scope("attention"):
                o = A.paged_verify_attention(q, pg["k"], pg["v"],
                                             block_tables, lengths,
                                             window=window,
                                             logit_cap=cfg.softcap_attn)
            with jax.named_scope("attn_out"):
                a = o.reshape(b, w, -1) @ blk["attn"]["wo"]
        return _attn_out_mlp(blk, cfg, x, a), pg

    x, new_pages = jax.lax.scan(
        body, x, (params["blocks"], windows, pages),
        unroll=flags.scan_unroll(cfg.n_layers))
    return _head(params, cfg, x), new_pages                  # (B, W, V)


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
    ``_verify_window`` forward scores the W = draft_len + 1 window
    (last token + drafts) and scatters its K/V into the pool; (3) the
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
        # are absorbed by the null page, mirroring _paged_tick's
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
        logits, pg = _verify_window(params, cfg, win, pg, block_tables,
                                    lens, wp, wo)
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
