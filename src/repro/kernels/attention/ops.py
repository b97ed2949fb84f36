"""The paged attention ops the served step programs call: one per cache
family, over (B, W) query tokens.

Every served program calls these jnp formulations on every backend:
prefill (B=1, W=chunk), decode (W=1) and speculative verify
(W=draft_len+1).  The Pallas kernels in ``attention`` compute the same
thing without gathering; they are reached from the kernel tests
(interpret mode against the dense oracles of ``ref``) and the TPU-compile
tests (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def gather_kv_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(n_pages, page, *feat) pool + (B, pages_per_seq) tables ->
    (B, pages_per_seq * page, *feat) per-sequence contiguous cache view."""
    b, pps = block_tables.shape
    page = pages.shape[1]
    return pages[block_tables].reshape(b, pps * page, *pages.shape[2:])


def _causal_mask(q_start: jax.Array, w: int, s: int,
                 window: jax.Array | int | None = None) -> jax.Array:
    """(B, W, S): key position <= q_start[b] + t, and inside the window."""
    pos = jnp.arange(s)
    q_pos = q_start[:, None] + jnp.arange(w)[None, :]        # (B, W)
    mask = pos[None, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= pos[None, None, :] > (q_pos[:, :, None] - window)
    return mask


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    tables: jax.Array, q_start: jax.Array, *,
                    window: jax.Array | int | None = None,
                    logit_cap: float | None = None,
                    scale: float | None = None) -> jax.Array:
    """Causal attention of W query tokens per sequence against a paged
    GQA KV cache.

    q: (B, W, Hq, D), sequence b's queries at global positions
    ``q_start[b] + t`` for t in [0, W), their own K/V already written to
    the pool; k_pages/v_pages: (n_pages, page, Hkv, D); tables:
    (B, width) int32 page map per sequence (possibly sliced to the live
    width); q_start: (B,).  Returns (B, W, Hq, Dv).

    Keys at positions past a query's own (stale or unwritten pages) are
    masked by the causal rule.  The gathered cache keeps its grouped Hkv
    layout, so the GQA expansion is never materialized: decode is
    bytes-bound on this read.  One formulation serves every W, so a
    W-row call computes each row with the op sequence of the one-token
    call at ``q_start + t`` (tests/test_speculative.py pins this
    bitwise): greedy speculation's tokens depend on it (DESIGN.md §8.8).
    Dense oracles: ``ref.paged_attention_ref`` (W=1),
    ``ref.paged_prefill_ref`` (B=1), ``ref.paged_verify_ref``.
    """
    b, w, hq, d = q.shape
    hkv, dv = v_pages.shape[2:]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = gather_kv_pages(k_pages, tables)   # (B, S, Hkv, D)
    v = gather_kv_pages(v_pages, tables)
    scores = jnp.einsum("bwhgd,bshd->bwhgs", q.reshape(b, w, hkv, g, d), k,
                        preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        scores = jnp.tanh(scores / logit_cap) * logit_cap
    mask = _causal_mask(q_start, w, k.shape[1], window)
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    wts = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bwhgs,bshd->bwhgd", wts, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, w, hq, dv).astype(q.dtype)


def paged_latent_attention(q_lat: jax.Array, q_rope: jax.Array,
                           ckv_pages: jax.Array, kr_pages: jax.Array,
                           tables: jax.Array, q_start: jax.Array, *,
                           scale: float) -> jax.Array:
    """``paged_attention`` for the COMPRESSED (MLA latent) cache family.

    q_lat: (B, W, H, kv_lora) absorbed-W_uk queries; q_rope: (B, W, H,
    qk_rope); ckv_pages (n_pages, page, kv_lora) and kr_pages (n_pages,
    page, qk_rope) are head-free latent pools; tables (B, width);
    q_start (B,).  Returns (B, W, H, kv_lora), expanded through W_uv by
    the caller (models.layers.mla_out).

    Every head shares one latent key/value, so the cache read is
    O(S * (kv_lora + qk_rope)) bytes instead of O(S * H * dh), and the
    head expansion is never materialized.  Scores use the decomposed
    q_lat . c_kv + q_rope . k_rope form (no feature concat — the concat
    form miscompiles under the XLA CPU SPMD partitioner,
    layers.latent_attention).  Dense oracles:
    ``ref.paged_latent_attention_ref`` (W=1),
    ``ref.paged_latent_prefill_ref`` (B=1), ``ref.paged_latent_verify_ref``.
    """
    w = q_lat.shape[1]
    ck = gather_kv_pages(ckv_pages, tables)   # (B, S, kv_lora)
    kr = gather_kv_pages(kr_pages, tables)    # (B, S, qk_rope)
    scores = (jnp.einsum("bqhk,bsk->bhqs", q_lat, ck,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr,
                           preferred_element_type=jnp.float32)) * scale
    mask = _causal_mask(q_start, w, ck.shape[1])
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)   # (B,H,W,S)
    wts = jax.nn.softmax(scores, axis=-1).astype(ck.dtype)
    out = jnp.einsum("bhqs,bsk->bqhk", wts, ck,
                     preferred_element_type=jnp.float32)
    return out.astype(q_lat.dtype)
