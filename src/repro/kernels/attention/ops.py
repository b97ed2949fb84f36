"""jit'd public wrappers for flash + paged attention (layout adapters).

Models use (B, S, H, D) layout; the flash kernel uses (B, H, S, D).  The
served path runs the jnp formulations on every backend: the chunked
``repro.models.layers.attention`` and the paged-gather bodies below.  No
model path passes ``use_kernel=True``; the Pallas kernels it selects are
reached from the kernel tests (interpret mode against the dense oracles
of ``ref``) and the TPU-compile tests (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.attention.attention import (
    flash_attention_pallas, paged_flash_decode_pallas,
    paged_flash_prefill_pallas, paged_latent_decode_pallas,
    paged_latent_prefill_pallas)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    logit_cap: float | None = None, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jax.Array:
    """q: (B, S, Hq, D), k/v: (B, S, Hkv, D) -> (B, S, Hq, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_pallas(qt, kt, vt, causal=causal, window=window,
                               logit_cap=logit_cap, bq=bq, bk=bk,
                               interpret=interpret)
    return o.transpose(0, 2, 1, 3)


def gather_kv_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(n_pages, page, *feat) pool + (B, pages_per_seq) tables ->
    (B, pages_per_seq * page, *feat) per-sequence contiguous cache view."""
    b, pps = block_tables.shape
    page = pages.shape[1]
    return pages[block_tables].reshape(b, pps * page, *pages.shape[2:])


def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, block_row: jax.Array,
                            start: jax.Array, *, window: int | None = None,
                            logit_cap: float | None = None,
                            q_chunk: int = 1024,
                            use_kernel: bool = False,
                            interpret: bool = False) -> jax.Array:
    """Chunked prefill for ONE slot straight off the paged KV cache.

    q: (1, C, Hq, D) the chunk's queries at global positions
    [start, start+C); k_pages/v_pages: (n_pages, page, Hkv, D);
    block_row: (pages_per_seq,) int32.  Returns (1, C, Hq, D).

    The jnp path gathers the slot's pages through the block row and runs
    the chunked online-softmax attention (models.layers.attention, so
    the activation-sharding constraints of meshed serving still apply);
    ``use_kernel=True`` lowers to the Pallas kernel with
    scalar-prefetched (start, block_row) — one (page, D) leaf-tile DMA
    per grid step, no gathered dense cache.  Dense oracle:
    ``ref.paged_prefill_ref``.
    """
    if use_kernel:
        o = paged_flash_prefill_pallas(
            q[0], k_pages, v_pages, block_row, start,
            scale=1.0 / math.sqrt(q.shape[-1]), window=window,
            logit_cap=logit_cap, interpret=interpret)
        return o[None].astype(q.dtype)
    from repro.models import layers as L  # lazy: models imports kernels

    c = q.shape[1]
    pps = block_row.shape[0]
    page = k_pages.shape[1]
    k_ctx = gather_kv_pages(k_pages, block_row[None])   # (1, S, Hkv, D)
    v_ctx = gather_kv_pages(v_pages, block_row[None])
    return L.attention(q, k_ctx, v_ctx,
                       q_positions=start + jnp.arange(c),
                       k_positions=jnp.arange(pps * page), causal=True,
                       window=window, logit_cap=logit_cap, q_chunk=q_chunk)


def paged_latent_prefill_attention(q_lat: jax.Array, q_rope: jax.Array,
                                   ckv_pages: jax.Array,
                                   kr_pages: jax.Array,
                                   block_row: jax.Array, start: jax.Array,
                                   *, scale: float, q_chunk: int = 1024,
                                   use_kernel: bool = False,
                                   interpret: bool = False) -> jax.Array:
    """Chunked MLA latent prefill for ONE slot off the COMPRESSED pools.

    q_lat: (1, C, H, kv_lora) absorbed-W_uk queries; q_rope: (1, C, H,
    qk_rope); head-free latent pools + block_row (pages_per_seq,).
    Returns (1, C, H, kv_lora) — expanded through W_uv by the caller.
    jnp path: gather + layers.latent_attention (decomposed scores);
    ``use_kernel=True`` lowers to the Pallas latent prefill kernel.
    Dense oracle: ``ref.paged_latent_prefill_ref``.
    """
    if use_kernel:
        _, c, h, kv = q_lat.shape
        o = paged_latent_prefill_pallas(
            q_lat[0], q_rope[0], ckv_pages, kr_pages, block_row, start,
            scale=scale, interpret=interpret)
        return o[None].astype(q_lat.dtype)
    from repro.models import layers as L  # lazy: models imports kernels

    c = q_lat.shape[1]
    pps = block_row.shape[0]
    page = ckv_pages.shape[1]
    ck_ctx = gather_kv_pages(ckv_pages, block_row[None])  # (1, S, kv_lora)
    kr_ctx = gather_kv_pages(kr_pages, block_row[None])
    return L.latent_attention(q_lat, q_rope, ck_ctx, kr_ctx,
                              q_positions=start + jnp.arange(c),
                              k_positions=jnp.arange(pps * page),
                              causal=True, q_chunk=q_chunk, scale=scale)


def paged_verify_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           window: int | None = None,
                           logit_cap: float | None = None,
                           scale: float | None = None,
                           use_kernel: bool = False,
                           interpret: bool = False) -> jax.Array:
    """Speculative-verify attention: a W-token window PER SLOT against
    the paged KV cache (the verify half of DESIGN.md §8.8).

    q: (B, W, Hq, D) — slot b's queries sit at global positions
    ``lengths[b] + t`` for t in [0, W): the last emitted token followed
    by its drafted continuation, whose K/V the caller has already
    scattered into the pool at those positions.  k_pages/v_pages:
    (n_pages, page, Hkv, D); block_tables: (B, pages_per_seq) int32;
    lengths: (B,).  Returns (B, W, Hq, Dhv).

    The jnp path is ``paged_decode_attention``'s exact op sequence —
    same gather, same grouped-Hkv einsum contraction, same
    mask/softcap/softmax ops — generalized to W query positions with a
    per-position causal mask (key position <= lengths[b] + t).  Keeping
    the formulation IDENTICAL to the decode tick keeps greedy
    speculation's tokens equal to the fused non-speculative engine's
    (the same logits at every accepted position up to the rounding of
    a W-row reduction, DESIGN.md §8.8); the W=1, mask-equal case IS
    the decode path, which tests/test_speculative.py pins bitwise.
    ``use_kernel=True`` reuses
    the PR 4 paged-PREFILL Pallas kernel (multi-token causal paged
    attention is exactly its job), vmapped over slots with per-slot
    (start=lengths[b], block row) scalar prefetch.  Dense oracle:
    ``ref.paged_verify_ref``.
    """
    b, w, hq, d = q.shape
    _, page, hkv, dhv = v_pages.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if use_kernel:
        o = jax.vmap(
            lambda qb, row, st: paged_flash_prefill_pallas(
                qb, k_pages, v_pages, row, st, scale=scale, window=window,
                logit_cap=logit_cap, interpret=interpret))(
            q, block_tables, lengths)
        return o.astype(q.dtype)                        # (B, W, Hq, D)
    k = gather_kv_pages(k_pages, block_tables)   # (B, S, Hkv, D)
    v = gather_kv_pages(v_pages, block_tables)
    s = k.shape[1]
    qr = q.reshape(b, w, hkv, g, d)
    scores = jnp.einsum("bwhgd,bshd->bwhgs", qr, k,
                        preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        scores = jnp.tanh(scores / logit_cap) * logit_cap
    pos = jnp.arange(s)
    q_pos = lengths[:, None] + jnp.arange(w)[None, :]        # (B, W)
    mask = pos[None, None, :] <= q_pos[:, :, None]           # (B, W, S)
    if window is not None:
        mask &= pos[None, None, :] > (q_pos[:, :, None] - window)
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    wts = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bwhgs,bshd->bwhgd", wts, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, w, hq, dhv).astype(q.dtype)


def paged_latent_verify_attention(q_lat: jax.Array, q_rope: jax.Array,
                                  ckv_pages: jax.Array,
                                  kr_pages: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array, *, scale: float,
                                  use_kernel: bool = False,
                                  interpret: bool = False) -> jax.Array:
    """Speculative-verify attention against a COMPRESSED (MLA latent)
    paged cache: a W-token window per slot at positions lengths[b] + t.

    q_lat: (B, W, H, kv_lora) absorbed-W_uk queries; q_rope: (B, W, H,
    qk_rope); head-free latent pools; returns (B, W, H, kv_lora),
    expanded through W_uv by the caller.  Same contract as
    ``paged_verify_attention``: the jnp path is
    ``paged_latent_decode_attention``'s decomposed-score op sequence
    (q_lat·c_kv + q_rope·k_rope, no feature concat — DESIGN.md §8.6)
    with a per-position causal mask, so the W=1 case is bitwise the
    decode tick; ``use_kernel=True`` vmaps the PR 4 latent-prefill
    Pallas kernel over slots.  Dense oracle:
    ``ref.paged_latent_verify_ref``.
    """
    b, w, h, kv = q_lat.shape
    if use_kernel:
        o = jax.vmap(
            lambda ql, qr, row, st: paged_latent_prefill_pallas(
                ql, qr, ckv_pages, kr_pages, row, st, scale=scale,
                interpret=interpret))(q_lat, q_rope, block_tables, lengths)
        return o.astype(q_lat.dtype)                 # (B, W, H, kv_lora)
    ck = gather_kv_pages(ckv_pages, block_tables)    # (B, S, kv_lora)
    kr = gather_kv_pages(kr_pages, block_tables)     # (B, S, qk_rope)
    s = ck.shape[1]
    scores = (jnp.einsum("bqhk,bsk->bhqs", q_lat, ck,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr,
                           preferred_element_type=jnp.float32)) * scale
    pos = jnp.arange(s)
    q_pos = lengths[:, None] + jnp.arange(w)[None, :]        # (B, W)
    mask = pos[None, None, :] <= q_pos[:, :, None]           # (B, W, S)
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)   # (B,H,W,S)
    wts = jax.nn.softmax(scores, axis=-1).astype(ck.dtype)
    out = jnp.einsum("bhqs,bsk->bqhk", wts, ck,
                     preferred_element_type=jnp.float32)
    return out.astype(q_lat.dtype)                   # (B, W, H, kv_lora)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           window: int | None = None,
                           logit_cap: float | None = None,
                           scale: float | None = None,
                           use_kernel: bool = False,
                           interpret: bool = False) -> jax.Array:
    """Single-token decode against a paged KV cache.

    q: (B, 1, Hq, D); k_pages/v_pages: (n_pages, page, Hkv, D);
    block_tables: (B, pages_per_seq) int32; lengths: (B,) valid positions.
    Returns (B, 1, Hq, D).

    The jnp path gathers each sequence's pages (the paged-gather read the
    block table schedules — bytes move once per page, the PACO leaf-tile
    surface) and keeps the cache in its grouped Hkv layout: decode is
    bytes-bound on the cache read, so the GQA expansion is never
    materialized.  ``use_kernel=True`` lowers to the Pallas kernel with
    scalar-prefetched block tables instead.
    """
    b, _, hq, d = q.shape
    _, page, hkv, dhv = v_pages.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if use_kernel:
        o = paged_flash_decode_pallas(
            q.reshape(b, hq, d), k_pages, v_pages, block_tables, lengths,
            scale=scale, window=window, logit_cap=logit_cap,
            interpret=interpret)
        return o.reshape(b, 1, hq, dhv).astype(q.dtype)
    k = gather_kv_pages(k_pages, block_tables)   # (B, S, Hkv, D)
    v = gather_kv_pages(v_pages, block_tables)
    s = k.shape[1]
    qr = q.reshape(b, hkv, g, d)
    scores = jnp.einsum("bhgd,bshd->bhgs", qr, k,
                        preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        scores = jnp.tanh(scores / logit_cap) * logit_cap
    pos = jnp.arange(s)
    mask = pos[None, :] < lengths[:, None]
    if window is not None:
        mask &= pos[None, :] >= (lengths[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgs,bshd->bhgd", w, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, dhv).astype(q.dtype)


def paged_latent_decode_attention(q_lat: jax.Array, q_rope: jax.Array,
                                  ckv_pages: jax.Array,
                                  kr_pages: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array, *, scale: float,
                                  use_kernel: bool = False,
                                  interpret: bool = False) -> jax.Array:
    """Single-token decode against a COMPRESSED (MLA latent) paged cache.

    q_lat: (B, 1, H, kv_lora) absorbed-W_uk queries; q_rope: (B, 1, H,
    qk_rope); ckv_pages: (n_pages, page, kv_lora); kr_pages: (n_pages,
    page, qk_rope) — head-free latent pools; block_tables
    (B, pages_per_seq) int32; lengths (B,).  Returns (B, 1, H, kv_lora):
    the latent attention output, expanded through W_uv by the caller
    (models.layers.mla_out).

    Every head shares one latent key/value, so the cache read is
    O(S * (kv_lora + qk_rope)) bytes — the small face of the paper's
    surface-minimizing cut — instead of O(S * H * dh); the head
    expansion is never materialized, and scores use the decomposed
    q_lat . c_kv + q_rope . k_rope form (no feature concat — the
    concat form miscompiles under the XLA CPU SPMD partitioner,
    layers.latent_attention).  The jnp path gathers each sequence's
    latent pages through the block table; ``use_kernel=True`` lowers to
    the Pallas kernel with scalar-prefetched block tables.  Dense
    oracle: ``ref.paged_latent_attention_ref``.
    """
    b, _, h, kv = q_lat.shape
    if use_kernel:
        o = paged_latent_decode_pallas(
            q_lat.reshape(b, h, kv), q_rope.reshape(b, h, -1), ckv_pages,
            kr_pages, block_tables, lengths, scale=scale,
            interpret=interpret)
        return o.reshape(b, 1, h, -1).astype(q_lat.dtype)
    ck = gather_kv_pages(ckv_pages, block_tables)   # (B, S, kv_lora)
    kr = gather_kv_pages(kr_pages, block_tables)    # (B, S, qk_rope)
    s = ck.shape[1]
    scores = (jnp.einsum("bqhk,bsk->bhqs", q_lat, ck,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr,
                           preferred_element_type=jnp.float32)) * scale
    pos = jnp.arange(s)
    mask = pos[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(ck.dtype)
    out = jnp.einsum("bhqs,bsk->bqhk", w, ck,
                     preferred_element_type=jnp.float32)
    return out.astype(q_lat.dtype)                  # (B, 1, H, kv_lora)
