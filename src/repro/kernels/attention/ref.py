"""Pure-jnp oracle for the flash attention kernel (dense softmax)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  logit_cap: float | None = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if logit_cap is not None:
        s = jnp.tanh(s / logit_cap) * logit_cap
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qp >= kp
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, block_tables: jax.Array,
                        lengths: jax.Array, *,
                        window: int | None = None,
                        logit_cap: float | None = None,
                        scale: float | None = None) -> jax.Array:
    """Dense oracle for the paged-gather decode path.

    q: (B, 1, Hq, D) one query token per sequence.
    k_pages/v_pages: (n_pages, page_size, Hkv, D) physical page pools.
    block_tables: (B, pages_per_seq) int32 page map per sequence.
    lengths: (B,) valid cache positions per sequence -> (B, 1, Hq, D).

    Materializes each sequence's full gathered cache and runs dense f32
    softmax — the correctness anchor for ops.paged_attention at W=1 and
    the Pallas decode kernel (tests/test_serve.py).
    """
    b, _, hq, d = q.shape
    _, page, hkv, _ = k_pages.shape
    pps = block_tables.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # gather: (B, pages_per_seq, page, Hkv, D) -> (B, S, Hkv, D)
    k = k_pages[block_tables].reshape(b, pps * page, hkv, d)
    v = v_pages[block_tables].reshape(b, pps * page, hkv, d)
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if logit_cap is not None:
        s = jnp.tanh(s / logit_cap) * logit_cap
    pos = jnp.arange(pps * page)
    mask = pos[None, :] < lengths[:, None]
    if window is not None:
        mask &= pos[None, :] >= (lengths[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_prefill_ref(q: jax.Array, k_pages: jax.Array,
                      v_pages: jax.Array, block_row: jax.Array,
                      start: jax.Array, *, window: int | None = None,
                      logit_cap: float | None = None,
                      scale: float | None = None) -> jax.Array:
    """Dense oracle for the paged chunked-prefill path.

    q: (1, C, Hq, D) one chunk of one slot at global positions
    [start, start+C); k_pages/v_pages: (n_pages, page, Hkv, D) pools;
    block_row: (pages_per_seq,) the slot's page map.  Materializes the
    slot's whole gathered cache and runs dense f32 softmax with the
    GLOBAL causal mask (q_pos = start + offset) — stale/future page
    contents are masked exactly as the kernel masks them.  The
    correctness anchor for ops.paged_attention at B=1 and
    paged_flash_prefill_pallas (tests/test_serve.py).  Returns
    (1, C, Hq, D)."""
    _, c, hq, d = q.shape
    _, page, hkv, _ = k_pages.shape
    pps = block_row.shape[0]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = k_pages[block_row].reshape(1, pps * page, hkv, d)
    v = v_pages[block_row].reshape(1, pps * page, hkv, d)
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if logit_cap is not None:
        s = jnp.tanh(s / logit_cap) * logit_cap
    q_pos = start + jnp.arange(c)[:, None]
    k_pos = jnp.arange(pps * page)[None, :]
    mask = q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_verify_ref(q: jax.Array, k_pages: jax.Array,
                     v_pages: jax.Array, block_tables: jax.Array,
                     lengths: jax.Array, *, window: int | None = None,
                     logit_cap: float | None = None,
                     scale: float | None = None) -> jax.Array:
    """Dense oracle for the speculative-verify path: each slot's W-token
    window (queries at global positions lengths[b] + t) re-expressed as
    one ``paged_prefill_ref`` call per slot over its own block row.
    q: (B, W, Hq, D); returns (B, W, Hq, D)."""
    outs = [paged_prefill_ref(q[i][None], k_pages, v_pages,
                              block_tables[i], lengths[i], window=window,
                              logit_cap=logit_cap, scale=scale)[0]
            for i in range(q.shape[0])]
    return jnp.stack(outs)


def paged_latent_verify_ref(q_lat: jax.Array, q_rope: jax.Array,
                            ckv_pages: jax.Array, kr_pages: jax.Array,
                            block_tables: jax.Array, lengths: jax.Array,
                            *, scale: float) -> jax.Array:
    """Dense oracle for the MLA latent speculative-verify path: one
    ``paged_latent_prefill_ref`` call (concat-and-broadcast formulation,
    deliberately what the production path avoids) per slot.
    q_lat: (B, W, H, kv_lora); returns (B, W, H, kv_lora)."""
    outs = [paged_latent_prefill_ref(q_lat[i][None], q_rope[i][None],
                                     ckv_pages, kr_pages,
                                     block_tables[i], lengths[i],
                                     scale=scale)[0]
            for i in range(q_lat.shape[0])]
    return jnp.stack(outs)


def paged_latent_prefill_ref(q_lat: jax.Array, q_rope: jax.Array,
                             ckv_pages: jax.Array, kr_pages: jax.Array,
                             block_row: jax.Array, start: jax.Array, *,
                             scale: float) -> jax.Array:
    """Dense oracle for the paged MLA latent chunked-prefill path.

    q_lat: (1, C, H, kv_lora); q_rope: (1, C, H, qk_rope); head-free
    latent pools ckv_pages (n_pages, page, kv_lora) / kr_pages (n_pages,
    page, qk_rope); block_row (pages_per_seq,).  Deliberately the
    formulation the production path avoids: gathers the latent pages,
    CONCATENATES the latent pair into per-position keys, BROADCASTS them
    to every head, and runs dense f32 softmax under the global causal
    mask.  Returns (1, C, H, kv_lora)."""
    _, c, h, kv = q_lat.shape
    page = ckv_pages.shape[1]
    pps = block_row.shape[0]
    q = jnp.concatenate([q_lat, q_rope], axis=-1)
    dk = q.shape[-1]
    ck = ckv_pages[block_row].reshape(1, pps * page, -1)
    kr = kr_pages[block_row].reshape(1, pps * page, -1)
    k = jnp.concatenate([ck, kr], axis=-1)           # (1, S, kv+rope)
    k = jnp.broadcast_to(k[:, :, None, :], (1, k.shape[1], h, dk))
    v = jnp.broadcast_to(ck[:, :, None, :],
                         (1, ck.shape[1], h, ck.shape[-1]))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = start + jnp.arange(c)[:, None]
    k_pos = jnp.arange(pps * page)[None, :]
    s = jnp.where((q_pos >= k_pos)[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return o.astype(q_lat.dtype)


def paged_latent_attention_ref(q_lat: jax.Array, q_rope: jax.Array,
                               ckv_pages: jax.Array, kr_pages: jax.Array,
                               block_tables: jax.Array,
                               lengths: jax.Array, *, scale: float
                               ) -> jax.Array:
    """Dense oracle for the paged MLA latent decode path.

    q_lat: (B, 1, H, kv_lora) absorbed queries; q_rope: (B, 1, H,
    qk_rope); ckv_pages (n_pages, page, kv_lora) / kr_pages (n_pages,
    page, qk_rope) are the head-free latent pools; block_tables
    (B, pages_per_seq); lengths (B,).  Deliberately the formulation the
    production path avoids: materializes each sequence's gathered
    latent cache, CONCATENATES the latent pair into per-position keys,
    BROADCASTS them to every head, and runs dense f32 softmax — the
    correctness anchor for ops.paged_latent_attention at W=1 and the
    Pallas latent decode kernel (tests/test_serve.py).  Returns
    (B, 1, H, kv_lora)."""
    b, _, h, kv = q_lat.shape
    page = ckv_pages.shape[1]
    pps = block_tables.shape[1]
    q = jnp.concatenate([q_lat, q_rope], axis=-1)
    dk = q.shape[-1]
    ck = ckv_pages[block_tables].reshape(b, pps * page, -1)
    kr = kr_pages[block_tables].reshape(b, pps * page, -1)
    k = jnp.concatenate([ck, kr], axis=-1)           # (B, S, kv+rope)
    k = jnp.broadcast_to(k[:, :, None, :], (b, k.shape[1], h, dk))
    v = jnp.broadcast_to(ck[:, :, None, :],
                         (b, ck.shape[1], h, ck.shape[-1]))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(pps * page)
    mask = pos[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return o.astype(q.dtype)
