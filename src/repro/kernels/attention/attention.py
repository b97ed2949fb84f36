"""Flash-style blocked attention Pallas kernel (online softmax, GQA,
causal masking, optional logit softcap and sliding window).

Grid (batch*kv_head, q_blocks, k_blocks); k-axis innermost so the running
(m, l, acc) statistics stay in VMEM scratch across key blocks.  BlockSpecs
tile Q/K/V at (bq, d)/(bk, d) — the PACO leaf tiling of the attention
cuboid (queries x keys x head_dim), with the surface-minimizing property
that only O(bq*d + bk*d) bytes move per program while bq*bk*d MACs run.

q: (B, Hq, S, D), k/v: (B, Hkv, S, D); grouped queries are folded into the
q-block dimension (G groups stacked along S).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, n_kb: int, bq: int, bk: int,
                  window: int | None, logit_cap: float | None):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        s = jnp.tanh(s / logit_cap) * logit_cap
    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, v_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "window", "logit_cap",
                              "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 128,
                           bk: int = 128, window: int | None = None,
                           logit_cap: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D).  Returns (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    # fold GQA groups into batch: q -> (B*Hkv, G*Sq, D) is wrong for causal
    # positions; instead fold G into the grid's batch dim.
    qf = q.reshape(b * hkv * g, sq, d)
    kf = jnp.repeat(k.reshape(b * hkv, sk, d), g, axis=0)
    vf = jnp.repeat(v.reshape(b * hkv, sk, d), g, axis=0)
    grid = (b * hq, sq // bq, sk // bk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          n_kb=grid[2], bq=bq, bk=bk, window=window,
                          logit_cap=logit_cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)


def _q_block(c: int, cap: int = 128) -> int:
    """Largest divisor of the chunk length not exceeding ``cap`` — the
    q-block extent of the prefill kernels (chunks are page multiples, not
    necessarily powers of two, so a plain min() would not divide)."""
    return max(b for b in range(1, min(c, cap) + 1) if c % b == 0)


# ---------------------------------------------------------------------------
# Paged PREFILL kernel (serving): chunked causal attention straight off the
# page pool — the ROADMAP "paged prefill Pallas kernel" item
# ---------------------------------------------------------------------------

def _paged_prefill_kernel(start_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, scale: float, bq: int,
                          hq: int, hkv: int, page: int, pps: int,
                          window: int | None, logit_cap: float | None):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = q_ref.shape[-1]
    q = q_ref[...].astype(jnp.float32)           # (bq*Hq, d): row t*Hq + h
    # every kv head of the page in one block; row p*Hkv + h of the
    # flattened tile is position p of kv head h
    k = k_ref[0].astype(jnp.float32).reshape(page * hkv, d)
    v = v_ref[0].astype(jnp.float32).reshape(page * hkv, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        s = jnp.tanh(s / logit_cap) * logit_cap
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # q positions are GLOBAL (start + chunk offset): the chunk attends
    # causally over the slot's whole gathered context, so stale or
    # not-yet-written page contents (k_pos > q_pos) are masked here.
    q_pos = start_ref[0] + i * bq + row // hq
    k_pos = j * page + col // hkv
    # a query head scores only the columns of its own kv group
    mask = ((row % hq) // (hq // hkv) == col % hkv) & (q_pos >= k_pos)
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq*Hq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pps - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "logit_cap", "interpret"))
def paged_flash_prefill_pallas(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_row: jax.Array,
                               start: jax.Array, *, scale: float,
                               window: int | None = None,
                               logit_cap: float | None = None,
                               interpret: bool = False) -> jax.Array:
    """Paged chunked prefill for ONE slot: q (C, Hq, D) at positions
    [start, start+C) vs page pools (n_pages, page, Hkv, D) indexed by
    block_row (pages_per_seq,).

    The prefill sibling of ``paged_flash_decode_pallas``: block_row and
    start ride scalar prefetch so the K/V BlockSpec index_map routes grid
    step (i, j) to physical page ``block_row[j]`` — one whole
    (page, Hkv, D) leaf-tile DMA per step, never a gathered dense
    (max_seq, D) cache.  The TPU compiler tiles the last two block dims
    by (8, 128), so the K/V block spans all Hkv heads (a size-1 head
    block is refused); query heads fold into the q-block rows (as in the
    latent kernel) and each query head masks out the other groups'
    columns — Hkv-fold redundant MXU work per step, in exchange for one
    dense (bq*Hq, page*Hkv) score tile.  The grid (C/bq, pps) keeps the
    page axis innermost so the online-softmax (m, l, acc) state stays in
    VMEM across key pages.  Causal masking is GLOBAL (q_pos = start +
    chunk offset), which also masks stale/future page contents.
    Returns (C, Hq, D).
    """
    c, hq, d = q.shape
    _, page, hkv, _ = k_pages.shape
    pps = block_row.shape[0]
    bq = _q_block(c, cap=max(1, 128 // hq))
    grid = (c // bq, pps)
    start = jnp.asarray(start, jnp.int32).reshape(1)
    kv_spec = pl.BlockSpec((1, page, hkv, d),
                           lambda i, j, st, bt: (bt[j], 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, scale=scale, bq=bq,
                          hq=hq, hkv=hkv, page=page, pps=pps,
                          window=window, logit_cap=logit_cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bq * hq, d), lambda i, j, st, bt: (i, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((bq * hq, d),
                                   lambda i, j, st, bt: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq * hq, 1), jnp.float32),   # running max
                pltpu.VMEM((bq * hq, 1), jnp.float32),   # running denom
                pltpu.VMEM((bq * hq, d), jnp.float32),   # output acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((c * hq, d), q.dtype),
        interpret=interpret,
    )(start, block_row, q.reshape(c * hq, d), k_pages, v_pages)
    return out.reshape(c, hq, d)


def _paged_latent_prefill_kernel(start_ref, bt_ref, ql_ref, qr_ref,
                                 ckv_ref, kr_ref, o_ref, m_ref, l_ref,
                                 acc_ref, *, scale: float, bq: int, h: int,
                                 page: int, pps: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ql = ql_ref[...].astype(jnp.float32)         # (bq*H, kv_lora)
    qr = qr_ref[...].astype(jnp.float32)         # (bq*H, qk_rope)
    ckv = ckv_ref[0].astype(jnp.float32)         # (page, kv_lora)
    kr = kr_ref[0].astype(jnp.float32)           # (page, qk_rope)
    # decomposed scores (no latent-pair concat; see DESIGN.md §8.6)
    s = (jnp.dot(ql, ckv.T, preferred_element_type=jnp.float32)
         + jnp.dot(qr, kr.T, preferred_element_type=jnp.float32)) * scale
    # row r of the flattened (bq*H) q block is position r // H
    q_pos = start_ref[0] + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0) // h
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq*H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
    # the latent IS the value
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, ckv, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pps - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_prefill_pallas(q_lat: jax.Array, q_rope: jax.Array,
                                ckv_pages: jax.Array, kr_pages: jax.Array,
                                block_row: jax.Array, start: jax.Array, *,
                                scale: float,
                                interpret: bool = False) -> jax.Array:
    """Paged MLA latent prefill for ONE slot: q_lat (C, H, kv_lora) +
    q_rope (C, H, qk_rope) at positions [start, start+C) vs head-free
    latent pools indexed by block_row (pages_per_seq,).

    The MQA extreme of the prefill kernel: all H heads share one latent
    key/value, so heads fold into the q-block rows (grid (C/bq, pps))
    and each step DMAs one (page, kv_lora + qk_rope) latent leaf tile —
    the smallest face the PACO cut schedule offers.  Scores use the
    decomposed q_lat·c_kv + q_rope·k_rope form; the latent doubles as
    the value (W_uv expansion happens outside).  Returns (C, H, kv_lora).
    """
    c, h, kv_lora = q_lat.shape
    rope = q_rope.shape[-1]
    page = ckv_pages.shape[1]
    pps = block_row.shape[0]
    bq = _q_block(c, cap=max(1, 128 // h))
    grid = (c // bq, pps)
    start = jnp.asarray(start, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_paged_latent_prefill_kernel, scale=scale, bq=bq,
                          h=h, page=page, pps=pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bq * h, kv_lora),
                             lambda i, j, st, bt: (i, 0)),
                pl.BlockSpec((bq * h, rope),
                             lambda i, j, st, bt: (i, 0)),
                pl.BlockSpec((1, page, kv_lora),
                             lambda i, j, st, bt: (bt[j], 0, 0)),
                pl.BlockSpec((1, page, rope),
                             lambda i, j, st, bt: (bt[j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((bq * h, kv_lora),
                                   lambda i, j, st, bt: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq * h, 1), jnp.float32),       # running max
                pltpu.VMEM((bq * h, 1), jnp.float32),       # running denom
                pltpu.VMEM((bq * h, kv_lora), jnp.float32),  # latent acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((c * h, kv_lora), q_lat.dtype),
        interpret=interpret,
    )(start, block_row, q_lat.reshape(c * h, kv_lora),
      q_rope.reshape(c * h, rope), ckv_pages, kr_pages)
    return out.reshape(c, h, kv_lora)


# ---------------------------------------------------------------------------
# Paged decode kernel (serving): block-table-indexed KV page pool
# ---------------------------------------------------------------------------

def _paged_decode_kernel(lengths_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, pps: int,
                         page: int, hkv: int, g: int, window: int | None,
                         logit_cap: float | None):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = q_ref.shape[-1]
    qb = q_ref[0].astype(jnp.float32)            # (Hq, D): row h*G + g
    # every kv head of the page in one block; row p*Hkv + h of the
    # flattened tile is position p of kv head h
    kb = k_ref[0].astype(jnp.float32).reshape(page * hkv, d)
    vb = v_ref[0].astype(jnp.float32).reshape(page * hkv, d)
    s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        s = jnp.tanh(s / logit_cap) * logit_cap
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    pos = j * page + col // hkv
    length = lengths_ref[b]
    # a query head scores only the columns of its own kv group
    mask = (row // g == col % hkv) & (pos < length)
    if window is not None:
        mask &= pos >= (length - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (Hq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, vb, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "logit_cap", "interpret"))
def paged_flash_decode_pallas(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tables: jax.Array,
                              lengths: jax.Array, *, scale: float,
                              window: int | None = None,
                              logit_cap: float | None = None,
                              interpret: bool = False) -> jax.Array:
    """Paged single-token decode: q (B, Hq, D) vs page pools
    (n_pages, page, Hkv, D) indexed by block_tables (B, pages_per_seq);
    query head h reads kv head h // (Hq / Hkv).

    Block tables and lengths ride scalar prefetch so the K/V BlockSpec
    index_map can route each grid step (b, j) to the physical page
    ``bt[b, j]`` — the kernel only ever DMAs the PACO leaf tiles (one
    whole (page, Hkv, D) page per step, all kv heads: the TPU compiler
    tiles the last two block dims by (8, 128) and refuses a size-1 head
    block) that the block table maps, never a dense (B, max_seq) cache.
    All Hq query heads score the flattened (page*Hkv, D) tile in one
    dot and mask out the other groups' columns.  Grid
    (B, pages_per_seq); the page axis is innermost so the (m, l, acc)
    online-softmax state stays in VMEM.  Returns (B, Hq, D).
    """
    b, hq, d = q.shape
    pps = block_tables.shape[1]
    _, page, hkv, _ = k_pages.shape
    grid = (b, pps)
    kv_spec = pl.BlockSpec((1, page, hkv, d),
                           lambda b, j, lens, bt: (bt[b, j], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, pps=pps,
                          page=page, hkv=hkv, g=hq // hkv, window=window,
                          logit_cap=logit_cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, hq, d), lambda b, j, lens, bt: (b, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, hq, d),
                                   lambda b, j, lens, bt: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, 1), jnp.float32),   # running max
                pltpu.VMEM((hq, 1), jnp.float32),   # running denom
                pltpu.VMEM((hq, d), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        interpret=interpret,
    )(lengths, block_tables, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Paged LATENT decode kernel (MLA serving): compressed head-free pages
# ---------------------------------------------------------------------------

def _paged_latent_decode_kernel(lengths_ref, bt_ref, ql_ref, qr_ref,
                                ckv_ref, kr_ref, o_ref, m_ref, l_ref,
                                acc_ref, *, scale: float, pps: int,
                                page: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ql = ql_ref[0].astype(jnp.float32)           # (H, kv_lora)
    qr = qr_ref[0].astype(jnp.float32)           # (H, qk_rope)
    ckv = ckv_ref[0].astype(jnp.float32)         # (page, kv_lora)
    kr = kr_ref[0].astype(jnp.float32)           # (page, qk_rope)
    # decomposed scores: q_lat . c_kv + q_rope . k_rope (two MXU dots —
    # same math as scoring the concatenated key, no concat needed)
    s = (jnp.dot(ql, ckv.T, preferred_element_type=jnp.float32)
         + jnp.dot(qr, kr.T, preferred_element_type=jnp.float32)) * scale
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < lengths_ref[b], s, NEG_INF)

    m_prev = m_ref[...]                          # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
    # the latent IS the value: acc accumulates (H, kv_lora)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, ckv, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_decode_pallas(q_lat: jax.Array, q_rope: jax.Array,
                               ckv_pages: jax.Array, kr_pages: jax.Array,
                               block_tables: jax.Array,
                               lengths: jax.Array, *, scale: float,
                               interpret: bool = False) -> jax.Array:
    """Paged MLA latent decode: q_lat (B, H, kv_lora) + q_rope (B, H,
    qk_rope) vs head-free latent pools ckv_pages (n_pages, page,
    kv_lora) / kr_pages (n_pages, page, qk_rope) indexed by block_tables
    (B, pages_per_seq).

    The MQA extreme of the paged decode kernel: ONE shared latent
    key/value for all H query heads, so the grid is just
    (B, pages_per_seq) and each step DMAs one (page, kv_lora + qk_rope)
    latent leaf tile — the smallest face the PACO cut schedule offers.
    The latent doubles as the value (acc is (H, kv_lora)); W_uv expansion
    happens outside the kernel.  Returns (B, H, kv_lora).
    """
    b, h, kv_lora = q_lat.shape
    rope = q_rope.shape[-1]
    pps = block_tables.shape[1]
    page = ckv_pages.shape[1]
    grid = (b, pps)
    return pl.pallas_call(
        functools.partial(_paged_latent_decode_kernel, scale=scale,
                          pps=pps, page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, kv_lora),
                             lambda b, j, lens, bt: (b, 0, 0)),
                pl.BlockSpec((1, h, rope),
                             lambda b, j, lens, bt: (b, 0, 0)),
                pl.BlockSpec((1, page, kv_lora),
                             lambda b, j, lens, bt: (bt[b, j], 0, 0)),
                pl.BlockSpec((1, page, rope),
                             lambda b, j, lens, bt: (bt[b, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, kv_lora),
                                   lambda b, j, lens, bt: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),        # running max
                pltpu.VMEM((h, 1), jnp.float32),        # running denom
                pltpu.VMEM((h, kv_lora), jnp.float32),  # latent accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, kv_lora), q_lat.dtype),
        interpret=interpret,
    )(lengths, block_tables, q_lat, q_rope, ckv_pages, kr_pages)
