"""Single-request reference decode — the engine parity oracle.

An intentionally independent code path from the serving engine: no KV
cache at all.  Each generated token re-runs a dense forward over the
whole context with the dense-softmax oracle attention
(``repro.kernels.attention.ref.attention_ref``), then takes the greedy
argmax of the final position.  O(steps * ctx^2) — fine at test scale,
and sharing nothing with the paged/incremental engine path it checks
(tests/test_serve.py asserts token-level bit-identity).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.attention.ref import attention_ref
from repro.models import layers as L
from repro.models import moe as M
from repro.models.transformer import _NO_WINDOW, _layer_windows

Params = dict[str, Any]


def mla_materialized_qkv(p: Params, cfg: ArchConfig, x: jax.Array,
                         positions: jax.Array
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """NAIVE UNCOMPRESSED MLA: materialize per-head k/v from the latent.

    k[b,s,h] = [W_uk c_kv | k_rope] and v[b,s,h] = W_uv c_kv — the
    textbook formulation the absorbed-W_uk production path
    (layers.apply_mla and the latent decode/paging paths) is
    algebraically equal to: q_lat . c_kv == (q_nope W_uk) . c_kv ==
    q_nope . (W_uk c_kv).  Deliberately the expensive h*dh-per-position
    layout: this is the independent oracle the golden test
    (tests/test_models.py::test_mla_absorbed_matches_uncompressed) and
    the serve parity suite pin the compressed path against.

    Returns q, k, v shaped (B, S, H, qk_nope + qk_rope) / same / (B, S,
    H, v_head)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = L.mla_queries(p, cfg, x, positions)
    c_kv, k_rope = L.mla_latents(p, cfg, x, positions)
    w_uk = p["w_uk"].reshape(m.kv_lora, h, m.qk_nope)
    w_uv = p["w_uv"].reshape(m.kv_lora, h, m.v_head)
    k_nope = jnp.einsum("bsk,khd->bshd", c_kv, w_uk)
    v = jnp.einsum("bsk,khd->bshd", c_kv, w_uv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope))], axis=-1)
    return q, k, v


def forward_ref(params: Params, cfg: ArchConfig, tokens: jax.Array
                ) -> jax.Array:
    """tokens (B, S) -> logits (B, S, V) via a plain per-layer Python loop
    (no scan, no cache) with oracle attention.  MLA archs run the naive
    UNCOMPRESSED formulation (materialized per-head k/v) — sharing
    nothing with the absorbed-latent engine path it checks."""
    if cfg.family != "decoder" or cfg.attn not in ("gqa", "mla"):
        raise NotImplementedError(
            "reference decode covers GQA/MLA decoders (the paged-engine "
            "scope)")
    b, s = tokens.shape
    x = params["embed"][tokens] * jnp.asarray(
        math.sqrt(cfg.d_model), params["embed"].dtype)
    positions = jnp.arange(s)
    with jax.ensure_compile_time_eval():   # static even under jit
        windows = [int(w) for w in _layer_windows(cfg, cfg.n_layers)]
    for i in range(cfg.n_layers):
        blk = jax.tree.map(lambda p: p[i], params["blocks"])
        window = None if windows[i] == _NO_WINDOW else windows[i]
        h = L.rms_norm(x, blk["ln1"])
        if cfg.attn == "mla":
            q, k, v = mla_materialized_qkv(blk["attn"], cfg, h, positions)
        else:
            q, k, v = L.gqa_qkv(blk["attn"], cfg, h, positions)
        o = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True,
                          window=window, logit_cap=cfg.softcap_attn)
        a = o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ blk["attn"]["wo"]
        if "ln1_post" in blk:
            a = L.rms_norm(a, blk["ln1_post"])
        x = x + a
        h = L.rms_norm(x, blk["ln2"])
        f = (M.apply_moe(blk["mlp"], cfg, h) if cfg.moe
             else L.apply_mlp(blk["mlp"], cfg, h))
        if "ln2_post" in blk:
            f = L.rms_norm(f, blk["ln2_post"])
        x = x + f
    x = L.rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return L.mask_vocab(
        L.softcap((x @ head).astype(jnp.float32), cfg.softcap_logits),
        cfg.vocab)


# one compile per (cfg, context length); run op by op, every op of the
# loop would compile anew for each new length
_forward_ref_jit = jax.jit(forward_ref, static_argnums=1)


def reference_decode(params: Params, cfg: ArchConfig, prompt: list[int], *,
                     max_new_tokens: int, eos_id: int = -1,
                     max_seq: int = 128) -> list[int]:
    """Greedy decode of one request; the engine's retirement semantics
    exactly: stop after max_new_tokens, on emitting eos_id, or when the
    context (prompt + generated) reaches max_seq."""
    ctx = list(prompt)
    out: list[int] = []
    while len(out) < max_new_tokens and len(ctx) < max_seq:
        logits = _forward_ref_jit(params, cfg,
                                  jnp.asarray([ctx], jnp.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        ctx.append(tok)
        if tok == eos_id:
            break
    return out
