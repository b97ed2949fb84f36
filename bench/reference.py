"""Plain float32 reference of the served decoder, and the comparison that
decides a run's ``correct``.

The reference imports nothing of the program.  It draws its weights from
the seed itself (``bench.weights``, one layer at a time) and runs the
published layer equations in ``jax.numpy`` at float32 with every matrix
product at precision HIGHEST, one layer at a time over all sampled
sequences, so that it fits on the chip beside nothing else.  Departures of
the program from the published model that the reference copies are listed
under ``assumed`` in each configuration file.

``mode="fp8"`` is the control: the same equations with every matrix
product's operands rounded to float8 e4m3 (weights per tensor, activations
per row, scaled to the format's range), the nearest precision below the
bfloat16 the configurations state.

What is compared: for every served token of the sampled requests, how far
its reference logit lies below the reference's largest logit at that
position (``gap``).  Greedy serving picks the argmax of its own bfloat16
logits, so the gap is 0 where the two agree and small where rounding
flips a near tie; a wrong cache, layer or token makes it large.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round(x, mode, axis):
    if mode == "f32":
        return x
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(spec: str, a, b, mode: str):
    """einsum of two float32 operands; in fp8 mode both are first rounded
    to e4m3 (``a`` per leading row, ``b`` per tensor)."""
    if mode == "fp8":
        a = _round(a, mode, axis=-1)
        b = _round(b, mode, axis=None)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope(x, positions, theta):
    """Rotate feature pairs (i, i + D/2) by position * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def layer(m: W.Model, w: dict, x, mode: str):
    """One decoder layer over one sequence x (T, d), causal."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rms_norm(x, w["ln1"], m.eps)
    q = mm("td,de->te", h, w["attn/wq"], mode).reshape(t, m.heads, -1)
    k = mm("td,de->te", h, w["attn/wk"], mode).reshape(t, m.kv_heads, -1)
    v = mm("td,de->te", h, w["attn/wv"], mode).reshape(t, m.kv_heads, -1)
    if m.qk_norm:
        q = rms_norm(q, w["attn/q_norm"], m.eps)
        k = rms_norm(k, w["attn/k_norm"], m.eps)
    q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
    g = m.heads // m.kv_heads          # query head j reads kv head j // g
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = mm("qhd,khd->hqk", q, k, mode) / math.sqrt(m.head_dim)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("hqk,khd->qhd", p, v, mode).reshape(t, -1)
    x = x + mm("te,ed->td", o, w["attn/wo"], mode)
    h = rms_norm(x, w["ln2"], m.eps)
    up = mm("td,df->tf", h, w["mlp/up"], mode)
    if m.act == "silu":
        f = jax.nn.silu(mm("td,df->tf", h, w["mlp/gate"], mode)) * up
    elif m.act == "relu2":
        f = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(f"activation {m.act!r} has no reference")
    return x + mm("tf,fd->td", f, w["mlp/down"], mode)


@functools.lru_cache(maxsize=None)
def _fns(m: W.Model, mode: str):
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))

    @jax.jit
    def embed(top, tokens):
        e = f32(top)["embed"][tokens]
        if mode == "fp8":   # the table is held in fp8 too
            e = _round(e, mode, axis=-1)
        return e * math.sqrt(m.d)

    @jax.jit
    def one_layer(w, x):
        return layer(m, f32(w), x, mode)

    @jax.jit
    def final(top, x):
        return rms_norm(x, top["final_norm"].astype(jnp.float32), m.eps)

    @jax.jit
    def logits(top, rows):
        top = f32(top)
        head = top["embed"].T if m.tied else top["lm_head"]
        return mm("nd,dv->nv", rows, head, mode)[:, :m.vocab]

    return embed, one_layer, final, logits


def hidden_rows(m: W.Model, seed: int, seqs: list[tuple[list[int], list[int]]],
                length: int, mode: str = "f32"):
    """Final-normed hidden state at every position that predicted a served
    token, stacked over ``seqs`` (prompt, served) in order: rows (N, d).
    Every sequence is padded to ``length`` so each function compiles once;
    padding lies after the scored positions, which causal attention never
    reads."""
    embed, one_layer, final, _ = _fns(m, mode)
    top = W.top_weights(m, seed)
    xs = []
    for prompt, served in seqs:
        toks = np.zeros((length,), np.int32)
        ctx = list(prompt) + list(served[:-1])
        toks[:len(ctx)] = ctx
        xs.append(embed(top, jnp.asarray(toks)))
    for li in range(m.layers):
        w = W.layer_weights(m, seed, li)
        xs = [one_layer(w, x) for x in xs]
        del w
    rows = []
    for (prompt, served), x in zip(seqs, xs):
        lo = len(prompt) - 1
        rows.append(final(top, x[lo:lo + len(served)]))
    return jnp.concatenate(rows, axis=0)


def compare(m: W.Model, seed: int, seqs: list[tuple[list[int], list[int]]],
            length: int, *, control: bool = False, block: int = 256) -> dict:
    """Gaps of the served tokens against the float32 reference.

    Returns ``gap`` (the widest), ``tokens`` (how many were compared),
    ``argmax_equal`` (served tokens equal to the reference argmax),
    ``echo`` (positions where the reference argmax repeats the input
    token: a share near 1 would make tokens a weak witness), and with
    ``control`` the fp8 control's widest gap (``control_gap``) on the same
    positions, where the control's own argmax stands for the served
    token."""
    served = np.concatenate([np.asarray(s, np.int64) for _, s in seqs])
    inputs = np.concatenate([np.asarray(([p[-1]] + list(s))[:-1], np.int64)
                             for p, s in seqs])
    top = W.top_weights(m, seed)
    logits = _fns(m, "f32")[3]
    rows = hidden_rows(m, seed, seqs, length)
    ctl_rows = ctl_logits = None
    if control:
        ctl_rows = hidden_rows(m, seed, seqs, length, mode="fp8")
        ctl_logits = _fns(m, "fp8")[3]
    gaps, ctl_gaps, argmax = [], [], []
    for i in range(0, rows.shape[0], block):
        lg = np.asarray(logits(top, rows[i:i + block]))
        best = lg.max(axis=1)
        idx = np.arange(lg.shape[0])
        gaps.append(best - lg[idx, served[i:i + block]])
        argmax.append(lg.argmax(axis=1))
        if control:
            pick = np.asarray(ctl_logits(top, ctl_rows[i:i + block])
                              ).argmax(axis=1)
            ctl_gaps.append(best - lg[idx, pick])
    gaps = np.concatenate(gaps)
    argmax = np.concatenate(argmax)
    out = {"gap": float(gaps.max()), "tokens": int(len(gaps)),
           "argmax_equal": int((argmax == served).sum()),
           "echo": int((argmax == inputs).sum())}
    if control:
        out["control_gap"] = float(np.concatenate(ctl_gaps).max())
    return out
