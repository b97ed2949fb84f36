"""Device-side n-gram (prompt-lookup) drafting for speculative decoding.

The drafter proposes ``draft_len`` continuation tokens per slot by
matching the tail n-gram of the slot's own token history against every
earlier position of that history and copying the continuation of the
most recent match — no draft model, no extra weights, pure jnp.  It runs
INSIDE the fused speculative dispatch (``models.verify_ticks``), so
drafting never costs a host round-trip; the batched paged verify step
then scores the whole window in one forward and keeps exactly the
greedy-correct prefix (DESIGN.md §8.8).

Quality of the proposals only moves the ACCEPTANCE RATE, never
correctness: rejected drafts are rolled back by the verify step, so any
deterministic proposal function yields the same greedy tokens.
Prompt-lookup is the classic weight-free drafter (arXiv:2304.04487 /
"prompt lookup decoding"): it wins exactly on the repeated-structure
contexts — code, retrieved documents, and the short cycles greedy
decoding itself falls into — where decode spends most of its time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def draft_ngram_propose(history: jax.Array, ctx_len: jax.Array, *,
                        draft_len: int, ngram: int = 2) -> jax.Array:
    """Propose ``draft_len`` tokens per slot from its own history.

    history: (B, H) int32 token ring per slot — positions [0, ctx_len[b])
    hold the slot's context (prompt + generated so far, INCLUDING the
    last emitted token at index ctx_len[b] - 1); later positions are
    ignored.  ctx_len: (B,) int32 in [1, H].

    Returns (B, draft_len) int32 proposals.  For each slot, the tail
    ``ngram`` tokens are matched against every earlier window of the
    history; the continuation start ``i`` of the MOST RECENT full match
    (largest i with history[i-ngram : i] == history[ctx_len-ngram :
    ctx_len], ngram <= i < ctx_len) supplies proposals history[i],
    history[i+1], ...; positions running past the known context — and
    every slot with no match or a context shorter than ngram+1 — fall
    back to repeating the last emitted token.

    Properties the engine and tests lean on (tests/test_speculative.py):
    deterministic (same inputs -> same proposals, no PRNG), proposals
    are always drawn from the slot's own context tokens (so a drafted
    token can never introduce an out-of-vocab id), and the function
    never reads another slot's row.  The drafter proposes TOKENS only;
    the scheduler's write plan caps how far past the context the verify
    window may write (never past max_seq - 1).
    """
    if draft_len < 1:
        raise ValueError(f"draft_len must be >= 1, got {draft_len}")
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")
    b, h = history.shape
    idx = jnp.arange(h)
    last = jnp.take_along_axis(history, (ctx_len - 1)[:, None], axis=1)
    # match[b, i] == True iff the ngram window ENDING at i (exclusive)
    # equals the tail window ending at ctx_len[b]: compare the j-th
    # element of both windows for j in [0, ngram).
    match = jnp.ones((b, h), bool)
    for j in range(ngram):
        shifted = history[:, jnp.clip(idx - ngram + j, 0, h - 1)]
        tail_j = jnp.take_along_axis(
            history, jnp.clip(ctx_len - ngram + j, 0, h - 1)[:, None],
            axis=1)
        match &= shifted == tail_j
    # i is the continuation START: need a full window before it and at
    # least one real context token at it (i == ctx_len would be the
    # trivial self-match with nothing known after it).
    valid = ((idx[None, :] >= ngram) & (idx[None, :] < ctx_len[:, None])
             & (ctx_len[:, None] > ngram))
    best = jnp.max(jnp.where(match & valid, idx[None, :], -1), axis=1)
    found = best >= 0
    pos = best[:, None] + jnp.arange(draft_len)[None, :]     # (B, D)
    in_ctx = found[:, None] & (pos < ctx_len[:, None])
    copied = jnp.take_along_axis(history, jnp.clip(pos, 0, h - 1), axis=1)
    return jnp.where(in_ctx, copied, last).astype(jnp.int32)
