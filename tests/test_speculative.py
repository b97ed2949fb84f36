"""Speculative decoding suite (ISSUE 5): device-side n-gram drafting,
batched paged verification, exact greedy acceptance.

(a) THE SPECULATION CONTRACT (DESIGN.md §8.8): ``models.verify_ticks``
    must emit exactly the greedy tokens the fused non-speculative
    ``decode_ticks`` would emit; accepted window positions carry the
    decode tick's KV within ``ACCEPTED_ATOL`` (the verify forward
    computes them from a W-row operand, which XLA may reduce in another
    order); rejected and null-routed positions roll back BIT-exactly to
    their pre-step contents (they are copies).  Only the null page,
    which absorbs out-of-plan garbage by design, is excluded.  Checked
    for BOTH cache families (GQA + MLA latent).
(b) ENGINE PARITY — the speculative engine serves every request
    token-identical to the non-speculative fused engine and to the
    dense reference oracle, across eos-mid-window, max-seq truncation,
    block-boundary preemption, prime page/pool geometries, and the
    window/softcap/MoE archs.
(c) DRAFTER — the pure n-gram drafter is deterministic, matches a numpy
    oracle (hypothesis property), and only ever proposes tokens from
    the slot's own context.
(d) KERNEL — the paged op over a window (and the Pallas prefill
    kernel vmapped over slots, interpret mode) vs the dense oracle, and
    each row of a W-window pinned BITWISE against the one-token call at
    its position (the equality the whole §8.8 parity argument rests
    on).
Plus the satellite guards: greedy-only speculation raises on sampled
configs, and the engine's geometry asserts are real ValueErrors now.
"""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.configs import get_arch
from repro.models import (decode_ticks, draft_ngram_propose, init_params,
                          verify_ticks)
from repro.serve import Request, ServeEngine, paco_draft_len, \
    paco_page_size, reference_decode

KEY = jax.random.PRNGKey(0)


def _cfg(arch="qwen3-0.6b"):
    """Reduced config with UNTIED embeddings (tied embeddings echo the
    last token at random init, which would fake high acceptance AND let
    a broken verify path pass parity)."""
    return dataclasses.replace(get_arch(arch).reduced(),
                               tie_embeddings=False)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, KEY)


def _assert_parity(engine, params, cfg, done):
    assert done, "engine drained nothing"
    for r in sorted(done, key=lambda r: r.uid):
        ref = reference_decode(params, cfg, r.prompt,
                               max_new_tokens=r.max_new_tokens,
                               eos_id=r.eos_id, max_seq=engine.max_seq)
        assert r.out == ref, (
            f"req {r.uid} (prompt {r.prompt}, preemptions "
            f"{r.preemptions}): engine {r.out} != reference {ref}")


# ---------------------------------------------------------------------------
# (a) verify_ticks vs decode_ticks: equal tokens, pool within the contract
# ---------------------------------------------------------------------------

# Accepted window positions hold K/V that the W-position verify forward
# computed and the W=1 decode tick computes again: the same dot products,
# but XLA may reduce a W-row and a 1-row operand in different orders, so
# float32 results can differ in the last bits (up to 8.3e-7 measured on
# these reduced configs, jax 0.9 CPU).  1e-5 is tens of ulps at the
# pools' O(1) magnitudes; a wrong position or a stale value is off by O(1).
ACCEPTED_ATOL = 1e-5


def _assert_pool_contract(pool_v, pool_d, pool0, written, n_pages):
    """Non-null pool positions in ``written`` ((n_pages + 1, page) bool)
    equal the decode path's ``pool_d`` within ACCEPTED_ATOL; every other
    non-null position is bit-identical to the pre-step ``pool0``
    (rolled back, null-routed or never written: copies)."""
    acc = written[:n_pages]
    for name in pool_v:
        got = np.asarray(pool_v[name])[:, :n_pages]
        np.testing.assert_array_equal(
            got[:, ~acc], pool0[name][:, :n_pages][:, ~acc],
            err_msg=f"leaf {name!r}: a rejected or unwritten position "
                    f"changed (rollback must restore its exact bytes)")
        np.testing.assert_allclose(
            got[:, acc], pool_d[name][:, :n_pages][:, acc], rtol=0,
            atol=ACCEPTED_ATOL,
            err_msg=f"leaf {name!r}: an accepted position differs from "
                    f"the decode tick's K/V beyond ACCEPTED_ATOL")


def _bitwise_vs_decode(arch, draft_len=3, steps=4, ngram=2, warm=4):
    """Run verify_ticks and decode_ticks from the SAME engine state and
    require: (1) each slot's emitted tokens are a prefix of the decode
    path's token stream; (2) every accepted window position holds the
    decode path's KV within ACCEPTED_ATOL; (3) every other non-null pool
    byte is untouched (rollback erased the rejected drafts)."""
    cfg = _cfg(arch)
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=2, max_seq=96, page_size=4,
                      prefill_chunk_len=8)
    eng.submit(Request(uid=0, prompt=[1, 2, 3, 1, 2, 3, 1],
                       max_new_tokens=80))
    eng.submit(Request(uid=1, prompt=[9, 9, 9, 9, 9], max_new_tokens=80))
    eng._admit()
    # warm the contexts with ``warm`` NON-speculative dispatches first:
    # greedy decode of a random-init model falls into short cycles after
    # ~10-40 tokens, which is where the n-gram drafter starts matching —
    # the comparison then exercises BOTH the accepted-write and the
    # rolled-back branch (the counts are per-arch: where each reduced
    # model's stream starts to repeat under the installed PRNG).
    for _ in range(warm):
        eng.tick()
    w = draft_len + 1
    span = steps * w
    eng._ensure_decode_pages(span)
    bt = eng.tables.device()
    toks0 = jnp.asarray(eng._last_tok, jnp.int32)
    lens0 = jnp.asarray(eng._ctx_len, jnp.int32)
    pool0 = {k: np.asarray(v) for k, v in eng.pool.pools.items()}
    ones = jnp.ones((2,), bool)
    bud = jnp.full((2,), 100, jnp.int32)
    eos = jnp.full((2,), -1, jnp.int32)

    # baseline: the fused non-speculative engine's scan, span ticks
    block_d, pool_d = decode_ticks(
        params, cfg, toks0, {k: jnp.asarray(v) for k, v in pool0.items()},
        bt, lens0, ones, bud, eos, jnp.zeros((span, 2), jnp.uint32),
        max_seq=eng.max_seq)
    block_d = np.asarray(block_d)                       # (span, B)
    pool_d = {k: np.asarray(v) for k, v in pool_d.items()}

    # speculative: steps draft->verify->accept windows
    limit = lens0 + span
    blocks_v, acc_v, _, pool_v = verify_ticks(
        params, cfg, toks0, {k: jnp.asarray(v) for k, v in pool0.items()},
        bt, lens0, ones, bud, eos, jnp.asarray(eng._hist), limit,
        jnp.zeros((steps,), jnp.int32), max_seq=eng.max_seq,
        draft_len=draft_len, ngram=ngram)
    blocks_v = np.asarray(blocks_v)                     # (steps, B, W)
    pool_v = {k: np.asarray(v) for k, v in pool_v.items()}

    total_accepted = 0
    n_pages = eng.pool.n_pages                          # null page excluded
    written = np.zeros((n_pages + 1, eng.page), bool)
    for slot in range(2):
        emitted = [int(t) for t in blocks_v[:, slot].ravel() if t >= 0]
        m = len(emitted)
        assert steps <= m <= span
        # uncapped budgets: every window ends on its correction token,
        # so the device-reported accepted counts must equal emits - 1
        assert int(np.asarray(acc_v)[:, slot].sum()) == m - steps
        total_accepted += m - steps                     # 1 forced emit/step
        # (1) tokens: exactly the non-speculative stream's prefix
        assert emitted == [int(t) for t in block_d[:m, slot]], \
            (slot, emitted, block_d[:, slot])
        # (2)+(3): the m written positions carry the decode path's KV,
        # every other position its original bytes
        for t in range(m):
            pos = int(lens0[slot]) + t
            written[int(eng.tables.row(slot)[pos // eng.page]),
                    pos % eng.page] = True
    _assert_pool_contract(pool_v, pool_d, pool0, written, n_pages)
    # the run must actually have accepted drafts, or the test is vacuous
    assert total_accepted > 0, "no draft was ever accepted"


def test_verify_ticks_bitwise_gqa():
    _bitwise_vs_decode("qwen3-0.6b")


def test_verify_ticks_bitwise_mla_latent():
    _bitwise_vs_decode("deepseek-v2-236b", warm=5)


def test_verify_ticks_bitwise_window_softcap():
    """gemma2: alternating local sliding windows + attn softcap through
    the verify path's per-position masks."""
    _bitwise_vs_decode("gemma2-2b", draft_len=2, steps=4)


def test_verify_rollback_under_budget_cap():
    """A slot with budget 1 still verifies a full window; everything past
    its single emitted token must roll back / null-route bit-exactly,
    leaving the non-null pool equal to one decode tick's result (the
    emitted position within ACCEPTED_ATOL)."""
    cfg = _cfg()
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, page_size=4,
                      prefill_chunk_len=8)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=50))
    eng.submit(Request(uid=1, prompt=[5, 6, 7], max_new_tokens=50))
    eng._admit()
    eng._ensure_decode_pages(1)
    bt = eng.tables.device()
    toks0 = jnp.asarray(eng._last_tok, jnp.int32)
    lens0 = jnp.asarray(eng._ctx_len, jnp.int32)
    pool0 = {k: np.asarray(v) for k, v in eng.pool.pools.items()}
    ones = jnp.ones((2,), bool)
    eos = jnp.full((2,), -1, jnp.int32)
    block_d, pool_d = decode_ticks(
        params, cfg, toks0, {k: jnp.asarray(v) for k, v in pool0.items()},
        bt, lens0, ones, jnp.full((2,), 1, jnp.int32), eos,
        jnp.zeros((1, 2), jnp.uint32), max_seq=eng.max_seq)
    blocks_v, _, _, pool_v = verify_ticks(
        params, cfg, toks0, {k: jnp.asarray(v) for k, v in pool0.items()},
        bt, lens0, ones, jnp.full((2,), 1, jnp.int32), eos,
        jnp.asarray(eng._hist), lens0 + 1,   # plan maps ONE position
        jnp.zeros((1,), jnp.int32), max_seq=eng.max_seq, draft_len=3)
    blocks_v = np.asarray(blocks_v)
    n_pages = eng.pool.n_pages
    written = np.zeros((n_pages + 1, eng.page), bool)
    for slot in range(2):
        emitted = [int(t) for t in blocks_v[:, slot].ravel() if t >= 0]
        assert emitted == [int(np.asarray(block_d)[0, slot])]
        pos = int(lens0[slot])
        written[int(eng.tables.row(slot)[pos // eng.page]),
                pos % eng.page] = True
    pool_d = {k: np.asarray(v) for k, v in pool_d.items()}
    _assert_pool_contract(pool_v, pool_d, pool0, written, n_pages)


# ---------------------------------------------------------------------------
# (b) engine-level parity: speculative engine == fused engine == oracle
# ---------------------------------------------------------------------------

_SPEC_PROMPTS = [[1, 2, 3, 1, 2, 3, 1], [9, 9, 9, 9, 9], [2, 4],
                 [7, 1, 7, 1, 7, 1]]


def _drain_spec_vs_fused(cfg, params, *, speculate=3, new_tokens=24,
                         **kw):
    outs = {}
    for spec in (None, speculate):
        eng = ServeEngine(params, cfg, speculate=spec,
                          spec_min_accept=0, **kw)
        for i, p in enumerate(_SPEC_PROMPTS):
            eng.submit(Request(uid=i, prompt=list(p),
                               max_new_tokens=new_tokens))
        done = eng.run_until_drained()
        assert len(done) == len(_SPEC_PROMPTS)
        eng.check_page_invariants()
        assert eng.pool.free_count() == eng.pool.n_pages
        outs[spec] = (eng, {r.uid: r.out for r in done})
    spec_eng, spec_out = outs[speculate]
    _, fused_out = outs[None]
    assert spec_out == fused_out, (spec_out, fused_out)
    _assert_parity(spec_eng, params, cfg,
                   [r for r in spec_eng.done])
    return spec_eng


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "gemma2-2b",
                                  "olmoe-1b-7b", "deepseek-v2-236b"])
def test_spec_engine_matches_fused_all_archs(arch):
    """Token-identical speculative serving on every parity arch: plain
    GQA, local windows + softcaps + post-norms, MoE mlp in the verify
    scan, and the MLA latent cache family."""
    cfg = _cfg(arch)
    params = init_params(cfg, KEY)
    eng = _drain_spec_vs_fused(cfg, params, slots=3, max_seq=64,
                               prefill_chunk_len=16)
    assert eng.stats["accepted_tokens"] > 0, \
        "speculation never accepted a draft — parity test is vacuous"


def test_spec_eos_mid_window(params, cfg):
    """eos landing INSIDE a verify window: the device emission cap must
    stop at exactly the reference position and roll back the rest of
    the window; a sibling slot decodes on unperturbed."""
    ref = reference_decode(params, cfg, [4, 2, 9], max_new_tokens=12,
                           max_seq=64)
    eos = ref[2]   # third generated token: mid-window for draft_len=3
    eng = ServeEngine(params, cfg, slots=2, max_seq=64, speculate=3,
                      ticks_per_dispatch=4, spec_min_accept=0)
    eng.submit(Request(uid=0, prompt=[4, 2, 9], max_new_tokens=12,
                       eos_id=eos))
    eng.submit(Request(uid=1, prompt=[7, 7], max_new_tokens=12,
                       eos_id=eos))
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)
    r0 = next(r for r in done if r.uid == 0)
    assert r0.out == ref[:3] and r0.out[-1] == eos


def test_spec_max_seq_truncation(params, cfg):
    """Budgets overrunning max_seq truncate identically: the device
    emission cap enforces the same max_seq rule as _emit even when the
    window would run past the last writable position."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=16, page_size=4,
                      speculate=3, spec_min_accept=0)
    eng.submit(Request(uid=0, prompt=list(range(1, 11)),
                       max_new_tokens=50))
    eng.submit(Request(uid=1, prompt=[3, 5], max_new_tokens=50))
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)
    r0 = next(r for r in done if r.uid == 0)
    assert len(r0.prompt) + len(r0.out) == 16


def test_spec_preemption_at_block_boundary(params, cfg):
    """Pool pressure with speculative pre-mapping (ticks x window
    positions per slot): the youngest request is preempted at the
    dispatch boundary, re-prefilled, and resumes bit-identically."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, page_size=4,
                      pool_pages=11, prefill_chunk_len=8, speculate=2,
                      ticks_per_dispatch=2)   # prime poo, spec_min_accept=0)
    for i, p in enumerate([[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]]):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=20))
    done = eng.run_until_drained()
    assert eng.stats["preemptions"] >= 1
    assert any(r.preemptions > 0 for r in done)
    eng.check_page_invariants()
    assert eng.pool.free_count() == eng.pool.n_pages
    _assert_parity(eng, params, cfg, done)


def test_spec_prime_page_geometry(params, cfg):
    """Odd page size + prime pool + draft window straddling page
    boundaries: parity must survive any window/page alignment."""
    eng = ServeEngine(params, cfg, slots=3, max_seq=63, page_size=7,
                      pool_pages=29, prefill_chunk_len=7, speculate=4, spec_min_accept=0)
    for i, p in enumerate([[1, 2, 3, 1, 2, 3], [5] * 9, [8, 6]]):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=9))
    done = eng.run_until_drained()
    assert len(done) == 3
    eng.check_page_invariants()
    _assert_parity(eng, params, cfg, done)


def test_spec_mla_latent_preemption():
    """MLA latent pages under speculative pre-mapping pressure: evictee
    resumes to the exact uncompressed-oracle continuation."""
    cfg = _cfg("deepseek-v2-236b")
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=3, max_seq=32, page_size=4,
                      pool_pages=11, prefill_chunk_len=8, speculate=2,
                      ticks_per_dispatch=2, spec_min_accept=0)
    for i, p in enumerate([[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]]):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=16))
    done = eng.run_until_drained()
    assert len(done) == 3
    assert eng.stats["preemptions"] >= 1
    eng.check_page_invariants()
    _assert_parity(eng, params, cfg, done)


def test_spec_pool_donation_no_copy(params, cfg):
    """The verify dispatch donates the pool pytree exactly like the
    decode dispatch: pre-dispatch leaves must be deleted (in-place page
    writes), and the in-place outputs still decode to parity."""
    probe = jnp.zeros((4,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(probe)
    if not probe.is_deleted():
        pytest.skip("backend does not implement buffer donation")
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, speculate=2,
                      prefill_chunk_len=8, spec_min_accept=0)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=6))
    eng.tick()   # prefill donates
    before = dict(eng.pool.pools)
    eng.tick()   # speculative decode dispatch
    for name, leaf in before.items():
        assert leaf.is_deleted(), \
            f"pool leaf {name!r} was copied through the verify dispatch"
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)


def test_spec_acceptance_stats_consistent(params, cfg):
    """accepted <= drafted, and every window emits its accepted drafts
    plus AT MOST one correction token (a flag-truncated window ends on
    an accepted draft instead — the device-reported count covers it):
    spec_windows <= decode_tokens <= spec_windows + accepted."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=64, speculate=3, spec_min_accept=0)
    for i, p in enumerate(_SPEC_PROMPTS[:3]):
        eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=12))
    eng.run_until_drained()
    s = eng.stats
    assert s["spec_windows"] > 0
    assert s["drafted_tokens"] == 3 * s["spec_windows"]
    assert 0 <= s["accepted_tokens"] <= s["drafted_tokens"]
    assert (s["spec_windows"] <= s["decode_tokens"]
            <= s["spec_windows"] + s["accepted_tokens"])


def test_spec_history_stays_device_resident(params, cfg):
    """Between speculative dispatches with no slot churn, the token
    history lives on device (the verify scan's appends mirror the host
    replay, so no per-dispatch re-upload); the cached copy must agree
    with the host history token-for-token over each slot's context."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=64, speculate=3,
                      ticks_per_dispatch=2, spec_min_accept=0)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=30))
    eng.submit(Request(uid=1, prompt=[9, 9, 9], max_new_tokens=30))
    eng.tick()
    assert eng._hist_dev is not None   # set by the verify dispatch
    eng.tick()                         # reuses + re-returns the copy
    for s in range(2):
        if eng.active[s] is not None:
            upto = eng._ctx_len[s] + 1
            np.testing.assert_array_equal(
                np.asarray(eng._hist_dev)[s, :upto],
                eng._hist[s, :upto])
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)


def test_spec_adaptive_fallback(params, cfg):
    """Acceptance-aware fallback: on a workload the drafter cannot
    predict (threshold forced above any real acceptance), the scheduler
    stops paying the verify cost — after the rolling window fills, most
    dispatches are plain fused decode with periodic speculative probes
    — and parity still holds, because the two dispatch kinds are
    bit-identical and switching is free."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=64, speculate=3,
                      ticks_per_dispatch=2, spec_min_accept=0.99)
    for i in range(4):
        eng.submit(Request(uid=i, prompt=[11 + 7 * i, 3 + i, 29],
                           max_new_tokens=24))
    done = eng.run_until_drained()
    s = eng.stats
    assert s["spec_fallback_dispatches"] > 0, \
        "fallback never engaged despite a 0.99 threshold"
    assert s["spec_windows"] > 0   # the pre-fill + probe windows ran
    _assert_parity(eng, params, cfg, done)
    # an always-speculate engine (threshold 0) must never fall back
    eng2 = ServeEngine(params, cfg, slots=2, max_seq=64, speculate=3,
                       ticks_per_dispatch=2, spec_min_accept=0)
    for i in range(4):
        eng2.submit(Request(uid=i, prompt=[11 + 7 * i, 3 + i, 29],
                            max_new_tokens=24))
    done2 = eng2.run_until_drained()
    assert eng2.stats["spec_fallback_dispatches"] == 0
    assert {r.uid: r.out for r in done2} == {r.uid: r.out for r in done}


# ---------------------------------------------------------------------------
# satellite guards: greedy-only contract + geometry ValueErrors
# ---------------------------------------------------------------------------

def test_speculate_rejects_sampled_configs(params, cfg):
    """top_k/temperature + speculate must raise NOW, naming exact
    rejection sampling — never silently emit non-parity tokens."""
    with pytest.raises(NotImplementedError,
                       match="(?i)rejection sampling"):
        ServeEngine(params, cfg, speculate=4, top_k=4)
    with pytest.raises(NotImplementedError,
                       match="(?i)rejection sampling"):
        ServeEngine(params, cfg, speculate=4, temperature=0.8)
    with pytest.raises(ValueError, match="speculate"):
        ServeEngine(params, cfg, speculate=-1)


def test_geometry_errors_name_the_value(params, cfg):
    """The old bare asserts are ValueErrors naming the offending value
    and the divisibility rule."""
    with pytest.raises(ValueError, match=r"page_size=5.*max_seq=64"):
        ServeEngine(params, cfg, max_seq=64, page_size=5)
    with pytest.raises(ValueError,
                       match=r"prefill_chunk_len=6.*page_size=4"):
        ServeEngine(params, cfg, max_seq=64, page_size=4,
                    prefill_chunk_len=6)
    with pytest.raises(ValueError,
                       match=r"prefill_chunk_len=24.*max_seq=64"):
        ServeEngine(params, cfg, max_seq=64, page_size=4,
                    prefill_chunk_len=24)
    with pytest.raises(ValueError, match=r"pool_pages=3"):
        ServeEngine(params, cfg, max_seq=64, page_size=4, pool_pages=3)


def test_paco_draft_len_is_leaf_tile():
    """The verify window is planned from the cache cuboid, not a magic
    number: window = draft_len + 1 never exceeds the PACO page size
    (one whole-page scatter per window) and stays in a sane range."""
    for slots in (1, 2, 3, 4, 7, 16):
        for max_seq in (16, 64, 128, 512):
            d = paco_draft_len(slots, max_seq, 64)
            page = paco_page_size(slots, max_seq, 64)
            assert 1 <= d <= 7
            assert d + 1 <= max(page, 2), (slots, max_seq, d, page)


# ---------------------------------------------------------------------------
# (c) the n-gram drafter: numpy oracle, determinism, membership
# ---------------------------------------------------------------------------

def _draft_oracle(hist, ctx_len, draft_len, ngram):
    b, h = hist.shape
    out = np.zeros((b, draft_len), np.int64)
    for i in range(b):
        L = int(ctx_len[i])
        row = hist[i]
        last = row[L - 1]
        best = -1
        if L > ngram:
            tail = row[L - ngram:L]
            for s_ in range(ngram, L):
                if np.array_equal(row[s_ - ngram:s_], tail):
                    best = s_          # ascending scan keeps the LAST
        for t in range(draft_len):
            out[i, t] = (row[best + t]
                         if best >= 0 and best + t < L else last)
    return out


def test_draft_ngram_matches_oracle_fixed():
    hist = np.array([
        [1, 2, 3, 1, 2, 3, 1, 2, 0, 0],    # periodic: match at i=5
        [7, 7, 7, 7, 7, 0, 0, 0, 0, 0],    # constant run
        [1, 2, 3, 4, 5, 6, 7, 8, 9, 10],   # no repeat: fallback
        [4, 0, 0, 0, 0, 0, 0, 0, 0, 0],    # ctx shorter than ngram
    ], np.int32)
    ctx = np.array([8, 5, 10, 1], np.int32)
    got = np.asarray(draft_ngram_propose(jnp.asarray(hist),
                                         jnp.asarray(ctx),
                                         draft_len=4, ngram=2))
    want = _draft_oracle(hist, ctx, 4, 2)
    np.testing.assert_array_equal(got, want)
    # periodic row: most recent [1,2] match ends at i=5, so the window
    # copies hist[5:8] = [3,1,2] and falls back to the last token (2)
    # once it runs past the known context; fallback rows repeat theirs.
    assert list(got[0]) == [3, 1, 2, 2]
    assert list(got[2]) == [10, 10, 10, 10]
    assert list(got[3]) == [4, 4, 4, 4]


@settings(max_examples=25, deadline=None)
@given(
    rows=st.lists(
        st.lists(st.integers(0, 4), min_size=1, max_size=14),
        min_size=1, max_size=4),
    draft_len=st.integers(1, 5),
    ngram=st.integers(1, 3),
)
def test_property_draft_ngram(rows, draft_len, ngram):
    """Hypothesis: the jnp drafter == the numpy oracle on random
    histories (tiny vocab so matches actually occur), is deterministic,
    and proposes only tokens already present in the slot's context."""
    h = max(len(r) for r in rows) + 2
    hist = np.zeros((len(rows), h), np.int32)
    ctx = np.zeros((len(rows),), np.int32)
    for i, r in enumerate(rows):
        hist[i, :len(r)] = r
        ctx[i] = len(r)
    got = np.asarray(draft_ngram_propose(jnp.asarray(hist),
                                         jnp.asarray(ctx),
                                         draft_len=draft_len,
                                         ngram=ngram))
    again = np.asarray(draft_ngram_propose(jnp.asarray(hist),
                                           jnp.asarray(ctx),
                                           draft_len=draft_len,
                                           ngram=ngram))
    np.testing.assert_array_equal(got, again)   # deterministic
    np.testing.assert_array_equal(
        got, _draft_oracle(hist, ctx, draft_len, ngram))
    for i, r in enumerate(rows):
        assert set(got[i]) <= set(r)            # context tokens only


# ---------------------------------------------------------------------------
# (d) paged verify attention: dense-oracle + bitwise-decode pins
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {}, {"window": 6}, {"logit_cap": 20.0},
    {"window": 3, "logit_cap": 5.0},
])
def test_paged_verify_matches_dense_ref(kw):
    from repro.kernels.attention import (paged_attention,
                                         paged_flash_prefill_pallas,
                                         paged_verify_ref)

    b, w, hq, hkv, d, page, n_pages = 3, 4, 4, 2, 16, 4, 13
    q = jax.random.normal(KEY, (b, w, hq, d))
    kp = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, hkv, d))
    vp = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, hkv, d))
    bt = jnp.asarray(np.array([[0, 3, 5, 7], [1, 2, 4, 6],
                               [8, 9, 10, 11]], np.int32))
    lens = jnp.asarray([5, 12, 0], jnp.int32)
    ref = paged_verify_ref(q, kp, vp, bt, lens, **kw)
    out = paged_attention(q, kp, vp, bt, lens, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-6)
    # multi-token causal paged attention is the prefill kernel's job:
    # one call per slot, with its own (start, block row)
    pal = jax.vmap(lambda qb, row, st: paged_flash_prefill_pallas(
        qb, kp, vp, row, st, scale=d ** -0.5, interpret=True, **kw))(
        q, bt, lens)
    np.testing.assert_allclose(pal, ref, atol=2e-6)


def test_paged_verify_w1_bitwise_decode():
    """THE §8.8 parity anchor: on the same pool, each row t of a
    W-token window computes BIT-identical output to the one-token
    (decode) call at position q_start + t — same gather, same einsum
    contraction, same mask values — so every accepted speculative
    position reproduces the decode tick exactly."""
    from repro.kernels.attention import paged_attention

    b, w, hq, hkv, d, page, n_pages = 3, 4, 4, 2, 16, 4, 13
    q = jax.random.normal(KEY, (b, w, hq, d))
    kp = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, hkv, d))
    vp = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, hkv, d))
    bt = jnp.asarray(np.array([[0, 3, 5, 7], [1, 2, 4, 6],
                               [8, 9, 10, 11]], np.int32))
    lens = jnp.asarray([5, 12, 1], jnp.int32)
    ver = paged_attention(q, kp, vp, bt, lens)
    for t in range(w):
        dec = paged_attention(q[:, t:t + 1], kp, vp, bt, lens + t)
        np.testing.assert_array_equal(np.asarray(ver[:, t:t + 1]),
                                      np.asarray(dec))


def test_paged_latent_verify_matches_dense_ref():
    from repro.kernels.attention import (paged_latent_attention,
                                         paged_latent_prefill_pallas,
                                         paged_latent_verify_ref)

    b, w, h, kv, rope, page, n_pages = 3, 4, 4, 16, 8, 4, 13
    scale = 1.0 / np.sqrt(kv + rope)
    ql = jax.random.normal(KEY, (b, w, h, kv))
    qr = jax.random.normal(jax.random.PRNGKey(9), (b, w, h, rope))
    ck = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, kv))
    kr = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, rope))
    bt = jnp.asarray(np.array([[0, 3, 5, 7], [1, 2, 4, 6],
                               [8, 9, 10, 11]], np.int32))
    lens = jnp.asarray([5, 12, 0], jnp.int32)
    ref = paged_latent_verify_ref(ql, qr, ck, kr, bt, lens, scale=scale)
    out = paged_latent_attention(ql, qr, ck, kr, bt, lens, scale=scale)
    np.testing.assert_allclose(out, ref, atol=2e-6)
    pal = jax.vmap(lambda a, r, row, st: paged_latent_prefill_pallas(
        a, r, ck, kr, row, st, scale=scale, interpret=True))(
        ql, qr, bt, lens)
    np.testing.assert_allclose(pal, ref, atol=2e-6)


def test_paged_latent_verify_w1_bitwise_decode():
    from repro.kernels.attention import paged_latent_attention

    b, w, h, kv, rope, page, n_pages = 3, 4, 4, 16, 8, 4, 13
    scale = 1.0 / np.sqrt(kv + rope)
    ql = jax.random.normal(KEY, (b, w, h, kv))
    qr = jax.random.normal(jax.random.PRNGKey(9), (b, w, h, rope))
    ck = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, kv))
    kr = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, rope))
    bt = jnp.asarray(np.array([[0, 3, 5, 7], [1, 2, 4, 6],
                               [8, 9, 10, 11]], np.int32))
    lens = jnp.asarray([5, 12, 1], jnp.int32)
    ver = paged_latent_attention(ql, qr, ck, kr, bt, lens, scale=scale)
    for t in range(w):
        dec = paged_latent_attention(ql[:, t:t + 1], qr[:, t:t + 1], ck, kr,
                                     bt, lens + t, scale=scale)
        np.testing.assert_array_equal(np.asarray(ver[:, t:t + 1]),
                                      np.asarray(dec))


# ---------------------------------------------------------------------------
# CI smoke: the launcher drains with --speculate and reference parity
# ---------------------------------------------------------------------------

def test_launch_serve_speculative_smoke(monkeypatch, capsys):
    """`launch.serve --reduced --speculate 4` end to end on CPU (ISSUE 5
    satellite): drains, reports acceptance, and --verify-parity checks
    every request against the dense oracle.  Bounded: 4 short requests
    at reduced scale."""
    from repro.launch import serve as launch_serve

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen3-0.6b", "--reduced", "--speculate", "4",
        "--requests", "4", "--new-tokens", "8", "--slots", "2",
        "--max-seq", "32", "--verify-parity"])
    launch_serve.main()
    out = capsys.readouterr().out
    assert "speculation: draft_len=4" in out
    assert "reference parity: ok (4 requests)" in out
