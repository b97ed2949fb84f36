"""Serving-engine parity/property suite (ISSUE 2 headline satellite).

(a) PARITY — every request served by the paged continuous-batching engine
    must emit tokens bit-identical to a single-request reference decode
    (dense re-forward per token through kernels/attention/ref.py), across
    unequal prompt lengths, eos early-exit, max-seq truncation, arrival
    mid-flight, and preemption/resume.
(b) PAGING — block-table invariants: no page shared across live slots,
    freed pages return to the pool, preempted requests resume with
    identical output, prefill issues exactly ceil(ctx/chunk) jitted calls
    per admission.
(c) PROPERTY — hypothesis-driven random prompt batches and random
    slot/page/pool geometry (primes included) via the optional-hypothesis
    shim (skips cleanly when hypothesis is absent).

Plus the paged-attention kernel oracle checks and the regression pin for
the old dense-engine cache-commit heuristic.
"""
import dataclasses
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.configs import get_arch
from repro.kernels.attention import (paged_attention_ref,
                                     paged_decode_attention)
from repro.models import init_params
from repro.serve import Request, ServeEngine, paco_page_size, \
    reference_decode

KEY = jax.random.PRNGKey(0)


def _cfg():
    """Reduced qwen3 with UNTIED embeddings: with tied embeddings a
    random-init decoder degenerately echoes its last token (logits ~
    x @ embed.T), which would let a broken cache path pass parity."""
    return dataclasses.replace(get_arch("qwen3-0.6b").reduced(),
                               tie_embeddings=False)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, KEY)


def _ref(params, cfg, req: Request, max_seq: int) -> list[int]:
    return reference_decode(params, cfg, req.prompt,
                            max_new_tokens=req.max_new_tokens,
                            eos_id=req.eos_id, max_seq=max_seq)


def _assert_parity(engine: ServeEngine, params, cfg, done) -> None:
    assert done, "engine drained nothing"
    for r in sorted(done, key=lambda r: r.uid):
        ref = _ref(params, cfg, r, engine.max_seq)
        assert r.out == ref, (
            f"req {r.uid} (prompt {r.prompt}, preemptions "
            f"{r.preemptions}): engine {r.out} != reference {ref}")


# ---------------------------------------------------------------------------
# (a) parity
# ---------------------------------------------------------------------------

def test_parity_unequal_prompts(params, cfg):
    """Prompts of different lengths sharing slots + page pool; more
    requests than slots so admission waits mid-flight."""
    eng = ServeEngine(params, cfg, slots=3, max_seq=64,
                      prefill_chunk_len=8)
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [3, 1], [9] * 12,
               [2, 4, 6, 8], [13]]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
    done = eng.run_until_drained()
    assert len(done) == len(prompts)
    eng.check_page_invariants()
    _assert_parity(eng, params, cfg, done)


def test_parity_eos_early_exit(params, cfg):
    """eos_id chosen from the reference output so it actually fires;
    the engine must stop at exactly the same position."""
    base = Request(uid=0, prompt=[4, 2, 9], max_new_tokens=10)
    ref_free = reference_decode(params, cfg, base.prompt,
                                max_new_tokens=10, max_seq=64)
    eos = ref_free[2]   # third generated token becomes eos
    eng = ServeEngine(params, cfg, slots=2, max_seq=64)
    eng.submit(Request(uid=0, prompt=[4, 2, 9], max_new_tokens=10,
                       eos_id=eos))
    eng.submit(Request(uid=1, prompt=[7, 7], max_new_tokens=10,
                       eos_id=eos))
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)
    r0 = next(r for r in done if r.uid == 0)
    assert r0.out[-1] == eos and len(r0.out) <= 3


def test_parity_eos_at_prefill(params, cfg):
    """eos as the FIRST generated token (emitted by prefill itself):
    the request must retire without ever entering a decode tick."""
    ref = reference_decode(params, cfg, [4, 2, 9], max_new_tokens=10,
                           max_seq=64)
    eng = ServeEngine(params, cfg, slots=2, max_seq=64)
    eng.submit(Request(uid=0, prompt=[4, 2, 9], max_new_tokens=10,
                       eos_id=ref[0]))
    done = eng.run_until_drained()
    assert done[0].out == [ref[0]]
    assert eng.stats["decode_steps"] == 0
    eng.check_page_invariants()


def test_parity_max_seq_truncation(params, cfg):
    """prompt + budget overruns max_seq: generation truncates when the
    context fills, identically to the reference."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=16, page_size=4)
    eng.submit(Request(uid=0, prompt=list(range(1, 11)),
                       max_new_tokens=50))
    eng.submit(Request(uid=1, prompt=[3, 5], max_new_tokens=50))
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)
    r0 = next(r for r in done if r.uid == 0)
    assert len(r0.prompt) + len(r0.out) == 16   # truncated at max_seq


def test_parity_arrival_mid_flight(params, cfg):
    """Requests submitted while others are mid-decode join via
    continuous batching without disturbing in-flight outputs."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=64,
                      prefill_chunk_len=8)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=12))
    eng.submit(Request(uid=1, prompt=[9, 8], max_new_tokens=12))
    for _ in range(4):
        eng.tick()
    eng.submit(Request(uid=2, prompt=[5, 5, 5, 5, 5], max_new_tokens=12))
    eng.submit(Request(uid=3, prompt=[2] * 9, max_new_tokens=4))
    done = eng.run_until_drained()
    assert len(done) == 4
    _assert_parity(eng, params, cfg, done)


@pytest.mark.parametrize("arch", ["gemma2-2b", "olmoe-1b-7b",
                                  "deepseek-v2-236b"])
def test_parity_window_softcap_moe_archs(arch):
    """End-to-end parity beyond plain GQA: gemma2 (alternating local
    sliding windows + attn/logit softcaps + post-norms), olmoe (MoE
    mlp in the decode scan), and deepseek-v2 (MLA latent paging: the
    engine serves compressed head-free c_kv/k_rope pages through the
    absorbed-W_uk decode path, checked against the naive UNCOMPRESSED
    re-forward oracle).  Prompts long enough that the context exceeds
    the reduced local_window (16), so the traced per-layer window
    actually masks."""
    cfg = dataclasses.replace(get_arch(arch).reduced(),
                              tie_embeddings=False)
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=3, max_seq=64,
                      prefill_chunk_len=16)
    prompts = [list(range(1, 25)), [5, 9, 2], [7] * 20, [3, 1, 4, 1, 5]]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=8))
    done = eng.run_until_drained()
    assert len(done) == len(prompts)
    eng.check_page_invariants()
    _assert_parity(eng, params, cfg, done)


def test_submit_rejects_invalid_requests(params, cfg):
    """Zero/negative token budgets are rejected up front: prefill always
    emits one token, so admitting them would diverge from the reference
    (which generates nothing)."""
    eng = ServeEngine(params, cfg, slots=1, max_seq=16)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=[1], max_new_tokens=0))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=1, prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=2, prompt=[1] * 16, max_new_tokens=4))


# ---------------------------------------------------------------------------
# (b) paging
# ---------------------------------------------------------------------------

def test_block_tables_disjoint_while_live(params, cfg):
    eng = ServeEngine(params, cfg, slots=4, max_seq=32, page_size=4)
    for i in range(6):
        eng.submit(Request(uid=i, prompt=[1 + i, 2, 3],
                           max_new_tokens=10))
    while eng.queue or any(eng.active):
        eng.tick()
        eng.check_page_invariants()   # after every tick, not just at end
    assert eng.pool.free_count() == eng.pool.n_pages


def test_pages_freed_on_retirement(params, cfg):
    eng = ServeEngine(params, cfg, slots=2, max_seq=32)
    eng.submit(Request(uid=0, prompt=[1, 2], max_new_tokens=3))
    done = eng.run_until_drained()
    assert len(done) == 1
    assert eng.pool.free_count() == eng.pool.n_pages
    assert eng.tables.live_pages(0) == []


def test_preemption_resumes_identically(params, cfg):
    """Pool too small for two full-length sequences: the youngest request
    is evicted mid-decode, re-queued, re-prefilled (prompt + generated),
    and still emits the exact reference continuation."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, page_size=4,
                      pool_pages=10, prefill_chunk_len=8)
    for i, p in enumerate([[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]]):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=20))
    done = eng.run_until_drained()
    assert eng.stats["preemptions"] >= 1
    assert any(r.preemptions > 0 for r in done)
    eng.check_page_invariants()
    assert eng.pool.free_count() == eng.pool.n_pages
    _assert_parity(eng, params, cfg, done)


def test_prefill_call_budget(params, cfg):
    """Chunked prefill: exactly ceil(ctx/chunk) jitted calls per
    admission — the O(prompt_len)-round-trips regression guard."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=64,
                      prefill_chunk_len=8)
    prompts = [[1], [2] * 8, [3] * 9, [4] * 17]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    done = eng.run_until_drained()
    assert eng.stats["preemptions"] == 0
    for r in done:
        assert r.prefill_calls == -(-len(r.prompt) // 8), \
            (r.uid, r.prefill_calls)


def test_request_timestamps_ordered_and_first_admission_kept(params, cfg):
    """Every served request carries t_submit <= t_admit <= t_first on the
    host clock; a preempted request keeps the time of its first admission
    and of its first token through re-admission."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, page_size=4,
                      pool_pages=10, prefill_chunk_len=8,
                      ticks_per_dispatch=4)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=20)
            for i, p in enumerate([[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]])]
    for r in reqs:
        eng.submit(r)
    seen = {}
    while eng.queue or any(r is not None for r in eng.active):
        eng.tick()
        for r in reqs:
            if r.t_admit is not None:
                first = seen.setdefault(r.uid, (r.t_admit, r.t_first))
                assert (r.t_admit, r.t_first) == first, r.uid
    assert eng.stats["preemptions"] >= 1
    assert any(r.preemptions > 0 for r in reqs)
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first, r.uid


def test_chip_smoke_timed_drain_reports_from_kept_counts(params, cfg,
                                                          monkeypatch,
                                                          capsys):
    """chip_smoke.py's timed drain at a tiny size on the CPU: what it
    prints comes from the engine's counts and the drain's wall clock."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(smoke, "NEW_TOKENS", 4)
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, page_size=4,
                      prefill_chunk_len=8, ticks_per_dispatch=4)
    outs = smoke.drain(eng, [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]],
                       timed=True)
    assert sorted(outs) == [0, 1, 2]
    assert all(len(o) == 4 for o in outs.values())
    assert "decode tok/s" in capsys.readouterr().out


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-236b"])
def test_step_programs_carry_every_leaf_scope(arch):
    """The lowered decode_ticks and prefill_chunk name their parts with
    the step programs' leaf scopes (op metadata), for GQA and MLA."""
    cfg = get_arch(arch).reduced()
    eng = ServeEngine(init_params(cfg, KEY), cfg, slots=2, max_seq=32)
    steps = eng.lower_steps()
    for name in ("decode_ticks", "prefill_chunk"):
        text = steps[name].as_text(debug_info=True)
        for scope in ("embed", "attn_in", "kv_write", "attention",
                      "attn_out", "mlp", "head", "sample"):
            assert re.search(rf'loc\("(?:[^"]*/)?{scope}/[^"]*"\(', text), \
                (arch, name, scope)


def test_paco_page_size_properties():
    """Page size is a PACO leaf-tile seq extent: divides max_seq, shrinks
    with more slots (the cuboid's non-seq faces absorb cuts), and stays
    sane on prime slot counts."""
    for slots in (1, 2, 3, 4, 7, 13):
        for max_seq in (16, 128, 512):
            page = paco_page_size(slots, max_seq, 64)
            assert 1 <= page <= max_seq and max_seq % page == 0, \
                (slots, max_seq, page)


def test_paco_page_size_non_pow2_divisors():
    """Regression: the old doubling loop required max_seq % (page*2) == 0
    at every step, so ANY odd max_seq degenerated to page=1 (a block
    table entry per token) and even-but-not-pow2 max_seq undershot its
    largest usable divisor.  The fix takes the largest divisor of
    max_seq <= the planner's leaf seq extent."""
    # odd/prime max_seq: must still divide, and must not collapse to 1
    # when a real divisor fits under the leaf extent
    for slots, max_seq in [(2, 63), (3, 45), (4, 33), (2, 81)]:
        page = paco_page_size(slots, max_seq, 64)
        assert max_seq % page == 0, (slots, max_seq, page)
        assert page > 1, (slots, max_seq, page)  # 63->{3,7,9,21}, 45->...
    # even, small 2-adic part: 36 = 4*9 — the old loop stalled at 4 even
    # when the leaf extent allowed the divisor 6
    page36 = paco_page_size(2, 36, 64)
    assert 36 % page36 == 0 and page36 >= 4, page36
    # prime max_seq has no divisor but itself: page=1 (or max_seq) is the
    # only legal answer — geometry stays valid, tables just get long
    for max_seq in (17, 31):
        page = paco_page_size(4, max_seq, 64)
        assert max_seq % page == 0, (max_seq, page)
    # an engine on an odd max_seq must come up with page > 1 and serve
    cfg = _cfg()
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=2, max_seq=63)
    assert eng.page > 1 and 63 % eng.page == 0, eng.page
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=3))
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)


# ---------------------------------------------------------------------------
# MLA latent paging (deepseek-v2): compressed pages, preemption, geometry
# ---------------------------------------------------------------------------

def _mla_cfg():
    return dataclasses.replace(get_arch("deepseek-v2-236b").reduced(),
                               tie_embeddings=False)


def test_mla_latent_preemption_resumes_identically():
    """MLA engine under pool pressure with PRIME slot/pool geometry: the
    youngest request is evicted, re-prefilled (latents recomputed from
    prompt + generated), and still emits the exact uncompressed-oracle
    continuation."""
    cfg = _mla_cfg()
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=3, max_seq=32, page_size=4,
                      pool_pages=11, prefill_chunk_len=8)  # prime pool
    for i, p in enumerate([[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]]):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=16))
    done = eng.run_until_drained()
    assert len(done) == 3
    assert eng.stats["preemptions"] >= 1
    eng.check_page_invariants()
    assert eng.pool.free_count() == eng.pool.n_pages
    _assert_parity(eng, params, cfg, done)


def test_mla_latent_pages_beat_dense_kv_bytes():
    """The latent cache family's reason to exist: bytes/token of the
    compressed c_kv/k_rope leaves must not exceed what dense per-head
    KV pages would cost for the same config — at FULL deepseek-v2 scale
    the ratio is (kv_lora + qk_rope) / (2*H*dh) = 576/32768 ~ 1.8%."""
    from repro.models import paged_cache_leaf_specs

    for cfg in (_mla_cfg(), get_arch("deepseek-v2-236b")):
        page = 4
        latent = paged_cache_leaf_specs(cfg, page)
        assert set(latent) == {"c_kv", "k_rope"}
        latent_bytes = sum(
            np.prod(s.shape) * s.dtype.itemsize for s in latent.values()
        ) / page
        # dense alternative: materialized per-head k (qk_nope + qk_rope)
        # and v (v_head) pages, the layout the GQA family stores
        m = cfg.mla
        dense_bytes = (cfg.n_layers * cfg.n_heads
                       * ((m.qk_nope + m.qk_rope) + m.v_head)
                       * cfg.dtype.itemsize)
        assert latent_bytes <= dense_bytes, (latent_bytes, dense_bytes)
    # full scale: the win is >50x
    cfg = get_arch("deepseek-v2-236b")
    m = cfg.mla
    assert (m.kv_lora + m.qk_rope) * 50 < cfg.n_heads * (
        m.qk_nope + m.qk_rope + m.v_head)


def test_mla_engine_chooses_latent_page_geometry():
    """paco_page_size plans the (slots x seq x kv_lora) cuboid for MLA:
    the engine's pool leaves are the head-free latent pages."""
    cfg = _mla_cfg()
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=2, max_seq=16)
    m = cfg.mla
    assert eng.pool.pools["c_kv"].shape[-1] == m.kv_lora
    assert eng.pool.pools["k_rope"].shape[-1] == m.qk_rope
    assert eng.pool.pools["c_kv"].ndim == 4   # (L, NP+1, page, kv_lora)
    assert eng.page == paco_page_size(2, 16, m.kv_lora)


# ---------------------------------------------------------------------------
# fused multi-tick decode (decode_ticks): bit-exactness, flags, donation
# ---------------------------------------------------------------------------

def _drain_with_invariants(eng):
    while eng.queue or any(r is not None for r in eng.active):
        eng.tick()
        eng.check_page_invariants()
    return eng.done


def test_decode_ticks_matches_single_ticks(params, cfg):
    """decode_ticks(n=4) must be BIT-EXACT against four decode_step_paged
    ticks with host-side argmax — same pools in, same tokens and same
    pool contents out (the fused scan is the same tick body under
    lax.scan with on-device sampling)."""
    from repro.models import decode_step_paged, decode_ticks

    eng = ServeEngine(params, cfg, slots=2, max_seq=32, page_size=4,
                      prefill_chunk_len=8)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=20))
    eng.submit(Request(uid=1, prompt=[5, 6, 7, 8, 9], max_new_tokens=20))
    eng._admit()
    eng._ensure_decode_pages(4)
    bt = eng.tables.device()
    toks0 = jnp.asarray(eng._last_tok, jnp.int32)
    lens0 = jnp.asarray(eng._ctx_len, jnp.int32)

    # path A: four single fused ticks, argmax synced per tick (PR 3 loop)
    pools = eng.pool.pools
    cur, lens, got = toks0[:, None], lens0, []
    for _ in range(4):
        logits, pools = decode_step_paged(params, cfg, cur, pools, bt,
                                          lens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        got.append(np.asarray(nxt))
        cur, lens = nxt[:, None], lens + 1

    # path B: one fused 4-tick dispatch, sampling on device
    block, pools_b = decode_ticks(
        params, cfg, toks0, eng.pool.pools, bt, lens0,
        jnp.ones((2,), bool), jnp.full((2,), 100, jnp.int32),
        jnp.full((2,), -1, jnp.int32), jnp.zeros((4, 2), jnp.uint32),
        max_seq=eng.max_seq)
    np.testing.assert_array_equal(np.asarray(block), np.stack(got))
    for name in pools:
        np.testing.assert_array_equal(np.asarray(pools[name]),
                                      np.asarray(pools_b[name]))


def test_fused_eos_mid_block(params, cfg):
    """eos firing INSIDE a 4-tick block: the device flags must stop the
    slot at exactly the reference position (later block entries are
    ignored by the host), and a sibling slot keeps decoding through the
    same dispatches unperturbed."""
    ref = reference_decode(params, cfg, [4, 2, 9], max_new_tokens=12,
                           max_seq=64)
    eos = ref[2]   # third generated token: tick 2 of the first block
    eng = ServeEngine(params, cfg, slots=2, max_seq=64,
                      ticks_per_dispatch=4)
    eng.submit(Request(uid=0, prompt=[4, 2, 9], max_new_tokens=12,
                       eos_id=eos))
    eng.submit(Request(uid=1, prompt=[7, 7], max_new_tokens=12,
                       eos_id=eos))
    done = _drain_with_invariants(eng)
    _assert_parity(eng, params, cfg, done)
    r0 = next(r for r in done if r.uid == 0)
    assert r0.out == ref[:3] and r0.out[-1] == eos


def test_fused_preemption_at_block_boundary(params, cfg):
    """Pool pressure with multi-tick dispatches: page pre-mapping for a
    whole block (budget-capped ticks_per_dispatch positions) exhausts
    the pool, preempting the youngest request AT THE DISPATCH BOUNDARY
    (never mid-scan — the device block always runs with fully mapped
    tables); the evictee resumes bit-identically."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, page_size=4,
                      pool_pages=10, prefill_chunk_len=8,
                      ticks_per_dispatch=4)
    for i, p in enumerate([[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]]):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=20))
    done = _drain_with_invariants(eng)
    assert eng.stats["preemptions"] >= 1
    assert any(r.preemptions > 0 for r in done)
    assert eng.pool.free_count() == eng.pool.n_pages
    _assert_parity(eng, params, cfg, done)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-236b"])
def test_pool_donation_no_copy(arch):
    """The pool pytree is donated through BOTH jitted hot-loop steps
    (prefill + fused decode): after one tick the pre-tick pool buffers
    must be DELETED — page writes landed in-place, not copy-on-write —
    for both cache families, and the in-place outputs must still decode
    to reference parity."""
    probe = jnp.zeros((4,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(probe)
    if not probe.is_deleted():
        pytest.skip("backend does not implement buffer donation")
    cfg = dataclasses.replace(get_arch(arch).reduced(),
                              tie_embeddings=False)
    params = init_params(cfg, KEY)
    eng = ServeEngine(params, cfg, slots=2, max_seq=32,
                      prefill_chunk_len=8)
    before = dict(eng.pool.pools)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=6))
    eng.tick()
    for name, leaf in before.items():
        assert leaf.is_deleted(), \
            f"{arch} pool leaf {name!r} was copied, not donated"
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)


def test_decode_table_width_capped(params, cfg):
    """The jnp paged-gather fallback materializes (slots, width*page)
    cache bytes per tick; the engine must slice the block tables to the
    live-context bucket instead of always gathering all pages_per_seq
    pages.  Max-allocation pin: with a short prompt and budget the
    recorded width stays at the small bucket, far under the full
    table."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=64, page_size=4,
                      prefill_chunk_len=4, ticks_per_dispatch=4)
    assert eng.pages_per_seq == 16
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
    done = eng.run_until_drained()
    # ctx peaks at prompt+new = 7 positions -> 2 pages -> bucket 2:
    # the gather allocation is 2*page = 8 positions, not max_seq = 64.
    assert eng.stats["max_table_width"] == 2, eng.stats
    _assert_parity(eng, params, cfg, done)


def test_topk_sampling_respects_flags(params, cfg):
    """top-k sampling still terminates on budget/eos flags and only emits
    tokens from the unmasked vocab (greedy parity is covered everywhere
    else; this pins the sampled path's contract)."""
    eng = ServeEngine(params, cfg, slots=2, max_seq=32, top_k=4,
                      temperature=0.8, seed=7)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=5))
    eng.submit(Request(uid=1, prompt=[9, 8, 7, 6], max_new_tokens=3))
    done = eng.run_until_drained()
    assert sorted(len(r.out) for r in done) == [3, 5]
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)
    eng.check_page_invariants()
    assert eng.pool.free_count() == eng.pool.n_pages


# ---------------------------------------------------------------------------
# paged-attention kernel parity (jnp production path + Pallas interpret)
# ---------------------------------------------------------------------------


def test_paged_latent_decode_matches_dense_ref():
    """MLA latent decode lowering (jnp gather path + Pallas interpret) ==
    the dense concat-and-broadcast oracle, on a prime page pool with
    mixed (including zero-page) lengths."""
    from repro.kernels.attention import (paged_latent_attention_ref,
                                         paged_latent_decode_attention)

    b, h, kv, rope, page, n_pages, pps = 3, 4, 16, 8, 4, 13, 4
    scale = 1.0 / np.sqrt(kv + rope)
    ql = jax.random.normal(KEY, (b, 1, h, kv))
    qr = jax.random.normal(jax.random.PRNGKey(9), (b, 1, h, rope))
    ck = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, kv))
    kr = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, rope))
    bt = jnp.asarray(np.array([[0, 3, 5, 7], [1, 2, 4, 6],
                               [8, 9, 10, 11]], np.int32))
    lens = jnp.asarray([5, 16, 1], jnp.int32)
    ref = paged_latent_attention_ref(ql, qr, ck, kr, bt, lens, scale=scale)
    out = paged_latent_decode_attention(ql, qr, ck, kr, bt, lens,
                                        scale=scale)
    np.testing.assert_allclose(out, ref, atol=2e-6)
    pal = paged_latent_decode_attention(ql, qr, ck, kr, bt, lens,
                                        scale=scale, use_kernel=True,
                                        interpret=True)
    np.testing.assert_allclose(pal, ref, atol=2e-6)

@pytest.mark.parametrize("kw", [
    {}, {"window": 6}, {"logit_cap": 20.0},
    {"window": 3, "logit_cap": 5.0},
])
def test_paged_decode_matches_dense_ref(kw):
    b, hq, hkv, d, page, n_pages, pps = 3, 4, 2, 16, 4, 13, 4
    q = jax.random.normal(KEY, (b, 1, hq, d))
    kp = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, hkv, d))
    vp = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, hkv, d))
    bt = jnp.asarray(np.array([[0, 3, 5, 7], [1, 2, 4, 6],
                               [8, 9, 10, 11]], np.int32))
    lens = jnp.asarray([5, 16, 1], jnp.int32)
    ref = paged_attention_ref(q, kp, vp, bt, lens, **kw)
    out = paged_decode_attention(q, kp, vp, bt, lens, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-6)
    pal = paged_decode_attention(q, kp, vp, bt, lens, use_kernel=True,
                                 interpret=True, **kw)
    np.testing.assert_allclose(pal, ref, atol=2e-6)


# ---------------------------------------------------------------------------
# paged PREFILL kernel parity (jnp production path + Pallas interpret)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {}, {"window": 5}, {"logit_cap": 20.0},
    {"window": 3, "logit_cap": 5.0},
])
def test_paged_prefill_matches_dense_ref(kw):
    """Chunked prefill straight off the page pool (jnp gather path +
    Pallas interpret) == the dense gathered-cache oracle, for a chunk at
    a nonzero start offset (past context in earlier pages, stale data in
    later ones — masked by the global causal rule)."""
    from repro.kernels.attention import (paged_prefill_attention,
                                         paged_prefill_ref)

    hq, hkv, d, page, n_pages, c = 4, 2, 16, 4, 13, 8
    q = jax.random.normal(KEY, (1, c, hq, d))
    kp = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, hkv, d))
    vp = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, hkv, d))
    row = jnp.asarray([2, 5, 7, 11], jnp.int32)
    start = jnp.asarray(8, jnp.int32)   # second chunk of the slot
    ref = paged_prefill_ref(q, kp, vp, row, start, **kw)
    out = paged_prefill_attention(q, kp, vp, row, start, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-6)
    pal = paged_prefill_attention(q, kp, vp, row, start, use_kernel=True,
                                  interpret=True, **kw)
    np.testing.assert_allclose(pal, ref, atol=2e-6)


@pytest.mark.parametrize("page,pps,n_pages,c,start", [
    (3, 3, 11, 3, 3),    # prime page + prime pool
    (5, 2, 7, 5, 5),     # prime page, chunk = one page, last chunk
    (2, 4, 13, 6, 0),    # chunk spanning 3 pages from position 0
])
def test_paged_prefill_prime_geometry_fixed(page, pps, n_pages, c, start):
    """Non-hypothesis prime-geometry pins (these run even where the
    property-test shim skips): odd pages, prime pools, multi-page and
    single-page chunks, first and last chunk positions."""
    from repro.kernels.attention import (paged_prefill_attention,
                                         paged_prefill_ref)

    hq, hkv, d = 4, 2, 8
    rng = np.random.RandomState(page * 100 + pps)
    row = jnp.asarray(rng.choice(n_pages, size=pps, replace=False)
                      .astype(np.int32))
    q = jax.random.normal(KEY, (1, c, hq, d))
    kp = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, hkv, d))
    vp = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, hkv, d))
    st = jnp.asarray(start, jnp.int32)
    ref = paged_prefill_ref(q, kp, vp, row, st)
    out = paged_prefill_attention(q, kp, vp, row, st)
    np.testing.assert_allclose(out, ref, atol=2e-6)
    pal = paged_prefill_attention(q, kp, vp, row, st, use_kernel=True,
                                  interpret=True)
    np.testing.assert_allclose(pal, ref, atol=2e-6)


def test_paged_latent_prefill_matches_dense_ref():
    """MLA latent prefill (decomposed-score jnp path + Pallas interpret)
    == the dense concat-and-broadcast oracle, on a prime page pool."""
    from repro.kernels.attention import (paged_latent_prefill_attention,
                                         paged_latent_prefill_ref)

    h, kv, rope, page, n_pages, c = 4, 16, 8, 4, 13, 8
    scale = 1.0 / np.sqrt(kv + rope)
    ql = jax.random.normal(KEY, (1, c, h, kv))
    qr = jax.random.normal(jax.random.PRNGKey(9), (1, c, h, rope))
    ck = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, kv))
    kr = jax.random.normal(jax.random.PRNGKey(2), (n_pages, page, rope))
    row = jnp.asarray([1, 3, 6, 12], jnp.int32)
    for start in (0, 8):
        st = jnp.asarray(start, jnp.int32)
        ref = paged_latent_prefill_ref(ql, qr, ck, kr, row, st,
                                       scale=scale)
        out = paged_latent_prefill_attention(ql, qr, ck, kr, row, st,
                                             scale=scale)
        np.testing.assert_allclose(out, ref, atol=2e-6)
        pal = paged_latent_prefill_attention(ql, qr, ck, kr, row, st,
                                             scale=scale, use_kernel=True,
                                             interpret=True)
        np.testing.assert_allclose(pal, ref, atol=2e-6)


@settings(max_examples=6, deadline=None)
@given(
    page=st.sampled_from([2, 3, 5]),      # prime pages included
    pps=st.integers(2, 4),
    extra_pages=st.integers(0, 6),        # pool sizes land on primes
    c_pages=st.integers(1, 3),            # chunk = c_pages * page
    chunk_idx=st.integers(0, 2),          # which chunk of the slot
    seed=st.integers(0, 99),
)
def test_property_paged_prefill_prime_geometries(page, pps, extra_pages,
                                                 c_pages, chunk_idx, seed):
    """Paged-prefill parity across random prime page/pool/chunk
    geometries: jnp gather path AND the Pallas kernel (interpret) vs the
    dense oracle, with the chunk starting at an arbitrary chunk
    boundary (ISSUE 4 satellite)."""
    from repro.kernels.attention import (paged_prefill_attention,
                                         paged_prefill_ref)

    hq, hkv, d = 4, 2, 8
    c = min(c_pages * page, pps * page)
    start_v = min(chunk_idx * c, pps * page - c)
    n_pages = pps + extra_pages + 1
    rng = np.random.RandomState(seed)
    row = jnp.asarray(rng.choice(n_pages, size=pps, replace=False)
                      .astype(np.int32))
    q = jax.random.normal(jax.random.PRNGKey(seed), (1, c, hq, d))
    kp = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (n_pages, page, hkv, d))
    vp = jax.random.normal(jax.random.PRNGKey(seed + 2),
                           (n_pages, page, hkv, d))
    start = jnp.asarray(start_v, jnp.int32)
    ref = paged_prefill_ref(q, kp, vp, row, start)
    out = paged_prefill_attention(q, kp, vp, row, start)
    np.testing.assert_allclose(out, ref, atol=2e-6)
    pal = paged_prefill_attention(q, kp, vp, row, start, use_kernel=True,
                                  interpret=True)
    np.testing.assert_allclose(pal, ref, atol=2e-6)


# ---------------------------------------------------------------------------
# regression: the old dense-engine cache-commit shape heuristic
# ---------------------------------------------------------------------------

def test_old_commit_heuristic_failure_pinned(params, cfg):
    """The pre-paging engine committed per-slot cache rows by SHAPE
    heuristic: any leaf with shape[1] == slots was assumed slot-major.
    Pinned here: with slots == n_layers, a layer-major (L, S, ...) leaf
    matches the heuristic and gets silently cross-written.  The paged
    engine must keep exact parity in exactly that geometry (slot count ==
    layer count == a plausible leaf dim), and no shape heuristic may
    decide what is per-slot state again."""
    slots = cfg.n_layers   # the coincidence the heuristic can't survive

    def old_commit(new, old, slot):
        # verbatim shape test from the old ServeEngine._decode_one_slot
        if new.ndim >= 2 and new.shape[1] == slots:
            return old.at[:, slot].set(new[:, slot])
        return old

    # a layer-major leaf (L=anything, S=slots): WRONGLY matched -> the
    # heuristic overwrites sequence column `slot` across all layers.
    layer_major = jnp.zeros((3, slots, 5))
    touched = old_commit(jnp.ones((3, slots, 5)), layer_major, slot=1)
    assert bool(jnp.any(touched != 0)), \
        "heuristic no longer misfires? keep the pin honest"
    # a per-slot leaf whose batch dim is NOT dim 1: silently never
    # committed (the dual failure mode).
    slot_major = jnp.zeros((slots, 7))
    missed = old_commit(jnp.ones((slots, 7)), slot_major, slot=1)
    assert bool(jnp.all(missed == 0))

    eng = ServeEngine(params, cfg, slots=slots, max_seq=8 * slots,
                      page_size=4)
    for i in range(slots + 1):
        eng.submit(Request(uid=i, prompt=[1 + i, 3], max_new_tokens=5))
    done = eng.run_until_drained()
    _assert_parity(eng, params, cfg, done)


# ---------------------------------------------------------------------------
# (c) hypothesis property tests (skip cleanly without hypothesis)
# ---------------------------------------------------------------------------

_PCFG = _cfg()
_PPARAMS = init_params(_PCFG, KEY)


@settings(max_examples=8, deadline=None)
@given(
    prompts=st.lists(
        st.lists(st.integers(1, 250), min_size=1, max_size=11),
        min_size=1, max_size=6),
    slots=st.integers(1, 5),
    page=st.sampled_from([2, 4, 8]),
    extra_pages=st.integers(0, 7),
)
def test_property_parity_random_batches(prompts, slots, page, extra_pages):
    """Random prompt batches over random slot/page geometry (pool sizes
    land on primes too): token parity + paging invariants always hold."""
    max_seq = 16
    pps = max_seq // page
    pool = pps + extra_pages   # >= one full sequence; often prime
    eng = ServeEngine(_PPARAMS, _PCFG, slots=slots, max_seq=max_seq,
                      page_size=page, pool_pages=pool,
                      prefill_chunk_len=page)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p[:max_seq - 1],
                           max_new_tokens=4))
    done = eng.run_until_drained()
    assert len(done) == len(prompts)
    eng.check_page_invariants()
    assert eng.pool.free_count() == eng.pool.n_pages
    for r in sorted(done, key=lambda r: r.uid):
        ref = reference_decode(_PPARAMS, _PCFG, r.prompt,
                               max_new_tokens=4, max_seq=max_seq)
        assert r.out == ref, (r.uid, r.prompt, r.out, ref)


@settings(max_examples=6, deadline=None)
@given(
    n_pages=st.sampled_from([7, 11, 13]),
    lens=st.lists(st.integers(0, 12), min_size=2, max_size=3),
)
def test_property_paged_attention_prime_pools(n_pages, lens):
    """Paged gather == dense oracle on prime-sized pools and random
    (including zero) lengths."""
    b = len(lens)
    page, pps, hkv, hq, d = 4, 3, 2, 4, 8
    rng = np.random.RandomState(sum(lens) + n_pages)
    bt = jnp.asarray(np.stack([
        rng.choice(n_pages, size=pps, replace=False)   # distinct per row
        for _ in range(b)]).astype(np.int32))
    q = jax.random.normal(KEY, (b, 1, hq, d))
    kp = jax.random.normal(jax.random.PRNGKey(3), (n_pages, page, hkv, d))
    vp = jax.random.normal(jax.random.PRNGKey(4), (n_pages, page, hkv, d))
    lv = jnp.asarray(lens, jnp.int32)
    ref = paged_attention_ref(q, kp, vp, bt, lv)
    out = paged_decode_attention(q, kp, vp, bt, lv)
    valid = np.asarray(lens) > 0   # zero-length rows are garbage-by-design
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid], atol=2e-6)
