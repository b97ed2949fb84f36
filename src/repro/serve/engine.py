"""Continuous-batching serving engine over a PACO-paged KV cache.

Production shape (DESIGN.md §8): requests queue up; a scheduler admits
them into fixed decode slots, prefills their prompts in page-aligned
chunks (one jitted ``prefill_chunk`` call per chunk — NOT one per token),
and advances every active slot with FUSED MULTI-TICK decode dispatches:
one jitted ``decode_ticks`` call runs ``ticks_per_dispatch`` decode
steps on-device — sampling, cache append, block-table advance, and
retirement flags included — so the host syncs one small (ticks, slots)
token block per dispatch instead of one argmax per token.  Cache state
lives in a shared pool of fixed-size pages (leaf tiles of the
slots x seq x feat cuboid, ``paging.paco_page_size``) mapped through
per-slot block tables; the pool pytree is DONATED through both jitted
steps, so page writes land in-place rather than copy-on-write.
Retirement frees pages back to the pool, and pool exhaustion preempts
the youngest request (its pages freed, the request re-queued to resume
with identical output).  Two cache families ride the same scheduler
(DESIGN.md §8.5): dense GQA k/v pages and compressed MLA latent pages
(c_kv/k_rope, feat = kv_lora).

With ``mesh=...`` the engine serves model-parallel: params are placed by
``dist.sharding.param_specs``, page pools by
``dist.sharding.pool_shardings`` (the same shardings double as the
jitted steps' pool ``out_shardings`` so donation stays layout-stable),
and both steps are traced under ``dist.act_sharding.use_mesh_rules``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import decode_ticks, paged_cache_leaf_specs, \
    prefill_chunk, sample_tokens, verify_ticks
from repro.serve import paging

Params = Any
# tail length the speculative n-gram drafter matches on
DRAFT_NGRAM = 2


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: int = -1  # -1 = never
    out: list[int] = dataclasses.field(default_factory=list)
    # instrumentation (tests + launch report)
    prefill_calls: int = 0
    preemptions: int = 0
    # host clock (time.perf_counter): submitted, first admitted to a slot
    # (kept across preemption), first token on the host
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None


def _width_bucket(width: int, pages_per_seq: int) -> int:
    """Round a live block-table width up to a power of two (clamped to
    the full table) so decode compilations stay O(log pages_per_seq)
    rather than one per distinct live length."""
    b = 1
    while b < width:
        b *= 2
    return min(b, pages_per_seq)


class ServeEngine:
    """Paged continuous-batching engine (decoder-family archs).

    ``ticks_per_dispatch`` sets how many decode steps one jitted
    dispatch fuses (DESIGN.md §8.7): larger values amortize dispatch +
    host-sync overhead over more tokens (throughput) at the cost of up
    to that many speculative page mappings per slot and token-block
    latency (a token is visible to the host only at the end of its
    dispatch).  ``top_k``/``temperature`` switch the device-side
    sampler from greedy argmax to top-k categorical
    (``models.sample_tokens``).

    ``speculate`` turns on SPECULATIVE decoding (DESIGN.md §8.8): each
    decode dispatch runs ``ticks_per_dispatch`` draft->verify->accept
    steps, every step advancing each live slot by 1..draft_len+1 tokens
    — drafts come from the device-side n-gram drafter
    (``models.draft_ngram_propose``, ``DRAFT_NGRAM`` tail length), the
    verify forward scores the whole window in one pass, and rejected
    drafts are rolled back: greedy tokens equal the non-speculative
    fused engine's, accepted pool positions hold its K/V up to the
    rounding of a reordered reduction, and rolled-back positions are
    bit-exact copies.  ``speculate=N`` drafts N tokens per window;
    ``speculate=0`` plans the window as a PACO leaf tile of the cache
    cuboid (``paging.paco_draft_len``).
    Greedy-only: combining it with top-k sampling raises (exact
    rejection sampling is the follow-up).

    ``spec_min_accept`` is the ADAPTIVE FALLBACK threshold: when the
    rolling draft-acceptance rate (last 32 verify windows) drops below
    it, the scheduler dispatches the plain fused decode instead —
    speculation must never cost throughput on a workload it cannot
    draft (a verify window spends ~W tokens of model compute to emit
    one token at zero acceptance).  Every 16th skipped dispatch runs a
    speculative PROBE to re-detect workload shifts.  Both dispatch kinds
    emit the same greedy tokens and leave the same pool up to that
    rounding, so switching has no paging or scheduling consequence.
    The break-even acceptance is backend-dependent (a weight-bandwidth
    -bound accelerator verifies W tokens for nearly the cost of one;
    a compute-bound CPU does not), so tune per deployment; 0 disables
    the fallback.
    """

    def __init__(self, params: Params, cfg: ArchConfig, *, slots: int = 4,
                 max_seq: int = 128, page_size: int | None = None,
                 pool_pages: int | None = None,
                 prefill_chunk_len: int | None = None, mesh=None,
                 ticks_per_dispatch: int = 8,
                 top_k: int | None = None, temperature: float = 1.0,
                 speculate: int | None = None,
                 spec_min_accept: float = 0.25, seed: int = 0):
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        # the cache cuboid's per-position feature extent, the first
        # cache leaf's last dim: head_dim for dense GQA KV, the
        # compressed kv_lora face for MLA latents.
        feat = next(iter(paged_cache_leaf_specs(cfg, 1).values())).shape[-1]
        self.page = page_size or paging.paco_page_size(
            slots, max_seq, feat)
        if max_seq % self.page != 0:
            raise ValueError(
                f"page_size={self.page} does not divide max_seq="
                f"{max_seq}: every sequence must span whole pages so "
                f"block tables stay rectangular — pass a page_size that "
                f"divides max_seq, or omit it for the PACO leaf size")
        self.pages_per_seq = max_seq // self.page
        # chunk: a few pages per jitted prefill call, dividing max_seq so
        # padded chunks never overrun the block table.
        if prefill_chunk_len is None:
            prefill_chunk_len = self.page
            while (prefill_chunk_len * 2 <= min(64, max_seq)
                   and max_seq % (prefill_chunk_len * 2) == 0):
                prefill_chunk_len *= 2
        if prefill_chunk_len % self.page != 0:
            raise ValueError(
                f"prefill_chunk_len={prefill_chunk_len} is not a "
                f"multiple of page_size={self.page}: each prefill chunk "
                f"scatters whole pages (no read-modify-write)")
        if max_seq % prefill_chunk_len != 0:
            raise ValueError(
                f"prefill_chunk_len={prefill_chunk_len} does not divide "
                f"max_seq={max_seq}: a padded final chunk would overrun "
                f"the block table")
        self.chunk = prefill_chunk_len
        assert ticks_per_dispatch >= 1, ticks_per_dispatch
        self.ticks = ticks_per_dispatch
        self.draft_len = None
        if speculate is not None:
            if top_k is not None or temperature != 1.0:
                raise NotImplementedError(
                    f"speculative decoding is greedy-only (got top_k="
                    f"{top_k}, temperature={temperature}): sampled "
                    "decoding would need exact REJECTION SAMPLING over "
                    "the draft window to preserve the output "
                    "distribution — a follow-up; drop --speculate or "
                    "use the default greedy sampler")
            if speculate < 0:
                raise ValueError(f"speculate must be >= 0 "
                                 f"(0 = PACO-planned), got {speculate}")
            self.draft_len = (speculate if speculate > 0 else
                              paging.paco_draft_len(slots, max_seq, feat))
        self.spec_min_accept = spec_min_accept
        # adaptive-fallback state: accepted-draft counts of the last 32
        # verify windows, and how many dispatches the fallback has
        # skipped since the last speculative probe.
        self._spec_recent = deque(maxlen=32)
        self._spec_skipped = 0
        n_pages = (pool_pages if pool_pages is not None
                   else slots * self.pages_per_seq)
        if n_pages < self.pages_per_seq:
            raise ValueError(
                f"pool_pages={n_pages} < pages_per_seq="
                f"{self.pages_per_seq}: the pool must hold at least one "
                f"full max_seq sequence or a lone request can never map")
        self.pool = paging.init_pool(
            paged_cache_leaf_specs(cfg, self.page), n_pages, self.page)
        self.tables = paging.BlockTables(slots, self.pages_per_seq,
                                         self.pool.null_page)

        self.mesh = mesh
        pool_out = None
        tok_out = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from repro.dist import sharding as D
            params = jax.device_put(
                params, D.to_named(mesh, D.param_specs(cfg, params, mesh)))
            pool_out = D.pool_shardings(cfg, mesh, self.pool.pools)
            self.pool.pools = jax.device_put(self.pool.pools, pool_out)
            tok_out = NamedSharding(mesh, PartitionSpec())
        self.params = params

        self.active: list[Request | None] = [None] * slots
        self.queue: deque[Request] = deque()
        self.done: list[Request] = []
        # host-authoritative per-slot state: number of cache positions
        # written, last emitted token (its KV lands on the next tick),
        # admission order (preemption victims are the youngest).
        self._ctx_len = [0] * slots
        self._last_tok = [0] * slots
        self._admit_order = [-1] * slots
        self._admit_seq = 0
        # per-slot token history (prompt + generated; row i valid up to
        # _ctx_len[i] inclusive, _hist[i, _ctx_len[i]] == _last_tok[i]):
        # the device-side n-gram drafter's haystack.  Maintained by
        # prefill and every dispatch replay; cleared on release.
        # ``_hist_dev`` caches the device copy between speculative
        # dispatches (the verify scan's appends mirror the host replay
        # exactly, so it stays valid until slot churn or a fused
        # fallback dispatch touches the host copy alone — then it is
        # dropped and re-uploaded once).
        self._hist = np.zeros((slots, max_seq), np.int32)
        self._hist_dev: jax.Array | None = None
        self._key = jax.random.PRNGKey(seed)
        self.stats = {"prefill_calls": 0, "decode_steps": 0,
                      "preemptions": 0, "retired": 0, "dispatches": 0,
                      "host_syncs": 0, "max_table_width": 0,
                      "prefill_tokens": 0, "decode_tokens": 0,
                      "spec_windows": 0, "drafted_tokens": 0,
                      "accepted_tokens": 0, "spec_fallback_dispatches": 0}

        def _prefill_fn(p, toks, start, last, key, pg, row):
            logits, pg = prefill_chunk(p, cfg, toks, start, pg, row)
            with jax.named_scope("sample"):
                tok = sample_tokens(logits[last][None], key=key,
                                    top_k=top_k, temperature=temperature)
            return tok[0], pg

        null_page = self.pool.null_page

        def _decode_fn(p, toks, pg, bt, lens, act, bud, eos, keys):
            return decode_ticks(p, cfg, toks, pg, bt, lens, act, bud,
                                eos, keys, max_seq=max_seq, top_k=top_k,
                                temperature=temperature,
                                null_page=null_page)

        # the pool pytree is DONATED through both hot-loop steps: page
        # writes are in-place pool updates, never copy-on-write of the
        # whole pool (tests pin this via .is_deleted() on the inputs).
        out_sh = {} if mesh is None else \
            {"out_shardings": (tok_out, pool_out)}
        self._prefill = jax.jit(_prefill_fn, donate_argnums=(5,), **out_sh)
        self._decode = jax.jit(_decode_fn, donate_argnums=(2,), **out_sh)
        if self.draft_len is not None:
            draft_len = self.draft_len

            def _verify_fn(p, toks, pg, bt, lens, act, bud, eos, hist,
                           limit, steps):
                return verify_ticks(p, cfg, toks, pg, bt, lens, act, bud,
                                    eos, hist, limit, steps,
                                    max_seq=max_seq, draft_len=draft_len,
                                    ngram=DRAFT_NGRAM, null_page=null_page)

            # same donation discipline as _decode; on a mesh the pool
            # out_shardings come from the same helper as placement
            # (dist.sharding.verify_shardings) so donation stays
            # layout-stable.
            v_sh = {}
            if mesh is not None:
                from repro.dist import sharding as D
                v_sh = {"out_shardings":
                        D.verify_shardings(cfg, mesh, self.pool.pools)}
            self._verify = jax.jit(_verify_fn, donate_argnums=(2,),
                                   **v_sh)

    # -- plumbing -----------------------------------------------------------

    def _mesh_cm(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.dist import act_sharding
        return act_sharding.use_mesh_rules(self.mesh)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def submit(self, req: Request) -> None:
        if not (1 <= len(req.prompt) < self.max_seq):
            raise ValueError(
                f"prompt length {len(req.prompt)} must be in "
                f"[1, max_seq={self.max_seq})")
        if req.max_new_tokens < 1:
            # prefill always emits one token; a zero budget would diverge
            # from reference_decode (which generates nothing)
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{req.max_new_tokens}")
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _emit(self, req: Request, tok: int) -> bool:
        """Record a generated token; True when the request retires (eos,
        token budget, or context hitting max_seq — truncation).  The
        device-side flag logic in ``decode_ticks`` mirrors this rule
        exactly, so the host and the fused scan agree on when a slot
        stops emitting."""
        req.out.append(tok)
        return (len(req.out) >= req.max_new_tokens or tok == req.eos_id
                or len(req.prompt) + len(req.out) >= self.max_seq)

    def _release_slot(self, slot: int) -> None:
        self.pool.release(self.tables.clear(slot))
        self.active[slot] = None
        self._ctx_len[slot] = 0
        self._last_tok[slot] = 0
        self._admit_order[slot] = -1
        self._hist[slot] = 0
        self._hist_dev = None

    def _retire(self, slot: int) -> None:
        req = self.active[slot]
        self._release_slot(slot)
        self.done.append(req)
        self.stats["retired"] += 1

    def _preempt(self, slot: int) -> None:
        """Evict a slot: pages freed, request re-queued FIRST so it resumes
        (prompt + generated so far re-prefilled) with identical output."""
        req = self.active[slot]
        self._release_slot(slot)
        req.preemptions += 1
        self.stats["preemptions"] += 1
        self.queue.appendleft(req)

    def _youngest_active(self) -> int:
        return max((s for s in range(self.slots)
                    if self.active[s] is not None),
                   key=lambda s: self._admit_order[s])

    # -- scheduler ----------------------------------------------------------

    def _admit(self) -> None:
        """Fill free slots from the queue head (FIFO).  Admission needs
        pages for every padded prefill chunk up front; if the pool can't
        supply them the queue waits (decode-time exhaustion, not
        admission, triggers preemption).  Each admitted slot's prefill
        returns its first sampled token as a DEVICE array; one batched
        sync at the end folds them all into host slot state — no
        per-request ``int(...)`` round-trip."""
        pending: list[tuple[int, jax.Array]] = []
        for slot in range(self.slots):
            if self.active[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            ctx = req.prompt + req.out
            n_chunks = -(-len(ctx) // self.chunk)
            got = self.pool.alloc(n_chunks * (self.chunk // self.page))
            if got is None:
                break
            self.queue.popleft()
            self.tables.assign(slot, 0, got)
            self.active[slot] = req
            self._admit_order[slot] = self._admit_seq
            self._admit_seq += 1
            if req.t_admit is None:
                req.t_admit = time.perf_counter()
            pending.append((slot, self._prefill_slot(slot, req, ctx)))
        if pending:
            with jax.profiler.TraceAnnotation("serve.first_token_sync"):
                toks = np.asarray(jnp.stack([t for _, t in pending]))
            now = time.perf_counter()
            self.stats["host_syncs"] += 1
            for (slot, _), tok in zip(pending, toks):
                req = self.active[slot]
                if req.t_first is None:
                    req.t_first = now
                tok = int(tok)
                self._last_tok[slot] = tok
                self._hist[slot, self._ctx_len[slot]] = tok
                self._hist_dev = None
                if self._emit(req, tok):
                    self._retire(slot)

    def _prefill_slot(self, slot: int, req: Request,
                      ctx: list[int]) -> jax.Array:
        """Chunked prefill: ceil(len(ctx)/chunk) jitted calls, each
        ingesting a whole page-aligned chunk (the per-token teacher-forced
        loop this replaces cost len(ctx) device round-trips).  Each call
        gets the block row SLICED to the chunk's live page extent
        (power-of-two bucket, like decode's table slicing) so the jnp
        gather path materializes O(width*page) context, not O(max_seq).
        Returns the first sampled token as a DEVICE scalar — the caller
        folds it into slot state at the batched sync point."""
        last = jnp.asarray((len(ctx) - 1) % self.chunk, jnp.int32)
        key = self._next_key()
        tok = None
        with self._mesh_cm():
            for i in range(0, len(ctx), self.chunk):
                width = _width_bucket(-(-(i + self.chunk) // self.page),
                                      self.pages_per_seq)
                self.stats["max_table_width"] = max(
                    self.stats["max_table_width"], width)
                toks = ctx[i:i + self.chunk]
                toks = toks + [0] * (self.chunk - len(toks))
                # the chunk's uploads and launch: the runtime may hold the
                # uploads until the device has run the chunks queued
                # before them
                with jax.profiler.TraceAnnotation("serve.prefill_chunk"):
                    row = jnp.asarray(self.tables.row(slot)[:width])
                    tok, self.pool.pools = self._prefill(
                        self.params, jnp.asarray([toks], jnp.int32),
                        jnp.asarray(i, jnp.int32), last, key,
                        self.pool.pools, row)
                req.prefill_calls += 1
                self.stats["prefill_calls"] += 1
        self.stats["prefill_tokens"] += len(ctx)
        self._ctx_len[slot] = len(ctx)
        self._hist[slot, :len(ctx)] = ctx
        self._hist_dev = None
        return tok

    def _ensure_decode_pages(self, n: int = 1) -> None:
        """Every active slot needs mapped pages for its next ``n`` write
        positions (capped by its remaining token budget and max_seq);
        exhaustion preempts the youngest active request until the
        allocation succeeds (oldest-first service order, so the oldest
        request always progresses and a lone survivor can always map —
        the pool holds at least one full sequence)."""
        order = sorted((s for s in range(self.slots)
                        if self.active[s] is not None),
                       key=lambda s: self._admit_order[s])
        for slot in order:
            if self.active[slot] is None:   # preempted below
                continue
            for idx in range(*self._write_page_range(slot, n)):
                if self.active[slot] is None:
                    break
                if self.tables.row(slot)[idx] != self.tables.null_page:
                    continue
                while True:
                    got = self.pool.alloc(1)
                    if got is not None:
                        self.tables.assign(slot, idx, got)
                        break
                    victim = self._youngest_active()
                    self._preempt(victim)
                    if victim == slot:
                        break

    def _planned_writes(self, slot: int, n: int) -> int:
        """How many of the next ``n`` ticks this slot can actually write:
        capped by the remaining token budget and the last writable
        position (max_seq - 2 — the tick that writes it emits the
        retiring token)."""
        req = self.active[slot]
        ctx = self._ctx_len[slot]
        return max(1, min(n, req.max_new_tokens - len(req.out),
                          (self.max_seq - 1) - ctx))

    def _write_page_range(self, slot: int, n: int) -> tuple[int, int]:
        """Half-open block-table index range slot will write over the
        next ``n`` ticks: positions [ctx, ctx + _planned_writes)."""
        ctx = self._ctx_len[slot]
        w = self._planned_writes(slot, n)
        return ctx // self.page, (ctx + w - 1) // self.page + 1

    def _use_speculation(self) -> bool:
        """Acceptance-aware fallback: speculate unless the rolling
        acceptance rate of the last 32 verify windows fell below
        ``spec_min_accept`` — then dispatch plain fused decode, probing
        speculatively every 16th dispatch to catch workload shifts.
        Free to toggle per dispatch: both paths emit the same greedy
        tokens (DESIGN.md §8.8)."""
        if self.draft_len is None:
            return False
        recent = self._spec_recent
        if (not self.spec_min_accept
                or len(recent) < recent.maxlen):
            return True
        rate = sum(recent) / (len(recent) * self.draft_len)
        if rate >= self.spec_min_accept:
            self._spec_skipped = 0
            return True
        self._spec_skipped += 1
        if self._spec_skipped >= 16:   # periodic probe
            self._spec_skipped = 0
            return True
        return False

    def tick(self) -> int:
        """Admit + one decode dispatch (``ticks_per_dispatch`` fused
        steps — draft/verify steps when speculating); returns #retired.

        Host spans (``jax.profiler.TraceAnnotation``, on the profiler's
        clock; free while no trace is recording): ``serve.tick`` around
        the whole call, and inside it ``serve.admit``
        (``serve.prefill_chunk`` per chunk launch,
        ``serve.first_token_sync``), ``serve.map_pages``,
        ``serve.dispatch_args``, ``serve.decode_launch``,
        ``serve.decode_sync`` and ``serve.replay``."""
        with jax.profiler.TraceAnnotation("serve.tick"):
            return self._tick()

    def _tick(self) -> int:
        with jax.profiler.TraceAnnotation("serve.admit"):
            self._admit()
        if all(r is None for r in self.active):
            return 0
        n = self.ticks
        # speculative dispatches extend the per-slot page pre-mapping
        # from n ticks to n x (draft_len + 1) window positions: every
        # in-plan window write needs a real page even when the draft is
        # later rejected (rollback restores contents, not mappings).
        use_spec = self._use_speculation()
        w = self.draft_len + 1 if use_spec else 1
        span = n * w
        with jax.profiler.TraceAnnotation("serve.map_pages"):
            self._ensure_decode_pages(span)
        live = [s for s in range(self.slots) if self.active[s] is not None]
        if not live:
            return 0
        # clamp the block to the largest per-slot write plan (power-of-
        # two bucket, mirroring the table-width buckets, so scan-length
        # compiles stay O(log ticks)): a drain tail of short-budget
        # stragglers doesn't run whole-model ticks with every lane
        # frozen.
        planned = max(self._planned_writes(s, span) for s in live)
        n_eff = min(n, _width_bucket(-(-planned // w), n))
        if use_spec:
            return self._dispatch_spec(live, n_eff)
        return self._dispatch_fused(live, n_eff)

    def _dispatch_arrays(self, live: list[int], span: int):
        """Per-slot device vectors shared by BOTH decode dispatch kinds
        (block tables sliced to the span's width bucket, last tokens,
        context lengths, active/budget/eos).  One construction site so
        the speculative and non-speculative dispatches can never drift
        apart — their equal greedy tokens are what make the
        acceptance-aware fallback free to switch between them."""
        width = _width_bucket(
            max(self._write_page_range(s, span)[1] for s in live),
            self.pages_per_seq)
        self.stats["max_table_width"] = max(
            self.stats["max_table_width"], width)
        bt = self.tables.device_view(width)
        toks = jnp.asarray(self._last_tok, jnp.int32)
        lens = jnp.asarray(self._ctx_len, jnp.int32)
        act = jnp.asarray([r is not None for r in self.active])
        bud = jnp.asarray([r.max_new_tokens - len(r.out) if r else 0
                           for r in self.active], jnp.int32)
        eos = jnp.asarray([r.eos_id if r else -1 for r in self.active],
                          jnp.int32)
        return bt, toks, lens, act, bud, eos

    def _dispatch_fused(self, live: list[int], n: int) -> int:
        """One fused decode dispatch: n on-device ticks, ONE host sync."""
        if self.draft_len is not None:   # acceptance-aware fallback hit
            self.stats["spec_fallback_dispatches"] += 1
            self._hist_dev = None   # this dispatch appends host-side only
        with jax.profiler.TraceAnnotation("serve.dispatch_args"):
            bt, toks, lens, act, bud, eos = self._dispatch_arrays(live, n)
            keys = jax.random.split(self._next_key(), n)
        with jax.profiler.TraceAnnotation("serve.decode_launch"), \
                self._mesh_cm():
            block, self.pool.pools = self._decode(
                self.params, toks, self.pool.pools, bt, lens, act, bud,
                eos, keys)
        with jax.profiler.TraceAnnotation("serve.decode_sync"):
            block = np.asarray(block)   # THE one device->host sync
        self.stats["decode_steps"] += n
        self.stats["dispatches"] += 1
        self.stats["host_syncs"] += 1
        finished = 0
        with jax.profiler.TraceAnnotation("serve.replay"):
            for slot in live:
                req = self.active[slot]
                for t in range(n):
                    tok = int(block[t, slot])
                    self._ctx_len[slot] += 1   # that tick wrote its KV
                    self._last_tok[slot] = tok
                    self._hist[slot, self._ctx_len[slot]] = tok
                    self.stats["decode_tokens"] += 1
                    if self._emit(req, tok):
                        # the device flag flipped this slot inactive at
                        # the same tick (decode_ticks mirrors _emit);
                        # later block[t', slot] entries are -1 filler.
                        self._retire(slot)
                        finished += 1
                        break
        return finished

    def _dispatch_spec(self, live: list[int], n: int) -> int:
        """One fused SPECULATIVE dispatch: n draft->verify->accept steps
        on-device, ONE host sync of an (n, slots, draft_len + 1) token
        block.  Each step advances a live slot by 1..draft_len+1 tokens
        (the greedy-accepted drafts plus the correction token), so the
        block replay below is ``_dispatch_fused``'s _emit replay with a
        variable per-step advance; -1 entries mark the un-emitted tail
        of each window (and every window of a retired slot)."""
        w = self.draft_len + 1
        span = n * w
        with jax.profiler.TraceAnnotation("serve.dispatch_args"):
            bt, toks, lens, act, bud, eos = self._dispatch_arrays(live,
                                                                  span)
            # one past the last position each slot's write plan mapped
            # real pages for (window writes beyond it are null-routed on
            # device)
            limit = jnp.asarray(
                [self._ctx_len[s] + self._planned_writes(s, span)
                 if self.active[s] is not None else 0
                 for s in range(self.slots)], jnp.int32)
            # device-resident history when the last dispatch's copy is
            # still valid (no slot churn, no fused fallback in between):
            # the hot loop then uploads no per-dispatch history at all.
            hist = (self._hist_dev if self._hist_dev is not None
                    else jnp.asarray(self._hist))
            steps = jnp.zeros((n,), jnp.int32)   # shape-only: sets N
        with jax.profiler.TraceAnnotation("serve.decode_launch"), \
                self._mesh_cm():
            block, accepted, self._hist_dev, self.pool.pools = \
                self._verify(self.params, toks, self.pool.pools, bt,
                             lens, act, bud, eos, hist, limit, steps)
        # the ONE device->host sync point per dispatch (the tiny
        # accepted-count block rides along with the token block)
        with jax.profiler.TraceAnnotation("serve.decode_sync"):
            block = np.asarray(block)
            accepted = np.asarray(accepted)
        self.stats["decode_steps"] += n
        self.stats["dispatches"] += 1
        self.stats["host_syncs"] += 1
        finished = 0
        with jax.profiler.TraceAnnotation("serve.replay"):
            for slot in live:
                req = self.active[slot]
                retired = False
                for t in range(n):
                    row = [int(x) for x in block[t, slot] if x >= 0]
                    if not row:
                        break   # slot went inactive in an earlier step
                    self.stats["spec_windows"] += 1
                    self.stats["drafted_tokens"] += self.draft_len
                    # device-reported: a flag-truncated window can end on
                    # an accepted draft, so len(row) - 1 would undercount
                    acc_w = int(accepted[t, slot])
                    self.stats["accepted_tokens"] += acc_w
                    self._spec_recent.append(acc_w)
                    for tok in row:
                        self._ctx_len[slot] += 1
                        self._last_tok[slot] = tok
                        self._hist[slot, self._ctx_len[slot]] = tok
                        self.stats["decode_tokens"] += 1
                        if self._emit(req, tok):
                            # device flags stopped this slot at the same
                            # token (verify_ticks mirrors _emit)
                            self._retire(slot)
                            finished += 1
                            retired = True
                            break
                    if retired:
                        break
        return finished

    def run_until_drained(self, max_ticks: int = 10_000) -> list[Request]:
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.active):
                break
            self.tick()
        return self.done

    # -- test/debug surface -------------------------------------------------

    def lower_steps(self, sharding=None) -> dict[str, jax.stages.Lowered]:
        """Lower each jitted step at its widest shapes (full block-table
        width, ``ticks_per_dispatch`` ticks) without running it; the
        ``.compile()`` of each is the program the hot loop runs, with its
        ``memory_analysis()``.  Params and pools are lowered as held
        (arrays or ShapeDtypeStructs); the per-slot arguments are
        ShapeDtypeStructs placed on ``sharding`` (None leaves them to
        jit, as the hot loop's host-made arrays are)."""
        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        b, w, n = self.slots, self.pages_per_seq, self.ticks
        per_slot = (arg((b, w)), arg((b,)), arg((b,), jnp.bool_), arg((b,)),
                    arg((b,)))   # tables, lens, active, budget, eos
        with self._mesh_cm():
            steps = {
                "prefill_chunk": self._prefill.lower(
                    self.params, arg((1, self.chunk)), arg(()), arg(()),
                    arg((2,), jnp.uint32), self.pool.pools, arg((w,))),
                "decode_ticks": self._decode.lower(
                    self.params, arg((b,)), self.pool.pools, *per_slot,
                    arg((n, 2), jnp.uint32))}
            if self.draft_len is not None:
                steps["verify_ticks"] = self._verify.lower(
                    self.params, arg((b,)), self.pool.pools, *per_slot,
                    arg((b, self.max_seq)), arg((b,)), arg((n,)))
        return steps

    def check_page_invariants(self) -> None:
        """Block-table/pool invariants (tests/test_serve.py): live rows
        disjoint, live pages off the free list, live + free == pool."""
        live = [s for s in range(self.slots) if self.active[s] is not None]
        self.tables.check_invariants(self.pool, live)
        n_live = sum(len(self.tables.live_pages(s)) for s in live)
        assert n_live + self.pool.free_count() == self.pool.n_pages, \
            (n_live, self.pool.free_count(), self.pool.n_pages)
