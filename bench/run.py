#!/usr/bin/env python3
"""Run one cell of the serving benchmark on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``) at the load the cell sets
(``bench/cells/<cell>.json``).  Each metric is read by
``bench/metrics/<metric>.py``.  A run builds the weights on the device from
the seed, builds the program's ``ServeEngine``, warms every step program
the cell's traffic reaches, then drives ``ServeEngine.submit`` and
``ServeEngine.tick`` for ``--seconds`` on the host clock.  It then checks
what the window served against the float32 reference
(``bench/reference.py``) and prints, as the last line of standard output,
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and (traced) ``breakdown``, and the numbers compared, each
beside its limit, under ``checks``.

With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it records a profiler trace of the window's last ten
seconds and reports the per-layer metrics.  With no TPU, or fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import spec  # noqa: E402
from bench import traffic as T  # noqa: E402
from bench import weights as W  # noqa: E402

TRACE_SECONDS = 10.0      # traced slice: the end of the window
DRAIN_LIMIT_S = 60.0      # open loop: how long past the close answers may come
MIN_CHECKED = 256         # served tokens the check must compare at least
CHECK_TOKENS = 384        # served tokens the check samples, at least
CHECK_SEQS = 8            # and requests, at most


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


class NoChip(SystemExit):
    pass


def require_chips(n: int):
    """The devices of the run.  Exits when JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: JAX finds no TPU (platform "
                     f"{devices[0].platform!r}); the benchmark never runs "
                     f"on another backend")
    if len(devices) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:n]


def arch_config(conf: dict, m: W.Model):
    """The program's ArchConfig: the registry entry's family and mechanisms,
    with every size taken from the configuration file."""
    from repro.configs import get_arch

    base = get_arch(conf["registry"])
    act = {"silu": "swiglu", "relu2": "sq_relu"}[m.act]
    same = dict(act=base.act == act, qk_norm=base.qk_norm == m.qk_norm,
                tie=base.tie_embeddings == m.tied, attn=base.attn == "gqa",
                plain=(base.moe is None and base.local_window is None
                       and base.softcap_attn is None
                       and base.softcap_logits is None
                       and base.family == "decoder"))
    if not all(same.values()):
        raise ValueError(f"{conf['name']}: the registry entry "
                         f"{base.name!r} differs from the file in "
                         f"{[k for k, v in same.items() if not v]}")
    return dataclasses.replace(
        base, n_layers=m.layers, d_model=m.d, n_heads=m.heads,
        n_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.ff,
        vocab=m.vocab, rope_theta=m.rope_theta,
        param_dtype=conf["torch_dtype"])


def check_tree(params, cfg) -> None:
    """The weights the benchmark made have exactly the program's layout."""
    import jax

    from repro.models import init_params

    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError(f"weights differ from the program's layout: "
                         f"{got} != {want}")


class CompileCounter:
    """Counts traces and backend compilations (JAX's monitoring events)."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.counts = {"traces": 0, "compiles": 0}

        def listen(event, duration, **_):
            if event in self.EVENTS:
                self.counts[self.EVENTS[event]] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def snapshot(self) -> dict:
        return dict(self.counts)


@dataclasses.dataclass
class Rec:
    """One request's life on the host clock (seconds of perf_counter)."""
    item: T.Item
    req: object = None
    due: float | None = None
    submitted: float | None = None
    admitted: float | None = None      # start of the tick that admitted it
    first: float | None = None         # end of the tick that emitted token 1
    last: float | None = None          # end of the tick that emitted the last
    done: float | None = None
    seen: int = 0                      # tokens seen so far


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    steps: int                         # decode ticks the dispatch ran
    decoded: list                      # (context at its first tick, ticks)
    prefilled: list                    # prompt lengths prefilled
    live_tokens: int                   # cache positions held after the tick
    mapped_pages: int


def warm_up(engine, items) -> dict:
    """Compile every program the traffic reaches: each prefill width bucket
    of the prompts, each decode width bucket x fused tick count, and the
    small programs the scheduler runs between dispatches.  Writes go to the
    pool's null page, so the pool is as it was."""
    import jax
    import jax.numpy as jnp

    from repro.serve.engine import _width_bucket

    e = engine
    t0 = time.perf_counter()
    null = e.pool.null_page
    widths = set()
    for it in items:
        for i in range(0, len(it.prompt), e.chunk):
            widths.add(_width_bucket(-(-(i + e.chunk) // e.page),
                                     e.pages_per_seq))
    key = jax.random.PRNGKey(0)
    for w in sorted(widths):
        tok, e.pool.pools = e._prefill(
            e.params, jnp.zeros((1, e.chunk), jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32), key,
            e.pool.pools, jnp.full((w,), null, jnp.int32))
    longest = max(len(it.prompt) + it.max_new for it in items)
    top = _width_bucket(-(-min(longest, e.max_seq) // e.page),
                        e.pages_per_seq)
    dwidths = [w for w in (2 ** k for k in range(16)) if w <= top]
    if top not in dwidths:
        dwidths.append(top)
    ticks = [n for n in (2 ** k for k in range(8)) if n <= e.ticks]
    b = e.slots
    for w in dwidths:
        for n in ticks:
            blk, e.pool.pools = e._decode(
                e.params, jnp.zeros((b,), jnp.int32), e.pool.pools,
                jnp.full((b, w), null, jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32),
                jnp.full((b,), -1, jnp.int32), jax.random.split(key, n))
    jax.block_until_ready((blk, tok, e.pool.pools))
    # the scheduler's own small programs: the batched first-token sync of
    # k admitted slots, the per-dispatch key splits, and the conversions
    # of its host lists to device arrays
    one = jax.jit(lambda: jnp.zeros((), jnp.int32))()
    for k in range(1, b + 1):
        np.asarray(jnp.stack([one] * k))
    for n in ticks:
        jax.random.split(e._next_key(), n)
    jnp.asarray([[0] * e.chunk], jnp.int32)
    jnp.asarray([0] * b, jnp.int32)
    jnp.asarray([True] * b)
    jnp.asarray(0, jnp.int32)
    return {"prefill_widths": sorted(widths), "decode_widths": dwidths,
            "tick_counts": ticks, "seconds": time.perf_counter() - t0}


class LoadLoop:
    """Drives the engine through the window and keeps the host records."""

    def __init__(self, engine, items, loop: str):
        from repro.serve import Request

        self.e = engine
        self.loop = loop
        self.recs = [Rec(item=it) for it in items]
        for r in self.recs:
            r.req = Request(uid=r.item.idx, prompt=list(r.item.prompt),
                            max_new_tokens=r.item.max_new)
        self.inflight: list[Rec] = []
        self.ticks: list[Tick] = []
        if loop == "open":   # due times are set when the window opens
            self.pending = sorted(self.recs, key=lambda r: r.item.due)
        else:
            self.queues: dict[int, list[Rec]] = {}
            for r in self.recs:
                self.queues.setdefault(r.item.client, []).append(r)
            self.pending = []

    def submit(self, r: Rec, now: float) -> None:
        if r.due is None:
            r.due = now
        r.submitted = now
        self.e.submit(r.req)
        self.inflight.append(r)

    def next_of(self, client: int, now: float) -> None:
        q = self.queues[client]
        if q:
            self.submit(q.pop(0), now)

    def submit_due(self, now: float) -> None:
        while self.pending and self.pending[0].due <= now:
            self.submit(self.pending.pop(0), now)

    def busy(self) -> bool:
        return bool(self.e.queue) or any(a is not None for a in self.e.active)

    def tick(self) -> None:
        import jax

        e = self.e
        steps0 = e.stats["decode_steps"]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.tick"):
            e.tick()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.record"):
            self._record(t0, t1, e.stats["decode_steps"] - steps0)

    def _record(self, t0: float, t1: float, steps: int) -> None:
        decoded, prefilled, finished = [], [], []
        current, self.inflight = self.inflight, []
        for r in current:
            n = len(r.req.out)
            if n > r.seen:
                fresh = r.admitted is None
                if fresh:
                    r.admitted = t0
                    r.first = t1
                    prefilled.append(len(r.req.prompt))
                ticks = n - r.seen - (1 if fresh else 0)
                if ticks > 0:
                    start = len(r.req.prompt) + r.seen + (1 if fresh else 0)
                    decoded.append((start, ticks))
                r.seen = n
                r.last = t1
            if n >= r.req.max_new_tokens:
                r.done = t1
                finished.append(r)
            else:
                self.inflight.append(r)
        if self.loop == "closed":
            for r in finished:
                self.next_of(r.item.client, t1)
        live = sum(len(a.prompt) + len(a.out) - 1
                   for a in self.e.active if a is not None)
        mapped = self.e.pool.n_pages - self.e.pool.free_count()
        self.ticks.append(Tick(t0, t1, steps, decoded, prefilled, live,
                               mapped))

    def wait_until(self, t: float) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.wait"):
            while time.perf_counter() < t:
                time.sleep(min(0.002, max(0.0, t - time.perf_counter())))

    def tokens(self) -> int:
        return sum(len(r.req.out) for r in self.recs)


def sample_for_check(recs: list[Rec], seed: int) -> list[Rec]:
    """The requests the check compares: the one with the longest context,
    then others drawn from the seed, until CHECK_TOKENS served tokens or
    CHECK_SEQS requests."""
    served = [r for r in recs if r.req is not None and len(r.req.out) > 0]
    if not served:
        return []
    served.sort(key=lambda r: (-(len(r.req.prompt) + len(r.req.out)),
                               r.item.idx))
    pick = [served[0]]
    rest = served[1:]
    order = np.random.default_rng(int(seed) + 1).permutation(len(rest))
    for i in order:
        if (sum(len(r.req.out) for r in pick) >= CHECK_TOKENS
                or len(pick) >= CHECK_SEQS):
            break
        pick.append(rest[i])
    return pick


def profile_options():
    """Device events and host spans; no Python function tracing."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


@dataclasses.dataclass
class CellSetup:
    """A cell set up for a seed: the weights and the engine."""
    bench: dict
    cell: dict
    conf: dict
    mix: dict
    load: dict          # bench/cells/<cell>.json: the cell's own settings
    model: W.Model
    cfg: object
    devices: list
    peak: dict
    engine: object
    seed: int
    counter: CompileCounter


@dataclasses.dataclass
class Window:
    """What one measured window left behind."""
    drv: LoadLoop
    t_open: float
    t_closed: float
    t_end: float
    tokens_window: int
    compiles: dict
    lateness: list
    n_window_ticks: int
    traced: list | None
    trace_dir: str | None


def open_session(root: Path, workload: str, seed: int, *,
                 chips=require_chips) -> CellSetup:
    bench = spec.load_benchmark(root)
    cell = spec.find_workload(bench, workload)
    conf = spec.load_config(root, bench, cell["config"])
    mix = spec.load_traffic(root, cell["traffic"])
    load = spec.load_cell(root, workload)
    devices = chips(int(cell["chips"]))

    import jax

    from bench import peaks as P
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve import ServeEngine

    dev0 = devices[0]
    peak = P.peaks(dev0.device_kind)
    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    say(f"device {dev0.platform} {dev0.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    m = W.model_from_config(conf)
    cfg = arch_config(conf, m)
    serving = conf["serving"]
    t0 = time.perf_counter()
    params = jax.block_until_ready(W.make_params(m, seed))
    check_tree(params, cfg)
    say(f"weights {sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30:.3f}"
        f" GiB from seed {seed} in {time.perf_counter() - t0:.2f}s")
    engine = ServeEngine(params, cfg, slots=serving["slots"],
                         max_seq=serving["max_seq"],
                         ticks_per_dispatch=serving["ticks_per_dispatch"])
    say(f"engine slots {engine.slots} x {engine.max_seq}, page {engine.page},"
        f" pool {engine.pool.n_pages} pages, chunk {engine.chunk}, "
        f"{engine.ticks} ticks/dispatch")
    return CellSetup(bench, cell, conf, mix, load, m, cfg, devices, peak,
                     engine, seed, CompileCounter())


def make_items(sess: CellSetup, seconds: float, rate: float | None = None):
    """The cell's requests, at the cell's own rate unless one is given."""
    if rate is None and "rate_per_s" in sess.load:
        rate = float(sess.load["rate_per_s"])
    return T.generate(sess.mix, seed=sess.seed, seconds=seconds,
                      vocab=sess.model.vocab, slots=sess.engine.slots,
                      rate=rate)


def window(sess: CellSetup, items, seconds: float, trace: bool) -> Window:
    """Fill the slots (closed loop), then drive the engine for ``seconds``;
    an open loop then serves on until every request due in the window has
    finished, or DRAIN_LIMIT_S has passed."""
    import jax

    engine = sess.engine
    drv = LoadLoop(engine, items, sess.mix["loop"])
    if drv.loop == "closed":
        # set-up fills every slot: each client's first request is admitted
        # and prefilled before the window opens
        now = time.perf_counter()
        for c in sorted(drv.queues):
            drv.next_of(c, now)
        while engine.queue:
            drv.tick()
        drv.ticks = []
    t_open = time.perf_counter()
    if drv.loop == "open":
        for r in drv.recs:
            r.due = t_open + r.item.due
    t_close = t_open + seconds
    tokens_open = drv.tokens()
    compiles_open = sess.counter.snapshot()
    # the traced slice is the window's last TRACE_SECONDS, so that stopping
    # the profiler (which writes the trace) falls after the close
    tr_lo = t_close - min(TRACE_SECONDS, seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced = None      # [host start, host end] of the traced slice
    window_span = None
    lateness = []

    def submit_due(now):
        with jax.profiler.TraceAnnotation("bench.submit"):
            before = len(drv.inflight)
            drv.submit_due(now)
            lateness.extend(now - r.due for r in drv.inflight[before:])

    while True:
        now = time.perf_counter()
        if trace and traced is None and now >= tr_lo:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options())
            window_span = jax.profiler.TraceAnnotation("bench.window")
            window_span.__enter__()
            traced = [time.perf_counter(), None]
        if now >= t_close:
            break
        if drv.loop == "open":
            submit_due(now)
        if drv.busy():
            drv.tick()
            continue
        marks = [t_close] + ([drv.pending[0].due] if drv.pending else [])
        if trace and traced is None:
            marks.append(tr_lo)
        drv.wait_until(min(marks))
    if drv.loop == "open":    # every arrival due inside the window is sent
        submit_due(t_close)
    t_closed = time.perf_counter()
    if traced is not None:
        window_span.__exit__(None, None, None)
        traced[1] = time.perf_counter()
        jax.profiler.stop_trace()
    tokens_window = drv.tokens() - tokens_open
    compiles = {k: v - compiles_open[k]
                for k, v in sess.counter.snapshot().items()}
    n_window_ticks = len(drv.ticks)
    if drv.loop == "open":
        with jax.profiler.TraceAnnotation("bench.drain"):
            limit = time.perf_counter() + DRAIN_LIMIT_S
            while drv.busy() and time.perf_counter() < limit:
                drv.tick()
    return Window(drv, t_open, t_closed, time.perf_counter(), tokens_window,
                  compiles, lateness, n_window_ticks, traced, trace_dir)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, chips=require_chips, control: bool = False) -> dict:
    """One run of one cell; returns the result object.  ``control`` also
    reads the fp8 control's widest gap on the same sample and judges it as
    the run is judged, as ``control_correct`` (calibration only; the
    benchmark's runs do not)."""
    sess = open_session(root, workload, seed, chips=chips)
    names = spec.cell_metrics(sess.bench, workload, trace)
    readers = {n: spec.load_metric(root, n) for n in names}
    engine = sess.engine
    items = make_items(sess, seconds)
    warm = warm_up(engine, items)
    say(f"{len(items)} requests generated; warm-up {warm}")
    dev0 = sess.devices[0]
    if trace:
        for name, lowered in engine.lower_steps().items():
            ma = lowered.compile().memory_analysis()
            say(f"memory_analysis {name} ({dev0.device_kind}): argument "
                f"{ma.argument_size_in_bytes} temp {ma.temp_size_in_bytes} "
                f"output {ma.output_size_in_bytes} alias "
                f"{ma.alias_size_in_bytes} bytes")
    win = window(sess, items, seconds, trace)
    setup_s = win.t_open - T_START
    window_s = win.t_closed - win.t_open
    engine.check_page_invariants()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in sess.devices)
    lat = win.lateness
    say(f"window {window_s:.3f}s: {win.n_window_ticks} ticks, "
        f"{win.tokens_window} tokens; traces {win.compiles['traces']} and "
        f"backend compiles {win.compiles['compiles']} inside the window "
        f"(want 0); generator late by p50 "
        f"{np.median(lat) if lat else 0:.4f}s max {max(lat) if lat else 0:.4f}"
        f"s over {len(lat)} arrivals; peak_bytes_in_use {peak_bytes} "
        f"({peak_bytes / 2**30:.3f} GiB on {dev0.device_kind})")
    say(f"engine.stats {engine.stats}")
    ctx = spec.Context(
        model=sess.model, cfg=sess.cfg, conf=sess.conf, mix=sess.mix,
        cell=sess.cell, seconds=seconds, recs=win.drv.recs,
        ticks=win.drv.ticks, t_open=win.t_open, t_close=win.t_closed,
        t_end=win.t_end, window_s=window_s, tokens_window=win.tokens_window,
        setup_s=setup_s, page=engine.page, chunk=engine.chunk,
        n_window_ticks=win.n_window_ticks, traced=win.traced, trace=None,
        peak=sess.peak)
    seqs = [(list(r.req.prompt), list(r.req.out))
            for r in sample_for_check(win.drv.recs, seed)]
    max_seq = engine.max_seq
    # free the program's state before the reference runs on the chip
    sess.engine = engine = win.drv.e = None
    gc.collect()

    from bench import reference

    t0 = time.perf_counter()
    limit = sess.conf["check"]["gap_limit"]
    got = (reference.compare(sess.model, seed, seqs, max_seq,
                             control=control) if seqs else
           {"gap": float("inf"), "tokens": 0, "argmax_equal": 0, "echo": 0})
    say(f"reference check of {len(seqs)} requests in "
        f"{time.perf_counter() - t0:.2f}s: {got}")
    if trace:
        from bench import trace_reduce

        t0 = time.perf_counter()
        ctx.trace = trace_reduce.reduce_trace(win.trace_dir)
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        say(f"trace reduced in {time.perf_counter() - t0:.2f}s: "
            f"{json.dumps(ctx.trace)}")
    metrics = {}
    units = spec.units(sess.bench)
    for name, reader in readers.items():
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    submitted = [r for r in ctx.recs if r.submitted is not None]
    # an open loop's requests are all due: one that never finished failed.
    # A closed loop's requests still running at the close are cut, not
    # failed.
    failed = (sum(1 for r in submitted if r.done is None)
              if sess.mix["loop"] == "open" else 0)
    checks = {
        "widest_gap": {"value": got["gap"], "limit": limit},
        "tokens_checked": {"value": got["tokens"], "limit": MIN_CHECKED},
    }

    def passes(gap):
        return gap <= limit and got["tokens"] >= MIN_CHECKED

    correct = passes(got["gap"])
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(sess.devices), "memory_peak_bytes": int(peak_bytes)}
    out = {"correct": bool(correct), "attempted": len(submitted),
           "failed": failed, "metrics": metrics, "device": device}
    if control:
        checks["control_gap"] = {"value": got["control_gap"],
                                  "limit": limit}
        out["control_correct"] = bool(passes(got["control_gap"]))
    if trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        out["breakdown"] = {"device_ops": ctx.trace["top_ops"],
                            "idle_gaps": ctx.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
