"""The reduction of the engine's own spans and named scopes
(``bench/engine_trace.py``) and the readers of the metrics built on them,
on made-up events whose answer is known, on the small trace of
``test_bench_trace.py`` (recorded before the engine had spans or scopes)
and on a small trace recorded on a TPU v5e chip with them
(``bench/record_trace.py``)."""
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import engine_trace as E
from bench import spec
from bench import trace_reduce as R
from bench import xplane

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
OLD_TRACE = DATA / "small_trace.xplane.pb"
SERVE_TRACE = DATA / "serve_trace.xplane.pb"
MS = 1_000_000.0
HOST, DEV = "/host:CPU", "/device:TPU:0"
DECODE = "jit(_decode_fn)/while/body/closed_call/while/body/closed_call"


def span(name, a, b):
    return (HOST, "python3", name, a * MS, b * MS, None, None)


def op(a, b, program="jit__decode_fn", tf_op=None, name="%f = f32[2] add"):
    return (DEV, R.OPS_LINE, name, a * MS, b * MS, program, tf_op)


@pytest.fixture
def restore_reduce(monkeypatch):
    """``install`` swaps ``trace_reduce.reduce_trace``; put it back."""
    monkeypatch.setattr(R, "reduce_trace", R.reduce_trace)


def test_scope_of_takes_the_innermost_leaf_scope():
    assert E.scope_of(f"{DECODE}/attention/dot_general:") == "attention"
    assert E.scope_of(f"{DECODE}/mlp/sample/add:") == "sample"
    assert E.scope_of("jit(_prefill_fn)/embed/mul:") == "embed"
    assert E.scope_of(f"{DECODE}/dynamic_slice:") == E.UNSCOPED
    assert E.scope_of(None) == E.UNSCOPED


def test_engine_spans_and_tick_self_time():
    ev = [span(R.WINDOW, 0, 100), span("bench.tick", 0, 60),
          span("serve.tick", 1, 59), span("serve.admit", 2, 10),
          span("serve.prefill_chunk", 3, 5),
          span("serve.first_token_sync", 6, 9),
          span("serve.map_pages", 11, 12),
          span("serve.dispatch_args", 12, 15),
          span("serve.decode_launch", 15, 16),
          span("serve.decode_sync", 16, 50), span("serve.replay", 50, 55),
          span("serve.tick", 95, 105),      # ends after the window
          op(0, 100)]
    got = E.reduce_events(ev)["engine_spans"]
    assert got["serve.tick"]["calls"] == 1
    assert got["serve.tick"]["seconds"] == pytest.approx(0.058)
    # 58 ms less its children's 8 + 1 + 3 + 1 + 34 + 5 ms
    assert got["serve.tick"]["self_s"] == pytest.approx(0.006)
    assert got["serve.decode_sync"] == {"calls": 1,
                                        "seconds": pytest.approx(0.034)}
    assert "bench.tick" not in got
    assert set(got) == {"serve.tick", "serve.admit", "serve.prefill_chunk",
                        "serve.first_token_sync", "serve.map_pages",
                        "serve.dispatch_args", "serve.decode_launch",
                        "serve.decode_sync", "serve.replay"}


def test_scope_attribution_sums_to_the_program():
    ev = [span(R.WINDOW, 0, 100),
          op(5, 45, tf_op="jit(_decode_fn)/while:", name="%w = while"),
          op(10, 20, tf_op=f"{DECODE}/attention/dot_general:"),
          op(20, 25, tf_op=f"{DECODE}/mlp/sample/add:"),
          op(25, 40),                                   # no tf_op
          op(95, 105, tf_op=f"{DECODE}/attention/gather:"),
          op(50, 52, program="jit__prefill_fn",
             tf_op="jit(_prefill_fn)/embed/mul:"),
          op(60, 61, program=None, tf_op=f"{DECODE}/mlp/add:")]
    r = E.reduce_events(ev)
    dec = r["scopes"]["jit__decode_fn"]
    assert dec == {"attention": pytest.approx(0.015),
                   "sample": pytest.approx(0.005),
                   E.UNSCOPED: pytest.approx(0.015)}
    assert r["scopes"]["jit__prefill_fn"] == {"embed": pytest.approx(0.002)}
    shares = [E.scope_share(r, "jit__decode_fn", s)
              for s in (*E.SCOPES, E.UNSCOPED)]
    assert sum(shares) == pytest.approx(100.0)
    assert E.scope_share(r, "jit__decode_fn", "attention") == \
        pytest.approx(100 * 15 / 35)
    assert E.scope_share(r, "jit__verify_fn", "attention") is None
    unscoped = {"scopes": {"jit__decode_fn": {E.UNSCOPED: 1.0}}}
    assert E.scope_share(unscoped, "jit__decode_fn", "attention") is None


def test_idle_gaps_take_the_innermost_span():
    ev = [span(R.WINDOW, 0, 110), span("bench.tick", 0, 60),
          span("serve.tick", 0, 60), span("serve.dispatch_args", 10, 20),
          span("serve.decode_launch", 20, 30), span("serve.replay", 40, 50),
          span("bench.wait", 60, 90),
          op(0, 12), op(14, 18), op(22, 38), op(44, 46), op(48, 62),
          op(80, 85), op(100, 105)]
    r = E.reduce_events(ev)
    # gaps 12-14: inside serve.dispatch_args; 18-22: half in it, half in
    # serve.decode_launch, so their parent serve.tick; 38-44 and 46-48:
    # serve.replay covers more than half; 62-80: bench.wait; 85-100:
    # bench.wait covers a third, the most of any span; 105-110: none
    assert r["idle_by_activity"] == {
        "serve.dispatch_args": pytest.approx(0.002),
        "serve.tick": pytest.approx(0.004),
        "serve.replay": pytest.approx(0.006 + 0.002),
        "bench.wait": pytest.approx(0.018 + 0.015),
        "none": pytest.approx(0.005)}
    assert r["idle_gaps"][:2] == [("bench.wait", pytest.approx(0.018)),
                                  ("bench.wait", pytest.approx(0.015))]


def _base(path):
    """``trace_reduce``'s own reduction, whatever ``install`` did."""
    return R.reduce_events(R._events(
        jax.profiler.ProfileData.from_file(str(path))))


def test_old_trace_keeps_every_value_of_the_reduction(restore_reduce):
    """On a trace with no engine spans or scopes, the added keys are empty
    and every key ``trace_reduce`` returns keeps its value."""
    assert [t[:5] for t in E._events(xplane.read(str(OLD_TRACE)))] == \
        list(R._events(jax.profiler.ProfileData.from_file(str(OLD_TRACE))))
    base = _base(OLD_TRACE)
    E.install()
    got = R.reduce_trace(str(OLD_TRACE))
    assert {k: got[k] for k in base} == base
    assert got["engine_spans"] == {}
    assert E.scope_share(got, "jit__decode_fn", "attention") is None
    assert sum(got["scopes"]["jit__decode_fn"].values()) == pytest.approx(
        base["programs"]["jit__decode_fn"]["seconds"], rel=0.01)


@pytest.fixture(scope="module")
def serve_trace():
    return _base(SERVE_TRACE), E.reduce_trace(str(SERVE_TRACE))


def test_chip_trace_has_every_engine_span(serve_trace):
    _, r = serve_trace
    spans = r["engine_spans"]
    assert {"serve.tick", "serve.admit", "serve.prefill_chunk",
            "serve.first_token_sync", "serve.map_pages",
            "serve.dispatch_args", "serve.decode_launch",
            "serve.decode_sync", "serve.replay"} <= set(spans)
    tick = spans["serve.tick"]
    assert tick["calls"] == 3
    assert 0 < tick["self_s"] < tick["seconds"]
    inner = sum(v["seconds"] for k, v in spans.items()
                if k not in ("serve.tick", "serve.prefill_chunk",
                             "serve.first_token_sync"))
    assert tick["seconds"] == pytest.approx(inner + tick["self_s"])


def test_chip_trace_scopes_cover_the_step_programs(serve_trace):
    base, r = serve_trace
    for program in ("jit__decode_fn", "jit__prefill_fn"):
        times = r["scopes"][program]
        # the decode step's greedy argmax is fused into the head's ops
        assert set(E.SCOPES) - {"sample"} <= set(times), (program, times)
        assert sum(times.values()) == pytest.approx(
            base["programs"][program]["seconds"], rel=0.05)
        assert sum(E.scope_share(r, program, s)
                   for s in times) == pytest.approx(100.0)


def test_chip_trace_names_idle_gaps_inside_ticks(serve_trace):
    base, r = serve_trace
    assert {k: v for k, v in base.items()
            if k not in ("idle_gaps", "idle_by_activity")} == \
        {k: r[k] for k in base if k not in ("idle_gaps", "idle_by_activity")}
    assert sum(r["idle_by_activity"].values()) == pytest.approx(
        sum(base["idle_by_activity"].values()))
    assert "bench.tick" not in r["idle_by_activity"]
    assert any(name.startswith("serve.") for name, _ in r["idle_gaps"])
    assert r["idle_by_activity"]["bench.wait"] == pytest.approx(
        base["idle_by_activity"]["bench.wait"])


def _ctx(reqs, firsts, trace=None, t_end=100.0):
    recs = [SimpleNamespace(req=q, due=1.0, first=f)
            for q, f in zip(reqs, firsts)]
    return SimpleNamespace(window_recs=lambda: recs, t_end=t_end,
                           trace=trace)


def _read(name, ctx):
    return spec.load_metric(ROOT, name).read(ctx)


def test_timestamp_readers(restore_reduce):
    reqs = [SimpleNamespace(t_submit=1.0, t_admit=1.5, t_first=1.75),
            SimpleNamespace(t_submit=2.0, t_admit=None, t_first=None)]
    ctx = _ctx(reqs, [2.0, None], t_end=10.0)
    # waits 0.5 and 8.0 (never admitted: to t_end); p95 by interpolation
    assert _read("admit_wait_p95_s", ctx) == pytest.approx(
        0.5 + 0.95 * 7.5)
    assert _read("prefill_wait_p95_s", ctx) == pytest.approx(0.25)
    assert _read("first_token_hold_p95_ms", ctx) == pytest.approx(250.0)
    bare = _ctx([SimpleNamespace()], [2.0])       # an engine without them
    for name in ("admit_wait_p95_s", "prefill_wait_p95_s",
                 "first_token_hold_p95_ms"):
        assert _read(name, bare) is None


def test_span_and_scope_readers(restore_reduce):
    trace = {"engine_spans": {
        "serve.tick": {"calls": 4, "seconds": 0.2, "self_s": 0.01},
        "serve.decode_sync": {"calls": 4, "seconds": 0.15},
        "serve.first_token_sync": {"calls": 1, "seconds": 0.01},
        "serve.prefill_chunk": {"calls": 3, "seconds": 0.02},
        "serve.dispatch_args": {"calls": 4, "seconds": 0.012}},
        "scopes": {"jit__decode_fn": {"attention": 1.0, "mlp": 2.0,
                                      E.UNSCOPED: 7.0}}}
    ctx = _ctx([], [], trace=trace)
    # 200 ms of ticks less 150 + 10 ms of syncs and 20 ms of prefill
    # launches, over 4 ticks; dispatch_args is host work and stays
    assert _read("engine_host_ms.chat", ctx) == pytest.approx(5.0)
    assert _read("engine_host_ms.long", ctx) == pytest.approx(5.0)
    assert _read("attention_share.long", ctx) == pytest.approx(10.0)
    assert _read("unscoped_share.long", ctx) == pytest.approx(70.0)
    old = _ctx([], [], trace={"engine_spans": {}, "scopes": {
        "jit__decode_fn": {E.UNSCOPED: 1.0}}})
    for name in ("engine_host_ms.long", "attention_share.long",
                 "unscoped_share.long"):
        assert _read(name, old) is None
    assert R.reduce_trace is E.reduce_trace
