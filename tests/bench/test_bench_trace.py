"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``bench/record_trace.py``: three engine ticks of one qwen3-0.6b layer)
and on made-up events whose answer is known."""
from pathlib import Path

import jax
import pytest

from bench import trace_reduce as R

TRACE = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    return list(R._events(jax.profiler.ProfileData.from_file(str(TRACE))))


@pytest.fixture(scope="module")
def reduced():
    return R.reduce_trace(str(TRACE))


def _window(raw):
    spans = [(s, e) for _, _, n, s, e in raw if n == R.WINDOW]
    return min(s for s, _ in spans), max(e for _, e in spans)


def test_busy_is_the_union_of_op_intervals(raw, reduced):
    lo, hi = _window(raw)
    ops = sorted((max(s, lo), min(e, hi)) for p, line, _, s, e in raw
                 if p == "/device:TPU:0" and line == R.OPS_LINE
                 and min(e, hi) > max(s, lo))
    # a second way: sweep over sorted start and end points
    points = sorted([(s, 1) for s, _ in ops] + [(e, -1) for _, e in ops],
                    key=lambda p: (p[0], -p[1]))
    depth, busy, since = 0, 0.0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_program_time_is_the_sum_of_its_executions(raw, reduced):
    lo, hi = _window(raw)
    for name in ("jit__decode_fn", "jit__prefill_fn"):
        durs = [e - s for p, line, n, s, e in raw
                if p == "/device:TPU:0" and line == R.MODULES_LINE
                and R.program_name(n) == name and lo <= s < hi]
        assert durs, name
        got = reduced["programs"][name]
        assert got["calls"] == len(durs)
        assert got["seconds"] == pytest.approx(sum(durs) * 1e-9, rel=1e-12)


def test_top_ops_count_innermost_ops_once(reduced):
    ops = reduced["top_ops"]
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(s for _, s in ops) <= reduced["busy_s"]
    assert all(name.startswith("%") and "{" not in name for name, _ in ops)
    assert not any(name.endswith(" while") for name, _ in ops)


def test_idle_gaps_are_named_by_host_spans(reduced):
    gaps = reduced["idle_gaps"]
    assert gaps and all(name in ("bench.tick", "bench.wait", "none")
                        for name, _ in gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(reduced["idle_by_activity"].values()) == pytest.approx(idle)
    # the host slept 10 ms three times between ticks
    assert reduced["idle_by_activity"]["bench.wait"] > 0.025


def test_reduction_of_known_events():
    ms = 1_000_000.0
    ev = [("/host:CPU", "python3", R.WINDOW, 0.0, 100 * ms),
          ("/host:CPU", "python3", "bench.tick", 0.0, 60 * ms),
          ("/host:CPU", "python3", "bench.wait", 60 * ms, 100 * ms)]
    for dev, busy in (("/device:TPU:0", [(10, 30), (20, 50)]),
                      ("/device:TPU:1", [(10, 20)])):
        ev += [(dev, R.OPS_LINE, f"%f.{i} = f32[2]{{0}} fusion(x)",
                s * ms, e * ms) for i, (s, e) in enumerate(busy)]
        ev += [(dev, R.MODULES_LINE, "jit_step(7)", 10 * ms, 50 * ms)]
        ev += [(dev, R.OPS_LINE, "%w = (s32[]) while(x)", 5 * ms, 60 * ms)]
    ev += [("/device:TPU:0", R.OPS_LINE, "%late = f32[2] add(x)",
            90 * ms, 120 * ms)]
    r = R.reduce_events(ev)
    assert r["devices"] == 2 and r["window_s"] == pytest.approx(0.1)
    # device 0: while 5-60 + 90-100 = 65 ms; device 1: 5-60 = 55 ms
    assert r["busy_s"] == pytest.approx((0.065 + 0.055) / 2)
    assert r["programs"]["jit_step"] == {"seconds": pytest.approx(0.04),
                                         "calls": 1.0}
    idle = r["idle_by_activity"]
    assert idle["bench.tick"] == pytest.approx(0.005)
    assert idle["bench.wait"] == pytest.approx((0.030 + 0.040) / 2)
    assert dict(r["top_ops"])["%f.1 = f32[2] fusion"] == pytest.approx(
        0.030 / 2)


def test_union_and_leaves():
    assert R.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    nested = [("loop", 0, 10), ("a", 1, 3), ("b", 3, 5), ("c", 11, 12)]
    assert [n for n, _, _ in R.leaves(nested)] == ["a", "b", "c"]
