"""The harness end to end on the CPU at a toy size, with its look for a
chip skipped: it finds cells, mixes and metrics by name, its check passes
what the engine serves, and it fails a run whose tokens are altered where
they are produced, and the float8 control."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run, spec
from bench import weights as W
from bench.peaks import PEAKS

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def cpu_chips(n):
    return jax.devices()[:n]


@pytest.fixture
def no_compile_cache(monkeypatch):
    """The harness turns on JAX's persistent cache; not in a test."""
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off (test)")
    monkeypatch.setitem(PEAKS, "cpu", PEAKS["TPU v5 lite"])
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout holding the toy cells tiny.chat and tiny.long, the
    repository's metric readers, and one metric that only this root has."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chat = {"tiny.chat"}
    rename = {w: ("tiny.chat" if "chat" in w else "tiny.long")
              for w in (x["name"] for x in bench["workloads"])}
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = sorted({rename[w] for w in m["workloads"]})
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "toy"}]
    bench["workloads"] = [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
         "chips": 1, "why": "toy"},
        {"name": "tiny.long", "config": "tiny", "traffic": "tiny-long",
         "chips": 1, "why": "toy"}]
    bench["end_to_end"].append(
        {"name": "served_requests", "unit": "requests", "better": "higher",
         "bound": 0.25, "source": "host_clock",
         "workloads": sorted(chat)})
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "cells").mkdir()
    shutil.copy(DATA / "tiny.chat.json", tmp_path / "bench" / "cells")
    shutil.copytree(ROOT / "bench" / "metrics", tmp_path / "bench" /
                    "metrics")
    (tmp_path / "bench" / "metrics" / "served_requests.py").write_text(
        "def read(ctx):\n"
        "    return sum(1 for r in ctx.recs if r.done is not None)\n")
    shutil.copy(DATA / "tiny.json", tmp_path / "bench" / "configs")
    for mix in ("tiny-chat", "tiny-long"):
        shutil.copy(DATA / f"{mix}.json", tmp_path / "bench" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_run_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-0.6b.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_harness_finds_pieces_added_as_files(tiny_root, no_compile_cache):
    out = run.run(tiny_root, "tiny.chat", 2**31 + 9, 4.0, False,
                  chips=cpu_chips)
    assert set(out["metrics"]) == {"ttft_p95_s", "tpot_p95_ms", "setup_s",
                                   "served_requests"}
    assert out["metrics"]["served_requests"]["value"] == out["attempted"]
    assert out["failed"] == 0 and out["attempted"] == 12
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_altered_token_is_not_correct(tiny_root, no_compile_cache,
                                      monkeypatch):
    from repro.serve.engine import ServeEngine

    emit = ServeEngine._emit

    def altered(self, req, tok):
        if req.uid % 2 == 0 and len(req.out) == 5:
            tok = (tok + 1) % self.cfg.vocab
        return emit(self, req, tok)

    monkeypatch.setattr(ServeEngine, "_emit", altered)
    out = run.run(tiny_root, "tiny.long", 2**31 + 10, 3.0, False,
                  chips=cpu_chips)
    assert not out["correct"]
    gap = out["checks"]["widest_gap"]
    assert gap["value"] > gap["limit"]


def test_control_fails_where_the_program_passes(tiny_root,
                                                no_compile_cache):
    out = run.run(tiny_root, "tiny.long", 2**31 + 11, 3.0, False,
                  chips=cpu_chips, control=True)
    c = out["checks"]
    assert out["correct"]
    assert c["widest_gap"]["value"] <= c["widest_gap"]["limit"]
    assert not out["control_correct"]
    assert c["control_gap"]["value"] > 3 * c["widest_gap"]["limit"]


def test_reference_equals_the_program_forward_in_float32():
    from repro.models import forward

    conf = json.loads((DATA / "tiny.json").read_text())
    m = W.model_from_config(conf)
    cfg = run.arch_config({**conf, "torch_dtype": "float32"}, m)
    # the program runs in float32 on the bfloat16 values the reference
    # draws for itself
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          W.make_params(m, 5))
    toks = np.random.default_rng(0).integers(0, m.vocab, 40)
    with jax.default_matmul_precision("highest"):
        want = forward(params, cfg, {"tokens": jnp.asarray(toks)[None]},
                       remat=False)[0, :, :m.vocab]
    prompt, served = list(toks[:10]), list(toks[10:]) + [0]
    rows = reference.hidden_rows(m, 5, [(prompt, served)], 64)
    got = reference._fns(m, "f32")[3](W.top_weights(m, 5), rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[9:]),
                               rtol=2e-4, atol=2e-5)


def test_layer_draws_equal_the_whole_tree():
    m = W.model_from_config(json.loads((DATA / "tiny.json").read_text()))
    seed = 2**33 + 1
    whole = W.make_params(m, seed)
    for li in (0, 1):
        for name, x in W.layer_weights(m, seed, li).items():
            node = whole["blocks"]
            for k in name.split("/"):
                node = node[k]
            np.testing.assert_array_equal(np.asarray(node[li]),
                                          np.asarray(x))
    assert not np.array_equal(np.asarray(whole["embed"]),
                              np.asarray(W.make_params(m, seed + 1)["embed"]))


def test_benchmark_file_keeps_its_contract():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
    metrics = b["end_to_end"] + b["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert spec.metric_path(ROOT, m["name"]).is_file()
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        mix = spec.load_traffic(ROOT, w["traffic"])
        if mix["loop"] == "open":
            assert spec.load_cell(ROOT, w["name"])["rate_per_s"] > 0
        mine = spec.cell_metrics(b, w["name"], False)
        assert "setup_s" in mine and len(set(mine) & e2e) >= 2
        assert spec.cell_metrics(b, w["name"], True)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert m["moves"] in spec.cell_metrics(b, w, False)
