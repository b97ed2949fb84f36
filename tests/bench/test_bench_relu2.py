"""nemotron-4-15b's mechanisms through the benchmark at a toy size on the
CPU: a squared-ReLU MLP with no gate, an untied output head and a GQA
group of 6 query heads per key/value head (``data/tiny-relu2.json``).

The program is compared with the benchmark's float32 reference
(``bench/reference.py``) on logits: its whole-sequence forward, and
prefill then decode through the paged cache at every decoded position.
The harness run of a closed-loop cell is correct, and both the float8
control and a run whose served tokens are altered are not."""
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import engine_trace, flops, reference, run, spec
from bench import trace_reduce as R
from bench import weights as W
from bench.peaks import PEAKS

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CONF = json.loads((DATA / "tiny-relu2.json").read_text())
CELL = "tiny-relu2.long"
SEED = 2**32 + 7
# Logits lie within about 4 of 0 at this size.  In float32 the program and
# the reference differ only in the order of their sums (the paged gather,
# the chunked prefill): 3e-6 measured, on the CPU.  1e-4 leaves 30 times
# that, and lies 300 times below what bfloat16 products give (3.4e-2).
ATOL_F32 = 1e-4
PROMPT, DECODED, PAGE, CHUNK = 32, 24, 8, 16


def model():
    return W.model_from_config(CONF)


def program(dtype: str):
    """The program's ArchConfig and weights in ``dtype``, on the values the
    reference draws for itself from the seed."""
    m = model()
    cfg = run.arch_config({**CONF, "torch_dtype": dtype}, m)
    params = jax.tree.map(lambda x: x.astype(dtype), W.make_params(m, SEED))
    return m, cfg, params


def reference_logits(m, prompt, served, length=64):
    """The reference's logits at every position that predicts a token of
    ``served`` (the last of which is never read)."""
    rows = reference.hidden_rows(m, SEED, [(prompt, served)], length)
    return np.asarray(reference._fns(m, "f32")[3](W.top_weights(m, SEED),
                                                  rows))


def test_registry_entry_is_the_configuration():
    """The file's mechanisms are the registry entry's (``arch_config``
    raises otherwise), and the group of 6 is the published one."""
    m, cfg, _ = program("bfloat16")
    assert (cfg.name, cfg.act, cfg.tie_embeddings, cfg.qk_norm) == (
        "nemotron-4-15b", "sq_relu", False, False)
    assert m.heads // m.kv_heads == 48 // 8 and m.kv_heads > 1
    assert "gate" not in W.make_params(m, SEED)["blocks"]["mlp"]


def test_forward_in_float32_equals_the_reference():
    from repro.models import forward

    m, cfg, params = program("float32")
    toks = np.random.default_rng(0).integers(0, m.vocab, 40)
    with jax.default_matmul_precision("highest"):
        want = forward(params, cfg, {"tokens": jnp.asarray(toks)[None]},
                       remat=False)[0, :, :m.vocab]
    got = reference_logits(m, list(toks[:10]), list(toks[10:]) + [0])
    np.testing.assert_allclose(got, np.asarray(want[9:]), rtol=0,
                               atol=ATOL_F32)


def paged_logits(dtype: str, toks: np.ndarray) -> list[np.ndarray]:
    """Each sequence's prompt in page-aligned chunks, then DECODED
    teacher-forced ticks over both slots at once, through one page pool:
    per sequence, the logits at positions PROMPT - 1 .. PROMPT + DECODED - 1
    (the prompt's last, then every decoded one)."""
    from repro.models import (decode_step_paged, paged_cache_leaf_specs,
                              prefill_chunk)
    from repro.serve import paging

    m, cfg, params = program(dtype)
    b, per_seq = toks.shape[0], -(-(PROMPT + DECODED) // PAGE)
    pages = paging.init_pool(paged_cache_leaf_specs(cfg, PAGE),
                             b * per_seq, PAGE).pools
    # the slots' pages interleaved, so a wrong table row reads the other's
    tables = jnp.arange(b * per_seq, dtype=jnp.int32).reshape(per_seq, b).T
    out = [[] for _ in range(b)]
    for s in range(b):
        for c in range(0, PROMPT, CHUNK):
            lg, pages = prefill_chunk(
                params, cfg, jnp.asarray(toks[s:s + 1, c:c + CHUNK]),
                jnp.int32(c), pages, tables[s])
        out[s].append(np.asarray(lg[-1, :m.vocab], np.float32))
    for j in range(DECODED):
        lg, pages = decode_step_paged(
            params, cfg, jnp.asarray(toks[:, PROMPT + j:PROMPT + j + 1]),
            pages, tables, jnp.full((b,), PROMPT + j, jnp.int32))
        for s in range(b):
            out[s].append(np.asarray(lg[s, :m.vocab], np.float32))
    return [np.stack(o) for o in out]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_decode_logits_against_the_full_forward(dtype):
    """float32 agrees with the reference's full forward at every decoded
    position within ATOL_F32; bfloat16 products, a precision below the
    reference's, must fail that tolerance."""
    m = model()
    toks = np.random.default_rng(1).integers(
        0, m.vocab, (2, PROMPT + DECODED)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = paged_logits(dtype, toks)
    for s, g in enumerate(got):
        want = reference_logits(m, list(toks[s, :PROMPT]),
                                list(toks[s, PROMPT:]) + [0])
        err = np.abs(g - want).max()
        if dtype == "float32":
            assert err <= ATOL_F32, (s, err)
        else:
            assert err > 10 * ATOL_F32, (s, err)


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
def relu2_root(tmp_path):
    """A checkout whose one cell, tiny-relu2.long, stands where the
    repository's nemotron-4-15b-pp4.decode-long stands: its metrics are
    that cell's, read by the repository's readers."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    nemotron = "nemotron-4-15b-pp4.decode-long"
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [CELL] if nemotron in m["workloads"] else []
    bench["configs"] = [{"name": "tiny-relu2", "source": "test",
                         "file": "bench/configs/tiny-relu2.json",
                         "reduced": [], "why": "toy"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-relu2",
                           "traffic": "tiny-relu2-long", "chips": 1,
                           "why": "toy"}]
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copytree(ROOT / "bench" / "metrics",
                    tmp_path / "bench" / "metrics")
    shutil.copy(DATA / "tiny-relu2.json", tmp_path / "bench" / "configs")
    shutil.copy(DATA / "tiny-relu2-long.json",
                tmp_path / "bench" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def cpu_chips(n):
    return jax.devices()[:n]


def test_run_is_correct_and_the_control_is_not(relu2_root,
                                               no_compile_cache):
    out = run.run(relu2_root, CELL, 2**31 + 21, 3.0, False,
                  chips=cpu_chips, control=True)
    c = out["checks"]
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["correct"], c
    assert c["tokens_checked"]["value"] >= run.MIN_CHECKED
    assert not out["control_correct"]
    assert c["control_gap"]["value"] > 3 * c["widest_gap"]["limit"]


def test_altered_token_is_not_correct(relu2_root, no_compile_cache,
                                      monkeypatch):
    from repro.serve.engine import ServeEngine

    emit = ServeEngine._emit

    def altered(self, req, tok):
        if req.uid % 3 == 0 and len(req.out) == 7:
            tok = (tok + 1) % self.cfg.vocab
        return emit(self, req, tok)

    monkeypatch.setattr(ServeEngine, "_emit", altered)
    out = run.run(relu2_root, CELL, 2**31 + 22, 3.0, False, chips=cpu_chips)
    gap = out["checks"]["widest_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"]


@pytest.fixture
def restore_reduce(monkeypatch):
    """The reader's import swaps ``trace_reduce.reduce_trace``; put it
    back."""
    monkeypatch.setattr(R, "reduce_trace", R.reduce_trace)


def test_weight_roofline_reader(restore_reduce):
    """Weights once a decode tick at the chip's bytes per second, over the
    decode program's time in the weight products' scopes and in no
    scope (where the compiler's weight copies land)."""
    m = model()
    peak = PEAKS["TPU v5 lite"]
    ticks = [SimpleNamespace(steps=8), SimpleNamespace(steps=3)]
    times = {"attn_in": 0.5, "attn_out": 0.25, "mlp": 1.0, "head": 0.25,
             "attention": 3.0, "sample": 0.1, engine_trace.UNSCOPED: 0.2}

    def ctx(scopes):
        return SimpleNamespace(model=m, peak=peak,
                               traced_ticks=lambda: ticks,
                               trace={"scopes": scopes})

    reader = spec.load_metric(ROOT, "weight_roofline.nemotron")
    want = 100.0 * flops.weight_bytes(m) * 11 / peak["hbm_bytes_per_s"] / 2.2
    assert reader.read(ctx({"jit__decode_fn": times})) == pytest.approx(
        want)
    # a program without the leaf scopes, or no decode in the slice
    assert reader.read(ctx({"jit__decode_fn": {
        engine_trace.UNSCOPED: 1.0}})) is None
    assert reader.read(ctx({})) is None
    assert R.reduce_trace is engine_trace.reduce_trace


def test_calibrated_file_keeps_every_key_of_the_draft():
    """The benchmark's nemotron-4-15b-pp4 file is the draft with its limit
    set, one assumption added and the deployment restated as a
    vocabulary-parallel pipeline (each stage holds a quarter of the
    embedding and of the head); every size is the draft's."""
    configs = ROOT / "bench" / "configs"
    draft = json.loads((configs / "nemotron-4-15b-pp4.json").read_text())
    new = json.loads((configs / "nemotron-4-15b-pp4.calibrated.json")
                     .read_text())
    restated = ("deployment", "deployment_cut")
    assert set(new["assumed"]) - set(draft["assumed"]) == {"cache"}
    assert set(new["check"]) == {"gap_limit", "note"}
    for key in set(draft) | set(new):
        if key not in ("assumed", "check") + restated:
            assert new[key] == draft[key], key
    for key, text in draft["assumed"].items():
        if key not in restated:
            assert new["assumed"][key] == text, key
    for text in (new["deployment"], new["assumed"]["deployment_cut"]):
        assert "arXiv:2411.05288" in text and "64000" in text
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == new["name"])
    assert entry["file"] == "bench/configs/nemotron-4-15b-pp4.calibrated.json"
    assert W.model_from_config(new) == W.model_from_config(draft)


def test_decode_long_12_doubles_decode_long_supply():
    """The same lengths as decode-long, twice the requests per client: at
    32 slots the thinnest client holds 7468 output tokens, more than a
    slot decodes in a 51 s window at 146 tokens a second."""
    from bench import traffic as T

    base = spec.load_traffic(ROOT, "decode-long")
    mix = spec.load_traffic(ROOT, "decode-long-12")
    assert {k: v for k, v in mix.items() if k not in (
        "requests_per_client", "why")} == {
        k: v for k, v in base.items() if k not in (
            "requests_per_client", "why")}
    assert mix["requests_per_client"] == 2 * base["requests_per_client"]
    items = T.generate(mix, seed=5, seconds=51, vocab=64000, slots=32)
    per_client = {}
    for it in items:
        per_client[it.client] = per_client.get(it.client, 0) + it.max_new
    assert len(per_client) == 32 and min(per_client.values()) == 7468
    assert min(per_client.values()) > 146 * 51
