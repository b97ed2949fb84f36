"""Launch-layer unit tests: HLO collective parsing, input specs, shape
cells, report generation — no device-count forcing needed."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, get_arch
from repro.launch.dryrun import _shape_bytes, collective_stats
from repro.launch.specs import input_specs, param_shapes, step_fn_for
from repro.train.train_step import TrainConfig


def test_shape_bytes_parsing():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[2,4]{1,0}") == 16
    assert _shape_bytes("(f32[8], bf16[4])") == 32 + 8
    assert _shape_bytes("pred[16]") == 16


def test_collective_stats_parsing():
    hlo = """
  %ag = f32[64,128]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = (bf16[32]{0}, bf16[32]{0}) all-reduce(%a, %b), to_apply=%sum
  %rs = f32[16]{0} reduce-scatter(%y), dimensions={0}
  %cp = f32[8,8]{1,0} collective-permute(%z)
  %a2a = bf16[4,4]{1,0} all-to-all(%w)
  %ag2 = f32[64,128]{1,0} all-gather-start(%x2)
"""
    stats = collective_stats(hlo)
    assert stats["all-gather"]["count"] == 2
    assert stats["all-gather"]["bytes"] == 2 * 64 * 128 * 4
    assert stats["all-reduce"]["bytes"] == 2 * 32 * 2
    assert set(stats) == {"all-gather", "all-reduce", "reduce-scatter",
                          "collective-permute", "all-to-all"}


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_shapes(arch, shape):
    cfg = get_arch(arch)
    cell = SHAPES[shape]
    specs = input_specs(cfg, cell)
    if cell.kind in ("train", "prefill"):
        toks = specs["batch"]["tokens"]
        assert toks.shape == (cell.global_batch, cell.seq_len)
        if cfg.family == "encdec":
            assert specs["batch"]["src_emb"].shape == (
                cell.global_batch, cell.seq_len, cfg.d_model)
    else:
        assert specs["tokens"].shape == (cell.global_batch, 1)
        assert specs["lengths"].shape == (cell.global_batch,)
        # cache leaves must be ShapeDtypeStructs (no allocation)
        for leaf in jax.tree.leaves(specs["cache"]):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-236b",
                                  "mamba2-780m", "seamless-m4t-medium",
                                  "zamba2-7b"])
def test_param_shapes_no_allocation(arch):
    cfg = get_arch(arch)
    shapes = param_shapes(cfg)
    leaves = jax.tree.leaves(shapes)
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    total = sum(x.size for x in leaves)
    assert total > 1e8  # full-size configs are big


def test_step_fn_selection():
    cfg = get_arch("qwen3-0.6b")
    _, name = step_fn_for(cfg, SHAPES["train_4k"], TrainConfig())
    assert name == "train_step"
    _, name = step_fn_for(cfg, SHAPES["prefill_32k"], TrainConfig())
    assert name == "prefill"
    _, name = step_fn_for(cfg, SHAPES["decode_32k"], TrainConfig())
    assert name == "serve_step"
    _, name = step_fn_for(get_arch("mamba2-780m"), SHAPES["prefill_32k"],
                          TrainConfig())
    assert name == "prefill(forward)"


def test_report_tables_render():
    from repro.launch import report
    t = report.dryrun_table("single")
    assert t.count("|") > 10
    r = report.roofline_table()
    assert "dominant" in r


def test_paco_weight_spec_rules():
    """The PACO longest-dim rule drives which dim takes 'model'."""
    from jax.sharding import Mesh, PartitionSpec as P
    import numpy as np
    from repro.dist.sharding import _weight_spec
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    # wide output => model on out (column parallel)
    assert _weight_spec(1024, 4096, mesh) == P("data", "model")
    # wide input => model on in (row parallel)
    assert _weight_spec(4096, 1024, mesh) == P("model", "data")
    # non-divisible out falls back to in
    assert _weight_spec(1024, 4090, mesh)[0] == "model"


def test_compile_cache_key_includes_named_scopes(tmp_path, monkeypatch):
    """A program that differs from a cached one only in its named scopes
    is compiled anew, so the profiler's trace shows its own scopes; the
    same program written on another line is found again."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    import repro.launch.compile_cache as C

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(C, "REPO_CACHE_DIR", tmp_path)
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_compilation_cache_include_metadata_in_key",
        "jax_traceback_in_locations_limit",
        "jax_persistent_cache_min_compile_time_secs")}

    def compiled(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2
        return jax.jit(f).lower(jnp.ones(8)).compile().as_text()

    def compiled_here(scope):
        def f(x):
            with jax.named_scope(scope): return jnp.sin(x) * 2  # noqa: E701
        return jax.jit(f).lower(jnp.ones(8)).compile().as_text()

    def entries():
        return sorted(p.name for p in tmp_path.iterdir())

    try:
        cc.reset_cache()
        jax.config.update("jax_enable_compilation_cache", True)
        assert C.use_compile_cache() == str(tmp_path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        assert "first/sin" in compiled("first")
        cached = entries()
        assert cached
        assert "first/sin" in compiled_here("first")
        assert entries() == cached
        assert "second/sin" in compiled("second")
        assert len(entries()) > len(cached)
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
