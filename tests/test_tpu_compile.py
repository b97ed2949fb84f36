"""Compiles for a described TPU v5e (no chip attached): the serving
engine's step programs and the paged attention kernels at real widths.

The TPU compiler refuses what CPU runs and interpret-mode Pallas accept
(block shapes off the (8, 128) tiling, programs larger than the chip's
memory), so these compiles guard the chip path without a chip.  The
topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles,
since a program compiled for a described device cannot be read back.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.attention.attention import (paged_flash_decode_pallas,
                                               paged_flash_prefill_pallas,
                                               paged_latent_decode_pallas,
                                               paged_latent_prefill_pallas)
from repro.models import init_params
from repro.serve import ServeEngine, paging

HBM_BYTES = 16 * 2**30          # one v5e chip
# chip_smoke.py's geometry: 16 slots x 2048 positions; the PACO page
# plan gives page 32, so 64 pages per sequence
SLOTS, MAX_SEQ = 16, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def engine(one_chip):
    """The serving engine for qwen3-0.6b at its published widths and
    depth, with params and pool as placed shapes (a real pool would be
    3.8 GB of host memory)."""
    cfg = get_arch("qwen3-0.6b")
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))

    def shaped_pool(specs, n_pages, page_size):
        pools = {name: jax.ShapeDtypeStruct(
                     (s.shape[0], n_pages + 1, *s.shape[1:]), s.dtype,
                     sharding=one_chip) for name, s in specs.items()}
        return paging.PagePool(pools=pools, page_size=page_size,
                               n_pages=n_pages, free=list(range(n_pages)))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paging, "init_pool", shaped_pool)
        engine = ServeEngine(params, cfg, slots=SLOTS, max_seq=MAX_SEQ,
                             speculate=0)
    assert (engine.page, engine.pages_per_seq) == (32, 64)
    return engine


@pytest.fixture(scope="module", params=["prefill_chunk", "decode_ticks",
                                        "verify_ticks"])
def steps(request, engine, one_chip):
    """(name, compiled program) of each of the engine's own step
    programs, lowered at its widest shapes."""
    name = request.param
    return name, engine.lower_steps(one_chip)[name].compile()


def test_engine_step_compiles_and_fits_one_chip(steps):
    name, compiled = steps
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, (name, total)


def test_engine_step_updates_pool_in_place(engine, steps):
    """The layer scan carries the donated pool and scatters into it: the
    step keeps no pool-sized temporary, and no instruction copies a pool
    leaf or writes one back whole (a per-layer slice restacked by
    dynamic-update-slice)."""
    name, compiled = steps
    pools = engine.pool.pools.values()
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 4, (name, temp, pool_bytes)
    leaf_sizes = {math.prod(p.shape) for p in pools}
    whole = [line.strip()[:160] for line in compiled.as_text().splitlines()
             if (m := re.search(r"= \w+\[([\d,]+)\]\S* "
                                r"(copy|dynamic-update-slice)\(", line))
             and math.prod(map(int, m.group(1).split(","))) in leaf_sizes]
    assert not whole, (name, whole)


def _kernel(name, one_chip):
    """(function, argument shapes) of one paged kernel at the engine
    geometry: qwen3-0.6b widths for GQA, deepseek-v2 for the latents."""
    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    b, page, pps, chunk = SLOTS, 32, MAX_SEQ // 32, 64
    n_pages = b * pps + 1
    tables, lens = shape(b, pps, dtype=jnp.int32), shape(b, dtype=jnp.int32)
    row, start = shape(pps, dtype=jnp.int32), shape(dtype=jnp.int32)
    if name.startswith("gqa"):
        cfg = get_arch("qwen3-0.6b")
        hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        kv = shape(n_pages, page, hkv, d)
        scale = d ** -0.5
        return {
            "gqa_decode": (lambda *a: paged_flash_decode_pallas(
                *a, scale=scale), (shape(b, hq, d), kv, kv, tables, lens)),
            "gqa_prefill": (lambda *a: paged_flash_prefill_pallas(
                *a, scale=scale), (shape(chunk, hq, d), kv, kv, row, start)),
            "gqa_verify": (lambda q, k, v, bt, st: jax.vmap(
                lambda qb, row, s0: paged_flash_prefill_pallas(
                    qb, k, v, row, s0, scale=scale))(q, bt, st),
                (shape(b, 8, hq, d), kv, kv, tables, lens)),
        }[name]
    cfg = get_arch("deepseek-v2-236b")
    h, m = cfg.n_heads, cfg.mla
    ckv, kr = shape(n_pages, page, m.kv_lora), shape(n_pages, page, m.qk_rope)
    scale = (m.qk_nope + m.qk_rope) ** -0.5
    return {
        "latent_decode": (lambda *a: paged_latent_decode_pallas(
            *a, scale=scale), (shape(b, h, m.kv_lora), shape(b, h, m.qk_rope),
                               ckv, kr, tables, lens)),
        "latent_prefill": (lambda *a: paged_latent_prefill_pallas(
            *a, scale=scale), (shape(chunk, h, m.kv_lora),
                               shape(chunk, h, m.qk_rope), ckv, kr, row,
                               start)),
    }[name]


@pytest.mark.parametrize("name", ["gqa_decode", "gqa_prefill", "gqa_verify",
                                  "latent_decode", "latent_prefill"])
def test_paged_kernel_compiles_for_tpu(one_chip, name):
    fn, args = _kernel(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
