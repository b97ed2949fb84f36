#!/usr/bin/env python3
"""Compile a configuration's programs for a described TPU v5e, without a
chip, and print what each holds (``memory_analysis()``).

    JAX_PLATFORMS=cpu python3 bench/aot_compile.py --config nemotron-4-15b-pp4

It compiles, at the configuration's serving geometry: the weight build of
``bench/weights.py``, the engine's step programs (``prefill_chunk`` and
``decode_ticks``, at their widest shapes) and one layer of the float32
reference over a whole ``max_seq`` sequence.  Nothing runs, so this says
nothing about time; it shows before a chip call whether the programs
compile and fit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def line(name: str, m) -> str:
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return (f"{name}: argument {m.argument_size_in_bytes / 2**30:.3f} GiB, "
            f"output {m.output_size_in_bytes / 2**30:.3f}, temp "
            f"{m.temp_size_in_bytes / 2**30:.3f}, alias "
            f"{m.alias_size_in_bytes / 2**30:.3f}, total "
            f"{total / 2**30:.3f} GiB")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import reference, run
    from bench import weights as W
    from repro.serve import ServeEngine, paging

    jax.config.update("jax_enable_compilation_cache", False)
    # by its file, so that a configuration compiles before any cell uses it
    conf = json.loads((ROOT / "bench" / "configs" / f"{args.config}.json")
                      .read_text())
    m = W.model_from_config(conf)
    cfg = run.arch_config(conf, m)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    key = placed(jax.eval_shape(lambda: W.seed_key(0)))
    build = W._builders(m, jnp.dtype(jnp.bfloat16))[0]
    print(line("weights", build.lower(key).compile().memory_analysis()))
    params = placed(jax.eval_shape(build, key))
    run.check_tree(params, cfg)

    def shaped_pool(specs, n_pages, page_size):
        pools = {name: jax.ShapeDtypeStruct(
            (s.shape[0], n_pages + 1, *s.shape[1:]), s.dtype, sharding=chip)
            for name, s in specs.items()}
        return paging.PagePool(pools=pools, page_size=page_size,
                               n_pages=n_pages, free=list(range(n_pages)))

    serving = conf["serving"]
    real_init_pool = paging.init_pool
    paging.init_pool = shaped_pool
    try:
        engine = ServeEngine(params, cfg, slots=serving["slots"],
                             max_seq=serving["max_seq"],
                             ticks_per_dispatch=serving["ticks_per_dispatch"])
    finally:
        paging.init_pool = real_init_pool
    print(f"engine: slots {engine.slots} x {engine.max_seq}, page "
          f"{engine.page}, {engine.pool.n_pages} pages, chunk {engine.chunk}")
    for name, lowered in engine.lower_steps(chip).items():
        print(line(name, lowered.compile().memory_analysis()))
    one_layer = reference._fns(m, "f32")[1]
    w = placed(jax.eval_shape(W._builders(m, jnp.dtype(jnp.bfloat16))[1],
                              key, jax.ShapeDtypeStruct((), jnp.int32)))
    x = jax.ShapeDtypeStruct((engine.max_seq, m.d), jnp.float32,
                             sharding=chip)
    print(line("reference layer (float32)",
               one_layer.lower(w, x).compile().memory_analysis()))
    print(json.dumps({"config": args.config, "compiled_for": "v5e:2x2 "
                      "(one chip), described, nothing run"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
