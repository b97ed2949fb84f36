"""Layer-to-stage pipeline partitioning over the pod axis (DESIGN.md §4).

``stage_ranges`` applies the 1-piece balanced-partition rule
(core.cuboid.plan_mm_1piece's floor(p/2):ceil(p/2) processor split — the
same arithmetic core.tree uses for round-robin balance) to the 1-D layer
interval: stages are contiguous, cover every layer, and differ in size by
at most one for ANY (n_layers, n_stages) — primes welcome, the paper's
headline property.

``pipeline_apply`` executes a GPipe forward schedule inside shard_map:
each device on the pipeline axis owns one stage's layer slice, microbatch
t enters stage 0 at step t, activations hop one stage per step via
ppermute, and the last stage's outputs are psum-broadcast back.  Total
steps = M + S - 1 (the GPipe bubble).
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stage_ranges(n_layers: int, n_stages: int) -> list[tuple[int, int]]:
    """Contiguous half-open layer ranges [lo, hi) per stage, PACO-balanced:
    max stage size - min stage size <= 1 for any inputs."""
    if not 1 <= n_stages:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")

    def rec(lo: int, hi: int, p: int) -> list[tuple[int, int]]:
        if p == 1:
            return [(lo, hi)]
        pl = p // 2  # floor:ceil processor split, layers cut by the ratio
        cut = lo + ((hi - lo) * pl) // p
        return rec(lo, cut, pl) + rec(cut, hi, p - pl)

    return rec(0, n_layers, n_stages)


def stack_stage_params(layers: Sequence[Any], n_stages: int
                       ) -> tuple[Any, jax.Array]:
    """Stack per-layer param pytrees into per-stage slabs.

    Returns (stage_params, mask): leaves gain leading (n_stages, max_per)
    dims; short stages are zero-padded and ``mask[s, j]`` marks real
    layers.  Shard the leading dim over the pipeline axis (P(axis)) so each
    device holds exactly its stage's layers.
    """
    ranges = stage_ranges(len(layers), n_stages)
    max_per = max(hi - lo for lo, hi in ranges)
    zero = jax.tree.map(jnp.zeros_like, layers[0])
    stage_trees = []
    mask_rows = []
    for lo, hi in ranges:
        sel = list(layers[lo:hi]) + [zero] * (max_per - (hi - lo))
        stage_trees.append(jax.tree.map(lambda *xs: jnp.stack(xs), *sel))
        mask_rows.append([j < hi - lo for j in range(max_per)])
    stage_params = jax.tree.map(lambda *xs: jnp.stack(xs), *stage_trees)
    return stage_params, jnp.asarray(mask_rows)


def pipeline_apply(stage_params: Any, mask: jax.Array, xs: jax.Array,
                   apply_layer: Callable[[Any, jax.Array], jax.Array],
                   mesh: Mesh, axis: str) -> jax.Array:
    """GPipe forward over mesh axis ``axis``.

    xs: (M, mb, ...) microbatches; returns the sequential layer stack's
    output for every microbatch.  stage_params/mask come from
    ``stack_stage_params`` with n_stages == mesh.shape[axis].
    """
    n_stages = mesh.shape[axis]
    m_total = xs.shape[0]

    def local(p_stage, mask_stage, xs_all):
        my_layers = jax.tree.map(lambda x: x[0], p_stage)  # (max_per, ...)
        my_mask = mask_stage[0]
        idx = jax.lax.axis_index(axis)

        def apply_stage(x):
            def body(x, inp):
                p_l, valid = inp
                return jnp.where(valid, apply_layer(p_l, x), x), None
            x, _ = jax.lax.scan(body, x, (my_layers, my_mask))
            return x

        fwd = [(i, i + 1) for i in range(n_stages - 1)]
        state = jnp.zeros_like(xs_all[0])
        outs = jnp.zeros_like(xs_all)
        for t in range(m_total + n_stages - 1):
            # stage s receives stage s-1's step-(t-1) output; stage 0 feeds
            # microbatch t (the clamp only ever re-feeds garbage that can
            # no longer reach the last stage before the schedule ends).
            prev = jax.lax.ppermute(state, axis, fwd) if fwd else state
            feed = xs_all[min(t, m_total - 1)]
            state = apply_stage(jnp.where(idx == 0, feed, prev))
            out_t = t - (n_stages - 1)
            if out_t >= 0:
                outs = outs.at[out_t].set(
                    jnp.where(idx == n_stages - 1, state, outs[out_t]))
        # only the last stage holds real outputs; broadcast via psum
        outs = jnp.where(idx == n_stages - 1, outs, 0)
        return jax.lax.psum(outs, axis)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=P(),
    )(stage_params, mask, xs)
