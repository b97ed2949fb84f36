"""PACO sample sort (paper Sect. III-G, Theorem 16).

Steps (exactly the paper's):
  1. pick k*p samples uniformly at random (oversampling k = O(log n)),
     sort them sequentially, take every k-th as the p-1 pivots;
  2. every processor partitions its n/p slice into p chunks by the pivots,
     builds the p x p count matrix [N], prefix-sums columns for destination
     offsets, and redistributes chunks with an all-to-all;
  3. each processor sorts its received bucket locally.

Two implementations:
  * ``paco_sort``        — plan-faithful host-level execution for arbitrary p
                           (returns sorted array + per-processor bucket sizes
                           for the (1+eps) w.h.p. balance check).
  * ``paco_sort_shmap``  — SPMD shard_map version with a fixed bucket
                           capacity and jax.lax.all_to_all; the MoE dispatch
                           in repro.models.moe reuses this machinery (tokens
                           ~ keys, experts ~ processors, capacity ~ expert
                           capacity).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def choose_pivots(x: jax.Array, p: int, key: jax.Array,
                  oversample: int | None = None) -> jax.Array:
    """Step 1: p-1 pivots via k*p random samples (k = O(log n))."""
    n = x.shape[0]
    # Theorem 16 wants k = O(log n) with a big-enough constant: 2·ln n
    # leaves ~2x-mean buckets at n=2k (measured), overflowing the SPMD
    # path's fixed capacity; 4·ln n keeps the max bucket under 1.3x.
    k = oversample or max(2, int(4 * math.log(max(n, 2))))
    idx = jax.random.randint(key, (k * p,), 0, n)
    samples = jnp.sort(x[idx])
    return samples[k::k][: p - 1]


def paco_sort(x: jax.Array, p: int, key: jax.Array,
              oversample: int | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Plan-faithful PACO sample sort for arbitrary p.

    Returns (sorted_array, bucket_sizes).  bucket_sizes[i] is the number of
    elements processor i sorts locally after redistribution; Theorem 16 says
    max(bucket_sizes) <= (1+eps) n/p w.h.p. — asserted in tests.
    """
    n = x.shape[0]
    pivots = choose_pivots(x, p, key, oversample)
    # Step 2a: each processor partitions its slice by the pivots.  The
    # destination bucket of every element is its pivot rank; the count
    # matrix [N]_{i,j} = #elements of slice i going to bucket j.
    bucket = jnp.searchsorted(pivots, x)  # in [0, p)
    sizes = jnp.bincount(bucket, length=p)
    # Step 2b/2c: prefix sums + redistribution == a stable counting sort of
    # the (bucket, element) pairs; local sort per bucket afterwards.
    order = jnp.argsort(bucket, stable=True)
    redistributed = x[order]
    # Step 3: local sort inside each bucket (segments of `redistributed`).
    # Host-level faithful loop over p buckets (sizes are data-dependent, so
    # this path runs eagerly — mirroring the paper's shared-memory setting).
    offs = jnp.concatenate([jnp.zeros((1,), sizes.dtype), jnp.cumsum(sizes)])
    parts = []
    for i in range(p):
        seg = redistributed[int(offs[i]): int(offs[i + 1])]
        parts.append(jnp.sort(seg))
    return jnp.concatenate(parts) if parts else redistributed, sizes


# ---------------------------------------------------------------------------
# SPMD version (fixed capacity, all_to_all)
# ---------------------------------------------------------------------------

def paco_sort_shmap(x: jax.Array, mesh: Mesh, axis: str, key: jax.Array,
                    *, capacity_factor: float = 4.0,
                    oversample: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """SPMD sample sort over mesh axis ``axis``.

    Every device keeps its length-(n/p) slice; buckets are padded to a fixed
    capacity C = capacity_factor * n/p^2 per (src, dst) pair, exchanged with
    jax.lax.all_to_all, and sorted locally with +inf padding pushed to the
    tail.  Returns (values, valid) both sharded over ``axis``: ``values`` is
    globally sorted once per-device padding (``~valid``) is dropped.
    """
    p = mesh.shape[axis]
    n = x.shape[0]
    per = n // p
    assert per * p == n, "n must divide p for the SPMD path (pad upstream)"
    cap = int(math.ceil(capacity_factor * per / p))
    pivots = choose_pivots(x, p, key, oversample)  # replicated

    def local(x_blk, pivots_blk):
        xs = x_blk.reshape(-1)  # (per,)
        bucket = jnp.searchsorted(pivots_blk, xs)  # (per,) in [0,p)
        # Stable sort by bucket; rank within bucket = position - bucket start
        order = jnp.argsort(bucket, stable=True)
        xs_s = xs[order]
        b_s = bucket[order]
        counts = jnp.bincount(b_s, length=p)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(per) - starts[b_s]
        # Scatter into a (p, cap) padded send buffer.  Overflow elements
        # (rank >= cap) are routed to a dump column so they drop WITHOUT
        # clobbering the valid element in slot cap-1.
        ok = rank < cap
        send = jnp.full((p, cap + 1), jnp.inf, xs.dtype)
        send = send.at[b_s, jnp.where(ok, rank, cap)].set(
            jnp.where(ok, xs_s, jnp.inf))[:, :cap]
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
        merged = jnp.sort(recv.reshape(-1))  # (p*cap,), +inf tail
        valid = merged != jnp.inf
        return merged[None], valid[None]

    vals, valid = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P(axis)),
    )(x, pivots)
    return vals.reshape(-1), valid.reshape(-1)
