"""PACO distributed matrix multiplication executors.

Three tiers, all driven by the planners in repro.core.cuboid:

  * ``paco_matmul``        — plan-faithful tile executor for *arbitrary* p
                             (primes welcome).  Executes every processor's
                             cuboid list and combines partial products,
                             exactly reproducing the paper's algorithm
                             semantics (shared-memory model).  Used for
                             correctness/balance validation and benchmarks.
  * ``paco_matmul_shmap``  — SPMD execution on a (pn, pm, pk) mesh derived
                             from the 1-piece cut tree via
                             ``cuboid.mesh_factors``: local tile matmul +
                             psum_scatter over the k-axis (the cut tree's
                             reduction schedule, O(log pk) latency).
  * ``paco_spec``          — turns a plan into pjit in/out shardings over a
                             given mesh axis for the production transformer
                             path (repro.dist.sharding builds on this).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cuboid as cub


# ---------------------------------------------------------------------------
# Tier 1: plan-faithful executor (arbitrary p)
# ---------------------------------------------------------------------------

def paco_matmul(a: jax.Array, b: jax.Array, p: int, *,
                planner: str = "1piece",
                throughputs: Sequence[float] | None = None) -> jax.Array:
    """C = A @ B executed tile-by-tile per the PACO plan for p processors.

    Semantically identical to ``a @ b``; structurally identical to the
    paper's algorithm: each processor computes the products of its assigned
    cuboid(s) into (temporary) C tiles, and tiles sharing output rows/cols
    (k-cuts) are reduced by addition.
    """
    n, k = a.shape
    k2, m = b.shape
    assert k == k2, (a.shape, b.shape)
    if planner == "1piece":
        plan = cub.plan_mm_1piece(n, m, k, p)
    elif planner == "mm":
        plan = cub.plan_mm(n, m, k, p, base=max(1, min(n, m, k) // (4 * p)))
    elif planner == "hetero":
        assert throughputs is not None and len(throughputs) == p
        plan = cub.plan_hetero(n, m, k, throughputs)
    else:
        raise ValueError(planner)
    out = jnp.zeros((n, m), dtype=jnp.result_type(a.dtype, b.dtype))
    for _proc, c in plan.tiles:
        if c.volume() == 0:
            continue
        part = a[c.n0:c.n1, c.k0:c.k1] @ b[c.k0:c.k1, c.m0:c.m1]
        out = out.at[c.n0:c.n1, c.m0:c.m1].add(part)
    return out


# ---------------------------------------------------------------------------
# Tier 2: shard_map SPMD executor on the cut-tree-derived 3-D grid
# ---------------------------------------------------------------------------

def make_paco_mesh(n: int, m: int, k: int, p: int,
                   devices: np.ndarray | None = None) -> Mesh:
    """Mesh shaped by the 1-piece cut tree's dimension factors."""
    pn, pm, pk = cub.mesh_factors(n, m, k, p)
    if devices is None:
        devices = np.array(jax.devices()[:p]).reshape(pn, pm, pk)
    else:
        devices = np.asarray(devices).reshape(pn, pm, pk)
    return Mesh(devices, axis_names=("pc_n", "pc_m", "pc_k"))


def paco_matmul_shmap(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    """SPMD PACO matmul on a ("pc_n","pc_m","pc_k") mesh.

    Each device holds A[n/pn, k/pk] and B[k/pk, m/pm] tiles (the faces of its
    cuboid), multiplies locally, and reduce-scatters partial C over the
    k-axis — the cut tree's reduction rounds.  C comes out sharded
    (n over pc_n, m over (pc_m, pc_k)): the reduce-scatter assigns each
    k-group member a disjoint C slab, the distributed-memory write-back of
    paper Sect. III-E-1.
    """
    def local(a_blk, b_blk):
        part = a_blk @ b_blk  # local cuboid product (MXU)
        # Reduction schedule: scatter over the k-cut group => each member
        # owns a disjoint slice of C; log(pk) rounds inside XLA.
        return jax.lax.psum_scatter(part, "pc_k", scatter_dimension=1,
                                    tiled=True)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P("pc_n", "pc_k"), P("pc_k", "pc_m")),
        out_specs=P("pc_n", ("pc_m", "pc_k")),
    )(a, b)


# ---------------------------------------------------------------------------
# Tier 3: pjit production path — plan => shardings
# ---------------------------------------------------------------------------

def paco_spec(n: int, m: int, k: int, p: int, axis: str
              ) -> tuple[P, P, P, bool]:
    """Choose which single matmul dimension the mesh axis ``axis`` shards,
    per the first cut of the PACO 1-piece tree (the dominant cut: the paper
    cuts the longest dimension first, minimizing exposed surface).

    Returns (spec_a, spec_b, spec_c, needs_psum).  With one mesh axis only a
    single dim can be sharded per tensor; the planner picks n, m, or k — the
    communication-minimizing choice that a fixed Megatron-style rule misses
    for skewed shapes.
    """
    d = cub.Cuboid(0, n, 0, m, 0, k).longest_dim()
    if d == "n":
        return P(axis, None), P(None, None), P(axis, None), False
    if d == "m":
        return P(None, None), P(None, axis), P(None, axis), False
    return P(None, axis), P(axis, None), P(None, None), True


def paco_matmul_pjit(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str
                     ) -> jax.Array:
    """jit-compiled matmul with PACO-planned GSPMD shardings."""
    n, k = a.shape
    _, m = b.shape
    sa, sb, sc, _ = paco_spec(n, m, k, mesh.shape[axis], axis)

    @functools.partial(
        jax.jit,
        in_shardings=(NamedSharding(mesh, sa), NamedSharding(mesh, sb)),
        out_shardings=NamedSharding(mesh, sc),
    )
    def run(x, y):
        return x @ y

    return run(a, b)
