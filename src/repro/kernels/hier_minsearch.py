"""Pallas TPU kernel for the paper's two-stage mapping decision.

The paper models each mapping decision as an RB-tree min-search with cost
``Omega_s = c_s * log(nu)`` on a scalar stack-machine GMN.  On TPU the
pointer-chasing log-search has no analogue; the TPU-native adaptation is a
lane-parallel reduction: the whole (k x m/k) load matrix lives in VMEM and a
fused kernel performs BOTH stages of the paper's hierarchy per decision —
stage 1: argmin over per-cluster load sums, stage 2: argmin inside the
winning cluster — then applies the load update in-place, sequentially for a
batch of T tasks (the sequential dependence is fundamental: decision t+1
must see the load of decision t, exactly like the paper's GMN pipeline).

This is the batch mapping path: `core/mapping.map_batch` routes here
through `kernels.ops.assign_tasks` (compiled on TPU, ``interpret=True``
everywhere else), and `tests/test_kernels_minsearch.py` pins it
decision-for-decision — tie cases included — to the pure-JAX oracle
`kernels.ref.assign_tasks_ref`.  The wall-clock serving engine
(`repro.serving.engine`) makes the same two-stage decision per request
through the numpy adapters in `core/policies.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _assign_kernel(loads_ref, costs_ref, assign_ref, out_loads_ref, *, n_tasks):
    loads = loads_ref[...].astype(jnp.float32)            # (k, m_per_k)
    k, mk = loads.shape
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (k, mk), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (k, mk), 1)

    def step(t, loads):
        csum = loads.sum(axis=1)                          # stage 1: cluster sums
        c = jnp.argmin(csum).astype(jnp.int32)
        in_c = row_ids == c
        masked = jnp.where(in_c, loads, jnp.inf)          # stage 2: inside cluster
        p = jnp.argmin(masked.min(axis=0)).astype(jnp.int32)
        assign_ref[t, 0] = c
        assign_ref[t, 1] = p
        hit = jnp.logical_and(in_c, col_ids == p)
        return loads + jnp.where(hit, costs_ref[t].astype(jnp.float32), 0.0)

    out_loads_ref[...] = jax.lax.fori_loop(0, n_tasks, step, loads)


@functools.partial(jax.jit, static_argnames=("interpret",))
def assign_tasks(loads, costs, *, interpret=False):
    """Map T tasks onto a (k, m_per_k) load matrix by two-stage min-search.

    Returns (assignments (T,2) int32, updated loads).
    """
    T = costs.shape[0]
    kernel = functools.partial(_assign_kernel, n_tasks=T)
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[
            pl.BlockSpec(loads.shape, lambda: (0,) * loads.ndim),
            # per-task scalars are read and written one at a time with a
            # dynamic index: Mosaic takes those only from/to SMEM
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(loads.shape, lambda: (0,) * loads.ndim),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, 2), jnp.int32),
            jax.ShapeDtypeStruct(loads.shape, jnp.float32),
        ],
        interpret=interpret,
    )(loads.astype(jnp.float32), costs)
