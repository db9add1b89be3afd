"""Pallas TPU Mamba-1 selective scan.

TPU-native adaptation of the CUDA selective-scan: the GPU kernel parallelizes
over (batch, channel) threads with a sequential time loop in registers.  On
TPU we tile channels into VPU-lane-aligned blocks (bd x N state tiles live in
VMEM scratch), run chunks of the sequence per grid step, and exploit the
sequential-grid guarantee of the TPU 'arbitrary' dimension to carry the SSM
state across chunks without HBM round-trips.

Validated with ``interpret=True`` against ``ref.selective_scan_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_scr, *,
                 chunk, block_d, n_state):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)               # (bd, N)
    d_skip = d_ref[0].astype(jnp.float32)            # (bd,)

    def step(t, h):
        xt = x_ref[0, t].astype(jnp.float32)         # (bd,)
        dtt = dt_ref[0, t].astype(jnp.float32)       # (bd,)
        bt = b_ref[0, t].astype(jnp.float32)         # (N,)
        ct = c_ref[0, t].astype(jnp.float32)         # (N,)
        da = jnp.exp(dtt[:, None] * a)               # (bd, N)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        y = (h * ct[None, :]).sum(axis=1) + d_skip * xt
        y_ref[0, t] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def selective_scan(x, dt, A, Bc, Cc, D_skip, *, chunk=128, block_d=256,
                   interpret=False):
    """x, dt (B,S,Di); A (Di,N); Bc, Cc (B,S,N); D_skip (Di,) -> y (B,S,Di)."""
    B, S, Di = x.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    block_d = min(block_d, Di)
    assert S % chunk == 0 and Di % block_d == 0
    nc = S // chunk
    nd = Di // block_d

    kernel = functools.partial(_scan_kernel, chunk=chunk, block_d=block_d,
                               n_state=N)
    # grid: (batch, channel-block) parallel, chunks sequential innermost so
    # the state scratch legitimately carries across chunk steps.  D goes in
    # as (1, Di): a 1-D operand gets an XLA tiling Mosaic refuses.
    return pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),  # x
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),  # dt
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),            # A
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),        # B
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),        # C
            pl.BlockSpec((1, block_d), lambda b, d, c: (0, d)),            # D
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, Di), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, Bc, Cc, D_skip.reshape(1, Di))
