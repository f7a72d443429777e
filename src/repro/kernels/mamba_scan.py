"""Pallas TPU selective-scan (Mamba-1) forward.

TPU adaptation of the CUDA selective-scan: instead of warp-level parallel
prefix sums, the state (bd, N) lives in vector registers / VMEM and the
kernel walks the sequence with a ``fori_loop``; parallelism comes from the
grid over (batch, d_inner blocks) — the d_inner axis is wide (8k+ lanes on
falcon-mamba), which is where the VPU earns its keep.  The sequence axis is
blocked via the grid's sequential last dimension so x/dt tiles of shape
(block_s, bd) stream through VMEM instead of requiring the whole sequence
resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, hT_ref, h_ref, *, block_s, n_state):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    a = a_ref[...].astype(jnp.float32)              # (bd, N)
    dskip = d_ref[...].astype(jnp.float32)          # (1, bd)

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)  # (bd,)
        x_t = x_ref[0, t, :].astype(jnp.float32)    # (bd,)
        b_t = b_ref[0, t, :].astype(jnp.float32)    # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)    # (N,)
        da = jnp.exp(dt_t[:, None] * a)             # (bd, N)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1) + x_t * dskip[0]
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    h_ref[...] = h

    @pl.when(si == ns - 1)
    def _final():
        hT_ref[0] = h


@functools.partial(jax.jit, static_argnames=("block_d", "block_s", "interpret"))
def mamba_scan(
    x: jnp.ndarray,      # (B, S, di)
    dt: jnp.ndarray,     # (B, S, di) fp32
    a: jnp.ndarray,      # (di, N) fp32 (negative)
    bmat: jnp.ndarray,   # (B, S, N) fp32
    cmat: jnp.ndarray,   # (B, S, N) fp32
    d_skip: jnp.ndarray,  # (di,) fp32
    h0: jnp.ndarray,     # (B, di, N) fp32
    *,
    block_d: int = 512,
    block_s: int = 256,
    interpret: bool = False,
):
    """Returns (y (B, S, di), hT (B, di, N))."""
    bsz, s, di = x.shape
    n = a.shape[-1]
    block_d = min(block_d, di)
    block_s = min(block_s, s)
    assert di % block_d == 0 and s % block_s == 0

    grid = (bsz, di // block_d, s // block_s)
    scratch = [pltpu.VMEM((block_d, n), jnp.float32)]

    y, ht = pl.pallas_call(
        functools.partial(_scan_kernel, block_s=block_s, n_state=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda b, dd, ss: (b, ss, dd)),  # x
            pl.BlockSpec((1, block_s, block_d), lambda b, dd, ss: (b, ss, dd)),  # dt
            pl.BlockSpec((1, block_s, n), lambda b, dd, ss: (b, ss, 0)),         # B
            pl.BlockSpec((1, block_s, n), lambda b, dd, ss: (b, ss, 0)),         # C
            pl.BlockSpec((block_d, n), lambda b, dd, ss: (dd, 0)),               # A
            pl.BlockSpec((1, block_d), lambda b, dd, ss: (0, dd)),               # D
            pl.BlockSpec((1, block_d, n), lambda b, dd, ss: (b, dd, 0)),         # h0
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda b, dd, ss: (b, ss, dd)),
            pl.BlockSpec((1, block_d, n), lambda b, dd, ss: (b, dd, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), x.dtype),
            jax.ShapeDtypeStruct((bsz, di, n), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, dt, jnp.asarray(bmat, jnp.float32), jnp.asarray(cmat, jnp.float32),
      jnp.asarray(a, jnp.float32), d_skip.reshape(1, di), h0)
    return y, ht
