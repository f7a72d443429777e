"""Pallas TPU flash-attention forward (blocked online-softmax, causal GQA).

Grid: (batch*heads, q_blocks, kv_blocks) — the last axis is sequential on
TPU, so the (m, l, acc) online-softmax state lives in VMEM scratch and is
carried across kv blocks.  Block sizes are chosen so q/k/v tiles and the
accumulator fit VMEM with MXU-aligned (multiple-of-128) matmul dims.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, block_q, block_k, causal, seq_q, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q + (seq_k - seq_q)  # align causal diagonal
    k_start = ki * block_k
    # skip blocks that lie entirely above the causal diagonal
    run = (not causal) or (q_start + block_q - 1 >= k_start)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)        # (bq, d)
        k = k_ref[0].astype(jnp.float32)        # (bk, d)
        v = v_ref[0].astype(jnp.float32)        # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_ref[...]                      # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                   # (bq, bk)
        corr = jnp.exp(m_prev - m_new)           # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0

    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    scale = 1.0 / math.sqrt(d)

    def kv_index(bh, qi, ki):
        return ((bh // hq) * hkv + (bh % hq) // group, ki, 0)

    grid = (b * hq, sq // block_q, skv // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=sq, seq_k=skv,
    )
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
