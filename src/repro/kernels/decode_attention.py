"""Pallas TPU flash-decode: one query token vs. a long KV cache.

Grid: (batch*heads, kv_blocks) with online-softmax state in VMEM scratch
(split-KV flash-decoding adapted to the TPU sequential-grid idiom: instead of
CUDA-style inter-SM parallel splits + a reduction pass, the kv axis is the
sequential innermost grid dimension and partial (m, l, acc) are carried in
scratch — one pass, no separate combine kernel needed).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, block_k):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0, 0]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (1, d)
        k = k_ref[0].astype(jnp.float32)             # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (1, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_len: jnp.ndarray,
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, Hq, D); k, v: (B, Hkv, S, D); kv_len: () or (B,) -> (B, Hq, D)."""
    b, hq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    block_k = min(block_k, skv)
    assert skv % block_k == 0

    qr = q.reshape(b * hq, 1, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1, 1), (b, 1))
    scale = 1.0 / math.sqrt(d)

    def kv_index(bh, ki):
        return ((bh // hq) * hkv + (bh % hq) // group, ki, 0)

    grid = (b * hq, skv // block_k)
    scratch = [
        pltpu.VMEM((1, 1), jnp.float32),
        pltpu.VMEM((1, 1), jnp.float32),
        pltpu.VMEM((1, d), jnp.float32),
    ]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, ki: (bh // hq, 0)),
            pl.BlockSpec((1, 1, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(b, hq, d)
