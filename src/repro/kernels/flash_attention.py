"""Blocked online-softmax attention kernel (prefill offload, paper Fig. 4).

The paper offloads the Grouped Multi-Query Attention dot-products to IMAX;
on TPU the prefill-phase attention is the flash-style blocked kernel below
(BlockSpec VMEM tiles, online softmax, f32 running statistics). GQA is
handled by mapping each query head to its KV group in the index maps.

Shapes: q (B, H, Sq, D); k, v (B, Hkv, Skv, D) with H % Hkv == 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import MASK_VALUE


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            sm_scale, causal, block_q, block_k, kv_len):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    should_run = True
    if causal:
        # Skip fully-above-diagonal blocks.
        should_run = ik * block_k <= (iq + 1) * block_q - 1

    @pl.when(should_run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = ki < kv_len                                 # padding mask
        if causal:
            mask = mask & (ki <= qi)
        s = jnp.where(mask, s, MASK_VALUE)
        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, sm_scale: float | None = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bk = min(block_k, skv)
    kv_pad = (skv + bk - 1) // bk * bk
    if kv_pad != skv:
        pad = [(0, 0), (0, 0), (0, kv_pad - skv), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    grid = (b, h, sq // bq, kv_pad // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, qq, kk, g=group: (bb, hh // g, kk, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, qq, kk, g=group: (bb, hh // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
