"""FlashAttention forward kernel (pl.pallas_call + explicit BlockSpec).

TPU mapping: grid (batch*heads, q-blocks, kv-blocks) with the kv dimension
innermost — TPU grids execute sequentially over the last axis, so the
online-softmax statistics live in VMEM scratch across kv iterations and
the output tile is written once on the final kv block.  Block shapes are
MXU-aligned (multiples of 128 on the contracting dims).

Validated in interpret mode against ref.naive_attention (tests sweep
shapes/dtypes); the blockwise XLA lowering in models/attention.py is the
same schedule for the dry-run path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)              # [bq, D]
        k = k_ref[0].astype(jnp.float32)              # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    if causal:
        # blocks strictly above the diagonal contribute nothing: skip
        pl.when(ki * block_k <= qi * block_q + (block_q - 1))(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_nhd(q, k, v, *, causal: bool, block_q: int = 128,
                        block_k: int = 128, scale=None,
                        interpret: bool = False):
    """q: [N, Sq, D]; k, v: [N, Sk, D] (N = batch*heads, kv pre-repeated).

    Returns [N, Sq, D].  ``interpret=True`` runs it in the Pallas
    interpreter (tests on CPU).
    """
    N, Sq, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(N, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda n, qi, ki: (n, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda n, qi, ki: (n, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda n, qi, ki: (n, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda n, qi, ki: (n, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),      # acc
            pltpu.VMEM((bq, 1), jnp.float32),      # m
            pltpu.VMEM((bq, 1), jnp.float32),      # l
        ],
        interpret=interpret,
    )(q, k, v)
