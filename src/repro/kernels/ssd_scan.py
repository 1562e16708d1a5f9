"""Mamba-2 SSD chunk kernel (pl.pallas_call + BlockSpec).

Grid (batch, chunks) with chunks innermost: the inter-chunk SSM state
lives in VMEM scratch and persists across sequential grid steps (the same
carry idiom as the flash kernel).  Within a chunk the kernel loops over
heads (fori) so the [Q, Q] decay/score matrix for one head stays VMEM-
sized; the intra-chunk compute is MXU matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int, n_heads: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # [Q, H, P]
    dt = dt_ref[0].astype(jnp.float32)        # [Q, H]
    A = a_ref[...].astype(jnp.float32)        # [H]
    b = b_ref[0].astype(jnp.float32)          # [Q, N]
    c = c_ref[0].astype(jnp.float32)          # [Q, N]
    Q = chunk

    dA = dt * A[None, :]                      # [Q, H]
    cum = jnp.cumsum(dA, axis=0)              # [Q, H]
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

    def head_body(h, _):
        cum_h = cum[:, h]                                  # [Q]
        decay = jnp.exp(cum_h[:, None] - cum_h[None, :])   # [Q, Q]
        mmat = jnp.where(tri, cb * decay * dt[None, :, h], 0.0)
        y_intra = jax.lax.dot_general(
            mmat, x[:, h, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [Q, P]
        y_inter = jax.lax.dot_general(
            c, state_ref[h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) \
            * jnp.exp(cum_h)[:, None]                      # [Q, P]
        y_ref[0, :, h, :] = (y_intra + y_inter).astype(y_ref.dtype)
        # state update: S' = exp(cum[-1]) S + sum_j decay_j dt_j b_j x_j
        sdecay = jnp.exp(cum_h[-1] - cum_h) * dt[:, h]     # [Q]
        s_new = jax.lax.dot_general(
            b * sdecay[:, None], x[:, h, :], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [N, P]
        state_ref[h] = state_ref[h] * jnp.exp(cum_h[-1]) + s_new
        return 0

    jax.lax.fori_loop(0, n_heads, head_body, 0)


def ssd_scan_pallas(xh, dt, A, B_, C_, *, chunk: int = 256,
                    interpret: bool = False):
    """xh: [B, S, H, P]; dt: [B, S, H]; A: [H]; B_, C_: [B, S, N].

    Returns (y [B, S, H, P], final_state [B, H, N, P]).
    """
    Bsz, S, H, Pd = xh.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    kernel = functools.partial(_ssd_kernel, chunk=Q, n_heads=H)
    y = pl.pallas_call(
        kernel,
        grid=(Bsz, nc),
        in_specs=[
            pl.BlockSpec((1, Q, H, Pd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, Q, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((H,), lambda b, c: (0,)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, H, Pd), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, S, H, Pd), xh.dtype),
        scratch_shapes=[pltpu.VMEM((H, N, Pd), jnp.float32)],
        interpret=interpret,
    )(xh, dt, A, B_, C_)
    # the final state is recomputed cheaply with the jnp path when callers
    # need to carry it (prefill -> decode); kernel users in the hot loop
    # (training) do not consume it
    return y
