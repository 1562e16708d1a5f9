"""fused_adamw — AdamW update + in-place write + MVStore ring append.

The measured Mode-U overhead is one extra full-parameter HBM write (the
copy-on-write version).  The paper fuses the version-list update into the
encounter-time write path (Alg. 3: in-place write + tryWriteToVersionList
under one lock hold); the TPU analogue fuses the optimizer's parameter
write and the ring-slot write into ONE kernel pass so the parameter tile
is read once and written twice while resident in VMEM — instead of a
second read-modify-write round trip.

The ring output aliases the ring input (input_output_aliasing): only the
selected slot row is touched, the other R-1 slots are never transferred.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(slot_ref, scal_ref, p_ref, g_ref, m_ref, v_ref, ring_ref,
                  p_out, m_out, v_out, ring_out, *, b1, b2, eps, wd,
                  has_ring):
    del slot_ref
    lr = scal_ref[0]
    scale = scal_ref[1]
    b1c = scal_ref[2]
    b2c = scal_ref[3]
    g = g_ref[...].astype(jnp.float32) * scale
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mhat = m / b1c
    vhat = v / b2c
    p32 = p_ref[...].astype(jnp.float32)
    step = mhat / (jnp.sqrt(vhat) + eps) + wd * p32
    newp = p32 - lr * step
    p_out[...] = newp.astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v
    if has_ring:
        ring_out[0] = newp.astype(ring_out.dtype)   # versioned commit


def fused_adamw_flat(p, g, m, v, ring, slot, *, lr, scale, b1c, b2c,
                     b1, b2, eps, wd, tile: int = 2048,
                     interpret: bool = False):
    """p: [n] params; g: [n] f32 grads; m, v: [n] f32 moments;
    ring: [R, n] or None; slot: int32 ring row to write.

    Returns (p', m', v', ring') with ring' aliasing ring.
    """
    n = p.shape[0]
    t = min(tile, n)
    assert n % t == 0, (n, t)
    has_ring = ring is not None
    scalars = jnp.stack([lr.astype(jnp.float32),
                         scale.astype(jnp.float32),
                         b1c.astype(jnp.float32),
                         b2c.astype(jnp.float32)])
    if not has_ring:
        ring = jnp.zeros((1, n), p.dtype)
        slot = jnp.zeros((), jnp.int32)

    kernel = functools.partial(_fused_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                               has_ring=has_ring)
    grid = (n // t,)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # slot, scalars
            grid=grid,
            in_specs=[
                pl.BlockSpec((t,), lambda i, s, _: (i,)),   # p
                pl.BlockSpec((t,), lambda i, s, _: (i,)),   # g
                pl.BlockSpec((t,), lambda i, s, _: (i,)),   # m
                pl.BlockSpec((t,), lambda i, s, _: (i,)),   # v
                pl.BlockSpec((1, t), lambda i, s, _: (s[0], i)),  # ring
            ],
            out_specs=[
                pl.BlockSpec((t,), lambda i, s, _: (i,)),
                pl.BlockSpec((t,), lambda i, s, _: (i,)),
                pl.BlockSpec((t,), lambda i, s, _: (i,)),
                pl.BlockSpec((1, t), lambda i, s, _: (s[0], i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n,), p.dtype),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct(ring.shape, ring.dtype),
        ],
        input_output_aliases={6: 3},        # ring in -> ring out
        interpret=interpret,
    )(slot.reshape(1), scalars, p, g, m, v, ring)
    p2, m2, v2, ring2 = outs
    return p2, m2, v2, (ring2 if has_ring else None)
