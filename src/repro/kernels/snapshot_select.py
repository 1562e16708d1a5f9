"""snapshot_select — the MVStore versioned read, as a Pallas TPU kernel.

The paper's hot read path is the version-list traversal ("newest version
with ts <= read_clock").  TPU adaptation: the ring timestamps are SCALAR-
PREFETCHED (SMEM) and the slot selection happens inside the BlockSpec
index map, so the kernel fetches ONLY the selected version's tiles from
HBM — the traversal costs zero extra HBM traffic, unlike a naive gather
that would read all R slots.  This is the Pallas analogue of following
exactly one list pointer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NO_TS = -1
LANES = 128


def _select_slot(ts_ref, clock, n_slots: int):
    """Newest slot with NO_TS < ts <= clock (0 if none: caller checks ok).

    A scalar walk over the SMEM timestamps (the index map may only load
    scalars there); ties keep the first slot, like ``argmax``."""
    best = jnp.int32(0)
    best_ts = jnp.int32(NO_TS)
    for r in range(n_slots):
        t = ts_ref[r]
        better = jnp.logical_and(jnp.logical_and(t != NO_TS, t <= clock),
                                 t > best_ts)
        best = jnp.where(better, r, best)
        best_ts = jnp.where(better, t, best_ts)
    return best


def _copy_kernel(ts_ref, clock_ref, ring_ref, o_ref):
    del ts_ref, clock_ref
    o_ref[...] = ring_ref[0]


def row_layout(n: int):
    """``(rows, cols, block_rows)``: the ``[rows, cols]`` view a ring row
    of ``n`` elements is copied through, and the rows per grid step.
    Blocks obey the TPU tiling rule: ``(block_rows, 128)`` with
    ``block_rows`` a multiple of 32 (any 8/16/32-bit dtype) or the whole
    row count.  A length that is not a multiple of 128 is one block."""
    if n % LANES:
        return 1, n, 1
    rows = n // LANES
    for br in (512, 256, 128, 64, 32):
        if rows % br == 0:
            return rows, LANES, br
    return rows, LANES, rows


def snapshot_select_flat(ring, ts, read_clock, *, interpret: bool = False):
    """ring: [R, n]; ts: [R] int32; read_clock: scalar int32.

    Returns (value [n], ok bool).  Only the selected slot's row is read.
    """
    R, n = ring.shape
    rows, cols, br = row_layout(n)

    def ring_index(i, ts_ref, clock_ref):
        return (_select_slot(ts_ref, clock_ref[0], R), i, 0)

    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // br,),
            in_specs=[pl.BlockSpec((1, br, cols), ring_index)],
            out_specs=pl.BlockSpec((br, cols),
                                   lambda i, ts_ref, clock_ref: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, cols), ring.dtype),
        interpret=interpret,
    )(ts, jnp.asarray(read_clock, jnp.int32).reshape(1),
      ring.reshape(R, rows, cols))
    ok = jnp.any(jnp.logical_and(ts != NO_TS, ts <= read_clock))
    return out.reshape(n), ok
