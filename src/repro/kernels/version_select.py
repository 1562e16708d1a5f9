"""Snapshot version select — the word-level versioned read as a kernel.

Multiverse resolves a versioned read by walking an address's version
list for the newest committed timestamp strictly below the reader's
snapshot clock (paper Alg. 2 traverse).  The packed VLT mirror
(``core/vlt.py``) keeps each lock bucket's newest ``D`` committed
``(timestamp, data)`` pairs in two int arrays, newest-first, so the walk
becomes an elementwise selection this kernel evaluates for an ENTIRE
batch of recently-written addresses in one launch:

    valid[n, j] = ts[n, j] < r_clock            (strict: the deferred
                                                 clock shares timestamps
                                                 across commits)
    value[n]    = data[n, first j with valid]   (rows are newest-first)
    ok[n]       = any(valid[n, :])

Timestamps arrive REBASED to the reader's clock (the ``ops`` wrapper
subtracts ``r_clock`` in int64 and clips to int32 — same treatment as
``kernels/validate.py``), so the predicate inside is ``ts < 0`` with the
clock scalar pinned to 0; empty slots carry the positive-saturated
sentinel and fail it naturally.  The batch rides in slot-major and
lane-dense (``[D, N / 128, 128]``), so each slot is one vector and the
select is a chain of int32 ``where``s.  On CPU the engine uses the numpy
twin (``core.vlt.np_version_select``); the kernel tests pin the two
element-for-element in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rebased-timestamp padding for ragged batches: positive-saturated, so
#: the ``ts < clock`` predicate rejects it for every clock value
PAD_TS = (1 << 31) - 1
#: lane width of the slot-major [D, N / 128, 128] layout
LANES = 128


def _select_kernel(params_ref, ts_ref, data_ref, val_ref, ok_ref):
    # walk the depth oldest-first so the newest qualifying slot is the
    # last one written: plain int32 selects, no argmax/any reductions
    clock = params_ref[0]
    val = jnp.zeros(val_ref.shape, val_ref.dtype)
    ok = jnp.zeros(ok_ref.shape, jnp.int32)
    for j in reversed(range(ts_ref.shape[0])):
        valid = ts_ref[j] < clock
        val = jnp.where(valid, data_ref[j], val)
        ok = jnp.where(valid, 1, ok)
    val_ref[...] = val
    ok_ref[...] = ok


def version_select_flat(ts, data, clock, *, tile: int = 1024,
                        interpret: bool = False):
    """ts: [D, N / 128, 128] int32 (rebased); data: [D, N / 128, 128]
    (32-bit); clock: int32 scalar.  Slot ``j`` of row ``n`` sits at
    ``[j, n // 128, n % 128]``, slot 0 newest.

    Returns ``(values, ok)``, both ``[N / 128, 128]``: per row, the
    newest ``data`` whose ``ts`` is strictly below ``clock``, and
    whether any slot qualified (``values`` is only meaningful where
    ``ok``).  Rows are tiled over the grid; ``D`` rides whole.
    """
    depth, rows, lanes = ts.shape
    assert lanes == LANES and tile % (8 * LANES) == 0, (ts.shape, tile)
    br = tile // LANES
    assert rows % br == 0, (rows, br)
    slots = pl.BlockSpec((depth, br, LANES), lambda i, params_ref: (0, i, 0))
    flat = pl.BlockSpec((br, LANES), lambda i, params_ref: (i, 0))
    params = jnp.asarray([clock], jnp.int32)
    return pl.pallas_call(
        _select_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // br,),
            in_specs=[slots, slots],
            out_specs=[flat, flat],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), data.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.int32)],
        interpret=interpret,
    )(params, ts, data)
