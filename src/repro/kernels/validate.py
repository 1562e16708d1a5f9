"""Bulk read-set validation — the commit-time hot path as a Pallas kernel.

The paper's update-transaction commit revalidates every read-set entry
against the lock table (Alg. 2 validateLock); the engine's scalar path
does this word-at-a-time in Python.  This kernel checks an ENTIRE
read-set's gathered lock words in one launch: the caller (the engine's
``ArrayLockTable.gather``) fancy-indexes the packed lock array once —
each element a consistent (locked, version, tid, flag) tuple — and the
kernel evaluates the per-backend validation predicate elementwise on the
VPU, tiled over the read set.

Three predicates cover every lock-version backend (``mode`` scalar):

    0 (V_LT)  own locks pass; foreign locks/flags fail; version <  rClock
              (Multiverse / DCTL, deferred clock)
    1 (V_LE)  locked-by-other fails;                    version <= rClock
              (TL2)
    2 (V_EQ)  locked-by-other fails;                    version == seen
              (TinySTM exact-snapshot)

Scalars ride in via ``PrefetchScalarGridSpec`` (SMEM), so one compiled
kernel serves every (r_clock, tid, mode) triple.  The read set rides in
lane-dense, as ``[N / 128, 128]`` int32 blocks of ``tile / 128`` rows.
On CPU the engine uses the numpy twin (``engine.validation.np_validate``);
the kernel tests pin the two together element-for-element in interpret
mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: padding element that every mode accepts: unlocked, unflagged,
#: version -1 (< and <= any clock), seen -1 (== its own version)
PAD = dict(ver=-1, own=-1, meta=0, seen=-1)
#: lane width of the [N / 128, 128] layout the kernel reads
LANES = 128


def _validate_kernel(params_ref, ver_ref, own_ref, meta_ref, seen_ref,
                     o_ref):
    # every predicate is an int32 0/1 vector: Mosaic cannot select
    # between boolean vectors, so masks are widened as soon as they exist
    def bit(cond):
        return jnp.where(cond, 1, 0)

    r_clock = params_ref[0]
    mode = params_ref[2]
    ver = ver_ref[...]
    meta = meta_ref[...]
    locked = meta & 1
    flagged = (meta >> 1) & 1
    mine = locked & bit(own_ref[...] == params_ref[1])
    free = (1 - locked) & (1 - flagged)
    unheld = (1 - locked) | mine
    ok_lt = mine | (free & bit(ver < r_clock))
    ok_le = unheld & bit(ver <= r_clock)
    ok_eq = unheld & bit(ver == seen_ref[...])
    o_ref[...] = jnp.where(mode == 0, ok_lt,
                           jnp.where(mode == 1, ok_le, ok_eq))


def validate_readset_flat(ver, own, meta, seen, r_clock, tid, mode, *,
                          tile: int = 1024, interpret: bool = False):
    """ver/own/meta/seen: [N / 128, 128] int32 (N a multiple of ``tile``,
    ``tile`` a multiple of 1024).

    Returns the [N / 128, 128] int32 validity mask (1 = entry still
    valid).  The caller reduces with ``all`` — keeping the mask exposed
    lets diagnostics name WHICH reads went stale, not just that one did.
    """
    rows, lanes = ver.shape
    assert lanes == LANES and tile % (8 * LANES) == 0, (ver.shape, tile)
    br = tile // LANES
    assert rows % br == 0, (rows, br)
    spec = pl.BlockSpec((br, LANES), lambda i, params_ref: (i, 0))
    params = jnp.asarray([r_clock, tid, mode], jnp.int32)
    return pl.pallas_call(
        _validate_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // br,),
            in_specs=[spec, spec, spec, spec],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(params, ver, own, meta, seen)
