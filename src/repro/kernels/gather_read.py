"""Batched snapshot read — the long-running-read hot path as a Pallas kernel.

The paper's headline workload is a transaction that reads THOUSANDS of
words (a range query / audit / scan) while updaters commit around it.
Word-at-a-time that read is bottlenecked by the interpreter, not the TM;
this kernel gathers an entire address batch from the heap in ONE launch:

    values[i] = heap[addrs[i]]          for i in [0, N)

so a `Txn.read_bulk` costs one heap gather + one lock-word gather + one
vectorized validation pass instead of N Python round-trips.

The same kernel serves both layers:

  * word level — ``heap`` is the live ``ArrayHeap`` buffer (int32 words
    on the device; wider heaps take the numpy twin);
  * store level — ``heap`` is the ring row ``snapshot_select`` (or the
    host-side slot scan) picked for the reader's clock, so a versioned
    bulk read is slot-select + this gather.

Layout: the heap stays in HBM as ``[H / 128, 128]`` rows
(``memory_space=pl.ANY``), so its size is bounded by HBM, not by the
kernel's VMEM.  Each grid step takes ``tile`` addresses as an SMEM block
and writes the ``[tile / 128, 128]`` lane-dense block of the ``[N]``
result.  A step takes one of two paths:

  * block path — the step's addresses are one run, ``addr[i] ==
    addr[0] + i``: ONE DMA copies the heap rows the run spans (``tile /
    128`` rows from a row boundary, one more otherwise, never a row past
    the heap's end), then whole-block lane rolls by ``addr[0] % 128``
    and a select between rows ``r`` and ``r + 1`` build the output.  A
    scan or an audit of a contiguous range is all block steps but for
    its padded tail;
  * row path — any other step (scattered, strided, descending or
    repeated addresses, padding): the 128-word row holding each address
    is copied into a ``[tile, 128]`` VMEM scratch (at most ``WINDOW``
    copies in flight), then each row is rotated so the wanted word lands
    in its output lane.

The path is chosen on the device: ``consecutive_tiles`` flags each step
whose addresses equal ``addr[0] + iota``, in the same jitted program,
and the kernel reads the flags in SMEM blocks of ``FLAGS`` steps beside
each step's addresses, so its SMEM use does not grow with the batch.
``ops.snapshot_read`` counts the steps by path from the same flags.

For CPU reads the engine uses the numpy twin (a single fancy-index in
``engine.bulkread.heap_gather``); the kernel tests pin the two together
element-for-element in interpret mode.

Out-of-range addresses are the caller's bug (the engine bounds-checks
against the allocation frontier before launching); padding uses address 0,
which every heap has (structures burn it as NULL).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: padding address: always allocated (address 0), gathered then discarded
PAD_ADDR = 0
#: lane width of a heap row (the TPU vreg's minor dimension)
LANES = 128
#: row copies kept in flight per grid step on the row path
WINDOW = 64
#: steps whose path flags share one SMEM block (XLA's rank-1 int32 tile)
FLAGS = 1024


def _row_copy(heap_hbm, rows, sem, src_row, dst_row):
    return pltpu.make_async_copy(heap_hbm.at[pl.ds(src_row, 1)],
                                 rows.at[pl.ds(dst_row, 1)], sem)


def _block_copy(heap_hbm, rows, sem, src_row, n_rows):
    return pltpu.make_async_copy(heap_hbm.at[pl.ds(src_row, n_rows)],
                                 rows.at[pl.ds(0, n_rows)], sem)


def _gather_rows(addr_ref, heap_hbm, o_ref, rows, sem):
    """Row path: one row DMA per address, then one roll and select per
    address into its output lane."""
    tile = addr_ref.shape[0]

    def fetch(i, c):
        _row_copy(heap_hbm, rows, sem, addr_ref[i] // LANES, i).start()

        @pl.when(i >= WINDOW)
        def _():
            _row_copy(heap_hbm, rows, sem, 0, 0).wait()
        return c

    jax.lax.fori_loop(0, tile, fetch, 0)

    def drain(i, c):
        _row_copy(heap_hbm, rows, sem, 0, 0).wait()
        return c

    jax.lax.fori_loop(0, min(tile, WINDOW), drain, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def place(i, c):
        col = i % LANES
        shift = (col - addr_ref[i] % LANES) % LANES
        word = pltpu.roll(rows[pl.ds(i, 1), :], shift, 1)
        q = i // LANES
        o_ref[pl.ds(q, 1), :] = jnp.where(lane == col, word,
                                          o_ref[pl.ds(q, 1), :])
        return c

    jax.lax.fori_loop(0, tile, place, 0)


def _gather_block(addr_ref, heap_hbm, o_ref, rows, sem):
    """Block path for ``addr[i] == addr[0] + i``: one DMA of the heap
    rows the run spans, then whole-block lane shifts.  Output row ``q``
    takes lanes ``j < 128 - s`` from heap row ``q`` and the rest from
    row ``q + 1``, each rolled left by ``s = addr[0] % 128``."""
    k = o_ref.shape[0]
    first = addr_ref[0]
    s = first % LANES
    # an aligned run spans exactly k rows: copying k + 1 could read past
    # the heap's last row.  A heap of fewer rows holds no such run; the
    # sizes are clipped to it only so that the copies can be built.
    heap_rows = heap_hbm.shape[0]

    @pl.when(s == 0)
    def _():
        cp = _block_copy(heap_hbm, rows, sem, first // LANES,
                         min(k, heap_rows))
        cp.start()
        cp.wait()

    @pl.when(s != 0)
    def _():
        cp = _block_copy(heap_hbm, rows, sem, first // LANES,
                         min(k + 1, heap_rows))
        cp.start()
        cp.wait()

    shift = (LANES - s) % LANES
    lo = pltpu.roll(rows[pl.ds(0, k), :], shift, 1)
    hi = pltpu.roll(rows[pl.ds(1, k), :], shift, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (k, LANES), 1)
    o_ref[...] = jnp.where(lane < LANES - s, lo, hi)


def _gather_kernel(run_ref, addr_ref, heap_hbm, o_ref, rows, sem):
    is_run = run_ref[pl.program_id(0) % FLAGS] != 0

    @pl.when(is_run)
    def _():
        _gather_block(addr_ref, heap_hbm, o_ref, rows, sem)

    @pl.when(jnp.logical_not(is_run))
    def _():
        _gather_rows(addr_ref, heap_hbm, o_ref, rows, sem)


def consecutive_tiles(addrs, tile: int):
    """int32 flag per ``tile``-address step: 1 where the step's
    addresses run ``addr[0], addr[0] + 1, ...``."""
    a = addrs.reshape(-1, tile)
    step = jnp.arange(tile, dtype=a.dtype)
    return jnp.all(a == a[:, :1] + step, axis=1).astype(jnp.int32)


def gather_read_flat(heap, addrs, *, tile: int = 1024,
                     interpret: bool = False):
    """heap: [R, 128] rows of a 32-bit dtype; addrs: [N] int32 flat word
    addresses (N a multiple of ``tile``, ``tile`` a multiple of 1024).

    Returns the gathered values as ``[N / 128, 128]`` (``heap.dtype``).
    """
    n = addrs.shape[0]
    assert heap.ndim == 2 and heap.shape[1] == LANES, heap.shape
    assert n % tile == 0 and tile % (8 * LANES) == 0, (n, tile)
    steps = n // tile
    flags = jnp.pad(consecutive_tiles(addrs, tile), (0, -steps % FLAGS))
    return pl.pallas_call(
        _gather_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((FLAGS,), lambda i: (i // FLAGS,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tile,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile // LANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), heap.dtype),
        scratch_shapes=[pltpu.VMEM((tile, LANES), heap.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(flags, addrs, heap)
