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
kernel's VMEM.  Each grid step takes ``tile`` addresses as an SMEM block,
DMAs the 128-word row holding each address into a ``[tile, 128]`` VMEM
scratch (at most ``WINDOW`` copies in flight), then rotates each row so
the wanted word lands in its output lane.  The output is the
``[N / 128, 128]`` lane-dense view of the ``[N]`` result.

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
#: row copies kept in flight per grid step
WINDOW = 64


def _row_copy(heap_hbm, rows, sem, src_row, dst_row):
    return pltpu.make_async_copy(heap_hbm.at[pl.ds(src_row, 1)],
                                 rows.at[pl.ds(dst_row, 1)], sem)


def _gather_kernel(addr_ref, heap_hbm, o_ref, rows, sem):
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


def gather_read_flat(heap, addrs, *, tile: int = 1024,
                     interpret: bool = False):
    """heap: [R, 128] rows of a 32-bit dtype; addrs: [N] int32 flat word
    addresses (N a multiple of ``tile``, ``tile`` a multiple of 1024).

    Returns the gathered values as ``[N / 128, 128]`` (``heap.dtype``).
    """
    n = addrs.shape[0]
    assert heap.ndim == 2 and heap.shape[1] == LANES, heap.shape
    assert n % tile == 0 and tile % (8 * LANES) == 0, (n, tile)
    return pl.pallas_call(
        _gather_kernel,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile // LANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), heap.dtype),
        scratch_shapes=[pltpu.VMEM((tile, LANES), heap.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(addrs, heap)
