"""Batched commit write-back — the scatter half of the snapshot gather.

``kernels/gather_read.py`` made the long-running READ an array operation
(``values[i] = heap[addrs[i]]``); this kernel is its commit-side twin.
An update transaction that buffered (or undo-logged) a large write set
publishes it to the heap in ONE launch instead of N interpreter
round-trips:

    out = heap;  out[addrs[i]] = values[i]      for i in [0, N)

Layout mirrors the gather kernel: the heap stays in HBM as
``[H / 128, 128]`` rows and is aliased to the output, so the update is
in place on the (copied or donated) buffer.  Addresses and values ride
in as SMEM blocks of ``tile``, SORTED by address (the ``ops`` wrapper
sorts them; write sets are dict-keyed, so addresses are unique).  The
kernel walks them in order, keeping one heap row in VMEM: a run of
addresses in the same row costs one row read and one row write, and a
row change writes the cached row back before fetching the next.  An
address at or past ``n_words`` is skipped, which is what the
ragged-batch padding relies on (``ops`` pads with ``n_words``).

For CPU write-back the engine uses the numpy twin (``np_write_back``
below — a single fancy-index assignment); the kernel tests pin the two
element-for-element in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def np_write_back(heap: np.ndarray, addrs: np.ndarray,
                  values: np.ndarray) -> np.ndarray:
    """Numpy twin: a copy of ``heap`` with ``out[addrs] = values``.

    Exact at any integer width (the wrapper routes int64-range payloads
    here instead of letting an x64-less jax truncate them — the
    ``version_select`` guard pattern).  Addresses must be in range and
    unique; the in-place engine path (``ArrayHeap.scatter``) shares this
    contract, and BOTH ends fail loudly — a negative address would wrap
    under numpy fancy indexing and silently overwrite a word near the
    end of the heap, so it raises like an out-of-range positive one.
    """
    a = np.asarray(addrs)
    if a.size and int(a.min(initial=0)) < 0:
        raise IndexError(int(a.min()))
    out = np.array(heap, copy=True)
    out[a] = values
    return out


def scatter_rows(heap_hbm, row, sem, n_words, addr_ref, val_ref, keep):
    """Write ``val_ref[i]`` at word ``addr_ref[i]`` of ``heap_hbm`` for
    every ``i`` of the block where ``keep(i)`` holds and the address is
    below ``n_words``.  Addresses must be ascending; ``row`` is a
    ``[1, 128]`` VMEM scratch holding the current heap row.  Shared by
    this kernel and the fused commit kernel."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def copy_in(r):
        cp = pltpu.make_async_copy(heap_hbm.at[pl.ds(r, 1)], row, sem)
        cp.start()
        cp.wait()

    def copy_out(r):
        cp = pltpu.make_async_copy(row, heap_hbm.at[pl.ds(r, 1)], sem)
        cp.start()
        cp.wait()

    def body(i, cur):
        a = addr_ref[i]
        live = jnp.logical_and(a < n_words, keep(i))
        r = a // LANES
        switch = jnp.logical_and(live, r != cur)

        @pl.when(jnp.logical_and(switch, cur >= 0))
        def _():
            copy_out(cur)

        @pl.when(switch)
        def _():
            copy_in(r)

        @pl.when(live)
        def _():
            row[...] = jnp.where(lane == a % LANES, val_ref[i], row[...])

        return jnp.where(switch, r, cur)

    cur = jax.lax.fori_loop(0, addr_ref.shape[0], body, jnp.int32(-1))

    @pl.when(cur >= 0)
    def _():
        copy_out(cur)


def _scatter_kernel(n_words, addr_ref, val_ref, heap_in, heap_hbm, row,
                    sem):
    del heap_in                      # aliased to heap_hbm
    scatter_rows(heap_hbm, row, sem, n_words, addr_ref, val_ref,
                 lambda i: True)


def scatter_write_flat(heap, addrs, values, *, n_words: int,
                       tile: int = 1024, interpret: bool = False):
    """heap: [R, 128] rows of a 32-bit dtype; addrs: [N] int32 ascending;
    values: [N] heap.dtype (N a multiple of ``tile``).  Addresses at or
    past ``n_words`` are skipped.  Returns the updated [R, 128] heap.
    """
    n = addrs.shape[0]
    assert heap.ndim == 2 and heap.shape[1] == LANES, heap.shape
    assert n % tile == 0, (n, tile)
    smem = pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        lambda *refs: _scatter_kernel(n_words, *refs),
        grid=(n // tile,),
        in_specs=[smem, smem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(heap.shape, heap.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), heap.dtype),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(addrs, values, heap)
