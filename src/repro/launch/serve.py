"""Serving driver: continuous-batching generation from MVStore snapshots.

The server is the paper's *versioned reader*: every decode step resolves
model parameters at a read clock via `mv_snapshot`, so serving can share
the store with a live trainer (serve-from-trainer) without ever reading
a torn update.  Batching is delegated to the ``repro.serve`` subsystem:
requests enter a ``RequestQueue``, the ``ContinuousBatchingScheduler``
keeps a fixed slot pool full (a freed slot is re-prefilled immediately,
the batch never drains to empty), and ``ModelSlotExecutor`` below maps
slots onto the compiled prefill/decode step functions.

Slot-level batching and per-request snapshot clocks meet in the decode
step: the hardware runs ONE parameter resolution per batched step, so
the executor resolves at the OLDEST active pinned clock — every step is
still a single consistent snapshot (never torn), and a request admitted
after a commit may simply be served a slightly staler consistent
version (bounded by the ring depth; telemetry reports the clocks each
request actually saw).  When the store is unversioned (Mode Q) and the
trainer commits mid-request, the snapshot read returns ok=False and the
affected requests restart at a fresh clock — the reader abort path,
now counted per request and surfaced through the normalized stats
schema (``Server.stats()``); sustained aborts flip the store to Mode U
through the controller heuristics.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --requests 8 --gen 16
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, MVStoreConfig, ParallelConfig,
                           get_config, smoke_config)
from repro.core import mvstore
from repro.core.stats_schema import normalize_stats
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import default_rules, use_rules
from repro.models import model_zoo as zoo
from repro.runtime.compile_cache import use_compile_cache
from repro.serve.metrics import ServeMetrics
from repro.serve.queue import Outcome, Request, RequestQueue
from repro.serve.scheduler import ContinuousBatchingScheduler, StepResult


class _ReaderMetrics(ServeMetrics):
    """ServeMetrics that also announces to a controller ReaderHandle, so
    serving aborts feed the K1/K2/K3 go-versioned heuristics."""

    def __init__(self, reader, **kw):
        super().__init__(**kw)
        self._reader = reader

    def on_snapshot_abort(self, n: int = 1) -> None:
        super().on_snapshot_abort(n)
        self._reader.on_abort(n)

    def on_prefill_retry(self, n: int = 1) -> None:
        super().on_prefill_retry(n)
        self._reader.on_abort(n)

    def on_complete(self, req, now=None, store_clock=None) -> None:
        super().on_complete(req, now=now, store_clock=store_clock)
        self._reader.on_commit(req.max_new, req.pinned_clock)


class ModelSlotExecutor:
    """SlotExecutor over the compiled prefill/decode step functions.

    Owns the batched decode cache ([group, n_slots, ...] leaves), a
    B=1 prefill jit and an insert jit that drops a freshly prefilled
    row into a freed slot (padding the k/v seq axis out to ``max_len``)
    — the continuous-batching primitive: one slot changes occupant,
    the other slots' decode stream never pauses.
    """

    def __init__(self, cfg, pcfg, mvcfg, rules, mesh, state_fn, *,
                 n_slots: int, max_len: int, reader=None):
        self.cfg = cfg
        self.mvcfg = mvcfg
        self.state_fn = state_fn
        self.n_slots = n_slots
        self.max_len = max_len
        self.reader = reader
        self._prefill1 = jax.jit(steps_mod.make_prefill_step(
            cfg, pcfg, mvcfg, rules, mesh))
        self._decode = jax.jit(steps_mod.make_decode_step(
            cfg, pcfg, mvcfg, rules, mesh), donate_argnums=(1,))
        self._insert = jax.jit(self._insert_fn, donate_argnums=(0,))
        self.cache = None
        self.cache_len = jnp.zeros((n_slots,), jnp.int32)
        self.tokens = jnp.zeros((n_slots,), jnp.int32)

    def current_clock(self) -> int:
        return int(self.state_fn().clock)

    @staticmethod
    def _insert_fn(full, one, slot):
        """Write a B=1 cache into batch row ``slot`` of the full cache.

        Any axis the prefill left short of the full leaf's (the k/v seq
        axis at prompt_len vs max_len) is zero-padded at the end; decode
        masks by cache_len, so the padding is never attended.
        """
        def upd(f, o):
            o = o[:, 0]                            # drop the B=1 axis
            target = f.shape[:1] + f.shape[2:]
            if o.shape != target:
                o = jnp.pad(o, [(0, t - s)
                                for t, s in zip(target, o.shape)])
            return jax.lax.dynamic_update_index_in_dim(
                f, o.astype(f.dtype), slot, 1)
        return jax.tree.map(upd, full, one)

    def _ensure_cache(self, one) -> None:
        if self.cache is None:
            blank = zoo.init_cache(self.cfg, self.n_slots, self.max_len,
                                   jnp.float32)
            self.cache = jax.tree.map(
                lambda z, o: jnp.zeros(z.shape, o.dtype), blank, one)

    @staticmethod
    def _is_reclaimed(err: RuntimeError) -> bool:
        # A live trainer donates its state buffers into the next step;
        # a reader still holding the old reference sees them deleted.
        # That is the TM "memory reclaimed under the reader" race — the
        # read aborts and re-pins at the fresh state (whose ring still
        # holds the pinned version if it is within the ring depth).
        return "deleted" in str(err)

    # -- SlotExecutor ----------------------------------------------------
    def prefill(self, slot: int, req: Request, clock: int) -> StepResult:
        state = self.state_fn()
        if self.reader is not None:
            self.reader.begin(int(clock))
        try:
            logits, cache1, len1, ok = self._prefill1(
                state, {"tokens": jnp.asarray(req.payload)[None]}, clock)
        except RuntimeError as err:
            if not self._is_reclaimed(err):
                raise
            return StepResult(False, clock)
        if not bool(ok):
            return StepResult(False, clock)
        self._ensure_cache(cache1)
        self.cache = self._insert(self.cache, cache1, slot)
        self.cache_len = self.cache_len.at[slot].set(len1[0])
        tok = int(jnp.argmax(logits[0]))
        self.tokens = self.tokens.at[slot].set(tok)
        return StepResult(True, int(clock), token=tok)

    def decode(self, slots: Sequence[int], clocks: Sequence[int]
               ) -> List[StepResult]:
        # one parameter resolution per batched step, at the oldest
        # active pin (see module docstring for the staleness contract)
        rc = min(clocks)
        state = self.state_fn()
        try:
            logits, self.cache, self.cache_len, ok = self._decode(
                state, self.cache, self.cache_len, self.tokens, rc)
        except RuntimeError as err:
            if not self._is_reclaimed(err):
                raise
            # the donated cache may be gone too; rebuild on re-prefill
            self.cache = None
            self.cache_len = jnp.zeros((self.n_slots,), jnp.int32)
            self.tokens = jnp.zeros((self.n_slots,), jnp.int32)
            return [StepResult(False, rc) for _ in slots]
        self.tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        okb = bool(ok)
        toks = np.asarray(self.tokens)
        return [StepResult(okb, rc, token=int(toks[i])) for i in slots]


class Server:
    """Continuous-batching server over ``n_slots`` decode slots.

    ``serve_batch`` keeps its original synchronous contract (submit B
    prompts, return [B, max_new] tokens) but now rides the scheduler:
    requests beyond the slot count queue up and fill freed slots as
    earlier requests finish.  ``submit``/``pump`` expose the
    asynchronous surface (examples/serve_snapshots.py drives it
    against a live trainer); ``stats()`` reports the normalized TM
    stats schema, with Mode-Q snapshot-read retries counted as aborts.
    """

    def __init__(self, cfg, *, batch: int, prompt_len: int, max_len: int,
                 mvcfg=None, mesh=None, controller=None, seed: int = 0,
                 params=None, mv_state=None):
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.mesh = mesh if mesh is not None else make_host_mesh()
        self.pcfg = ParallelConfig(
            remat="none", attn_block_q=min(512, prompt_len),
            attn_block_k=min(512, prompt_len))
        self.mvcfg = mvcfg or MVStoreConfig(mode="Q")
        self.rules = default_rules(self.mesh)
        if batch % self.mesh.devices.size != 0:
            self.rules = self.rules.with_(batch=None)
        self.controller = controller
        self.reader = controller.reader() if controller else None
        if mv_state is None:
            with use_rules(self.rules, self.mesh):
                params = params if params is not None else zoo.init_params(
                    cfg, jax.random.PRNGKey(seed))
            versioned = "all" if self.mvcfg.mode in ("U",) else "none"
            mv_state = mvstore.mv_init(params, self.mvcfg,
                                       versioned=versioned)
        self.mv_state = mv_state
        self.metrics = (_ReaderMetrics(self.reader, seed=seed)
                        if self.reader is not None
                        else ServeMetrics(seed=seed))
        self.queue = RequestQueue(max_depth=max(64, 4 * batch),
                                  n_servers=batch)
        self.executor = ModelSlotExecutor(
            cfg, self.pcfg, self.mvcfg, self.rules, self.mesh,
            lambda: self.mv_state, n_slots=batch, max_len=max_len,
            reader=self.reader)
        # retry-forever like the original per-batch loop; every retry is
        # still counted and surfaced through stats()
        self.scheduler = ContinuousBatchingScheduler(
            self.queue, self.executor, self.metrics,
            max_request_aborts=1 << 30)
        self._rid = 0

    @property
    def aborts(self) -> int:
        """Snapshot-read retries (prefill + in-flight decode aborts)."""
        return self.metrics.snapshot_aborts + self.metrics.prefill_retries

    # -- async surface ---------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int) -> Request:
        self._rid += 1
        req = Request(rid=self._rid, payload=np.asarray(prompt),
                      max_new=max_new)
        adm = self.queue.offer(req)
        if adm.value != "admitted":
            raise RuntimeError(f"request {req.rid} not admitted: {adm}")
        return req

    def pump(self) -> bool:
        """One scheduler iteration; returns False when idle."""
        return self.scheduler.step()

    # -- sync surface ----------------------------------------------------
    def serve_batch(self, prompts: np.ndarray, max_new: int
                    ) -> np.ndarray:
        """prompts: [B, S] int32 -> generated [B, max_new] int32."""
        reqs = [self.submit(p, max_new) for p in prompts]
        while any(r.outcome is Outcome.PENDING for r in reqs):
            if not self.pump():
                time.sleep(1e-5)
        return np.stack(
            [np.asarray(r.tokens[:max_new], np.int32) for r in reqs])

    def stats(self) -> Dict[str, object]:
        """Serving counters in the normalized TM stats schema."""
        return normalize_stats(
            {"commits": self.metrics.completed,
             "aborts": self.aborts,
             "ro_commits": self.metrics.completed},
            backend="mvserve", mode=self.mvcfg.mode)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend != "none" or cfg.is_encdec:
        print(f"note: {args.arch} needs frontend embeds; serving the "
              "text path only")
    server = Server(cfg, batch=args.batch, prompt_len=args.prompt_len,
                    max_len=args.prompt_len + args.gen)
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(args.requests, args.prompt_len),
        dtype=np.int32)
    t0 = time.time()
    out = server.serve_batch(prompts, args.gen)
    dt = time.time() - t0
    m = server.metrics
    print(f"done: {args.requests} requests x {args.gen} tokens in "
          f"{dt:.1f}s ({args.requests * args.gen / dt:.1f} tok/s) "
          f"occupancy={m.occupancy:.2f} "
          f"p50={m.latency.percentile(50) * 1e3:.0f}ms "
          f"p99={m.latency.percentile(99) * 1e3:.0f}ms "
          f"(out shape {out.shape})")
    print(f"stats: {server.stats()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
