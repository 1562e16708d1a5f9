"""End-to-end training driver.

Wires together: config registry -> mesh/rules -> MVStore(+controller) ->
step variants (the compiled-step-as-transaction scheme) -> data pipeline
-> fault-tolerant supervisor with snapshot-consistent checkpoints.

Runs on whatever devices exist (CPU smoke scale included):

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --steps 40 --ckpt-dir /tmp/ckpt

The MVStore mode cycle is live: snapshot readers (the checkpointer, eval)
announce aborts; the controller flips Q->QtoU->U when they starve and back
when they drain, swapping compiled step variants at step boundaries.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, SMOKE_SHAPE, MVStoreConfig,
                           ParallelConfig, ShapeConfig, get_config,
                           smoke_config)
from repro.core import mvcontroller, mvstore
from repro.data.pipeline import make_batch_iterator
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import default_rules, use_rules
from repro.models import model_zoo as zoo
from repro.optim import adamw
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.fault_tolerance import FaultPlan, TrainSupervisor


class Trainer:
    """Owns the MVStore state and the compiled step variants."""

    def __init__(self, cfg, shape, *, pcfg=None, mvcfg=None, opt_cfg=None,
                 mesh=None, seed: int = 0, controller=None):
        self.cfg = cfg
        self.shape = shape
        self.mesh = mesh if mesh is not None else make_host_mesh()
        self.pcfg = pcfg or ParallelConfig(
            attn_block_q=min(1024, shape.seq_len),
            attn_block_k=min(1024, shape.seq_len))
        self.mvcfg = mvcfg or MVStoreConfig()
        self.opt_cfg = opt_cfg or adamw.AdamWConfig(warmup_steps=10)
        self.rules = default_rules(self.mesh)
        if shape.global_batch % self.mesh.devices.size != 0:
            self.rules = self.rules.with_(batch=None)
        self.controller = controller or mvcontroller.MVController(
            mvcfg=self.mvcfg, start_bg=True)
        with use_rules(self.rules, self.mesh):
            params = zoo.init_params(cfg, jax.random.PRNGKey(seed))
        versioned = "all" if self.mvcfg.mode in ("U", "QtoU", "UtoQ") \
            else "none"
        mv = mvstore.mv_init(params, self.mvcfg, versioned=versioned)
        opt = adamw.init(params, self.opt_cfg)
        self.state = steps_mod.TrainState(mv=mv, opt=opt)
        self._variants: Dict[tuple, callable] = {}
        self.step_times = []

    # -- compiled-step-variant cache (local mode fixed at trace time) ----
    def _variant(self, local_mode: str, versioned_key: frozenset):
        key = (local_mode, versioned_key)
        if key not in self._variants:
            mvcfg = self.mvcfg.replace(mode=local_mode)
            fn = steps_mod.make_train_step(self.cfg, self.pcfg, mvcfg,
                                           self.opt_cfg, self.rules,
                                           self.mesh)
            self._variants[key] = jax.jit(fn, donate_argnums=(0,))
        return self._variants[key]

    def train_step(self, state, batch):
        state = state._replace(mv=self.controller.trainer_tick(state.mv))
        local_mode = self.controller.current_local_mode()
        fn = self._variant(local_mode,
                           frozenset(state.mv.ring))
        batch = jax.tree.map(jnp.asarray, batch)
        t0 = time.time()
        state, metrics = fn(state, batch)
        self.step_times.append(time.time() - t0)
        return state, metrics

    def batch_at(self, step: int):
        it = make_batch_iterator(self.cfg, self.shape, start_step=step)
        return next(it)

    def snapshot_reader(self):
        return self.controller.reader()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mv-mode", default="Q", choices=["Q", "U"])
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeConfig("train_cli", args.seq, args.batch, "train")
    trainer = Trainer(cfg, shape,
                      mvcfg=MVStoreConfig(mode=args.mv_mode))
    sup = TrainSupervisor(ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every,
                          reader=trainer.snapshot_reader())
    fault = FaultPlan(fail_at_steps=(args.inject_failure_at,)) \
        if args.inject_failure_at >= 0 else None

    losses = []

    def on_step(step, state, metrics):
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"mode {trainer.controller.current_local_mode()} "
                  f"rings {len(state.mv.ring)}", flush=True)

    step, state = sup.run(state=trainer.state,
                          train_step=trainer.train_step,
                          batch_at=trainer.batch_at,
                          n_steps=args.steps, fault_plan=fault,
                          on_step=on_step)
    trainer.controller.stop()
    sup.manager.close()
    print(f"done: {step} steps, restarts={sup.restarts}, "
          f"first loss {losses[0]:.4f} last {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
