"""Decoder-only LM: scanned layer groups, train / prefill / decode paths.

Layers are stacked and scanned in *groups* of one interleave period (period
1 for uniform archs; 8 for jamba's 1:7 attn:mamba + alternating dense/MoE
pattern) so the traced HLO contains one period regardless of depth — this
is what keeps 88-layer lowering tractable and the compiled program compact.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.launch.sharding import ParamMeta, shard_act, stack_meta
from repro.models import blocks
from repro.models import mamba as mamba_mod
from repro.models.common import rmsnorm, rmsnorm_meta, softmax_xent

VOCAB_PAD_MULTIPLE = 256


def layer_period(cfg: ModelConfig) -> int:
    if cfg.family == "ssm":
        return 1
    p = 1
    if cfg.attn_layer_period:
        p = cfg.attn_layer_period
    if cfg.moe.num_experts:
        p = math.lcm(p, cfg.moe.every_n_layers)
    return p


def layer_kinds(cfg: ModelConfig):
    """[(mixer, ffn)] for each sub-layer of one period."""
    kinds = []
    for i in range(layer_period(cfg)):
        mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
        if cfg.is_moe_layer(i):
            ffn = "moe"
        elif cfg.d_ff:
            ffn = "dense"
        else:
            ffn = "none"
        kinds.append((mixer, ffn))
    return kinds


def n_groups(cfg: ModelConfig) -> int:
    p = layer_period(cfg)
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return cfg.n_layers // p


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def lm_meta(cfg: ModelConfig) -> dict:
    vpad = cfg.padded_vocab(VOCAB_PAD_MULTIPLE)
    group = {f"sub{j}": blocks.sublayer_meta(cfg, kind)
             for j, kind in enumerate(layer_kinds(cfg))}
    meta = {
        "embed": ParamMeta((vpad, cfg.d_model), ("fsdp", "tp"),
                           init="embed", dtype=cfg.dtype),
        "layers": stack_meta(group, n_groups(cfg)),
        "final_norm": rmsnorm_meta(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        meta["lm_head"] = ParamMeta((cfg.d_model, vpad), ("fsdp", "vocab"),
                                    dtype=cfg.dtype)
    return meta


def embed_lookup(table, tokens, pcfg: ParallelConfig):
    from repro.launch.sharding import current_mesh, current_rules

    rules, mesh = current_rules(), current_mesh()
    if pcfg.gather_mode == "onehot":
        oh = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
        h = oh @ table
    elif mesh is not None and rules is not None:
        # Explicit shard_map: GSPMD's gather partitioning mishandles a
        # 2D-sharded table (fsdp x tp).  Each device all-gathers the table
        # rows over the fsdp axis (cheap: the width stays tp-sharded) and
        # gathers locally; the backward transposes to scatter-add +
        # reduce-scatter automatically.
        fsdp_ax = rules.get("fsdp")

        def body(tbl, tok):
            if fsdp_ax is not None:
                tbl = jax.lax.all_gather(tbl, fsdp_ax, axis=0, tiled=True)
            return jnp.take(tbl, tok, axis=0)

        h = jax.shard_map(
            body, mesh=mesh,
            in_specs=(rules.spec(("fsdp", "tp")),
                      rules.spec(("batch", None))),
            out_specs=rules.spec(("batch", None, "tp")),
            check_vma=False)(table, tokens)
    else:
        h = jnp.take(table, tokens, axis=0)
    return shard_act(h, ("batch", None, None))


def lm_logits(params, h, cfg: ModelConfig):
    if cfg.tie_embeddings:
        logits = h @ params["embed"].T
    else:
        logits = h @ params["lm_head"]
    return shard_act(logits, ("batch", None, "vocab"))


# ---------------------------------------------------------------------------
# Sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def lm_forward(params, tokens, cfg: ModelConfig, pcfg: ParallelConfig, *,
               prefix_embeds=None, want_cache: bool = False):
    """tokens: [B, S_text].  Returns (hidden [B, S_total, d], cache, aux)."""
    kinds = layer_kinds(cfg)
    h = embed_lookup(params["embed"], tokens, pcfg)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
        h = shard_act(h, ("batch", None, None))
    B, S, _ = h.shape
    positions = jnp.arange(S)[None, :]

    def group_body(carry, gp):
        x, aux = carry
        caches = {}
        for j, kind in enumerate(kinds):
            x, c, a = blocks.sublayer_apply(
                gp[f"sub{j}"], x, kind, cfg, pcfg, positions=positions,
                want_cache=want_cache)
            aux = aux + a
            if want_cache:
                caches[f"sub{j}"] = c
        return (x, aux), caches if want_cache else None

    remat_on = pcfg.remat != "none" and not want_cache
    if remat_on:
        group_body = jax.checkpoint(group_body)
    k = 1
    if remat_on and pcfg.remat.startswith("group:"):
        k = int(pcfg.remat.split(":")[1])

    if pcfg.scan_layers and k > 1 and not want_cache:
        # Two-level checkpointing: scan over super-groups of k periods,
        # saving one residual per super-group instead of per period —
        # peak activation memory / k at ~(1 + 1/k) recompute cost.
        G = n_groups(cfg)
        assert G % k == 0, (G, k)
        stacked = jax.tree.map(
            lambda x: x.reshape((G // k, k) + x.shape[1:]),
            params["layers"])

        def outer_body(carry, gpk):
            for j in range(k):
                gp = jax.tree.map(lambda t: t[j], gpk)
                carry, _ = group_body(carry, gp)
            return carry, None

        (h, aux), _ = jax.lax.scan(
            jax.checkpoint(outer_body),
            (h, jnp.zeros((), jnp.float32)), stacked)
        caches = None
    elif pcfg.scan_layers:
        (h, aux), caches = jax.lax.scan(
            group_body, (h, jnp.zeros((), jnp.float32)), params["layers"])
    else:
        aux = jnp.zeros((), jnp.float32)
        cs = []
        for g in range(n_groups(cfg)):
            gp = jax.tree.map(lambda x: x[g], params["layers"])
            (h, aux), c = group_body((h, aux), gp)
            cs.append(c)
        caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *cs)
                  if want_cache else None)
    h = rmsnorm(h, params["final_norm"], cfg.rms_eps)
    return h, caches, aux


def lm_loss(params, batch, cfg: ModelConfig, pcfg: ParallelConfig):
    """batch: tokens [B, S_text], labels [B, S_text], optional
    patch_embeds/frame_embeds [B, F, d].  Returns scalar loss."""
    prefix = batch.get("patch_embeds")
    h, _, aux = lm_forward(params, batch["tokens"], cfg, pcfg,
                           prefix_embeds=prefix)
    if prefix is not None:
        h = h[:, prefix.shape[1]:]
    logits = lm_logits(params, h, cfg)
    loss = softmax_xent(logits, batch["labels"], cfg.vocab_size)
    return loss + aux


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Zeroed decode cache for the scanned stack (leaves lead with groups)."""
    kinds = layer_kinds(cfg)
    kv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    group_cache = {}
    for j, (mixer, _) in enumerate(kinds):
        if mixer == "attn":
            group_cache[f"sub{j}"] = {
                "k": jnp.zeros((batch, max_len, kv * dh), dtype),
                "v": jnp.zeros((batch, max_len, kv * dh), dtype),
            }
        else:
            st = mamba_mod.mamba_init_state(batch, cfg.d_model, cfg.mamba,
                                            dtype)
            group_cache[f"sub{j}"] = dict(st._asdict())
    g = n_groups(cfg)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (g,) + x.shape), group_cache)


def cache_logical_axes(cfg: ModelConfig):
    """Logical axes tree matching init_cache output (for dry-run specs)."""
    kinds = layer_kinds(cfg)
    group = {}
    for j, (mixer, _) in enumerate(kinds):
        if mixer == "attn":
            group[f"sub{j}"] = {
                "k": (None, "batch", "seq_shard", "kv_flat"),
                "v": (None, "batch", "seq_shard", "kv_flat"),
            }
        else:
            group[f"sub{j}"] = {
                "ssm": (None, "batch", "tp", None, None),
                "conv_x": (None, "batch", None, "tp"),
                "conv_B": (None, "batch", None, None),
                "conv_C": (None, "batch", None, None),
            }
    return group


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------


def lm_prefill(params, tokens, cfg: ModelConfig, pcfg: ParallelConfig, *,
               prefix_embeds=None):
    """Returns (last-position logits [B, V], cache, cache_len [B])."""
    h, caches, _ = lm_forward(params, tokens, cfg, pcfg,
                              prefix_embeds=prefix_embeds, want_cache=True)
    logits = lm_logits(params, h[:, -1:], cfg)[:, 0]
    B, S = h.shape[0], h.shape[1]
    return logits, caches, jnp.full((B,), S, jnp.int32)


def lm_decode_step(params, cache, cache_len, token, cfg: ModelConfig,
                   pcfg: ParallelConfig):
    """One decode step.  token: [B] int32; cache_len: [B] valid positions.

    Returns (logits [B, V], new_cache, new_cache_len).
    """
    kinds = layer_kinds(cfg)
    h = embed_lookup(params["embed"], token[:, None], pcfg)

    def apply_group(x, gp, gc):
        new_gc = {}
        for j, kind in enumerate(kinds):
            x, c, _ = blocks.sublayer_apply(
                gp[f"sub{j}"], x, kind, cfg, pcfg, positions=None,
                cache=gc[f"sub{j}"], cache_len=cache_len, moe_groups=1)
            new_gc[f"sub{j}"] = c
        return x, new_gc

    if pcfg.scan_layers:
        # The cache rides in the CARRY (not xs/ys): XLA aliases while-loop
        # carries in place, so the multi-GB KV buffers are updated without
        # a second copy (xs/ys stacking would double-buffer them).
        def group_body(carry, xs):
            x, full_cache = carry
            gp, g = xs
            gc = jax.tree.map(
                lambda buf: jax.lax.dynamic_index_in_dim(
                    buf, g, 0, keepdims=False), full_cache)
            x, new_gc = apply_group(x, gp, gc)
            full_cache = jax.tree.map(
                lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                    buf, new.astype(buf.dtype), g, 0), full_cache, new_gc)
            return (x, full_cache), None

        (h, new_cache), _ = jax.lax.scan(
            group_body, (h, cache),
            (params["layers"], jnp.arange(n_groups(cfg))))
    else:
        new_cache = cache
        for g in range(n_groups(cfg)):
            gp = jax.tree.map(lambda x: x[g], params["layers"])
            gc = jax.tree.map(lambda x: x[g], new_cache)
            h, nc = apply_group(h, gp, gc)
            new_cache = jax.tree.map(
                lambda buf, new: buf.at[g].set(new.astype(buf.dtype)),
                new_cache, nc)
    h = rmsnorm(h, params["final_norm"], cfg.rms_eps)
    logits = lm_logits(params, h, cfg)[:, 0]
    return logits, new_cache, cache_len + 1
