"""Logical-axis sharding: one rule table maps model-code axis names to mesh axes.

Model code never mentions mesh axes directly; it annotates params and
activations with *logical* axes ('batch', 'tp', 'fsdp', 'experts', 'vocab',
'seq_shard', ...).  ``Rules`` maps logical -> mesh axes.  The dry-run, the
trainer and the hillclimb all reconfigure sharding by swapping rule tables,
never by touching model code (this is how SSPerf iterations change sharding).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Rule table
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Rules:
    """Mapping from logical axis names to mesh axis names (or None)."""

    table: Tuple[Tuple[str, Any], ...] = ()

    def get(self, logical: Optional[str]):
        if logical is None:
            return None
        for k, v in self.table:
            if k == logical:
                return v
        return None

    def with_(self, **kw) -> "Rules":
        tbl = dict(self.table)
        tbl.update(kw)
        return Rules(tuple(tbl.items()))

    def spec(self, axes: Sequence[Optional[str]]) -> P:
        return P(*[self.get(a) for a in axes])


def default_rules(mesh: Mesh, *, fsdp: bool = True,
                  shard_seq: bool = False) -> Rules:
    """DP(+pod) / FSDP over 'data', Megatron TP + EP over 'model'.

    ``shard_seq`` activates sequence sharding over 'data' for cells whose
    global batch is smaller than the data axis (long-context decode).
    """
    axes = mesh.axis_names
    batch: Any = tuple(a for a in ("pod", "data") if a in axes) or None
    data = "data" if "data" in axes else None
    model = "model" if "model" in axes else None
    table = {
        "batch": batch,
        "fsdp": data if fsdp else None,        # param/optimizer ZeRO-3 dim
        "tp": model,                           # Megatron column/row dim
        "experts": model,                      # expert parallelism
        "vocab": model,                        # embedding/LM-head vocab dim
        "kv_flat": model,                      # flattened kv*dh cache dim
        "seq_shard": data if shard_seq else None,  # SP for long decode
        "ring": None,                          # MVStore version-ring dim
        "heap_shard": data,                    # sharded-store shard dim
    }
    return Rules(tuple(table.items()))


def shard_device_slices(mesh: Mesh, n_shards: int):
    """One device slice per store shard (``core/shardstore.py``).

    The sharded store partitions its heap at the ADDRESS level (spans
    round-robin over shards), so its unit of placement is a whole
    shard, not a tensor axis: shard ``s``'s buffers are ``device_put``
    onto slice ``s``.  Slices round-robin over the mesh's devices in
    row-major order — with fewer shards than devices each shard owns a
    distinct device; with more, shards wrap (clock independence is
    preserved either way, placement is only locality)."""
    import numpy as _np
    devs = list(_np.asarray(mesh.devices).flat)
    return [devs[s % len(devs)] for s in range(n_shards)]


# Current (rules, mesh), set by the launcher around trace time.
_RULES: contextvars.ContextVar = contextvars.ContextVar(
    "repro_sharding_rules", default=(None, None))


@contextlib.contextmanager
def use_rules(rules: Optional[Rules], mesh: Optional[Mesh] = None):
    tok = _RULES.set((rules, mesh))
    try:
        yield
    finally:
        _RULES.reset(tok)


def current_rules() -> Optional[Rules]:
    return _RULES.get()[0]


def current_mesh() -> Optional[Mesh]:
    return _RULES.get()[1]


def shard_act(x, axes: Sequence[Optional[str]]):
    """Annotate an activation with logical axes (no-op without rules)."""
    rules, mesh = _RULES.get()
    if rules is None:
        return x
    spec = rules.spec(axes)
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Abstract parameters: single source of truth for shape + sharding + init.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamMeta:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axes, len == len(shape)
    init: str = "normal"                 # normal | zeros | ones | embed
    scale: float = 1.0
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _scaled_normal(key, *, shape, dtype, std):
    # one fused program: only the ``dtype`` result is ever allocated (run
    # eagerly, a full-width leaf held two float32 copies at its peak)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def materialize(meta_tree, key, dtype_override: Optional[str] = None):
    """Turn a tree of ParamMeta into concrete initialized arrays."""
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(
        meta_tree, is_leaf=lambda x: isinstance(x, ParamMeta))
    keys = jax.random.split(key, len(leaves))
    out = []
    for m, k in zip(leaves, keys):
        dt = jnp.dtype(dtype_override or m.dtype)
        if m.init == "zeros":
            a = jnp.zeros(m.shape, dt)
        elif m.init == "ones":
            a = jnp.ones(m.shape, dt)
        else:
            fan_in = m.shape[-2] if len(m.shape) >= 2 else m.shape[-1]
            std = m.scale / max(fan_in, 1) ** 0.5
            a = _scaled_normal(k, shape=tuple(m.shape), dtype=dt, std=std)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


def abstract_params(meta_tree, rules: Rules, mesh: Mesh,
                    dtype_override: Optional[str] = None):
    """ShapeDtypeStructs (with sharding) for a ParamMeta tree — dry-run use."""
    import jax.numpy as jnp

    def one(m: ParamMeta):
        dt = jnp.dtype(dtype_override or m.dtype)
        sh = NamedSharding(mesh, rules.spec(m.axes))
        return jax.ShapeDtypeStruct(m.shape, dt, sharding=sh)

    return jax.tree.map(one, meta_tree,
                        is_leaf=lambda x: isinstance(x, ParamMeta))


def param_specs(meta_tree, rules: Rules):
    """PartitionSpec tree matching a ParamMeta tree."""
    return jax.tree.map(lambda m: rules.spec(m.axes), meta_tree,
                        is_leaf=lambda x: isinstance(x, ParamMeta))


def stack_meta(meta_tree, n: int, axis_name: Optional[str] = None):
    """Prepend a stacking dim (layers) to every ParamMeta in a tree."""
    def one(m: ParamMeta):
        return dataclasses.replace(
            m, shape=(n,) + m.shape, axes=(axis_name,) + m.axes)
    return jax.tree.map(one, meta_tree,
                        is_leaf=lambda x: isinstance(x, ParamMeta))
