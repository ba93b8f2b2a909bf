"""Mesh-rules sharding configuration.

``ShardingConfig`` is the single declarative description of how one
workload is distributed over a mesh: which mesh axes carry data
parallelism, tensor (model) parallelism, FSDP parameter sharding, expert
parallelism, and how decode KV caches are laid out.  ``rules(mesh)``
compiles it into a :class:`MeshRules` table mapping the *logical* axis
names the model code uses (``"batch"``, ``"heads"``, ``"ff"``,
``"vocab"``, ``"expert"``, ``"kv_seq"``, ...) onto concrete mesh axes;
``repro.dist.api.constrain`` consults the active table at trace time, so
the same model source lowers unsharded on one device and fully
distributed on a pod.

The ``*_specs`` helpers derive :class:`~jax.sharding.PartitionSpec` trees
for parameters, optimizer state, data batches and decode caches from
shape trees.  Every placement is divisibility-checked against the actual
leaf shape and falls back to replication for that dimension when the
shard count does not divide it — a config is never invalid, only less
sharded.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Mapping

import jax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ShardingConfig", "MeshRules", "param_specs", "opt_specs",
           "batch_specs", "cache_specs"]

Axes = tuple[str, ...]


@dataclass(frozen=True)
class MeshRules:
    """Logical-axis -> mesh-axes table bound to one mesh.

    ``rules["batch"]`` etc. are tuples of mesh axis names (possibly
    empty).  The table is what ``use_rules`` installs and what
    ``constrain``/``current_rules`` read back; model code never sees the
    ShardingConfig itself.
    """

    mesh: Mesh
    rules: Mapping[str, Axes] = field(default_factory=dict)

    def axes(self, name: str | None) -> Axes:
        if name is None:
            return ()
        return tuple(self.rules.get(name, ()))

    def axes_size(self, axes: Axes) -> int:
        return _axes_size(self.mesh, axes)

    def spec_dim(self, name: str | None, extent: int):
        """PartitionSpec entry for one dimension of extent ``extent``."""
        return _dim_entry(self.mesh, self.axes(name), extent)


def _present(axes, mesh: Mesh) -> Axes:
    return tuple(a for a in axes if a in mesh.axis_names)


@dataclass(frozen=True)
class ShardingConfig:
    """Declarative distribution policy for one workload.

    data_axes / model_axes / fsdp_axes / expert_axes name mesh axes (they
    are filtered against the mesh actually in use, so one config works on
    both the 8-device host mesh and the 256-chip pod).  ``kv_shard``
    picks the decode-cache layout:

      * ``"heads"``     — KV heads over the model axes (default)
      * ``"batch_seq"`` — batch over data axes, cache sequence over model
                          axes (sequence-sharded decode path)
      * ``"seq"``       — cache sequence over the data axes, batch
                          replicated (single-sequence long-context decode)
      * ``"none"``      — batch over data axes only

    ``grad_compression`` ("none" | "int8" | "topk") switches the train
    step to error-feedback compressed gradients (see
    ``repro.dist.compression``).
    """

    data_axes: Axes = ("data",)
    model_axes: Axes = ("model",)
    fsdp_axes: Axes = ()
    expert_axes: Axes = ()
    kv_shard: str = "heads"          # "heads" | "batch_seq" | "seq" | "none"
    seq_parallel: bool = False
    microbatches: int = 1
    remat: bool = False
    remat_policy: str = "full"       # "full" | "save_dots"
    mamba_tp: bool = False
    moments_dtype: str = "float32"
    grad_compression: str = "none"   # "none" | "int8" | "topk"

    # -- derived ---------------------------------------------------------------
    def batch_axes(self, mesh: Mesh) -> Axes:
        """Mesh axes carrying the batch dimension (pod axis included)."""
        if self.kv_shard == "seq":
            return ()                 # single-sequence decode: replicate batch
        pod = ("pod",) if "pod" in mesh.axis_names else ()
        return pod + _present(self.data_axes, mesh)

    def kv_seq_axes(self, mesh: Mesh) -> Axes:
        if self.kv_shard == "seq":
            pod = ("pod",) if "pod" in mesh.axis_names else ()
            return pod + _present(self.data_axes, mesh)
        if self.kv_shard == "batch_seq":
            return _present(self.model_axes, mesh)
        return ()

    def rules(self, mesh: Mesh) -> MeshRules:
        """Compile this config into the logical-axis table for ``mesh``."""
        model = _present(self.model_axes, mesh)
        return MeshRules(mesh=mesh, rules={
            "batch": self.batch_axes(mesh),
            "seq": model if self.seq_parallel else (),
            "heads": model,
            "kv_heads": model if self.kv_shard == "heads" else (),
            "ff": model,
            "mamba_ff": model if self.mamba_tp else (),
            "vocab": model,
            "expert": _present(self.expert_axes, mesh),
            "kv_seq": self.kv_seq_axes(mesh),
        })


# -- PartitionSpec derivation ---------------------------------------------------

def _axes_size(mesh: Mesh, axes: Axes) -> int:
    return math.prod(mesh.shape[a] for a in axes) if axes else 1


def _dim_entry(mesh: Mesh, axes: Axes, extent: int):
    """PartitionSpec entry for one dimension: ``axes`` when they divide
    ``extent``, else None (the subsystem-wide replication fallback)."""
    size = _axes_size(mesh, axes)
    if not axes or size <= 1 or extent < size or extent % size:
        return None
    return axes if len(axes) > 1 else axes[0]


def _is_shape_leaf(x: Any) -> bool:
    return hasattr(x, "shape")


def _weight_spec(shape: tuple[int, ...], mesh: Mesh,
                 scfg: ShardingConfig) -> P:
    """2D weight sharding: one dim over the model axes (TP), another over
    the FSDP axes — largest divisible dims win, replicate otherwise."""
    spec: list = [None] * len(shape)
    used: set[str] = set()
    for axes in (_present(scfg.model_axes, mesh),
                 _present(scfg.fsdp_axes, mesh)):
        # a mesh axis may appear in both roles (e.g. fsdp over the model
        # axes); it can shard only one dim of any given leaf
        axes = tuple(a for a in axes if a not in used)
        size = _axes_size(mesh, axes)
        if size <= 1:
            continue
        cands = sorted(
            (i for i in range(len(shape))
             if spec[i] is None and shape[i] >= size and shape[i] % size == 0),
            key=lambda i: (-shape[i], i))
        if cands:
            spec[cands[0]] = axes if len(axes) > 1 else axes[0]
            used.update(axes)
    return P(*spec)


def param_specs(shapes: Any, mesh: Mesh, scfg: ShardingConfig) -> Any:
    """PartitionSpec tree for a parameter (or parameter-shaped) tree."""
    return jax.tree.map(lambda l: _weight_spec(tuple(l.shape), mesh, scfg),
                        shapes, is_leaf=_is_shape_leaf)


def opt_specs(opt_shapes: Any, param_shapes: Any, mesh: Mesh,
              scfg: ShardingConfig) -> Any:
    """PartitionSpec tree for AdamW state ({m, v, count}).

    Moment leaves (fp32 mirrors, or int8 {q, scale, minv} blocks whose
    last axis is block-padded) get the same 2D weight treatment as the
    parameters they shadow; divisibility fallback handles the padding.
    ``param_shapes`` is accepted for API symmetry with the callers.
    """
    del param_shapes
    return jax.tree.map(lambda l: _weight_spec(tuple(l.shape), mesh, scfg),
                        opt_shapes, is_leaf=_is_shape_leaf)


def batch_specs(shapes: Any, mesh: Mesh, scfg: ShardingConfig) -> Any:
    """PartitionSpec tree for a host data batch: leading dim over the
    batch axes (when divisible), everything else replicated."""
    batch = scfg.batch_axes(mesh)

    def leaf(l) -> P:
        shape = tuple(l.shape)
        if not shape:
            return P()
        return P(_dim_entry(mesh, batch, shape[0]),
                 *([None] * (len(shape) - 1)))

    return jax.tree.map(leaf, shapes, is_leaf=_is_shape_leaf)


def cache_specs(shapes: Any, mesh: Mesh, scfg: ShardingConfig) -> Any:
    """PartitionSpec tree for stacked decode state.

    Leaves carry a leading per-group stack axis.  Attention KV caches —
    the 5-D ``(G, S, KV, B, hd)`` leaves keyed ``"k"``/``"v"`` — are laid
    out per ``kv_shard``; every other state leaf (SSM / RWKV / conv,
    including the 5-D ``"wkv"`` state) shards batch (dim 1) only.
    """
    batch = scfg.batch_axes(mesh)
    kv_seq = scfg.kv_seq_axes(mesh)
    kv_heads = (_present(scfg.model_axes, mesh)
                if scfg.kv_shard == "heads" else ())

    def leaf(path, l) -> P:
        shape = tuple(l.shape)
        key = getattr(path[-1], "key", None) if path else None
        if len(shape) == 5 and key in ("k", "v"):
            return P(None, _dim_entry(mesh, kv_seq, shape[1]),
                     _dim_entry(mesh, kv_heads, shape[2]),
                     _dim_entry(mesh, batch, shape[3]), None)
        if len(shape) >= 2:
            return P(None, _dim_entry(mesh, batch, shape[1]),
                     *([None] * (len(shape) - 2)))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(leaf, shapes,
                                            is_leaf=_is_shape_leaf)
