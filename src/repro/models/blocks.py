"""Layer assembly: (norm -> mixer -> residual) + (norm -> channel -> residual).

Mixer kinds: "attn" (GQA), "mamba" (selective SSM), "rwkv" (RWKV-6 time
mix).  The channel path is an MLP, an MoE layer (per the arch's interleave
mask), or the RWKV channel mix.  Heterogeneous stacks (Jamba) group layers
into the smallest repeating pattern; ``init_group``/``apply_group`` handle
one pattern instance and the LM scans over stacked groups.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .attention import (decode_attention, full_attention, init_attention,
                        init_kv_cache)
from .config import ArchConfig
from .layers import apply_mlp, apply_norm, init_mlp, init_norm
from .mamba import apply_mamba, decode_mamba, init_mamba, init_mamba_state
from .moe import apply_moe, init_moe
from .rwkv6 import (apply_rwkv_cmix, apply_rwkv_tmix, init_rwkv_cmix,
                    init_rwkv_state, init_rwkv_tmix)

Params = dict[str, Any]


def init_layer(key, cfg: ArchConfig, kind: str, is_moe: bool) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": init_norm(cfg), "norm2": init_norm(cfg)}
    if kind == "attn":
        p["mixer"] = init_attention(k1, cfg)
    elif kind == "mamba":
        p["mixer"] = init_mamba(k1, cfg)
    elif kind == "rwkv":
        p["mixer"] = init_rwkv_tmix(k1, cfg)
    else:
        raise ValueError(kind)
    if kind == "rwkv":
        p["channel"] = init_rwkv_cmix(k2, cfg)
    elif is_moe:
        p["channel"] = init_moe(k3, cfg)
    else:
        p["channel"] = init_mlp(k4, cfg)
    return p


def apply_layer(p: Params, x: jax.Array, cfg: ArchConfig, kind: str,
                is_moe: bool, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Training path. Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    h = apply_norm(p["norm1"], x, cfg)
    if kind == "attn":
        mixed = full_attention(p["mixer"], h, cfg, positions=positions,
                               causal=True)
    elif kind == "mamba":
        mixed = apply_mamba(p["mixer"], h, cfg)
    else:
        mixed, _ = apply_rwkv_tmix(p["mixer"], h, cfg)
    x = x + checkpoint_name(mixed, "mixer_out")
    h = apply_norm(p["norm2"], x, cfg)
    if kind == "rwkv":
        ch, _ = apply_rwkv_cmix(p["channel"], h, cfg)
    elif is_moe:
        ch, aux = apply_moe(p["channel"], h, cfg)
    else:
        ch = apply_mlp(p["channel"], h, cfg)
    return x + checkpoint_name(ch, "channel_out"), aux


def init_layer_state(cfg: ArchConfig, kind: str, batch: int,
                     max_len: int) -> Params:
    if kind == "attn":
        return init_kv_cache(cfg, batch, max_len)
    if kind == "mamba":
        return init_mamba_state(cfg, batch)
    return init_rwkv_state(cfg, batch)


def prefill_layer(p: Params, x: jax.Array, cfg: ArchConfig, kind: str,
                  is_moe: bool, positions: jax.Array
                  ) -> tuple[jax.Array, Params]:
    """Full-sequence forward that also emits the layer's decode state."""
    h = apply_norm(p["norm1"], x, cfg)
    if kind == "attn":
        mixed, kv = full_attention(p["mixer"], h, cfg, positions=positions,
                                   causal=True, return_kv=True)
        state: Params = kv
    elif kind == "mamba":
        mixed, state = apply_mamba(p["mixer"], h, cfg, return_state=True)
    else:
        mixed, state = apply_rwkv_tmix(p["mixer"], h, cfg, return_state=True)
    x = x + mixed
    h = apply_norm(p["norm2"], x, cfg)
    if kind == "rwkv":
        ch, cstate = apply_rwkv_cmix(p["channel"], h, cfg, return_state=True)
        state = {**state, **cstate}
    elif is_moe:
        ch, _ = apply_moe(p["channel"], h, cfg)
    else:
        ch = apply_mlp(p["channel"], h, cfg)
    return x + ch, state


def decode_layer(p: Params, x: jax.Array, stack: Params, g: jax.Array,
                 cfg: ArchConfig, kind: str, is_moe: bool, pos: jax.Array
                 ) -> tuple[jax.Array, Params]:
    """Single-token decode path. x: (B, 1, D).

    ``stack`` is this slot's decode state for every group, stacked on a
    leading axis; the layer's own is at index ``g``.  An attention layer
    writes its new K/V row into the stack in place.  Mamba and RWKV
    states have no slot axis and are small, so the layer's state is read
    out and written back whole."""
    h = apply_norm(p["norm1"], x, cfg)
    if kind == "attn":
        mixed, stack = decode_attention(p["mixer"], h, stack, cfg, pos=pos,
                                        layer=g)
    else:
        state = jax.tree.map(
            lambda s: jax.lax.dynamic_index_in_dim(s, g, keepdims=False),
            stack)
        if kind == "mamba":
            mixed, state = decode_mamba(p["mixer"], h, state, cfg)
        else:
            mixed, tstate = apply_rwkv_tmix(p["mixer"], h, cfg, state=state)
            state = {**state, **tstate}
    x = x + mixed
    h = apply_norm(p["norm2"], x, cfg)
    if kind == "rwkv":
        ch, cstate = apply_rwkv_cmix(p["channel"], h, cfg, state=state)
        state = {**state, **cstate}
    elif is_moe:
        ch, _ = apply_moe(p["channel"], h, cfg)
    else:
        ch = apply_mlp(p["channel"], h, cfg)
    if kind != "attn":
        stack = write_state(stack, state, g)
    return x + ch, stack


# -- groups (smallest repeating pattern; the LM scans over these) -------------

def group_slots(cfg: ArchConfig) -> list[tuple[str, str, bool]]:
    """[(slot_name, kind, is_moe)] for one group instance."""
    pattern = cfg.group_pattern
    moe_mask = cfg.moe_layer_mask()[: len(pattern)]
    return [(f"slot{i}", kind, moe_mask[i])
            for i, kind in enumerate(pattern)]


def init_group(key, cfg: ArchConfig) -> Params:
    slots = group_slots(cfg)
    keys = jax.random.split(key, len(slots))
    return {name: init_layer(k, cfg, kind, is_moe)
            for (name, kind, is_moe), k in zip(slots, keys)}


def apply_group(p: Params, x: jax.Array, cfg: ArchConfig,
                positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    aux = jnp.float32(0.0)
    for name, kind, is_moe in group_slots(cfg):
        x, a = apply_layer(p[name], x, cfg, kind, is_moe, positions)
        aux = aux + a
    return x, aux


def write_state(stack: Params, state: Params, g: jax.Array) -> Params:
    """One layer's decode ``state`` written in place into ``stack``, every
    group's, at group ``g``.  A leaf shorter than the stack's fills its
    leading part: a prompt's K/V fills the first slots of the cache."""
    return jax.tree.map(
        lambda s, n: jax.lax.dynamic_update_slice(
            s, n[None].astype(s.dtype), (g,) + (0,) * n.ndim), stack, state)


def prefill_group(p: Params, x: jax.Array, state: Params, g: jax.Array,
                  cfg: ArchConfig, positions: jax.Array
                  ) -> tuple[jax.Array, Params]:
    """Prefill group ``g``, writing its layers' decode state into
    ``state``, every group's, stacked."""
    state = dict(state)
    for name, kind, is_moe in group_slots(cfg):
        x, s = prefill_layer(p[name], x, cfg, kind, is_moe, positions)
        state[name] = write_state(state[name], s, g)
    return x, state


def init_group_state(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    return {name: init_layer_state(cfg, kind, batch, max_len)
            for name, kind, _ in group_slots(cfg)}


def decode_group(p: Params, x: jax.Array, state: Params, g: jax.Array,
                 cfg: ArchConfig, pos: jax.Array) -> tuple[jax.Array, Params]:
    """Decode group ``g``; ``state`` is every group's, stacked, and is
    returned with group ``g``'s part advanced."""
    state = dict(state)
    for name, kind, is_moe in group_slots(cfg):
        x, state[name] = decode_layer(p[name], x, state[name], g, cfg, kind,
                                      is_moe, pos)
    return x, state
