"""Decoder-only language model: embed -> scan(layer groups) -> norm -> loss.

* scan-over-layers with stacked group parameters keeps the HLO one group
  body + a loop regardless of depth (96-layer models compile in seconds);
* optional ``jax.checkpoint`` (remat) around the scanned group body;
* the loss is a chunked, vocab-parallel softmax cross-entropy that never
  materialises the full (B, T, V) logits tensor;
* the VLM frontend ("stub_patches") prepends precomputed patch embeddings
  (the assignment specifies modality frontends as stubs) and masks them
  out of the loss.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..dist.api import constrain
from .blocks import (apply_group, decode_group, init_group, init_group_state,
                     prefill_group)
from .config import ArchConfig
from .layers import apply_norm, embed_tokens, init_embed, init_norm

Params = dict[str, Any]


def _remat_policy(remat: bool | str):
    if remat == "save_dots":
        return jax.checkpoint_policies.save_only_these_names(
            "mixer_out", "channel_out", "mlp_hidden", "qkv_out")
    return jax.checkpoint_policies.nothing_saveable


def chunked_xent(h: jax.Array, head_w: jax.Array, targets: jax.Array,
                 mask: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Mean cross-entropy over masked positions, chunked along T.

    h: (B, T, D); head_w: (D, V); targets/mask: (B, T).
    """
    b, t, d = h.shape
    c = min(cfg.logit_chunk, t)
    while t % c:
        c -= 1
    n_chunks = t // c
    dtc = jnp.dtype(cfg.compute_dtype)

    def chunk(carry, idx):
        loss_sum, count = carry
        hs = jax.lax.dynamic_slice_in_dim(h, idx * c, c, axis=1)
        ts = jax.lax.dynamic_slice_in_dim(targets, idx * c, c, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask, idx * c, c, axis=1)
        logits = (hs.astype(dtc) @ head_w.astype(dtc)).astype(jnp.float32)
        logits = constrain(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
        loss_sum = loss_sum + jnp.sum((lse - ll) * ms)
        count = count + ms.sum()
        return (loss_sum, count), None

    (loss_sum, count), _ = jax.lax.scan(
        chunk, (jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(n_chunks))
    return loss_sum / jnp.maximum(count, 1.0)


@dataclass(frozen=True)
class LM:
    cfg: ArchConfig

    # -- init -----------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_layers, k_norm = jax.random.split(key, 3)
        group_keys = jax.random.split(k_layers, cfg.n_groups)
        layers = jax.vmap(lambda k: init_group(k, cfg))(group_keys)
        return {
            "embed": init_embed(k_emb, cfg),
            "layers": layers,
            "final_norm": init_norm(cfg),
        }

    # -- forward --------------------------------------------------------------
    def backbone(self, params: Params, x: jax.Array, positions: jax.Array,
                 remat: bool | str = False) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg

        def body(carry, group_params):
            h, aux = carry
            h, a = apply_group(group_params, h, cfg, positions)
            return (h, aux + a), None

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(remat))
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                   params["layers"])
        x = apply_norm(params["final_norm"], x, cfg)
        return x, aux

    def embed_inputs(self, params: Params, batch: dict
                     ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Returns (x, positions, targets, loss_mask)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = embed_tokens(params["embed"], tokens, cfg)
        targets = batch["labels"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        if cfg.frontend == "stub_patches":
            patches = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
            pad = jnp.zeros(patches.shape[:2], targets.dtype)
            targets = jnp.concatenate([pad, targets], axis=1)
            mask = jnp.concatenate([jnp.zeros(patches.shape[:2], mask.dtype),
                                    mask], axis=1)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return x, positions, targets, mask

    def loss(self, params: Params, batch: dict, *,
             remat: bool | str = False) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, positions, targets, mask = self.embed_inputs(params, batch)
        h, aux = self.backbone(params, x, positions, remat=remat)
        head_w = (params["embed"]["tokens"].T if cfg.tie_embeddings
                  else params["embed"]["lm_head"])
        xent = chunked_xent(h, head_w, targets, mask, cfg)
        aux_w = cfg.moe.router_aux_weight if cfg.moe else 0.0
        total = xent + aux_w * aux / max(cfg.n_layers, 1)
        return total, {"xent": xent, "aux": aux}

    # -- prefill ---------------------------------------------------------------
    def prefill(self, params: Params, tokens: jax.Array, *, max_len: int = 0,
                patch_embeds: jax.Array | None = None
                ) -> tuple[jax.Array, Params]:
        """Process a full prompt; returns (last-position logits, decode state).

        The decode state is allocated at ``max_len`` capacity (defaults to
        the prompt length) and carried through the layer loop: each layer
        writes its state into the stack in place, attention its K/V into
        the first slots, so no layer's state is stacked or padded after.
        Attention KV caches are sharded per the installed rules ("kv_seq"
        maps the cache sequence dim).
        """
        cfg = self.cfg
        batch = {"tokens": tokens, "labels": jnp.zeros_like(tokens)}
        if patch_embeds is not None:
            batch["patch_embeds"] = patch_embeds
        x, positions, _, _ = self.embed_inputs(params, batch)
        state = self.init_decode_state(x.shape[0], max(max_len, x.shape[1]))

        def body(carry, scanned):
            h, state = carry
            group_params, g = scanned
            return prefill_group(group_params, h, state, g, cfg,
                                 positions), None

        (x, state), _ = jax.lax.scan(
            body, (x, state), (params["layers"], jnp.arange(cfg.n_groups)))
        x = apply_norm(params["final_norm"], x, cfg)
        state = jax.tree_util.tree_map_with_path(
            lambda path, c: constrain(c, None, "kv_seq", "kv_heads", "batch",
                                      None)
            if path[-1].key in ("k", "v") else c, state)
        head_w = (params["embed"]["tokens"].T if cfg.tie_embeddings
                  else params["embed"]["lm_head"])
        dtc = jnp.dtype(cfg.compute_dtype)
        last = x[:, -1:]
        logits = (last.astype(dtc) @ head_w.astype(dtc)).astype(jnp.float32)
        return constrain(logits, "batch", None, "vocab"), state

    # -- decode ----------------------------------------------------------------
    def init_decode_state(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg

        def one(_):
            return init_group_state(cfg, batch, max_len)

        # stack per-group states along a leading axis, carried through
        # the layer loop and indexed by group
        return jax.vmap(one)(jnp.arange(cfg.n_groups))

    def decode_step(self, params: Params, state: Params, tokens: jax.Array,
                    pos: jax.Array) -> tuple[jax.Array, Params]:
        """tokens: (B, 1) -> (logits (B, 1, V), new_state).

        The stacked state is carried through the layer loop, not scanned
        over and rebuilt: each attention layer writes its new K/V row into
        the stack in place and reads its own layer from it, so a call
        writes one row per layer and copies no cache.  The cache's own
        layout, (S, KV, B, hd padded) per layer (``models/attention.py``),
        is the one attention reads and XLA's default for the stored array,
        so prefill emits it as decode reads it and decode, jitted with the
        state donated, updates it in place.
        """
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens, cfg)

        def body(carry, scanned):
            h, state = carry
            group_params, g = scanned
            return decode_group(group_params, h, state, g, cfg, pos), None

        (x, state), _ = jax.lax.scan(
            body, (x, state), (params["layers"], jnp.arange(cfg.n_groups)))
        x = apply_norm(params["final_norm"], x, cfg)
        head_w = (params["embed"]["tokens"].T if cfg.tie_embeddings
                  else params["embed"]["lm_head"])
        dtc = jnp.dtype(cfg.compute_dtype)
        logits = (x.astype(dtc) @ head_w.astype(dtc)).astype(jnp.float32)
        logits = constrain(logits, "batch", None, "vocab")
        return logits, state
