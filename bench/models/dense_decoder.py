"""The dense decoder (Qwen2.5, Phi-3-mini) as the harness runs and counts it.

A configuration file names its architecture by its ``reference`` key; the
harness finds this module as ``bench/models/<reference>.py`` and the plain
reference as ``bench/reference/<reference>.py``.  An architecture module
gives the harness two things:

* ``arch_config(config, positions=None)``: the program's ``ArchConfig``
  for the file, refused (``ValueError``) where the program would not run
  the model the file states, for requests of ``positions`` tokens;
* ``counts(config)``: the operations and needed bytes of the program's
  calls, with ``prefill(rows, prompt)`` and ``decode(rows, pos)``, each
  ``(flops, bytes)``, and ``served_row_flops(prompt, gen)``.

The counts are what the algorithm needs, not what an implementation
happens to do: causal attention counts the lower triangle only, a decode
reads the cache up to ``pos + 1`` (not the ``max_len`` slots a padded
cache holds), and every weight is read once per call.  A roofline share
is then ``max(flops / peak_flops, bytes / peak_bytes_per_s) /
device_time`` (``bench.counts.least_time``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace

PARAM_BYTES = 2       # bf16 weights and cache, as served
LOGIT_BYTES = 4       # f32 logits out of prefill and decode

# the configuration file's keys (published names) -> the program's fields
ARCH_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
               "num_hidden_layers": "n_layers",
               "num_attention_heads": "n_heads",
               "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
               "vocab_size": "vocab_size",
               "tie_word_embeddings": "tie_embeddings",
               "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
               "attention_bias": "qkv_bias", "torch_dtype": "compute_dtype"}


def arch_config(config: dict, positions: int | None = None):
    """The program's ``ArchConfig`` for the configuration file: the
    registry's architecture with every width the file states.  The
    program applies no sliding window, so requests of more
    ``positions`` than the file's window are refused."""
    from repro import configs
    base = configs.get(config["arch"])
    if base.family != "dense" or config["hidden_act"] != "silu" \
            or base.mlp_type != "swiglu" or base.norm_type != "rmsnorm":
        raise ValueError(f"{config['arch']}: not the dense SwiGLU decoder "
                         "that the file and its reference describe")
    window = config.get("sliding_window")
    if positions is not None and window is not None and positions > window:
        raise ValueError(f"{positions} positions pass the configuration's "
                         f"sliding_window {window}, which the program "
                         "does not apply")
    fields = {f: config[k] for k, f in ARCH_FIELDS.items()}
    return replace(base, layer_kinds=("attn",) * fields["n_layers"],
                   attn_impl="auto", **fields)


def counts(config: dict) -> "Counts":
    return Counts(d=config["hidden_size"], layers=config["num_hidden_layers"],
                  heads=config["num_attention_heads"],
                  kv_heads=config["num_key_value_heads"],
                  head_dim=config["head_dim"],
                  d_ff=config["intermediate_size"],
                  vocab=config["vocab_size"],
                  tied=config["tie_word_embeddings"],
                  qkv_bias=config["attention_bias"])


@dataclass(frozen=True)
class Counts:
    d: int          # hidden size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool

    # -- parameters --------------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o and the three SwiGLU matrices of one layer."""
        qkv = self.d * (self.heads + 2 * self.kv_heads) * self.head_dim
        return qkv + self.heads * self.head_dim * self.d + 3 * self.d * self.d_ff

    @property
    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d      # + norms

    @property
    def params(self) -> int:
        """Every parameter of the model."""
        embed = self.vocab * self.d * (1 if self.tied else 2)
        return self.layers * self.layer_params + embed + self.d

    @property
    def weight_bytes(self) -> int:
        """Weights one call reads: every layer, the output head and the
        final norm.  The embedding table counts once when it is the head
        too; a separate table is read one row per token (``_embed_bytes``)."""
        head = self.vocab * self.d
        return PARAM_BYTES * (
            self.layers * self.layer_params + head + self.d)

    @property
    def kv_bytes_per_token(self) -> int:
        return (self.layers * 2 * self.kv_heads * self.head_dim
                * PARAM_BYTES)

    def _embed_bytes(self, tokens: int) -> int:
        return 0 if self.tied else tokens * self.d * PARAM_BYTES

    # -- calls --------------------------------------------------------------
    def prefill(self, rows: int, prompt: int) -> tuple[float, float]:
        """(flops, bytes) of prefilling ``rows`` prompts of ``prompt``
        tokens: every layer over every position, causal attention, the
        cache written, and logits at the last position only."""
        tokens = rows * prompt
        pairs = rows * prompt * (prompt + 1) / 2          # causal (q, k) pairs
        flops = (2 * tokens * self.layers * self.layer_matmul_params
                 + 4 * self.layers * self.heads * self.head_dim * pairs
                 + 2 * rows * self.d * self.vocab)
        nbytes = (self.weight_bytes + self._embed_bytes(tokens)
                  + tokens * self.kv_bytes_per_token
                  + rows * self.vocab * LOGIT_BYTES)
        return float(flops), float(nbytes)

    def decode(self, rows: int, pos: int) -> tuple[float, float]:
        """(flops, bytes) of one decode step of ``rows`` rows writing
        position ``pos``: attention over positions ``0 .. pos``."""
        span = pos + 1
        flops = (2 * rows * (self.layers * self.layer_matmul_params
                             + self.d * self.vocab)
                 + 4 * rows * self.layers * self.heads * self.head_dim * span)
        nbytes = (self.weight_bytes + self._embed_bytes(rows)
                  + rows * span * self.kv_bytes_per_token   # read, new included
                  + rows * self.kv_bytes_per_token          # write
                  + rows * self.vocab * LOGIT_BYTES)
        return float(flops), float(nbytes)

    def served_row_flops(self, prompt: int, gen: int) -> float:
        """Model flops of serving one row: its prefill and the ``gen - 1``
        decode steps after it (the first token comes from the prefill)."""
        flops = self.prefill(1, prompt)[0]
        for i in range(gen - 1):
            flops += self.decode(1, prompt + i)[0]
        return flops
