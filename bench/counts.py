"""Operations and needed bytes of one prefill or decode call of a dense
decoder, from the widths in a configuration file under ``bench/configs``.

These are what the algorithm needs, not what an implementation happens to
do: causal attention counts the lower triangle only, a decode reads the
cache up to ``pos + 1`` (not the ``max_len`` slots a padded cache holds),
and every weight is read once per call.  A roofline share is then
``max(flops / peak_flops, bytes / peak_bytes_per_s) / device_time``.
"""

from __future__ import annotations

from dataclasses import dataclass

PARAM_BYTES = 2       # bf16 weights and cache, as served
LOGIT_BYTES = 4       # f32 logits out of prefill and decode


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

    @classmethod
    def from_config(cls, config: dict) -> "Counts":
        return cls(d=config["hidden_size"], layers=config["num_hidden_layers"],
                   heads=config["num_attention_heads"],
                   kv_heads=config["num_key_value_heads"],
                   head_dim=config["head_dim"],
                   d_ff=config["intermediate_size"],
                   vocab=config["vocab_size"],
                   tied=config["tie_word_embeddings"],
                   qkv_bias=config["attention_bias"])

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


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
