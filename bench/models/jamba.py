"""The Jamba hybrid (AI21-Jamba2-3B) as the harness runs and counts it.

A block of Mamba-1 mixers and grouped-query attention without positional
encoding, every layer followed by a dense SwiGLU MLP (HF ``JambaConfig``
with ``num_experts`` 1).  Attention sits at the layers ``i`` with ``i %
attn_layer_period == attn_layer_offset``, Mamba everywhere else.  The
harness finds this module by the configuration's ``reference`` key, as
``bench/models/<reference>.py``, and the plain reference as
``bench/reference/<reference>.py``; it gives:

* ``arch_config(config, positions=None)``: the program's ``ArchConfig``
  for the file, refused (``ValueError``) where the program would not run
  the model the file states;
* ``counts(config)``: the operations and needed bytes of the program's
  calls, ``prefill(rows, prompt)`` and ``decode(rows, pos)``, each
  ``(flops, bytes)``, ``served_row_flops(prompt, gen)``, and
  ``scan(rows, tokens)``, one layer's selective scan.

The counts are what the algorithm needs: causal attention counts the
lower triangle, a decode reads the cache up to ``pos + 1``, every weight
is read once per call, and the recurrent state is read and written once
per decode step.  The selective scan counts, per token and state entry,
the discretisation ``exp(dt A)`` (a product and an exponential), the
update ``dA h + (dt x) B`` (three operations, ``dt x`` once per channel)
and the readout ``C . h`` (a product and a sum), and per channel ``dt x``
and the skip ``D x``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

PARAM_BYTES = 2       # bf16 weights, cache and conv window, as served
F32 = 4               # A_log, D, the scan's operands and state, logits


def _kinds(config: dict) -> tuple[str, ...]:
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return tuple("attn" if i % period == offset else "mamba"
                 for i in range(config["num_hidden_layers"]))


def arch_config(config: dict, positions: int | None = None):
    """The program's ``ArchConfig`` for the configuration file: the
    registry's architecture with every width and the layer order the
    file states."""
    from repro import configs
    try:
        base = configs.get(config["arch"])
    except KeyError:
        raise ValueError(f"{config['arch']}: not in the program's "
                         "registry") from None
    from repro.models.config import MambaConfig
    refusals = {
        "the registry's architecture is not a hybrid":
            base.family != "hybrid",
        "hidden_act is not SiLU": config["hidden_act"] != "silu",
        "the program's Mamba projections have no bias":
            config["mamba_proj_bias"],
        "the program's causal conv has a bias": not config["mamba_conv_bias"],
        "the file's layers hold experts, which its reference does not":
            config["num_experts"] != 1,
        "the program applies no sliding window":
            config["sliding_window"] is not None,
        "the program scans whole periods of layers":
            config["num_hidden_layers"] % config["attn_layer_period"],
    }
    for why, refused in refusals.items():
        if refused:
            raise ValueError(f"{config['arch']}: {why}")
    if positions is not None and positions > config["max_position_embeddings"]:
        raise ValueError(f"{positions} positions pass the configuration's "
                         "max_position_embeddings")
    return replace(
        base, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        vocab_size=config["vocab_size"],
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"], layer_kinds=_kinds(config),
        mamba=MambaConfig(d_state=config["mamba_d_state"],
                          d_conv=config["mamba_d_conv"],
                          expand=config["mamba_expand"],
                          dt_rank=config["mamba_dt_rank"]),
        moe=None, positions="none", mlp_type="swiglu", attn_impl="auto")


def counts(config: dict) -> "Counts":
    kinds = _kinds(config)
    return Counts(d=config["hidden_size"], layers=len(kinds),
                  attn_layers=kinds.count("attn"),
                  heads=config["num_attention_heads"],
                  kv_heads=config["num_key_value_heads"],
                  head_dim=config["hidden_size"]
                  // config["num_attention_heads"],
                  d_ff=config["intermediate_size"],
                  vocab=config["vocab_size"],
                  tied=config["tie_word_embeddings"],
                  d_inner=config["mamba_expand"] * config["hidden_size"],
                  d_state=config["mamba_d_state"],
                  d_conv=config["mamba_d_conv"],
                  dt_rank=config["mamba_dt_rank"])


@dataclass(frozen=True)
class Counts:
    d: int            # hidden size
    layers: int
    attn_layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int

    @property
    def mamba_layers(self) -> int:
        return self.layers - self.attn_layers

    # -- parameters ---------------------------------------------------------
    @property
    def attn_matmul_params(self) -> int:
        """q, k, v and o of one attention layer."""
        return self.d * (self.heads + 2 * self.kv_heads) * self.head_dim \
            + self.heads * self.head_dim * self.d

    @property
    def mamba_matmul_params(self) -> int:
        """in_proj, x_proj, dt_proj and out_proj of one Mamba layer."""
        di, r, s = self.d_inner, self.dt_rank, self.d_state
        return self.d * 2 * di + di * (r + 2 * s) + r * di + di * self.d

    @property
    def mlp_params(self) -> int:
        return 3 * self.d * self.d_ff

    @property
    def matmul_params(self) -> int:
        """Every matmul weight of the layers, the output head aside."""
        return (self.attn_layers * self.attn_matmul_params
                + self.mamba_layers * self.mamba_matmul_params
                + self.layers * self.mlp_params)

    @property
    def weight_bytes(self) -> int:
        """Weights one call reads: every layer, the output head (the
        embedding table when tied; a separate table is read one row per
        token) and the norms."""
        di, s, r = self.d_inner, self.d_state, self.dt_rank
        mamba_small = PARAM_BYTES * (self.d_conv * di + 2 * di + r + 2 * s) \
            + F32 * (di * s + di)          # conv, conv and dt biases, norms
        norms = PARAM_BYTES * (2 * self.layers + 1) * self.d
        return (PARAM_BYTES * (self.matmul_params + self.vocab * self.d)
                + self.mamba_layers * mamba_small + norms)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.attn_layers * 2 * self.kv_heads * self.head_dim \
            * PARAM_BYTES

    @property
    def ssm_bytes_per_row(self) -> int:
        """One row's recurrent state: the scan state and the conv window
        of every Mamba layer."""
        return self.mamba_layers * (
            F32 * self.d_inner * self.d_state
            + PARAM_BYTES * (self.d_conv - 1) * self.d_inner)

    def _embed_bytes(self, tokens: int) -> int:
        return 0 if self.tied else tokens * self.d * PARAM_BYTES

    # -- calls ----------------------------------------------------------------
    def scan(self, rows: int, tokens: int) -> tuple[float, float]:
        """(flops, bytes) of one layer's selective scan over ``rows``
        sequences of ``tokens``: its operands dt, x, B and C in and y out,
        in float32."""
        n = rows * tokens
        flops = n * self.d_inner * (7 * self.d_state + 3)
        nbytes = F32 * n * (3 * self.d_inner + 2 * self.d_state)
        return float(flops), float(nbytes)

    def _token_flops(self) -> float:
        """Flops of one token through every layer, attention's scores
        aside: the matmuls, the causal conv and the scan."""
        return (2 * self.matmul_params
                + self.mamba_layers * (2 * self.d_conv * self.d_inner
                                       + self.scan(1, 1)[0]))

    def prefill(self, rows: int, prompt: int) -> tuple[float, float]:
        """(flops, bytes) of prefilling ``rows`` prompts of ``prompt``
        tokens: every layer over every position, causal attention, the
        cache and the recurrent state written, and logits at the last
        position only."""
        tokens = rows * prompt
        pairs = rows * prompt * (prompt + 1) / 2
        flops = (tokens * self._token_flops()
                 + 4 * self.attn_layers * self.heads * self.head_dim * pairs
                 + 2 * rows * self.d * self.vocab)
        nbytes = (self.weight_bytes + self._embed_bytes(tokens)
                  + tokens * self.kv_bytes_per_token
                  + rows * self.ssm_bytes_per_row
                  + rows * self.vocab * F32)
        return float(flops), float(nbytes)

    def decode(self, rows: int, pos: int) -> tuple[float, float]:
        """(flops, bytes) of one decode step of ``rows`` rows writing
        position ``pos``: attention over positions ``0 .. pos``, every
        Mamba layer's state read and written once."""
        span = pos + 1
        flops = (rows * (self._token_flops() + 2 * self.d * self.vocab)
                 + 4 * rows * self.attn_layers * self.heads * self.head_dim
                 * span)
        nbytes = (self.weight_bytes + self._embed_bytes(rows)
                  + rows * span * self.kv_bytes_per_token
                  + rows * self.kv_bytes_per_token
                  + 2 * rows * self.ssm_bytes_per_row
                  + rows * self.vocab * F32)
        return float(flops), float(nbytes)

    def served_row_flops(self, prompt: int, gen: int) -> float:
        """Model flops of serving one row: its prefill and the ``gen - 1``
        decode steps after it (the first token comes from the prefill)."""
        flops = self.prefill(1, prompt)[0]
        for i in range(gen - 1):
            flops += self.decode(1, prompt + i)[0]
        return flops
