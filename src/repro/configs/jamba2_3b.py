"""AI21-Jamba2-3B — hybrid Mamba + attention (13:1), dense SwiGLU MLP.

[hf: ai21labs/AI21-Jamba2-3B config.json; arXiv:2403.19887]  28L
d_model=2560 20H over 1 KV head of 128, d_ff=8192 in every layer
(num_experts 1), vocab=65536, tied, rms_norm_eps 1e-6; attention at
layer index 7 of each 14-layer block (attn_layer_period=14, offset=7,
HF JambaConfig's rule; the published config does not spell the order
out); mamba d_state=16 d_conv=4 expand=2 dt_rank=160, conv bias, no
projection biases, RMSNorms on dt, B and C.

No positional embeddings (the Mamba layers carry position information).
"""
from ..models.config import ArchConfig, MambaConfig

_KINDS = tuple("attn" if i % 14 == 7 else "mamba" for i in range(28))


def full() -> ArchConfig:
    return ArchConfig(
        name="jamba2-3b",
        family="hybrid",
        n_layers=28,
        d_model=2560,
        n_heads=20,
        n_kv_heads=1,
        head_dim=128,
        d_ff=8192,
        vocab_size=65536,
        mlp_type="swiglu",
        norm_eps=1e-6,
        tie_embeddings=True,
        layer_kinds=_KINDS,
        mamba=MambaConfig(d_state=16, d_conv=4, expand=2, dt_rank=160),
        positions="none",
        source="[hf: ai21labs/AI21-Jamba2-3B; arXiv:2403.19887]",
    )
