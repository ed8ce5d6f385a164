"""Jamba-v0.1-52B  [hybrid]  32L d_model=4096 32H (GQA kv=8) d_ff=14336
vocab=65536, MoE 16 experts top-2.  Mamba+attention 1:7 interleave
(attn_layer_period=8 offset 4), MoE every 2nd layer (offset 1).
No positional embeddings (the SSM layers carry position).  [arXiv:2403.19887; hf]

The router keeps the softmax weights of its top-2 as they are
(``modeling_jamba.JambaSparseMoeBlock``: softmax over the 16, then top-k, no
renormalisation).

AI21-Jamba2-Mini has the same widths key for key (its ``config.json``:
hidden 4096, 32 query / 8 KV heads, intermediate 14336, 16 experts top-2,
Mamba d_state 16, d_conv 4, expand 2, dt_rank 256, attention period 8
offset 4, expert period 2 offset 1, vocabulary 65536, rms_norm_eps 1e-6), and
is registered from this one as ``ai21-jamba2-mini``, served with bfloat16
parameters.
"""
from .base import ModelConfig, register

# one period = 8 layers: attn at index 4, MoE at odd indices
_PATTERN = tuple(
    ("attn" if i == 4 else "mamba", "moe" if i % 2 == 1 else "mlp")
    for i in range(8)
)

FULL = ModelConfig(
    name="jamba-v0.1-52b",
    family="hybrid",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    vocab=65536,
    pattern=_PATTERN,
    use_rope=False,
    n_experts=16,
    top_k=2,
    d_ff_expert=14336,
    norm_topk_prob=False,
    moe_impl="gather",
    mamba_d_state=16,
    mamba_d_conv=4,
    mamba_expand=2,
    source="https://huggingface.co/ai21labs/Jamba-v0.1/blob/main/config.json",
)

SMOKE = FULL.replace(
    n_layers=8, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
    vocab=256, n_experts=4, top_k=2, d_ff_expert=96, dtype="float32",
    remat=False, attn_impl="naive", mamba_chunk=16,
)

register(FULL, SMOKE)

JAMBA2_MINI = FULL.replace(
    name="ai21-jamba2-mini",
    source="https://huggingface.co/ai21labs/AI21-Jamba2-Mini/blob/main/config.json",
    serve_param_dtype="bfloat16",
)

register(JAMBA2_MINI, SMOKE.replace(name=JAMBA2_MINI.name))
