"""Config system: ModelConfig covers all assigned architectures; ShapeConfig
covers the assigned input-shape sets; input_specs() builds the
ShapeDtypeStruct stand-ins the dry-run lowers against (no allocation).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# (mixer, ffn) kinds per sub-layer; a model is pattern × n_periods
MIXERS = ("attn", "mla", "mamba", "mlstm", "slstm")
FFNS = ("mlp", "moe", "none")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | hybrid | xlstm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: tuple[tuple[str, str], ...] = (("attn", "mlp"),)
    head_dim: int = 0                # 0 -> d_model // n_heads
    # attention
    qk_norm: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    attn_impl: str = "blocked"       # naive | blocked | flash
    attn_compute_dtype: str = "f32"  # f32 (baseline) | bf16 (opt: f32 accum)
    mla_absorb: bool = False         # MLA absorbed formulation (opt)
    pad_vocab: bool = False          # pad V to /256 so embed/head shard (opt)
    bkv: int = 512
    logit_softcap: float = 0.0
    # mlp
    act: str = "silu"                # silu | gelu (gelu => GeGLU when gated)
    gated_mlp: bool = True           # False: plain 2-layer MLP (whisper)
    # embeddings
    tie_embeddings: bool = False
    embed_scale: float = 1.0
    pos_embed: str = "rope"          # rope | sinusoidal
    norm_eps: float = 1e-6
    # moe
    n_experts: int = 0               # the router's width, as published
    top_k: int = 0
    d_ff_expert: int = 0
    # the experts held here, a contiguous block (0: all n_experts): one
    # chip's share of an expert-parallel deployment
    experts_held: int = 0
    first_expert_held: int = 0
    norm_topk_prob: bool = True      # renormalise the top-k gate weights
    moe_impl: str = "gather"         # gather | noc | dense
    moe_topology: str = "fattree"    # fattree | ring | mesh2d | torus2d
    capacity_factor: float = 1.25
    # >0: CONNECT flit-buffer-depth capacity knob — each (src, expert)
    # dispatch FIFO holds this many token slots and capacity_factor is
    # DERIVED from it (models.moe.dispatch_capacity); 0: use capacity_factor
    moe_flit_buffer_depth: int = 0
    aux_weight: float = 0.01
    # mamba
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk: int = 256
    # xlstm
    xlstm_proj_factor: float = 2.0
    xlstm_chunk: int = 128
    # encoder (enc-dec) / frontend (audio, vlm)
    n_enc_layers: int = 0
    enc_seq: int = 0                 # whisper: 1500 frames
    d_frontend: int = 0              # mel bins / ViT width
    n_patches: int = 0               # vlm prefix length
    # compute
    dtype: str = "bfloat16"
    serve_param_dtype: str = "float32"   # bfloat16 => serving reads bf16 params
    remat: bool = True
    analysis_unroll: bool = False    # roofline analysis: unroll inner seq scans
    seq_shard_kv: bool = False       # long-context: shard KV/state seq over 'data'
    source: str = ""                 # the published config these widths follow

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab // 256) * 256 if self.pad_vocab else self.vocab

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_periods(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, (self.name, self.n_layers, len(self.pattern))
        return self.n_layers // len(self.pattern)

    @property
    def cdtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- analytics -----------------------------------------------------------
    def param_count(self) -> int:
        from ..models.transformer import abstract_params
        from ..models.layers import count_params
        return count_params(abstract_params(self))

    def active_param_count(self) -> int:
        """MoE: params touched per token (for MODEL_FLOPS = 6·N_active·D)."""
        if not self.n_experts:
            return self.param_count()
        from ..models.transformer import abstract_params
        from ..models.layers import is_spec
        tree = abstract_params(self)
        total = 0
        for path, spec in jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_spec)[0]:
            n = 1
            for s in spec.shape:
                n *= s
            if "experts" in spec.axes:
                n = n // spec.shape[spec.axes.index("experts")] * self.top_k
            total += n
        return total


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def long_context_ok(cfg: ModelConfig) -> bool:
    """long_500k runs for SSM/hybrid archs; skipped for pure full-attention."""
    mixers = {m for m, _ in cfg.pattern}
    return bool(mixers & {"mamba", "mlstm", "slstm"})


def cell_supported(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    if shape.name == "long_500k" and not long_context_ok(cfg):
        return False, "pure full-attention arch: 500k dense-KV decode out of regime (per spec)"
    return True, ""


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    def tok(s):
        return jax.ShapeDtypeStruct(s, jnp.int32)
    if shape.kind == "train":
        specs = {"tokens": tok((B, S)), "labels": tok((B, S))}
    elif shape.kind == "prefill":
        specs = {"tokens": tok((B, S))}
    else:  # decode: one new token against a seq_len cache
        specs = {"tokens": tok((B, 1))}
    if cfg.family == "encdec":
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_seq, cfg.d_frontend), cfg.cdtype)
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.n_patches, cfg.d_frontend), cfg.cdtype)
    return specs


# registry filled by the per-arch modules
REGISTRY: dict[str, ModelConfig] = {}
SMOKE_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig, smoke: ModelConfig) -> ModelConfig:
    REGISTRY[cfg.name] = cfg
    SMOKE_REGISTRY[cfg.name] = smoke
    return cfg


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    from . import ALL_ARCHS  # noqa: F401  (import side effect: fill registry)
    reg = SMOKE_REGISTRY if smoke else REGISTRY
    if name not in reg:
        raise KeyError(f"unknown arch {name!r}; have {sorted(reg)}")
    return reg[name]
