"""Least bytes and operations of one greedy decode step of a hybrid
(Mamba, attention, routed experts) language model, from shapes alone.

A step takes one token of each of ``batch`` sequences through every layer.
Whatever implements it has to read, once each:

- every weight outside the experts (the embedding only at the batch's rows);
- each held expert that some token of the step is routed to: with ``k`` of
  ``E`` experts drawn per token, uniformly, an expert is touched by ``batch``
  tokens with probability 1 - (1 - k/E)^batch, in expectation;
- the attention keys and values of every cached position, ``pos`` of them a
  sequence;
- the Mamba conv and SSM state, read and written.

Its operations are two per weight of each (token, weight) pair that the
token touches (the held experts it is routed to, k/E of them each), and
four per cached key element for the scores and the weighted values.
"""
from __future__ import annotations


def _moe_layers(cfg) -> int:
    return cfg.n_periods * sum(f == "moe" for _, f in cfg.pattern)


def _layers(cfg, mixer: str) -> int:
    return cfg.n_periods * sum(m == mixer for m, _ in cfg.pattern)


def expert_bytes(cfg, param_bytes: int) -> int:
    """One expert's gate, up and down projections."""
    return 3 * cfg.d_model * cfg.d_ff_expert * param_bytes


def experts_touched(cfg, batch: int) -> float:
    """Expected held experts that ``batch`` tokens' routing touches, over all MoE layers."""
    p = 1.0 - (1.0 - cfg.top_k / cfg.n_experts) ** batch
    return _moe_layers(cfg) * (cfg.experts_held or cfg.n_experts) * p


def experts_min_bytes(cfg, batch: int, param_bytes: int) -> float:
    return experts_touched(cfg, batch) * expert_bytes(cfg, param_bytes)


def other_params(cfg, n_params: int) -> int:
    """Weights outside the experts and the embedding table."""
    held = cfg.experts_held or cfg.n_experts
    experts = _moe_layers(cfg) * held * 3 * cfg.d_model * cfg.d_ff_expert
    return n_params - experts - cfg.vocab * cfg.d_model


def state_bytes(cfg, batch: int) -> int:
    """Mamba conv and SSM state of every sequence, float32."""
    di = cfg.mamba_expand * cfg.d_model
    per = di * cfg.mamba_d_state + (cfg.mamba_d_conv - 1) * di
    return _layers(cfg, "mamba") * batch * per * 4


def kv_bytes(cfg, batch: int, pos: float, cache_bytes: int) -> float:
    return _layers(cfg, "attn") * batch * pos * 2 * cfg.n_kv_heads * cfg.hd * cache_bytes


def min_bytes(cfg, n_params: int, batch: int, pos: float, param_bytes: int,
              cache_bytes: int) -> float:
    """Least HBM bytes of one decode step at a mean of ``pos`` cached positions."""
    return ((other_params(cfg, n_params) + batch * cfg.d_model) * param_bytes
            + experts_min_bytes(cfg, batch, param_bytes)
            + kv_bytes(cfg, batch, pos, cache_bytes) + 2 * state_bytes(cfg, batch))


def flops(cfg, n_params: int, batch: int, pos: float) -> float:
    held = cfg.experts_held or cfg.n_experts
    routed = _moe_layers(cfg) * held * cfg.top_k / cfg.n_experts * 3 * cfg.d_model * cfg.d_ff_expert
    attn = _layers(cfg, "attn") * 4 * pos * cfg.n_heads * cfg.hd
    return batch * (2 * (other_params(cfg, n_params) + routed) + attn)


def min_step_seconds(cfg, n_params: int, batch: int, pos: float, param_bytes: int,
                     cache_bytes: int, peaks: dict) -> float:
    """The larger of the step's least bytes over the HBM peak and its
    operations over the bf16 peak."""
    return max(min_bytes(cfg, n_params, batch, pos, param_bytes, cache_bytes)
               / peaks["hbm_bytes_per_s"],
               flops(cfg, n_params, batch, pos) / peaks["bf16_flops_per_s"])
