"""Jittable step functions (train / prefill / decode) with explicit
shardings — shared by the trainer, the server, and the dry-run.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..core.partition import DEFAULT_RULES, cross_pod_mean
from ..core.serdes import QuasiSerdesConfig
from ..models import transformer as T
from ..models.layers import param_pspecs, param_shapes
from ..optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule


def batch_pspec(mesh: Mesh) -> P:
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return P(axes if axes else None)


def shardings_for_params(cfg: ModelConfig, mesh: Mesh):
    specs = T.abstract_params(cfg)
    pspecs = param_pspecs(specs, DEFAULT_RULES, mesh.axis_names, dict(mesh.shape))
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps), pspecs)


def batch_shardings(batch_specs: dict, mesh: Mesh, shape: ShapeConfig):
    bp = batch_pspec(mesh)

    n_batch = 1
    for a in (bp[0] if isinstance(bp[0], tuple) else ((bp[0],) if bp[0] else ())):
        n_batch *= mesh.shape[a]

    def of(k, v):
        if (v.ndim >= 2 and v.shape[0] == shape.global_batch
                and shape.global_batch % max(n_batch, 1) == 0):
            return NamedSharding(mesh, P(bp[0], *([None] * (v.ndim - 1))))
        return NamedSharding(mesh, P())

    return {k: of(k, v) for k, v in batch_specs.items()}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, mesh: Mesh, opt_cfg: AdamWConfig,
                    *, pod_sync: str = "auto",
                    serdes: Optional[QuasiSerdesConfig] = None,
                    total_steps: int = 10_000, warmup: int = 200):
    """pod_sync:
      'auto'   — flat XLA all-reduce over (pod, data)  [baseline]
      'serdes' — per-pod grads in a fully-manual shard_map, cross-pod
                 exchange through quasi-SERDES endpoints  [paper-faithful cut]
    """
    n_pods = mesh.shape.get("pod", 1)

    def lr_of(step):
        return cosine_schedule(step, peak_lr=opt_cfg.lr, warmup=warmup,
                               total=total_steps)

    def grads_auto(params, batch):
        (loss, mets), grads = jax.value_and_grad(T.loss, has_aux=True)(params, batch, cfg)
        return loss, mets, grads

    def grads_serdes(params, batch):
        """Fully-manual shard_map region (manual over *every* mesh axis).

        A partial-manual region (``axis_names={'pod'}``, data/model auto
        inside) still aborts XLA's SPMD partitioner on jax 0.9 (a
        ``partition_group_list`` check in spmd_partitioner_util).
        Fully-manual sidesteps it: params enter replicated, each device
        computes grads on its own (pod × data) batch shard, the within-pod
        average is an explicit pmean over 'data' (the on-chip all-reduce),
        and only the cross-pod exchange goes through the quasi-SERDES
        endpoints over the cut.  Model-axis devices redundantly compute
        identical grads — the replication that makes the region's outputs
        valid under ``out_specs=P()``."""
        data_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
        sync_axes = ("pod",) + data_axes

        def pod_local(params, batch):
            (loss, mets), grads = jax.value_and_grad(T.loss, has_aux=True)(params, batch, cfg)
            if data_axes:
                grads = jax.tree.map(lambda g: lax.pmean(g, data_axes), grads)
            grads, _ = cross_pod_mean(grads, "pod", serdes, n_pods=n_pods,
                                      serialized=True)
            loss = lax.pmean(loss, sync_axes)
            mets = jax.tree.map(lambda m: lax.pmean(m, sync_axes), mets)
            return loss, mets, grads

        blead = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        bspec = jax.tree.map(lambda _: P(blead), batch)
        return jax.shard_map(
            pod_local, mesh=mesh,
            in_specs=(P(), bspec), out_specs=(P(), P(), P()),
            check_vma=False)(params, batch)

    grads_fn = grads_auto if (pod_sync == "auto" or n_pods == 1) else grads_serdes

    def train_step(state, batch):
        params, opt_state = state["params"], state["opt"]
        loss, mets, grads = grads_fn(params, batch)
        new_params, new_opt, om = adamw_update(params, grads, opt_state, opt_cfg,
                                               lr=lr_of(opt_state["step"]))
        mets = dict(mets, loss=loss, **om)
        return {"params": new_params, "opt": new_opt}, mets

    return train_step


def jit_train_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                   opt_cfg: AdamWConfig = AdamWConfig(), **kw):
    """Returns (jitted fn, state_specs, batch ShapeDtypeStructs) for lowering."""
    from ..configs.base import input_specs

    step = make_train_step(cfg, mesh, opt_cfg, **kw)
    psh = shardings_for_params(cfg, mesh)
    state_sh = {"params": psh,
                "opt": {"m": psh, "v": psh, "step": NamedSharding(mesh, P())}}
    bspecs = input_specs(cfg, shape)
    bsh = batch_shardings(bspecs, mesh, shape)
    jitted = jax.jit(step, in_shardings=(state_sh, bsh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    pshapes = param_shapes(T.abstract_params(cfg))
    opt_shapes = jax.eval_shape(adamw_init, pshapes)
    state_shapes = {"params": pshapes, "opt": opt_shapes}
    return jitted, state_shapes, bspecs


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def cache_struct(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len))


def serve_param_shapes(cfg: ModelConfig):
    shp = param_shapes(T.abstract_params(cfg))
    if cfg.serve_param_dtype == "bfloat16":
        shp = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shp)
    return shp


def jit_prefill(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig):
    from ..configs.base import input_specs

    psh = shardings_for_params(cfg, mesh)
    bspecs = input_specs(cfg, shape)
    bsh = batch_shardings(bspecs, mesh, shape)
    extra = cfg.n_patches if cfg.family == "vlm" else 0
    cstruct = cache_struct(cfg, shape.global_batch, shape.seq_len + extra)

    def fn(params, batch, cache):
        return T.prefill(params, batch, cfg, cache)

    jitted = jax.jit(fn, in_shardings=(psh, bsh, None), donate_argnums=(2,))
    return jitted, bspecs, cstruct


def jit_decode(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig):
    """One decode step against a cache holding shape.seq_len tokens."""
    from ..configs.base import input_specs

    psh = shardings_for_params(cfg, mesh)
    bspecs = input_specs(cfg, shape)
    bsh = batch_shardings(bspecs, mesh, shape)
    cstruct = cache_struct(cfg, shape.global_batch, shape.seq_len)
    if cfg.family == "encdec":
        cstruct = dict(cstruct)
        cstruct["enc_out"] = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.enc_seq, cfg.d_model), cfg.cdtype)
    # cache starts at seq_len - 1 (full context), decode appends 1 token
    cstruct = dict(cstruct)

    def fn(params, batch, cache):
        return T.decode_step(params, batch, cfg, cache)

    jitted = jax.jit(fn, in_shardings=(psh, bsh, None), donate_argnums=(2,))
    return jitted, bspecs, cstruct
