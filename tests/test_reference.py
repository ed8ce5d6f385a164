"""The system against its plain float32 reference (``models/reference.py``),
and the one-chip held-experts MoE layer against ``dense_ref``.

Weights are the program's init with every leaf scaled elementwise by a draw
from U(0.5, 1.5) (zero leaves get N(0, 0.1)), so that norm scales, biases and
the Mamba decay rates all differ from their init and a term left out shows.
Everything runs in float32 at full matmul precision on the CPU: the system
and the reference then part by float32 rounding in a different order of
summation, about 1e-6 of the largest logit.  Each tolerance below is that,
with room, and far below what a missing term moves (a whole sublayer, a norm
or a gate weight moves logits by about their own size).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch import serve
from repro.models import moe as M
from repro.models import reference as R
from repro.models import ssm
from repro.models import transformer as T
from repro.models.layers import init_params

# float32 rounding in another order of summation, relative to the largest logit
RTOL = 1e-4


def scrambled(params, seed: int):
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = [a * jax.random.uniform(k, a.shape, minval=0.5, maxval=1.5)
           if np.any(np.asarray(a)) else 0.1 * jax.random.normal(k, a.shape)
           for a, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def jamba(held: int = 0, first: int = 0):
    """The smoke Jamba with the router's width 8, holding ``held`` from ``first``."""
    return get_config("ai21-jamba2-mini", smoke=True).replace(
        n_experts=8, experts_held=held, first_expert_held=first)


def close(a, b, rtol=RTOL) -> bool:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= rtol * float(np.abs(b).max())


@pytest.mark.parametrize("held,first", [(0, 0), (4, 0), (4, 4)],
                         ids=["all-experts", "experts-0-3", "experts-4-7"])
def test_forward_matches_reference(held, first, rng):
    cfg = jamba(held, first)
    params = scrambled(init_params(T.abstract_params(cfg), jax.random.key(0)), 1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 20)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, _, _, st = T.forward(params, {"tokens": tokens}, cfg)
    assert st["moe_experts"].shape == (4, 2, 20, 2)
    assert close(logits, R.forward(params, tokens, cfg))


def test_prefill_and_decode_match_reference(rng):
    """Through ``launch.serve``'s jitted prefill and greedy decode, the hybrid
    cache (attention keys and values, Mamba conv and SSM state) gives the
    logits of the reference's full forward over the same tokens."""
    cfg = jamba(4, 2)
    params = scrambled(init_params(T.abstract_params(cfg), jax.random.key(2)), 3)
    B, P, G = 2, 11, 5
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (B, P)), jnp.int32)
    got = []
    with jax.default_matmul_precision("highest"):
        state = serve.new_state(cfg, B, P + G)
        logits, state = serve.prefill(params, {"tokens": prompts[:, :7]}, state, cfg)
        logits, state = serve.prefill(params, {"tokens": prompts[:, 7:]}, state, cfg)
        got.append(logits)
        for _ in range(G):
            logits, state = serve.decode(params, logits, state, cfg)
            got.append(logits)
    tokens = state["tokens"]
    assert np.array_equal(tokens[:, :P], prompts)
    assert np.array_equal(tokens[:, P:], np.stack([np.argmax(g, -1) for g in got[:-1]], 1))
    want = R.forward(params, tokens, cfg)
    assert close(jnp.stack(got, 1), want[:, P - 1:])
    # the recorded routing is the reference's own top-2
    assert close(R.forward(params, tokens, cfg, experts=state["experts"]), want)


@pytest.mark.parametrize("path", ["chunked", "decode-steps"])
def test_mamba_matches_reference(path, rng):
    """The chunked scan (prefill) and the recurrent update (decode), each with
    the dt/B/C norms, against the reference's sequential scan."""
    cfg = jamba().replace(d_model=32, mamba_d_state=8)
    c = ssm.MambaConfig(32, d_state=8, d_conv=4, expand=2, chunk=8, eps=cfg.norm_eps)
    p = scrambled(init_params(ssm.mamba_specs(c), jax.random.key(4)), 5)
    x = jnp.asarray(rng.normal(size=(2, 21, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ssm.mamba_apply(p, x, c)[0] if path == "chunked" else ssm.mamba_scan_ref(p, x, c)
        want = jax.vmap(lambda s: R.mamba(p, s, cfg))(x)
    assert close(got, want)


D, E, K, F = 32, 8, 2, 48


def layer(held: int = 0, first: int = 0, **kw) -> M.MoEConfig:
    return M.MoEConfig(D, E, K, F, n_held=held, first_held=first, **kw)


def share(params, held: int, first: int):
    return {k: v if k == "router" else v[first:first + held] for k, v in params.items()}


@pytest.mark.parametrize("case", ["random", "one-hot-router", "renormalised"])
def test_held_path_matches_dense_ref(case, rng):
    """The one-chip path drops no token: it equals ``dense_ref`` however the
    tokens are routed, also when every token goes to the same two experts
    (where the capacity of the ``gather`` engine, 3 slots an expert at 16
    tokens, would drop 13 of them)."""
    c = layer(norm_topk_prob=case == "renormalised")
    params = init_params(M.moe_specs(c), jax.random.key(6))
    if case == "one-hot-router":
        params["router"] = params["router"].at[:, :2].add(5.0)
    x = jnp.asarray(rng.normal(size=(2, 8, D)), jnp.float32) + 1.0
    with jax.default_matmul_precision("highest"):
        out, _, st = M.moe_apply(params, x, c)
        want, _ = M.dense_ref(params, x, c)
    if case == "one-hot-router":
        assert set(np.unique(np.asarray(st.experts)).tolist()) == {0, 1}
    assert st.engine == "held" and st.fallback is None
    assert close(out, want)


def test_held_shares_sum_to_the_uncut_layer(rng):
    """Experts 0-3 and 4-7, each computing its share at the full router, add
    up to ``dense_ref`` of the layer that holds all eight."""
    params = init_params(M.moe_specs(layer()), jax.random.key(7))
    x = jnp.asarray(rng.normal(size=(3, 5, D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        parts = [M.moe_apply(share(params, 4, f), x, layer(4, f))[0] for f in (0, 4)]
        want, _ = M.dense_ref(params, x, layer())
    assert close(parts[0] + parts[1], want)
    assert not close(parts[0], want) and not close(parts[1], want)


def test_norm_topk_prob(rng):
    """Without renormalisation (Jamba) the gates are the softmax weights of
    the top-2; with it (Qwen3-MoE) they are those over their sum."""
    params = init_params(M.moe_specs(layer()), jax.random.key(8))
    x = jnp.asarray(rng.normal(size=(1, 6, D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        raw, _, _ = M.moe_apply(params, x, layer(norm_topk_prob=False))
        norm, _, _ = M.moe_apply(params, x, layer(norm_topk_prob=True))
        probs = jax.nn.softmax(x @ params["router"], axis=-1)
    top = jnp.sum(jax.lax.top_k(probs, K)[0], axis=-1, keepdims=True)
    assert float(jnp.max(top)) < 1.0
    assert close(raw, norm * top)


def test_held_path_publishes_its_share():
    from repro.telemetry.metrics import disable_metrics, enable_metrics

    c = layer(4, 4)
    params = init_params(M.moe_specs(c), jax.random.key(9))
    reg = enable_metrics()
    try:
        jax.jit(lambda p, x: M.moe_apply(p, x, c)[0]).lower(
            params, jnp.zeros((1, 4, D), jnp.float32))
        gauges = reg.snapshot()["gauges"]
    finally:
        disable_metrics()
    assert gauges["models.moe.router_width"] == E
    assert gauges["models.moe.experts_held"] == 4


def test_held_path_shards_the_batch():
    """On 4 devices with a size-1 ``model`` axis, each device runs the held
    layer on its own quarter of the batch: the output is ``dense_ref``'s, and
    a device's program costs the FLOPs of one quarter alone (run outside a
    ``shard_map`` it gathered the batch and cost the whole batch's)."""
    from tests.conftest import run_with_devices

    out = run_with_devices(f"""
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh
from repro.models import moe as M
from repro.models.layers import init_params
c = M.MoEConfig({D}, {E}, {K}, {F}, n_held=4)
params = init_params(M.moe_specs(c), jax.random.key(10))
x = jax.random.normal(jax.random.key(11), (8, 4, {D}))
with jax.default_matmul_precision("highest"):
    f = jax.jit(lambda p, x: (lambda out, _, st: (out, st.experts))(*M.moe_apply(p, x, c)))
    flops = lambda x: f.lower(params, x).compile().cost_analysis()["flops"]
    quarter, whole = flops(x[:2]), flops(x)
    want = M.dense_ref(params, x, c)[0]
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        got, experts = f(params, xs)
        per_device = flops(xs)
        engine = M.moe_apply(params, xs, c)[2].engine
print(json.dumps(dict(quarter=quarter, whole=whole, per_device=per_device,
                      engine=engine, devices=len(got.sharding.device_set),
                      experts=list(experts.shape),
                      err=float(jnp.max(jnp.abs(got - want))),
                      scale=float(jnp.max(jnp.abs(want))))))
""", 4)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["engine"] == "held" and r["devices"] == 4 and r["experts"] == [8, 4, K]
    assert r["err"] <= RTOL * r["scale"]
    assert r["whole"] > 3 * r["quarter"]
    assert r["per_device"] <= 1.05 * r["quarter"]
