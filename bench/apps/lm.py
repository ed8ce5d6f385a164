"""A hybrid language model (Mamba, attention, routed experts) served on one
chip: greedy decode of a batch of long documents through the hybrid cache.

Set-up builds the program's configuration from the cell's configuration file
(:func:`model_config`), makes the weights (bfloat16, as served) and the
prompts from the seed on the chip, and prefills every prompt through
``launch.serve.prefill`` in chunks of ``prefill_chunk`` tokens.  The timed
call is one ``launch.serve.decode`` for the whole batch: it takes the greedy
choice of the last logits as each sequence's next token and returns the
logits of that position.  The state it carries on the chip (the cache, the
tokens chosen, the routed experts of every position) is donated from call to
call.

The plain reference (``bench/reference/lm.py``) recomputes, one sequence at
a time in float32, the logits at the positions of the kept calls, with the
program's tokens and routing teacher-forced, and each kept call counts the
logits outside ``tolerance`` and the routing choices it cannot confirm.
Errors are in units of the root mean square of the reference logits of their
row: ``tolerance["logit"]`` bounds each one, and at most a share
``tolerance["tail_share"]`` of all checked logits may exceed
``tolerance["tail"]``.  ``tolerance["routing"]`` bounds the routing's slack,
in units of the root mean square of the token's router logits.
"""
from __future__ import annotations

import functools
import itertools
import math
import sys
import time
import zlib
from unittest import mock

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import decode, new_state, prefill, steps
from repro.models import moe
from repro.models import transformer as T
from repro.models.layers import count_params, is_spec

from bench.apps.bmvm import seed_key
from bench.reference import lm as reference
from bench.work import lm as work

STD = 0.02          # the normal init of every projection and the embedding
ERROR_STEPS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0)   # logit errors counted in the report
SLACK_STEPS = (0.1, 0.2, 0.3, 0.5, 1.0)                # routing slacks counted in it


def model_config(conf: dict, traffic: dict) -> ModelConfig:
    """The registry's ``conf["arch"]`` at the widths, depth, experts held and
    vocabulary of the configuration file, which uses the published config's
    own keys."""
    base = get_config(conf["arch"])
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    cfg = base.replace(
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=conf["num_key_value_heads"], head_dim=d // heads,
        d_ff=conf["intermediate_size"], d_ff_expert=conf["intermediate_size"],
        vocab=conf["vocab_size"], n_experts=conf["router_width"],
        experts_held=conf["num_experts"], first_expert_held=conf["first_expert_held"],
        top_k=conf["num_experts_per_tok"], norm_topk_prob=conf["norm_topk_prob"],
        mamba_d_state=conf["mamba_d_state"], mamba_d_conv=conf["mamba_d_conv"],
        mamba_expand=conf["mamba_expand"], norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"], mamba_chunk=traffic["mamba_chunk"])
    agree = {"pattern": cfg.pattern == tuple(reference.pattern(conf)),
             "mamba_dt_rank": -(-d // 16) == conf["mamba_dt_rank"],
             "hidden_act": cfg.act == conf["hidden_act"],
             "biases": conf["mamba_conv_bias"] and not conf["mamba_proj_bias"],
             "positions": not cfg.use_rope}
    if not all(agree.values()):
        raise ValueError(f"{conf['arch']} does not compute the file's "
                         f"{[k for k, v in agree.items() if not v]}")
    return cfg


def _leaf_kind(path: str) -> str:
    name = path.rsplit("/", 1)[-1]
    if name in ("norm1", "norm2", "final_norm", "dt_norm", "b_norm", "c_norm", "d_skip"):
        return "ones"
    if name in ("conv_w", "conv_b", "dt_b", "a_log"):
        return name
    return "normal"


PIECE = 2 ** 22     # elements drawn at a time: the draw's temporaries are ~16 bytes each


def _draw(key, shape, kind, d_conv):
    if kind in ("conv_w", "conv_b"):
        bound = d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if kind == "dt_b":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return -(dt + jnp.log(-jnp.expm1(-dt))) / 4.6     # the bias, -4.6 dt_b, is softplus^-1(dt)
    return jax.random.normal(key, shape, jnp.float32) * STD


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0,))
def _put(buf, key, i, rows, kind, d_conv):
    """Rows ``i * rows`` on of ``buf`` drawn from ``key`` folded with ``i``."""
    piece = _draw(jax.random.fold_in(key, i), (rows, buf.shape[1]), kind, d_conv)
    return lax.dynamic_update_slice(buf, piece.astype(buf.dtype), (i * rows, 0))


def _leaf(key, shape, kind, d_conv):
    """One parameter, bfloat16: Hugging Face Jamba's init (normal, std 0.02,
    for every projection, the router and the embedding; unit norm scales and
    D), PyTorch's for the depthwise conv, and Mamba's for dt and A (dt
    log-uniform in [1e-3, 1e-1] through the softplus, A_n = n).  Random
    leaves are drawn about ``PIECE`` elements at a time, one jitted call a
    block of rows, in place."""
    bf = jnp.bfloat16
    if kind == "ones":
        return jnp.ones(shape, bf)
    if kind == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
                                shape).astype(bf)
    n, cols = math.prod(shape[:-1]), shape[-1]
    rows = math.gcd(n, 1 << (max(1, PIECE // cols).bit_length() - 1))
    buf = jnp.zeros((n, cols), bf)
    for i in range(n // rows):
        buf = _put(buf, key, i, rows, kind, d_conv)
    return buf.reshape(shape)


def make_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """The served weights, each leaf from ``key`` folded with its path."""
    specs = T.abstract_params(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_spec)
    leaves = []
    for path, spec in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        leaves.append(_leaf(jax.random.fold_in(key, zlib.crc32(name.encode())),
                            spec.shape, _leaf_kind(name), cfg.mamba_d_conv))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_prompts(key: jax.Array, shape: tuple[int, int], vocab: int) -> jax.Array:
    return jax.random.randint(key, shape, 0, vocab, jnp.int32)


@jax.jit
def errors(prog, ref):
    """Per logit, |prog - ref| over the root mean square of ``ref``'s row."""
    rms = jnp.sqrt(jnp.mean(ref * ref, axis=-1, keepdims=True))
    return jnp.abs(prog.astype(jnp.float32) - ref) / rms


class App:
    """One LM cell on one chip: ``step(logits) -> logits`` is the timed call."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices: list, peaks):
        if len(devices) != 1:
            raise ValueError(f"the lm family runs on one chip, not {len(devices)}")
        self.conf, self.tol = config, config["tolerance"]
        self.cfg = cfg = model_config(config, traffic)
        B, P, C = traffic["batch"], traffic["prompt"], traffic["prefill_chunk"]
        self.max_len = traffic["max_len"]
        if P % C or P >= self.max_len:
            raise ValueError(f"prompt {P} is not whole chunks of {C} below max_len {self.max_len}")
        self.items_per_call = B
        self.mesh = make_host_mesh()
        key = seed_key(seed)
        t0 = time.monotonic()
        self.params = make_params(cfg, jax.random.fold_in(key, 0))
        prompts = make_prompts(jax.random.fold_in(key, 1), (B, P), cfg.vocab)
        prompts.block_until_ready()
        t1 = time.monotonic()
        with jax.set_mesh(self.mesh):
            self.state = new_state(cfg, B, self.max_len)
            for i in range(0, P, C):
                self.v0, self.state = prefill(self.params, {"tokens": prompts[:, i:i + C]},
                                              self.state, cfg)
        self.v0.block_until_ready()
        print(f"bench: weights and prompts {t1 - t0:.3f} s, prefill of {B} x {P} tokens "
              f"in chunks of {C} {time.monotonic() - t1:.3f} s", file=sys.stderr, flush=True)
        self.pos = self.first = P
        self._n_params = count_params(T.abstract_params(cfg))
        self._peaks = peaks
        self.work = self._work(P)

    def _work(self, pos: float) -> dict:
        cfg, B = self.cfg, self.items_per_call
        return {"min_bytes": work.min_bytes(cfg, self._n_params, B, pos, 2, 2),
                "experts_min_bytes": work.experts_min_bytes(cfg, B, 2),
                "step_min_s": (work.min_step_seconds(cfg, self._n_params, B, pos, 2, 2,
                                                     self._peaks)
                               if self._peaks else None)}

    def advance(self, step, logits: jax.Array) -> jax.Array:
        """One decode through ``step(params, logits, state, cfg)``."""
        if self.pos >= self.max_len:
            raise RuntimeError(f"position {self.pos} would wrap the cache of {self.max_len}")
        with jax.set_mesh(self.mesh):
            logits, self.state = step(self.params, logits, self.state, self.cfg)
        self.pos += 1
        return logits

    def step(self, logits: jax.Array) -> jax.Array:
        return self.advance(decode, logits)

    def warm(self) -> None:
        """Two decode steps; the window goes on from where they leave the state."""
        self.v0 = self.step(self.step(self.v0)).block_until_ready()
        self.first = self.pos

    def free(self) -> None:
        """Drop the cache; keep the weights, the tokens and the routing."""
        # the call at position q attends to q + 1 cached positions
        self.work = self._work((self.first + self.pos - 1) / 2 + 1)
        self.tokens, self.experts = self.state["tokens"], self.state["experts"]
        self.state = self.step = None

    def expected(self, answers: dict[int, jax.Array]):
        """Compare answer t, the logits at position ``first + t``, with the
        reference's.  Answer t counts its logits off by more than
        ``tolerance["logit"]`` and the routing choices up to its position
        whose slack exceeds ``tolerance["routing"]``; the last answer also
        counts the logits, of all answers, off by more than
        ``tolerance["tail"]`` beyond a share ``tolerance["tail_share"]`` of
        them.  Yields (t, count) in order of t."""
        ts = sorted(answers)
        rows = np.array([self.first + t for t in ts])
        n = int(rows[-1]) + 1
        counts = np.zeros(len(ts), np.int64)
        tol = self.tol
        err, slack, off_tokens, tail = 0.0, -np.inf, 0, 0
        above = np.zeros(len(ERROR_STEPS), np.int64)
        over = np.zeros(len(SLACK_STEPS), np.int64)
        row_err, row_max = [], []
        t0 = time.monotonic()
        for b in range(self.items_per_call):
            ref, s = reference.logits_at(self.params, self.tokens[b, :n],
                                         self.experts[:, b, :n], jnp.asarray(rows), self.conf)
            prog = jnp.stack([answers[t][b] for t in ts])
            e = errors(prog, ref)
            counts += np.asarray(jnp.sum(e > tol["logit"], axis=-1))
            tail += int(jnp.sum(e > tol["tail"]))
            err = max(err, float(jnp.max(e)))
            row_err += np.sqrt(np.mean(np.square(np.asarray(e)), axis=-1)).tolist()
            row_max += np.asarray(jnp.max(e, axis=-1)).tolist()
            above += [int(jnp.sum(e > x)) for x in ERROR_STEPS]
            s = np.asarray(s)
            slack = max(slack, float(s.max()))
            over += [int(np.sum(s > x)) for x in SLACK_STEPS]
            # a routing choice counts against the first answer that depends on it
            off = np.flatnonzero(s > tol["routing"])
            off_tokens += len(off)
            np.add.at(counts, np.searchsorted(rows, off), 1)
            if b == 0:
                print(f"bench: reference of one sequence {time.monotonic() - t0:.3f} s",
                      file=sys.stderr, flush=True)
        checked = len(ts) * self.items_per_call * self.cfg.vocab
        counts[-1] += max(0, tail - int(tol["tail_share"] * checked))
        touched = [len(set(np.asarray(self.experts[layer, :, r]).ravel().tolist())
                       & set(range(self.cfg.first_expert_held,
                                   self.cfg.first_expert_held + self.cfg.experts_held)))
                   for r in rows for layer in range(self.experts.shape[0])]
        print(f"bench: reference {time.monotonic() - t0:.3f} s for {self.items_per_call} "
              f"sequences of {n} positions; largest logit error {err:.6g} of its row's "
              f"rms (limit {tol['logit']}); above {tol['tail']} of it {tail / checked:.4%} "
              f"of the logits (limit {tol['tail_share']:.2%}); logits above "
              f"{'/'.join(map(str, ERROR_STEPS))} of it: {'/'.join(map(str, above))} of "
              f"{checked}, rms error of a row "
              f"smallest {np.min(row_err):.6g} median {np.median(row_err):.6g} largest "
              f"{np.max(row_err):.6g}, largest error of the three worst rows "
              f"{'/'.join(f'{x:.6g}' for x in sorted(row_max)[-3:])}; routing slack at "
              f"most {slack:.6g} of the router logits' rms (limit {tol['routing']}), "
              f"{off_tokens} choices beyond it, tokens above "
              f"{'/'.join(map(str, SLACK_STEPS))} of it: {'/'.join(map(str, over))} of "
              f"{self.items_per_call * n}; "
              f"{np.mean(touched):.3f} distinct held experts touched per checked call "
              f"and MoE layer (expected "
              f"{work.experts_touched(self.cfg, self.items_per_call) / self.experts.shape[0]:.3f})",
              file=sys.stderr, flush=True)
        yield from zip(ts, counts.tolist())


def _replaced(app: App, module, name: str, make, at: int | None = None):
    """The program's decode with ``module.name`` replaced by
    ``make(module.name)``, compiled here, in set-up: at every call of the
    app's step, or at call ``at`` alone (the warm-up makes calls 0 and 1)."""
    with mock.patch.object(module, name, make(getattr(module, name))), jax.set_mesh(app.mesh):
        step = steps.__wrapped__(app.cfg)[1].lower(app.params, app.v0, app.state).compile()
    count = itertools.count()

    def call(v):
        if at is not None and next(count) != at:
            return app.advance(decode, v)
        return app.advance(lambda p, lg, s, _: step(p, lg, s), v)
    return call


def e4m3_experts(app: App):
    """The control: the program's decode with the held experts' output rounded
    to float8 e4m3 (``reduce_precision``, 4 exponent and 3 mantissa bits)."""
    return _replaced(app, moe, "grouped_ffn", lambda ffn: lambda *a, **kw: lax.reduce_precision(
        ffn(*a, **kw), exponent_bits=4, mantissa_bits=3))


FAULT_CALL = 2      # the window's first call, kept whenever the window has few calls


def wrong_token(app: App):
    """A planted fault in one slot: at the window's first call, sequence 0
    goes through the model with the token after the one it chose, while the
    state records the chosen one."""
    def make(forward):
        def run(params, batch, cfg, cache=None):
            tok = batch["tokens"]
            return forward(params, {**batch, "tokens": tok.at[0].set((tok[0] + 1) % cfg.vocab)},
                           cfg, cache)
        return run
    return _replaced(app, T, "forward", make, at=FAULT_CALL)


def wrong_expert(app: App):
    """A planted routing fault: at the window's first call, every token's
    second expert is the next one up (past the first), at its own softmax
    weight, and the state records it so."""
    def make(router):
        def route(x_flat, wr, c):
            w, idx, aux, mece = router(x_flat, wr, c)
            nxt = (idx[:, 1] + 1) % c.n_experts
            idx = idx.at[:, 1].set(jnp.where(nxt == idx[:, 0], (nxt + 1) % c.n_experts, nxt))
            logits = lax.dot(x_flat, wr.astype(x_flat.dtype), preferred_element_type=jnp.float32)
            w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)
            if c.norm_topk_prob:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            return w.astype(x_flat.dtype), idx, aux, mece
        return route
    return _replaced(app, moe, "_router", make, at=FAULT_CALL)


CONTROLS = {"e4m3": e4m3_experts, "token": wrong_token, "expert": wrong_expert}
