"""Random weights of the `solar_open2` family from --seed, made on the device
one leaf at a time (3.3 B parameters through numpy would cost the set-up a
minute, and two sets at once do not fit beside the engine's state).

The benchmark makes the weights, never the program: `leaves` is the
benchmark's own table of every leaf's name, shape and distribution, worked
out from the configuration file's numbers. `initializer` hands the program's
constructor each leaf as it asks for it (and refuses a name or a shape the
table does not have); `flat` hands the plain reference the same numbers.
Every value is one that `round_to` holds (bfloat16 for the cell), so the
program's cast loses nothing and the comparison sees the arithmetic alone.

Distributions (the configuration file's `assumed` has the reasons): matrices
and tables normal(0, init_std), the head's normal(0, head_init_std) where the
file gives one; norm gains 1 + normal(0, init_std); the
router's selection bias normal(0, select_bias_std); convolution taps uniform
in +-1/sqrt(taps); A_log = log of uniform(1, 16) a head; dt_bias the inverse
softplus of exp(uniform(log 0.001, log 0.1)) a channel.

Where the file's `assumed.select_bias_balance` says so, that draw of the
selection bias is only where `balanced_biases` starts from: it then moves
each layer's bias, as the family's training moves it (the bias is there to
balance load without an auxiliary loss), until the experts take equal shares
of a calibration batch drawn from the seed, layer after layer through the
plain reference's own layers. A random router left as drawn favours the
experts whose rows happen to lie along what all tokens' hidden states have
in common, by the seed, and a share of 40 of 320 experts then does more or
less work than an eighth, by the seed (PERF.md, PR 34).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark import weights as _gpt_weights
from benchmark.reference import solar_open2 as _reference


def leaves(cfg):
    """[(name, shape, kind)] in a fixed order: a leaf's place in it is the
    stream its numbers are drawn from."""
    lin = cfg["linear_attn_config"]
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    Hk, dk, K = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    r = cfg["assumed"]["kda_low_rank"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    dep = cfg.get("deployment", {})
    E = dep.get("n_routed_experts_published", cfg["n_routed_experts"])
    count = cfg["n_routed_experts"]
    V = cfg["vocab_size"]
    out = [("embed.weight", (V, d), "matrix")]
    for l in range(L):
        pre = f"layers.{l}."
        out.append((pre + "norm1.weight", (d,), "gain"))
        if l in cfg["gqa_layers"]:
            a = pre + "attn."
            out += [(a + "q.weight", (d, H * hd), "matrix"),
                    (a + "k.weight", (d, KV * hd), "matrix"),
                    (a + "v.weight", (d, KV * hd), "matrix"),
                    (a + "g.weight", (d, H * hd), "matrix"),
                    (a + "o.weight", (H * hd, d), "matrix")]
        else:
            a = pre + "kda."
            out += [(a + n + ".weight", (d, Hk * dk), "matrix")
                    for n in ("q", "k", "v")]
            out += [(a + n + "_conv.weight", (K, Hk * dk), "taps")
                    for n in ("q", "k", "v")]
            out += [(a + "b.weight", (d, Hk), "matrix"),
                    (a + "f_down.weight", (d, r), "matrix"),
                    (a + "f_up.weight", (r, Hk * dk), "matrix"),
                    (a + "A_log", (Hk,), "a_log"),
                    (a + "dt_bias", (Hk * dk,), "dt_bias"),
                    (a + "g_down.weight", (d, r), "matrix"),
                    (a + "g_up.weight", (r, Hk * dk), "matrix"),
                    (a + "o_norm.weight", (dk,), "gain"),
                    (a + "o.weight", (Hk * dk, d), "matrix")]
        out.append((pre + "norm2.weight", (d,), "gain"))
        m = pre + "moe."
        out += [(m + "router.weight", (d, E), "matrix"),
                (m + "router.bias", (E,), "select_bias"),
                (m + "experts.gate", (count, d, f), "matrix"),
                (m + "experts.up", (count, d, f), "matrix"),
                (m + "experts.down", (count, f, d), "matrix")]
        if fs:
            out += [(m + "shared.gate.weight", (d, fs), "matrix"),
                    (m + "shared.up.weight", (d, fs), "matrix"),
                    (m + "shared.down.weight", (fs, d), "matrix")]
    out += [("norm.weight", (d,), "gain"),
            ("lm_head.weight", (d, V), "head")]
    return out


def n_params(cfg):
    return sum(math.prod(shape) for _, shape, _ in leaves(cfg))


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std", "bias_std",
                                             "head_std", "round_to"))
def _draw(key, shape, kind, std, bias_std, head_std, round_to):
    f32 = jnp.float32
    if kind in ("matrix", "head"):
        x = (head_std if kind == "head" else std) \
            * jax.random.normal(key, shape, f32)
    elif kind == "gain":
        x = 1.0 + std * jax.random.normal(key, shape, f32)
    elif kind == "select_bias":
        x = bias_std * jax.random.normal(key, shape, f32)
    elif kind == "taps":
        lim = 1.0 / math.sqrt(shape[0])
        x = jax.random.uniform(key, shape, f32, -lim, lim)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown kind {kind!r}")
    return x if round_to is None else x.astype(round_to)


@functools.partial(jax.jit, static_argnames=("D", "l"))
def _to_router(P, x, D, l):
    """x [n, s, d] through layer l's mixer: (x after it, the router's
    input [n * s, d], its sigmoid scores [n * s, E]), the reference's way."""
    pre = f"layers.{l}."
    mix, sub = (_reference._gqa, "attn.") if l in D.gqa_layers \
        else (_reference._kda, "kda.")
    h = _reference._rms(x, P[pre + "norm1.weight"], D.eps)
    x = x + jax.vmap(lambda hs: mix(P, pre + sub, hs, D, "float32"))(h)
    h = _reference._rms(x, P[pre + "norm2.weight"], D.eps).reshape(
        -1, x.shape[-1])
    return x, h, jax.nn.sigmoid(_reference._dot(
        "sd,de->se", h, P[pre + "moe.router.weight"], "float32"))


@functools.partial(jax.jit, static_argnames=("D", "l"))
def _past_experts(P, x, h, D, l):
    return x + _reference.moe_layer(P, f"layers.{l}.moe.", h, D).reshape(
        x.shape)


@functools.partial(jax.jit, static_argnames=("k", "rounds", "round_to"))
def _balance(scores, bias, k, rounds, round_to):
    """The bias [E] under which the top k of `scores + bias` [n, E] give
    every expert the same number of the n tokens, as nearly as steps on
    counts get: an expert with more than its share is lowered, one with
    less raised, by steps that shrink from 0.02 to 0.0002 (the 8th and 9th
    of a token's scores lie about 0.005 apart). The load is counted with the
    bias rounded as it will be stored."""
    n, E = scores.shape
    first, last = 2e-2, 2e-4

    def stored(b):
        return b if round_to is None else b.astype(round_to).astype(b.dtype)

    def step(i, b):
        _, chosen = jax.lax.top_k(scores + stored(b), k)
        load = jnp.zeros((E,), jnp.float32).at[chosen.reshape(-1)].add(1.0) \
            * (E / (n * k))
        rate = first * (last / first) ** (i / max(rounds - 1, 1))
        return b - rate * jnp.clip(load - 1.0, -1.0, 3.0)

    return stored(jax.lax.fori_loop(0, rounds, step,
                                    bias.astype(jnp.float32)))


_BALANCED = {}      # (the file's numbers, seed, round_to) -> {name: bias}


def balanced_biases(cfg, seed, round_to, table, draw):
    """{leaf name: bias} of every layer's selection bias once balanced (the
    module's docstring), or {} where the file asks for none. Worked out once
    a process for a configuration and seed: the program's constructor and
    the reference's `flat` are handed the same numbers."""
    spec = cfg["assumed"].get("select_bias_balance")
    if not spec:
        return {}
    memo = (json.dumps(cfg, sort_keys=True), int(seed), str(round_to))
    if memo not in _BALANCED:
        D = _reference.dims_of(cfg)
        key = jax.random.fold_in(_gpt_weights.seed_key(seed), len(table))
        ids = jax.random.randint(
            key, (int(spec["sequences"]), int(spec["length"])), 0,
            cfg["vocab_size"])
        x = draw("embed.weight")[ids].astype(jnp.float32)
        out = {}
        for l in range(D.layers):
            pre = f"layers.{l}."
            P = {name: draw(name) for name in table if name.startswith(pre)}
            x, h, scores = _to_router(P, x, D, l)
            name = pre + "moe.router.bias"
            bias = _balance(scores, P[name], D.top_k, int(spec["rounds"]),
                            round_to)
            P[name] = out[name] = bias if round_to is None \
                else bias.astype(round_to)
            x = _past_experts(P, x, h, D, l)
            del P
        _BALANCED[memo] = out
    return _BALANCED[memo]


def _maker(cfg, seed, round_to):
    table = {name: (i, tuple(shape), kind)
             for i, (name, shape, kind) in enumerate(leaves(cfg))}
    key = _gpt_weights.seed_key(seed)
    std = float(cfg["assumed"]["init_std"])
    bias_std = float(cfg["assumed"]["select_bias_std"])
    head_std = float(cfg["assumed"].get("head_init_std", std))

    def draw(name):
        i, shape, kind = table[name]
        return _draw(jax.random.fold_in(key, i), shape, kind, std, bias_std,
                     head_std, round_to)

    balanced = balanced_biases(cfg, seed, round_to, table, draw)

    def make(name):
        return balanced[name] if name in balanced else draw(name)

    return table, make


def initializer(cfg, seed, round_to=None):
    """`initializer(name, shape, kind, dtype)` for the program's constructor:
    the benchmark's numbers for that leaf, in `dtype`. The program's own
    `kind` is not read."""
    table, make = _maker(cfg, seed, round_to)
    asked = set()

    def init(name, shape, kind, dtype):
        if name not in table or tuple(shape) != table[name][1]:
            raise KeyError(f"the program asks for {name} {tuple(shape)}; the "
                           f"benchmark's table has {table.get(name)}")
        asked.add(name)
        return make(name).astype(dtype)

    init.missing = lambda: sorted(set(table) - asked)
    return init


def flat(cfg, seed, round_to=None):
    """{name: array} of every leaf, in the dtype it was rounded to."""
    table, make = _maker(cfg, seed, round_to)
    return {name: make(name) for name in table}
