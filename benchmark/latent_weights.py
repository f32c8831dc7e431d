"""Random weights of the `axk1` family from --seed, made on the device one leaf
at a time, as benchmark/hybrid_weights.py makes the other family's (whose
`_draw` this uses: matrices and tables normal(0, init_std), the head's
normal(0, head_init_std), norm gains 1 + normal(0, init_std); every value one
that `round_to` holds).

`leaves` is the benchmark's own table of every leaf's name, shape and
distribution, worked out from the configuration file's numbers. `initializer`
hands the program's constructor each leaf as it asks for it (and refuses a name
or a shape the table does not have); `flat` hands the plain reference the same
numbers.

**The router's balance.** This family's router has no selection bias
(`topk_method` "none"): its 8 experts a token are the 8 largest sigmoid scores
themselves. A random router left as drawn favours the experts whose columns
lie along what all tokens' hidden states have in common, by the seed (PERF.md,
PR 34), and a share of 12 of 192 experts then does more or less work than a
sixteenth, by the seed. A trained router does not: the config names a
sequence-wise balance loss (`seq_aux`). Where the file's
`assumed.router_balance` says so, each expert layer's router matrix is
balanced INSIDE ITSELF: over a calibration batch drawn from the seed, layer
after layer through the plain reference's own layers, with u the unit vector
along the mean of the router's inputs h and a = h . u,

    W_r  <-  W_r + u (delta / mean(a))^T

adds about delta_e to expert e's logit for every token (exactly a / mean(a)
times it), and delta is stepped, as `hybrid_weights._balance` steps a bias,
until the 192 experts take equal shares of the batch's assignments, the load
counted with every token's own a. The matrix is then rounded as it will be
stored. Nothing but `router.weight` moves.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark import hybrid_weights as _hybrid
from benchmark import weights as _gpt_weights
from benchmark.reference import axk1 as _reference


def leaves(cfg):
    """[(name, shape, kind)] in a fixed order: a leaf's place in it is the
    stream its numbers are drawn from."""
    d, L, H = cfg["hidden_size"], cfg["num_hidden_layers"], \
        cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    E = cfg.get("deployment", {}).get("n_routed_experts_published",
                                      cfg["n_routed_experts"])
    count, V, w = cfg["n_routed_experts"], cfg["vocab_size"], \
        cfg["intermediate_size"]
    out = [("embed.weight", (V, d), "matrix")]
    for l in range(L):
        pre = f"layers.{l}."
        a = pre + "attn."
        out += [(pre + "norm1.weight", (d,), "gain"),
                (a + "q_a.weight", (d, rq), "matrix"),
                (a + "q_norm.weight", (rq,), "gain"),
                (a + "q_b.weight", (rq, H * (dn + dr)), "matrix"),
                (a + "kv_a.weight", (d, r + dr), "matrix"),
                (a + "kv_norm.weight", (r,), "gain"),
                (a + "kv_b.weight", (r, H * (dn + dv)), "matrix"),
                (a + "o.weight", (H * dv, d), "matrix"),
                (pre + "norm2.weight", (d,), "gain")]
        if l < cfg["first_k_dense_replace"]:
            m = pre + "mlp."
            out += [(m + "gate.weight", (d, w), "matrix"),
                    (m + "up.weight", (d, w), "matrix"),
                    (m + "down.weight", (w, d), "matrix")]
            continue
        m = pre + "moe."
        out += [(m + "router.weight", (d, E), "matrix"),
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


@functools.partial(jax.jit, static_argnames=("D", "l"))
def _past_attention(P, x, D, l):
    """x [n, s, d] through layer l's attention: (x after it, the feed-
    forward's input [n * s, d]), the reference's way."""
    pre = f"layers.{l}."
    h = _reference._rms(x, P[pre + "norm1.weight"], D.eps)
    x = x + jax.vmap(lambda hs: _reference.attn_layer(P, pre + "attn.", hs,
                                                      D))(h)
    return x, _reference._rms(x, P[pre + "norm2.weight"], D.eps).reshape(
        -1, x.shape[-1])


@functools.partial(jax.jit, static_argnames=("D", "l"))
def _past_ffn(P, x, h, D, l):
    return x + _reference.ffn_layer(P, l, h, D).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("k", "rounds", "round_to"))
def balanced_router(h, w, k, rounds, round_to):
    """The router matrix w [d, E] balanced over its inputs h [n, d] (the
    module's docstring): steps on every expert's logit offset shrinking from
    0.3 to 0.0005 (the 8th and 9th of a token's 192 logits lie about 0.1
    apart), an expert with more than its share lowered, one with less raised.
    Returns (the matrix, rounded as stored; the offsets delta [E])."""
    n, E = h.shape[0], w.shape[1]
    first, last = 3e-1, 5e-4
    mean = jnp.mean(h, axis=0)
    u = mean / jnp.maximum(jnp.linalg.norm(mean), 1e-30)
    a = jnp.dot(h, u, precision=jax.lax.Precision.HIGHEST)
    a = a / jnp.mean(a)
    z = jnp.dot(h, w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)

    def step(i, delta):
        _, chosen = jax.lax.top_k(z + a[:, None] * delta, k)
        load = jnp.zeros((E,), jnp.float32).at[chosen.reshape(-1)].add(1.0) \
            * (E / (n * k))
        rate = first * (last / first) ** (i / max(rounds - 1, 1))
        return delta - rate * jnp.clip(load - 1.0, -1.0, 3.0)

    delta = jax.lax.fori_loop(0, rounds, step, jnp.zeros((E,), jnp.float32))
    out = w.astype(jnp.float32) \
        + u[:, None] * (delta / jnp.mean(jnp.dot(h, u)))[None, :]
    return (out if round_to is None else out.astype(round_to)), delta


_BALANCED = {}      # (the file's numbers, seed, round_to) -> {name: matrix}


def balanced_routers(cfg, seed, round_to, table, draw):
    """{leaf name: matrix} of every expert layer's router once balanced, or
    {} where the file asks for none. Worked out once a process for a
    configuration and seed: the program's constructor and the reference's
    `flat` are handed the same numbers."""
    spec = cfg["assumed"].get("router_balance")
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
            x, h = _past_attention(P, x, D, l)
            name = pre + "moe.router.weight"
            if name in P:
                P[name] = out[name] = balanced_router(
                    h, P[name], D.top_k, int(spec["rounds"]), round_to)[0]
            x = _past_ffn(P, x, h, D, l)
            del P
        _BALANCED[memo] = out
    return _BALANCED[memo]


def _maker(cfg, seed, round_to):
    table = {name: (i, tuple(shape), kind)
             for i, (name, shape, kind) in enumerate(leaves(cfg))}
    key = _gpt_weights.seed_key(seed)
    std = float(cfg["assumed"]["init_std"])
    head_std = float(cfg["assumed"].get("head_init_std", std))

    def draw(name):
        i, shape, kind = table[name]
        return _hybrid._draw(jax.random.fold_in(key, i), shape, kind, std,
                             0.0, head_std, round_to)

    balanced = balanced_routers(cfg, seed, round_to, table, draw)

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
