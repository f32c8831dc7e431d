"""Random weights from --seed, made on the device in one jitted call.

The benchmark makes the weights, never the program: the same call feeds the
program's model (`flat`, under the program's parameter names) and the plain
reference (`stacked`, one array per kind of leaf with the layers leading).
Both are the same numbers. `round_to="bfloat16"` rounds every value to one
that bfloat16 holds (kept as float32), for a configuration that is served
in bfloat16: the program's cast then loses nothing, and the comparison with
the reference sees the arithmetic alone.
"""
import functools

import jax
import jax.numpy as jnp

#: stacked leaf -> (program's name inside a block, shape from (h,), kind)
BLOCK_LEAVES = {
    "ln1_w": ("ln1.weight", lambda h: (h,), "gain"),
    "ln1_b": ("ln1.bias", lambda h: (h,), "bias"),
    "qkv_w": ("attn.qkv.weight", lambda h: (h, 3 * h), "matrix"),
    "qkv_b": ("attn.qkv.bias", lambda h: (3 * h,), "bias"),
    "proj_w": ("attn.proj.weight", lambda h: (h, h), "matrix"),
    "proj_b": ("attn.proj.bias", lambda h: (h,), "bias"),
    "ln2_w": ("ln2.weight", lambda h: (h,), "gain"),
    "ln2_b": ("ln2.bias", lambda h: (h,), "bias"),
    "fc1_w": ("mlp.fc1.weight", lambda h: (h, 4 * h), "matrix"),
    "fc1_b": ("mlp.fc1.bias", lambda h: (4 * h,), "bias"),
    "fc2_w": ("mlp.fc2.weight", lambda h: (4 * h, h), "matrix"),
    "fc2_b": ("mlp.fc2.bias", lambda h: (h,), "bias"),
}
TOP_LEAVES = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
              "lnf_w": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}
_MATRICES = tuple(suffix for suffix, _, kind in BLOCK_LEAVES.values()
                  if kind == "matrix") + ("wte.weight", "wpe.weight")


def is_matrix(name):
    """Whether the program's leaf `name` has two dimensions: a block's
    matrix or a table, never a gain or a bias."""
    return name.endswith(_MATRICES)


def seed_key(seed):
    """A PRNG key from any whole number up to past 2**32 (a plain
    PRNGKey(seed) overflows beyond 31 bits when x64 is off)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def dims(cfg):
    return (cfg["n_embd"], cfg["n_layer"],
            cfg.get("assumed", {}).get("vocab_rows", cfg["vocab_size"]),
            cfg["n_positions"],
            float(cfg.get("assumed", {}).get("init_std", 0.02)))


def _stacked(key, dims_, round_to):
    h, L, rows, positions, std = dims_

    def draw(i, shape, kind):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if kind == "gain":
            x = 1.0 + x
        if round_to is not None:
            x = x.astype(round_to).astype(jnp.float32)
        return x

    out = {"wte": draw(0, (rows, h), "matrix"),
           "wpe": draw(1, (positions, h), "matrix"),
           "lnf_w": draw(2, (h,), "gain"), "lnf_b": draw(3, (h,), "bias"),
           "blocks": {}}
    for i, (name, (_, shape, kind)) in enumerate(BLOCK_LEAVES.items()):
        out["blocks"][name] = draw(10 + i, (L,) + shape(h), kind)
    return out


def flat_names(L):
    """[(program name, stacked leaf, layer or None)] for L layers, in a
    fixed order."""
    names = [(prog, top, None) for top, prog in TOP_LEAVES.items()]
    for i in range(L):
        for leaf, (suffix, _, _) in BLOCK_LEAVES.items():
            names.append((f"gpt.blocks.{i}.{suffix}", leaf, i))
    return names


@functools.partial(jax.jit, static_argnames=("dims_", "round_to"))
def _stacked_jit(key, dims_, round_to):
    return _stacked(key, dims_, round_to)


@functools.partial(jax.jit, static_argnames=("dims_", "round_to"))
def _flat_jit(key, dims_, round_to):
    return unstack(_stacked(key, dims_, round_to), dims_[1])


def unstack(tree, n_layers):
    """Stacked tree -> {program name: array}."""
    out = {}
    for prog, leaf, i in flat_names(n_layers):
        out[prog] = tree[leaf] if i is None else tree["blocks"][leaf][i]
    return out


def stacked(cfg, seed, round_to=None):
    return _stacked_jit(seed_key(seed), dims(cfg), round_to)


def flat(cfg, seed, round_to=None):
    return _flat_jit(seed_key(seed), dims(cfg), round_to)
