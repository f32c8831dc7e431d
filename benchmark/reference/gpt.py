"""The plain reference: GPT-2 as published, in straightforward jax.numpy.

Float32, every product at matmul precision "highest", no kernels, no cache,
no batching tricks: token + position embedding, pre-LN blocks (causal
softmax(QK^T / sqrt(d)) V attention, `gelu_new` MLP), final layer norm, the
tied table as the head; for training the mean cross-entropy over every
position against given labels, its gradients, and AdamW with decoupled
decay as python/paddle/optimizer/adamw.py defines it. It imports nothing of
the program and is handed the weights the benchmark made (weights.stacked).

`precision` puts the reference in the program's place at a lower precision
(the control that `correct` has to fail): "bfloat16" rounds both operands of
every product to bfloat16, "fp8" to float8_e4m3fn with one scale a tensor
(amax -> 448), each accumulating in float32; gradients pass straight through
the rounding. `fault` plants one of the
faults a training cell can have.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q(x, precision):
    """Round an operand to `precision`; the gradient passes straight
    through (the rounding is of the forward operands, as a lower-precision
    path of the program would round them; cotangents stay float32)."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        low = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return (x + jax.lax.stop_gradient(low - x)).astype(jnp.bfloat16)


def _dot(eq, a, b, precision):
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    return jnp.einsum(eq, _q(a, precision), _q(b, precision),
                      preferred_element_type=jnp.float32)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(x, W, n_head, eps, precision):
    b, s, h = x.shape
    d = h // n_head
    a = _ln(x, W["ln1_w"], W["ln1_b"], eps)
    qkv = _dot("bsh,hk->bsk", a, W["qkv_w"], precision) + W["qkv_b"]
    q, k, v = (t.reshape(b, s, n_head, d) for t in jnp.split(qkv, 3, -1))
    att = _dot("bqnd,bknd->bnqk", q, k, precision) / jnp.sqrt(
        jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = _dot("bnqk,bknd->bqnd", att, v, precision).reshape(b, s, h)
    x = x + _dot("bsh,hk->bsk", o, W["proj_w"], precision) + W["proj_b"]
    a = _ln(x, W["ln2_w"], W["ln2_b"], eps)
    a = _gelu_new(_dot("bsh,hk->bsk", a, W["fc1_w"], precision)
                  + W["fc1_b"])
    return x + _dot("bsk,kh->bsh", a, W["fc2_w"], precision) + W["fc2_b"]


def logits(P, ids, n_head, eps=1e-5, precision="float32", remat=False):
    """ids [b, s] -> logits [b, s, rows of the table]."""
    s = ids.shape[1]
    x = P["wte"][ids] + P["wpe"][:s]

    def body(x, W):
        return _block(x, W, n_head, eps, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, P["blocks"])
    x = _ln(x, P["lnf_w"], P["lnf_b"], eps)
    return _dot("bsh,vh->bsv", x, P["wte"], precision)


def loss(P, ids, labels, n_head, eps=1e-5, precision="float32"):
    """Mean cross-entropy of every position's logits against `labels`."""
    lg = logits(P, ids, n_head, eps, precision, remat=True)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision",
                                             "rows"))
def loss_and_grads(P, ids, labels, n_head, eps, precision, rows):
    """Loss and gradients of the batch mean, row block by row block so
    that the activations of one block of `rows` rows live at a time."""
    b = ids.shape[0]
    blocks = b // rows
    ids = ids.reshape(blocks, rows, -1)
    labels = labels.reshape(blocks, rows, -1)

    def one(carry, xs):
        l_acc, g_acc = carry
        l, g = jax.value_and_grad(loss)(P, xs[0], xs[1], n_head, eps,
                                        precision)
        return (l_acc + l / blocks,
                jax.tree_util.tree_map(lambda a, x: a + x / blocks,
                                       g_acc, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, P)
    (l, g), _ = jax.lax.scan(one, (jnp.zeros((), jnp.float32), zero),
                             (ids, labels))
    return l, g


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(0, 2, 3))
def adamw(P, G, M, V, t, hp):
    """One AdamW step as the program's optimizer defines it: decay first
    (p *= 1 - lr*wd), then Adam with lr_t = lr*sqrt(1-b2^t)/(1-b1^t) and
    epsilon added to sqrt(v) uncorrected. hp = (lr, b1, b2, eps, wd)."""
    lr, b1, b2, eps, wd = hp
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)

    def leaf(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        return p * (1 - lr * wd) - lr_t * m / (jnp.sqrt(v) + eps), m, v

    out = jax.tree_util.tree_map(leaf, P, G, M, V)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    """L2 norm of every leaf; stacked block leaves give one a layer."""
    out = {k: jnp.sqrt(jnp.sum(v * v)) for k, v in tree.items()
           if k != "blocks"}
    out["blocks"] = {
        k: jnp.sqrt(jnp.sum(v * v, axis=tuple(range(1, v.ndim))))
        for k, v in tree["blocks"].items()}
    return out


SAMPLE = 4096


def sample_index(n, k=SAMPLE):
    """Evenly spaced positions of a flattened leaf of n elements."""
    k = min(n, k)
    return (jnp.arange(k) * n) // k


@jax.jit
def leaf_samples(tree):
    """An evenly spaced sample of every leaf's elements (SAMPLE at most);
    stacked block leaves give one row a layer."""
    out = {k: v.reshape(-1)[sample_index(v.size)]
           for k, v in tree.items() if k != "blocks"}
    out["blocks"] = {
        k: v.reshape(v.shape[0], -1)[:, sample_index(v[0].size)]
        for k, v in tree["blocks"].items()}
    return out


@jax.jit
def _diff(a, b):
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def train_readings(P0, batches, n_head, eps, hp, precision="float32",
                   rows=1, fault=None):
    """Three steps from P0 on `batches` [(ids, labels)]: the losses, the
    per-leaf norm of the first gradient (and an evenly spaced sample of its
    elements) and of the parameters' change after the last step, as
    stacked trees of host numbers.

    fault="half_batch": the second half of every batch is left out and
    the mean taken over the rest."""
    P = jax.tree_util.tree_map(jnp.copy, P0)
    M = jax.tree_util.tree_map(jnp.zeros_like, P)
    V = jax.tree_util.tree_map(jnp.zeros_like, P)
    losses, grad_norm, grad_sample = [], None, None
    for t, (ids, labels) in enumerate(batches, start=1):
        if fault == "half_batch":
            half = max(1, ids.shape[0] // 2)
            ids, labels = ids[:half], labels[:half]
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        r = rows if ids.shape[0] % rows == 0 else 1
        l, G = loss_and_grads(P, jnp.asarray(ids), jnp.asarray(labels),
                              n_head, eps, precision, r)
        losses.append(l)
        if t == 1:
            grad_norm, grad_sample = leaf_norms(G), leaf_samples(G)
        P, M, V = adamw(P, G, M, V, jnp.float32(t), hp)
        del G
    delta_norm = leaf_norms(_diff(P, P0))
    return jax.device_get({"loss": losses, "grad_norm": grad_norm,
                           "grad_sample": grad_sample,
                           "delta_norm": delta_norm})


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def sequence_logits(P, ids, n_head, eps, precision):
    """ids [s] (one sequence, right-padded: padding is causally invisible
    to what precedes it) -> logits [s, rows]; row j predicts token j + 1."""
    return logits(P, ids[None], n_head, eps, precision)[0]
