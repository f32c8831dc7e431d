"""Mixture-of-Experts with expert parallelism (GShard/Switch-style).

No reference equivalent — SURVEY.md §2.3 (last row) records expert parallelism as
ABSENT in thisjiang/Paddle and requires the TPU build to exceed the reference here.

TPU-native design (not a port of any CUDA MoE):
- gating/dispatch/combine are einsums over a *static* capacity axis, so every shape is
  fixed at trace time and XLA tiles the expert FFN matmuls onto the MXU as one batched
  [E, tokens_per_expert, d] x [E, d, dff] contraction;
- expert parallelism = `shard_map` over the 'ep' mesh axis with two
  `jax.lax.all_to_all`s (tokens -> owning expert rank and back), the ICI-native
  equivalent of the NCCL alltoall a GPU MoE would use;
- the load-balance auxiliary loss is the GShard loss: E * sum_e(frac_tokens_e * mean_prob_e).

All functions here are pure jnp functions over raw arrays (usable under jit/vjp);
`paddle_tpu.nn.MoELayer` wraps them for the Layer API.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def compute_capacity(num_tokens, num_experts, k, capacity_factor, multiple_of=4):
    """Static per-shard expert capacity: ceil(k*T/E * factor), padded up."""
    cap = int(math.ceil(num_tokens * k / num_experts * capacity_factor))
    cap = max(multiple_of, ((cap + multiple_of - 1) // multiple_of) * multiple_of)
    return min(cap, num_tokens)


def topk_gating(logits, k, capacity):
    """Top-k gating with static capacity.

    logits: [T, E]. Returns (combine [T, E, C] f32, dispatch [T, E, C] bool, aux_loss).

    Tokens beyond an expert's capacity (in token order, higher-priority choice first —
    the GShard policy) are dropped for that expert; combine weights are the top-k
    softmax probabilities renormalized over the *kept* choices.
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)  # [T, k]

    counts = jnp.zeros((E,), jnp.int32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), bool)
    kept_prob_sum = jnp.zeros((T,), jnp.float32)

    for j in range(k):
        idx_j = topi[:, j]  # [T]
        mask_j = jax.nn.one_hot(idx_j, E, dtype=jnp.int32)  # [T, E]
        # position of each token in its chosen expert's queue (this choice level)
        pos_in_expert = jnp.cumsum(mask_j, axis=0) - 1 + counts[None, :]  # [T, E]
        pos_j = jnp.sum(pos_in_expert * mask_j, axis=1)  # [T]
        keep = pos_j < capacity
        counts = counts + jnp.sum(mask_j, axis=0)
        onehot_pos = jax.nn.one_hot(pos_j, capacity, dtype=jnp.float32)  # [T, C]
        sel = (mask_j.astype(jnp.float32) * keep[:, None].astype(jnp.float32))  # [T, E]
        disp_j = sel[:, :, None] * onehot_pos[:, None, :]  # [T, E, C]
        dispatch = dispatch | (disp_j > 0)
        combine = combine + topv[:, j][:, None, None] * disp_j
        kept_prob_sum = kept_prob_sum + topv[:, j] * keep.astype(jnp.float32)

    # renormalize combine weights over kept choices
    denom = jnp.where(kept_prob_sum > 0, kept_prob_sum, 1.0)
    combine = combine / denom[:, None, None]

    # GShard load-balance loss over the top-1 assignment
    frac_tokens = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(frac_tokens * mean_prob)
    return combine, dispatch, aux_loss


def expert_ffn(xe, w1, b1, w2, b2, activation=jax.nn.gelu):
    """Batched per-expert FFN. xe: [E, C, d]; w1: [E, d, f]; w2: [E, f, d]."""
    h = jnp.einsum("ecd,edf->ecf", xe, w1) + b1[:, None, :]
    h = activation(h)
    return jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]


def moe_dense(x, gate_w, w1, b1, w2, b2, k=2, capacity_factor=2.0,
              activation=jax.nn.gelu):
    """Single-shard MoE: x [T, d] through E experts. Returns (out [T, d], aux_loss)."""
    T, d = x.shape
    E = gate_w.shape[1]
    capacity = compute_capacity(T, E, k, capacity_factor)
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    combine, dispatch, aux = topk_gating(logits, k, capacity)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)  # [E, C, d]
    ye = expert_ffn(xe, w1, b1, w2, b2, activation)
    out = jnp.einsum("tec,ecd->td", combine.astype(ye.dtype), ye)
    return out.astype(x.dtype), aux


def moe_spmd(x, gate_w, w1, b1, w2, b2, k=2, capacity_factor=2.0,
             activation=jax.nn.gelu, axis_name="ep"):
    """Expert-parallel MoE body for use inside shard_map.

    x: [T_local, d] this rank's tokens. w1/b1/w2/b2 hold only this rank's local
    experts ([E_local, ...]); gate_w is replicated [d, E_total]. Tokens are routed to
    the rank owning their expert with all_to_all over `axis_name` and routed back
    after the expert FFN.
    """
    ep = jax.lax.psum(1, axis_name)
    T, d = x.shape
    E = gate_w.shape[1]
    E_local = w1.shape[0]
    assert E_local * ep == E, "experts must shard evenly over the ep axis"
    capacity = compute_capacity(T, E, k, capacity_factor)

    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    combine, dispatch, aux = topk_gating(logits, k, capacity)

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)  # [E, C, d]
    # group by owning rank and exchange: [ep, E_local, C, d] -> rows from every rank
    xe = xe.reshape(ep, E_local, capacity, d)
    xe = jax.lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=0, tiled=False)
    # now axis 0 = source rank; fold into the capacity axis per local expert
    xe = jnp.moveaxis(xe, 0, 1).reshape(E_local, ep * capacity, d)

    ye = expert_ffn(xe, w1, b1, w2, b2, activation)

    ye = jnp.moveaxis(ye.reshape(E_local, ep, capacity, d), 1, 0)
    ye = jax.lax.all_to_all(ye, axis_name, split_axis=0, concat_axis=0, tiled=False)
    ye = ye.reshape(E, capacity, d)

    out = jnp.einsum("tec,ecd->td", combine.astype(ye.dtype), ye).astype(x.dtype)
    return out, jax.lax.pmean(aux, axis_name)


def expert_parallel_moe(x, gate_w, w1, b1, w2, b2, mesh, k=2, capacity_factor=2.0,
                        activation=jax.nn.gelu, axis_name="ep"):
    """shard_map wrapper: x [T, d] sharded on tokens, experts sharded over `axis_name`.

    Returns (out [T, d], aux_loss scalar). Differentiable.
    """
    body = functools.partial(moe_spmd, k=k, capacity_factor=capacity_factor,
                             activation=activation, axis_name=axis_name)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name, None), P(None, None),
                  P(axis_name, None, None), P(axis_name, None),
                  P(axis_name, None, None), P(axis_name, None)),
        out_specs=(P(axis_name, None), P()),
    )
    return fn(x, gate_w, w1, b1, w2, b2)
