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

The DROPLESS form (`sigmoid_topk_routing`, `moe_dropless`; `paddle_tpu.nn.DroplessMoELayer`)
is the serving one: no capacity, no token ever dropped under any imbalance. A layer is told
which experts it HOLDS (`held=(first, count)` of the router's `num_experts`: one chip's share
of an expert-parallel deployment), routes over all of them and computes its own experts'
part of the result. Assignments are sorted by expert, each held expert's run is padded to
whole tiles of `tile` rows, and one loop walks the tiles that exist: work grows with the
assignments that land on held experts, never with tokens x k x experts. Its trip count is
data, so it is a forward-only form; training keeps the capacity form above.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.device import on_tpu


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


# -- the dropless form ---------------------------------------------------------

def f32_operands(a, b):
    """The operands of a product that is accumulated and returned in float32. The chip
    takes bfloat16 operands as they are; XLA's CPU runtime has no bfloat16 x bfloat16 ->
    float32 product for any but the smallest shapes, so off the chip (tests, rehearsals)
    they are widened."""
    if a.dtype != jnp.float32 and not on_tpu():
        return a.astype(jnp.float32), b.astype(jnp.float32)
    return a, b


def dot_f32(a, b):
    """a @ b accumulated and returned in float32."""
    return jnp.dot(*f32_operands(a, b), preferred_element_type=jnp.float32)


def sigmoid_topk_routing(x, router_w, select_bias, k, normalize=True, scale=1.0):
    """Sigmoid scores with a selection bias, in float32: x [T, d], router_w [d, E],
    select_bias [E] or None. The k experts of a token are the top k of `s + bias`; their
    weights are the scores `s` themselves (the bias only selects), divided by their sum
    where `normalize`, times `scale`. Returns (experts [T, k] int32, weights [T, k] f32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    pick = s if select_bias is None else s + select_bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(pick, k)
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if normalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights * scale


def dropless_tile(num_assignments, num_experts):
    """Rows a tile of the grouped products holds: a power of two from 8 to 256 near twice
    the assignments an expert expects under even routing, so that a decode step's few rows
    an expert are not padded to a prefill's tile and a prefill's many do not re-read an
    expert's weights once every 8 rows."""
    want = max(1, 2 * num_assignments // max(num_experts, 1))
    return int(min(256, max(8, 1 << (want - 1).bit_length())))


def moe_dropless(x, experts, weights, w_gate, w_up, w_down, held=None, num_experts=None,
                 tile=None, activation=jax.nn.silu):
    """The held experts' part of a gated-MLP expert layer, no token dropped.

    x [T, d]; experts, weights [T, k] from the router (over all `num_experts`); w_gate,
    w_up [count, d, f] and w_down [count, f, d] are the held experts' weights, expert
    `first + i` at row i. held=(first, count), default all. Returns (y [T, d] float32:
    sum over a token's held experts of weight * down(act(gate x) * up x), zero for a token
    with none; counts: int32 scalars `assignments_held`, `rows_computed` (tiles walked x
    rows a tile) and `experts_touched` (held experts with an assignment))."""
    T, d = x.shape
    k = experts.shape[1]
    count = w_gate.shape[0]
    first = 0 if held is None else int(held[0])
    if held is not None and int(held[1]) != count:
        raise ValueError(f"held={held} names {held[1]} experts, the weights hold {count}")
    E = int(num_experts or count)
    N = T * k
    tm = int(tile or dropless_tile(N, E))
    max_tiles = -(-N // tm) + count
    P_rows = max_tiles * tm

    # the sort and the gathers that lay the assignments out in expert order are the
    # router's; the loop over the tiles is `moe/experts`, the weighted sum `moe/combine`
    with jax.named_scope("moe/router"):
        flat_e = experts.reshape(N) - first
        is_held = (flat_e >= 0) & (flat_e < count)
        group = jnp.where(is_held, flat_e, count)             # the rest sorts last
        sizes = jnp.sum(group[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :],
                        axis=0, dtype=jnp.int32)
        tiles = (sizes + tm - 1) // tm                        # tiles an expert's run takes
        tile_end = jnp.cumsum(tiles)
        n_tiles = tile_end[-1]
        tile_start = tile_end - tiles
        run_start = jnp.cumsum(sizes) - sizes
        # gathers only (a sort and its inverse), no scatter: the assignments in expert order,
        # then for every padded row the assignment it carries, if any
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        tile_expert = jnp.minimum(jnp.searchsorted(
            tile_end, jnp.arange(max_tiles, dtype=jnp.int32), side="right"),
            count - 1).astype(jnp.int32)
        p_row = jnp.arange(P_rows, dtype=jnp.int32)
        tau = p_row // tm
        e_p = tile_expert[tau]
        r_p = (tau - tile_start[e_p]) * tm + p_row % tm
        carries = (tau < n_tiles) & (r_p < sizes[e_p])
        a_p = order[jnp.clip(run_start[e_p] + r_p, 0, N - 1)]
        src = jnp.where(carries, a_p // k, T)
        x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[src]   # [P_rows, d]
        # and for every assignment the padded row that carries it
        safe = jnp.minimum(group, count - 1)
        rank = jnp.argsort(order).astype(jnp.int32) - run_start[safe]
        row = jnp.clip(tile_start[safe] * tm + rank, 0, P_rows - 1)

    def body(t, y_pad):
        e = tile_expert[t]
        xt = jax.lax.dynamic_slice_in_dim(x_pad, t * tm, tm, 0)
        wg = jax.lax.dynamic_index_in_dim(w_gate, e, 0, keepdims=False)
        wu = jax.lax.dynamic_index_in_dim(w_up, e, 0, keepdims=False)
        wd = jax.lax.dynamic_index_in_dim(w_down, e, 0, keepdims=False)
        a = activation(dot_f32(xt, wg)) * dot_f32(xt, wu)
        yt = dot_f32(a.astype(x.dtype), wd)
        return jax.lax.dynamic_update_slice_in_dim(y_pad, yt.astype(x.dtype), t * tm, 0)

    with jax.named_scope("moe/experts"):
        y_pad = jax.lax.fori_loop(0, n_tiles, body, jnp.zeros((P_rows, d), x.dtype))
    with jax.named_scope("moe/combine"):
        picked = y_pad[row].astype(jnp.float32)               # [N, d]
        w_flat = jnp.where(is_held, weights.reshape(N), 0.0)
        y = jnp.sum((picked * w_flat[:, None]).reshape(T, k, d), axis=1)
    counts = {"assignments_held": jnp.sum(sizes), "rows_computed": n_tiles * tm,
              "experts_touched": jnp.sum(sizes > 0, dtype=jnp.int32)}
    return y, counts


def gated_mlp(x, w_gate, w_up, w_down, activation=jax.nn.silu):
    """down(act(gate x) * up x): one expert over all rows (a shared expert), float32."""
    a = activation(dot_f32(x, w_gate)) * dot_f32(x, w_up)
    return dot_f32(a.astype(x.dtype), w_down)


def moe_dropless_layer(x, router_w, select_bias, w_gate, w_up, w_down, k, shared=None,
                       held=None, normalize=True, scale=1.0, tile=None,
                       activation=jax.nn.silu, router_x=None):
    """Router, the held experts' part and the shared expert (`shared`: its (gate, up,
    down) or None), x [T, d]. The router's width is `num_experts`, whatever is held.
    `router_x`: the router's own input where the caller has x wider than the experts take
    it (the float32 it rounded to bfloat16: a router decides by differences of a hundredth
    between scores, and should not decide by a rounding). Returns (y [T, d] float32,
    counts: `assignments` (static), `assignments_held`, `rows_computed`,
    `experts_touched`)."""
    with jax.named_scope("moe/router"):
        experts, weights = sigmoid_topk_routing(x if router_x is None else router_x,
                                                router_w, select_bias, k, normalize, scale)
    y, counts = moe_dropless(x, experts, weights, w_gate, w_up, w_down, held=held,
                             num_experts=router_w.shape[1], tile=tile,
                             activation=activation)
    if shared is not None:
        with jax.named_scope("moe/shared"):
            y = y + gated_mlp(x, *shared, activation=activation)
    counts = dict(counts, assignments=jnp.int32(x.shape[0] * k))
    return y, counts
