"""Long-context sequence/context parallelism: ring attention + Ulysses.

No reference equivalent — SURVEY.md §5 records SP/CP as ABSENT in thisjiang/Paddle
(sequence length there is scaled only via recompute/pipeline). These are TPU-native
additions required by the build plan (SURVEY.md §2.3 last row, §7 step 7):

- ring attention: sequence-sharded Q stays resident; K/V blocks rotate around the ICI
  ring with jax.lax.ppermute while a running (max, sum, acc) online-softmax merges each
  block — memory O(seq/N), compute overlapped with the rotation.
- Ulysses: all_to_all swaps the sharded axis from sequence to heads before standard
  attention and back after — cheap on ICI, needs heads % sp == 0.

Both are pure functions over raw arrays meant to be called inside shard_map bodies
(axis name 'sp'); `ring_attention`/`ulysses_attention` wrap them for Layer use.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .spmd import _pvary as _vary   # the ONE device-varying carry helper


# the one source of truth for sequence-parallel attention impl names
# (GPTConfig validates against this same tuple)
VALID_SP_IMPLS = ("ring", "ring_flash", "ulysses", "ulysses_flash")


def _block_attn(q, k, v, scale, causal_mask=None):
    """Plain softmax stats for one K/V block: returns (acc, m, l)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal_mask is not None:
        s = jnp.where(causal_mask, s, -1e30)
    m = jnp.max(s, axis=-1)  # [b,h,q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return acc, m, l


def ring_attention_spmd(q, k, v, axis_name="sp", causal=False):
    """Blockwise ring attention inside shard_map.

    q,k,v: [batch, seq_shard, heads, head_dim] (this rank's sequence shard).
    Rotates K/V around the ring; merges blocks with online softmax.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, h, d = q.shape

    def mask_for(block_rank):
        if not causal:
            return None
        # global positions: q at idx*sq + i ; k at block_rank*sq + j
        qi = idx * sq + jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
        kj = block_rank * sq + jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
        return (qi >= kj)[None, None]  # [1,1,q,k]

    def body(i, carry):
        k_blk, v_blk, acc, m_run, l_run = carry
        src_rank = (idx - i) % n  # which rank's K/V we now hold
        blk_acc, m_blk, l_blk = _block_attn(q, k_blk, v_blk, scale, mask_for(src_rank))
        m_new = jnp.maximum(m_run, m_blk)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_blk - m_new)
        l_new = l_run * alpha + l_blk * beta
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + blk_acc * beta.transpose(0, 2, 1)[..., None]
        # rotate K/V to the next rank (ride the ICI ring)
        perm = [(r, (r + 1) % n) for r in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_next, v_next, acc, m_new, l_new

    acc0 = _vary(jnp.zeros((b, sq, h, d), jnp.float32), axis_name)
    m0 = _vary(jnp.full((b, h, sq), -1e30, jnp.float32), axis_name)
    l0 = _vary(jnp.zeros((b, h, sq), jnp.float32), axis_name)
    _, _, acc, m_fin, l_fin = jax.lax.fori_loop(
        0, n, body, (k.astype(jnp.float32), v.astype(jnp.float32), acc0, m0, l0)
    )
    out = acc / l_fin.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring attention with per-block Pallas flash kernels (forward AND backward).
# ---------------------------------------------------------------------------

def _fold_heads(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _unfold_heads(x3, b, h):
    bh, s, d = x3.shape
    return jnp.swapaxes(x3.reshape(b, h, s, d), 1, 2)


def _ring_flash_fwd(q, k, v, axis_name, causal, interpret):
    """Ring forward with flash-kernel blocks: returns (out [b,sq,h,d],
    lse [b*h, sq] f32 — the GLOBAL row logsumexp, exactly what the flash
    backward kernels need per ring pair)."""
    from ..ops import flash_attention as fa

    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    q3 = _fold_heads(q)

    def pair(k3, v3, src):
        if not causal:
            return fa._flash_fwd(q3, k3, v3, False, scale, interpret)
        return jax.lax.switch(
            jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2)),
            (lambda: fa._flash_fwd(q3, k3, v3, False, scale, interpret),
             lambda: fa._flash_fwd(q3, k3, v3, True, scale, interpret),
             lambda: (jnp.zeros_like(q3),
                      jnp.full((b * h, sq), -1e30, jnp.float32))))

    def body(i, carry):
        k3_blk, v3_blk, o_run, lse_run = carry
        src = (idx - i) % n
        o_blk, lse_blk = pair(k3_blk, v3_blk, src)
        # merge normalized per-block outputs via logsumexp weights:
        # sum_i o_i * exp(lse_i - lse_tot) == acc_tot / l_tot
        lse_new = jnp.logaddexp(lse_run, lse_blk)
        o_run = (o_run * jnp.exp(lse_run - lse_new)[..., None]
                 + o_blk.astype(jnp.float32)
                 * jnp.exp(lse_blk - lse_new)[..., None])
        perm = [(r, (r + 1) % n) for r in range(n)]
        return (jax.lax.ppermute(k3_blk, axis_name, perm),
                jax.lax.ppermute(v3_blk, axis_name, perm), o_run, lse_new)

    o0 = _vary(jnp.zeros((b * h, sq, d), jnp.float32), axis_name)
    lse0 = _vary(jnp.full((b * h, sq), -1e30, jnp.float32), axis_name)
    # fold heads ONCE; the ring carries [b*h, sq, d] blocks (ppermute is
    # layout-agnostic), avoiding per-hop transpose copies
    _, _, o_fin, lse_fin = jax.lax.fori_loop(
        0, n, body, (_fold_heads(k), _fold_heads(v), o0, lse0))
    return _unfold_heads(o_fin, b, h).astype(q.dtype), lse_fin


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention_spmd(q, k, v, axis_name="sp", causal=False,
                              interpret=False):
    """Ring attention whose per-block math runs the Pallas flash kernels
    (ops/flash_attention.py) instead of materializing [sq, sq] score
    blocks: per-rank memory O(sq * blk) in the kernel, O(sq) merge state.
    Differentiable — the custom VJP re-rotates K/V and calls the flash
    BACKWARD kernels per ring pair with the global (out, lse, dout), whose
    row-local form makes per-pair calls exact contributions to the global
    softmax gradient; dK/dV partial sums ride the ring with their block
    and arrive home after n hops. The flash-fusion step the r2 kernel
    docstring planned. interpret=True runs the kernels on CPU (tests)."""
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, interpret)
    return out


def _rf_fwd(q, k, v, axis_name, causal, interpret):
    out, lse = _ring_flash_fwd(q, k, v, axis_name, causal, interpret)
    return out, (q, k, v, out, lse)


def _rf_bwd(axis_name, causal, interpret, res, g):
    from ..ops import flash_attention as fa

    q, k, v, out, lse = res
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    q3, o3 = _fold_heads(q), _fold_heads(out)
    do3 = _fold_heads(g).astype(q3.dtype)
    # delta = rowsum(dO * O) is hop-invariant: compute once for all pairs
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)

    def pair_bwd(k3, v3, src):
        def run(causal_flag):
            return fa._flash_bwd(q3, k3, v3, o3, lse, do3, causal_flag,
                                 scale, interpret, delta=delta)
        if not causal:
            return run(False)
        return jax.lax.switch(
            jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2)),
            (lambda: run(False), lambda: run(True),
             lambda: (jnp.zeros_like(q3), jnp.zeros_like(q3),
                      jnp.zeros_like(q3))))

    def body(i, carry):
        k3_blk, v3_blk, dk_acc, dv_acc, dq_run = carry
        src = (idx - i) % n
        dq_c, dk_c, dv_c = pair_bwd(k3_blk, v3_blk, src)
        dq_run = dq_run + dq_c.astype(jnp.float32)
        # dK/dV partial sums belong to the block currently held: they
        # rotate WITH it and are complete when the block arrives home
        dk_acc = dk_acc + dk_c.astype(jnp.float32)
        dv_acc = dv_acc + dv_c.astype(jnp.float32)
        perm = [(r, (r + 1) % n) for r in range(n)]
        rot = lambda x: jax.lax.ppermute(x, axis_name, perm)
        return rot(k3_blk), rot(v3_blk), rot(dk_acc), rot(dv_acc), dq_run

    z3 = lambda: _vary(jnp.zeros((b * h, sq, d), jnp.float32), axis_name)
    _, _, dk_fin, dv_fin, dq_fin = jax.lax.fori_loop(
        0, n, body, (_fold_heads(k), _fold_heads(v), z3(), z3(), z3()))
    return (_unfold_heads(dq_fin, b, h).astype(q.dtype),
            _unfold_heads(dk_fin, b, h).astype(k.dtype),
            _unfold_heads(dv_fin, b, h).astype(v.dtype))


ring_flash_attention_spmd.defvjp(_rf_fwd, _rf_bwd)


def ulysses_attention_spmd(q, k, v, axis_name="sp", causal=False,
                           use_flash=False, interpret=False):
    """Ulysses (DeepSpeed-style) attention inside shard_map.

    Input: [batch, seq_shard, heads, head_dim] sequence-sharded.
    all_to_all -> [batch, seq_full, heads_shard, head_dim], full attention locally,
    all_to_all back. Needs heads % sp_size == 0. With use_flash the local
    attention runs the differentiable Pallas flash kernel (full-seq must be
    a multiple of 128, head_dim of 64) instead of materializing [s, s].
    """
    n = jax.lax.psum(1, axis_name)

    def seq_to_heads(x):
        # [b, s/n, h, d] -> [b, s, h/n, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    scale = 1.0 / math.sqrt(q.shape[-1])
    if use_flash:
        from ..ops import flash_attention as fa

        b, h_loc = qh.shape[0], qh.shape[2]  # _flash derives its own scale
        o3 = fa._flash(_fold_heads(qh), _fold_heads(kh), _fold_heads(vh),
                       causal, interpret)
        return heads_to_seq(_unfold_heads(o3, b, h_loc)).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
    if causal:
        sq = s.shape[-2]
        mask = jnp.tril(jnp.ones((sq, sq), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh)
    return heads_to_seq(out).astype(q.dtype)


def sequence_parallel_attention(q, k, v, mesh, impl="ring", causal=False,
                                axis_name="sp", interpret=None):
    """Convenience wrapper: shard_map over the 'sp' axis of `mesh` on seq
    dim 1. impl: 'ring' (einsum blocks), 'ring_flash' (Pallas flash-kernel
    blocks — per-shard seq must be a multiple of 128), 'ulysses', or
    'ulysses_flash' (local attention through the flash kernel — FULL seq
    must be a multiple of 128). interpret applies to the *_flash impls:
    True/False is the caller's word; None asks the platform test now, at
    call time (interpreted off-TPU, so models configured with a *_flash
    impl work on the CPU test mesh)."""
    if interpret is None:
        from ..core.device import on_tpu

        interpret = impl.endswith("_flash") and not on_tpu()
    if impl == "ring":
        body = functools.partial(ring_attention_spmd, axis_name=axis_name,
                                 causal=causal)
    elif impl == "ring_flash":
        body = functools.partial(ring_flash_attention_spmd,
                                 axis_name=axis_name, causal=causal,
                                 interpret=interpret)
    elif impl in ("ulysses", "ulysses_flash"):
        body = functools.partial(ulysses_attention_spmd,
                                 axis_name=axis_name, causal=causal,
                                 use_flash=impl == "ulysses_flash",
                                 interpret=interpret)
    else:
        raise ValueError(
            f"impl must be one of {'|'.join(VALID_SP_IMPLS)}, got {impl!r}")
    spec = P(None, axis_name, None, None)
    kw = {}
    if impl.endswith("_flash"):
        # pallas_call's out_shape carries no vma typing; skip the check
        kw["check_vma"] = False
    mapped = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, **kw)
    return mapped(q, k, v)


def full_attention_reference(q, k, v, causal=False):
    """Unsharded reference for tests."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
