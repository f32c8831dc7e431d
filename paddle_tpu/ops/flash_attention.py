"""Pallas flash-attention (fwd + custom-VJP bwd) for TPU.

No reference equivalent (the reference composes attention from matmuls,
python/paddle/nn/layer/transformer.py:83); this is a TPU-native addition following
the blockwise online-softmax (FlashAttention-2) recipe from
/opt/skills/guides/pallas_guide.md: a 3-D grid (batch*heads, q blocks, kv blocks)
streams one [128, d] K/V block through VMEM per step while (acc, m, l) persist in
VMEM scratch across the kv dimension — nothing scales with seq in VMEM, so 16k+
sequences fit. The forward also emits the per-row logsumexp; the backward
recomputes P = exp(S - L) blockwise (dq kernel and dk/dv kernel), never
materializing the [s, s] matrix in HBM.

Supported: head_dim % 64 == 0, seq % 128 == 0, fp32/bf16, seq >= 1024. Block
sizes adapt to seq (largest of 512/256/128 dividing it): 512-wide blocks keep
the MXU fed ([512, d] @ [d, 512] tiles) and cut grid-step overhead (block-size
and flash-vs-XLA speed: not measured by any ledger row yet). Below s=1024 the
[s, s] materialization XLA does is assumed cheap enough that flash doesn't
pay. `interpret=True` runs the kernels on CPU.

Hand-rolled rather than importing jax.experimental.pallas.ops.tpu.flash_attention
deliberately: the framework owns its hot kernels end-to-end (same reason the
reference carries its own fused attention ops), the guide-driven implementation is
the template for further custom kernels, and upstream's experimental API/layout
has no stability promise. The planned ring-attention fusion landed in
distributed/long_context.py `ring_flash_attention_spmd`: these forward AND
backward kernels run per ring block (global-lse blockwise calls are exact).
"""
import functools
import math
import operator

import jax
import jax.numpy as jnp

from ..core.device import on_tpu

_NEG = -1e30


def _block_for(s):
    """Largest MXU-friendly block (512/256/128) that tiles seq exactly.
    FLAGS_flash_attention_block forces a specific size for tuning sweeps."""
    from ..flags import get_flag

    forced = get_flag("flash_attention_block", 0)
    if forced:
        if forced not in (128, 256, 512) or s % forced:
            raise ValueError(
                f"FLAGS_flash_attention_block={forced} must be 128/256/512 "
                f"and divide seq {s}")
        return forced
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    raise ValueError(f"seq {s} not divisible by 128")


# ---------------------------------------------------------------------------
# static audit manifest (analysis/pallas_audit.py, ISSUE 13)
# ---------------------------------------------------------------------------

#: representative supported configs: the s=1024 entry floor and the 16k
#: long-context windowed config, at the gpt2s head_dim
_AUDIT_CONFIGS = ((1024, 64), (16384, 64))


def audit_manifest():
    """Audit entries for the fwd/dq/dkv kernels — block sizes through
    the SAME _block_for the runtime uses (pure arithmetic)."""
    entries = []
    for dtype in ("float32", "bfloat16"):
        for s, d in _AUDIT_CONFIGS:
            blk = _block_for(s)
            row = [{"name": "q", "block": (blk, d), "dtype": dtype},
                   {"name": "k", "block": (blk, d), "dtype": dtype},
                   {"name": "v", "block": (blk, d), "dtype": dtype}]
            entries.append({
                "kernel": f"flash.fwd[s={s},d={d},{dtype}]",
                "op": "flash_fwd", "in_dtype": dtype,
                "acc_dtype": "float32", "matmul": True,
                "grid": {"seq_q": (s, blk), "seq_k": (s, blk)},
                "buffers": row + [
                    {"name": "o", "block": (blk, d), "dtype": dtype},
                    {"name": "lse", "block": (1, blk),
                     "dtype": "float32"},
                    {"name": "acc(scratch)", "block": (blk, d),
                     "dtype": "float32", "stream": False},
                    {"name": "m(scratch)", "block": (blk, 128),
                     "dtype": "float32", "stream": False},
                    {"name": "l(scratch)", "block": (blk, 128),
                     "dtype": "float32", "stream": False}]})
            entries.append({
                "kernel": f"flash.dq[s={s},d={d},{dtype}]",
                "op": "flash_dq", "in_dtype": dtype,
                "acc_dtype": "float32", "matmul": True,
                "grid": {"seq_q": (s, blk), "seq_k": (s, blk)},
                "buffers": row + [
                    {"name": "do", "block": (blk, d), "dtype": dtype},
                    {"name": "lse", "block": (1, blk),
                     "dtype": "float32"},
                    {"name": "delta", "block": (1, blk),
                     "dtype": "float32"},
                    {"name": "dq", "block": (blk, d), "dtype": dtype},
                    {"name": "dq_acc(scratch)", "block": (blk, d),
                     "dtype": "float32", "stream": False}]})
            entries.append({
                "kernel": f"flash.dkv[s={s},d={d},{dtype}]",
                "op": "flash_dkv", "in_dtype": dtype,
                "acc_dtype": "float32", "matmul": True,
                "grid": {"seq_q": (s, blk), "seq_k": (s, blk)},
                "buffers": row + [
                    {"name": "do", "block": (blk, d), "dtype": dtype},
                    {"name": "lse", "block": (1, blk),
                     "dtype": "float32"},
                    {"name": "delta", "block": (1, blk),
                     "dtype": "float32"},
                    {"name": "dk", "block": (blk, d), "dtype": dtype},
                    {"name": "dv", "block": (blk, d), "dtype": dtype},
                    {"name": "dk_acc(scratch)", "block": (blk, d),
                     "dtype": "float32", "stream": False},
                    {"name": "dv_acc(scratch)", "block": (blk, d),
                     "dtype": "float32", "stream": False}]})
    return entries


def supported(q_shape, dtype_str):
    """Pure shape/dtype/platform predicate: does the compiled kernel serve
    this call? q_shape: (batch, seq, heads, head_dim). The platform test
    raises if the backend cannot start — it never answers "no" for it."""
    if len(q_shape) != 4:
        return False
    b, s, h, d = q_shape
    if d % 64 != 0 or s % 128 != 0 or s < 1024:
        return False
    if dtype_str not in ("float32", "bfloat16"):
        return False
    return on_tpu()


def _kv_index(causal, n_win=None):
    """K/V block map for (b, qi, ki) grids: on masked steps (causal ki > qi,
    or window ki < qi - n_win) alias a block already needed so no new DMA
    is issued."""
    if not causal:
        return lambda b, qi, ki: (b, ki, 0)
    if n_win is None:
        return lambda b, qi, ki: (b, jnp.minimum(ki, qi), 0)
    return lambda b, qi, ki: (b, jnp.clip(ki, jnp.maximum(qi - n_win, 0),
                                          qi), 0)


def _q_index(causal, n_win=None):
    """Q/dO block map for (b, ki, qi) grids: masked steps alias into the
    visible band [ki, ki + n_win]."""
    if not causal:
        return lambda b, ki, qi: (b, qi, 0)
    if n_win is None:
        return lambda b, ki, qi: (b, jnp.maximum(qi, ki), 0)
    return lambda b, ki, qi: (b, jnp.clip(qi, ki, ki + n_win), 0)


def _lse_index(causal, n_win=None):
    if not causal:
        return lambda b, ki, qi: (b, 0, qi)
    if n_win is None:
        return lambda b, ki, qi: (b, 0, jnp.maximum(qi, ki))
    return lambda b, ki, qi: (b, 0, jnp.clip(qi, ki, ki + n_win))


def _causal_mask(qi, ki, scores, window=None):
    """Causal (and optionally sliding-window) score mask: keep
    k_pos <= q_pos, and with `window` also q_pos - k_pos < window."""
    bq, bk = scores.shape
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep &= (q_pos - k_pos) < window
    return jnp.where(keep, scores, _NEG)


def _n_win(window, blk):
    """Max block distance qi - ki with any visible position (conservative
    by at most one block; exact masking happens inside the kernel)."""
    return None if window is None else (window - 1 + blk - 1) // blk


# ---------------- forward kernel ---------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                causal, scale, n_k, d, blk, window=None, nwin=None):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros((blk, d), jnp.float32)
        m_ref[...] = jnp.full((blk, 128), _NEG, jnp.float32)
        l_ref[...] = jnp.zeros((blk, 128), jnp.float32)

    run = (ki <= qi) if causal else (ki >= 0)
    if nwin is not None:
        run &= (qi - ki) <= nwin

    @pl.when(run)
    def _step():
        q_blk = q_ref[...].astype(jnp.float32) * scale        # [BQ, d]
        k_blk = k_ref[...].astype(jnp.float32)                # [BK, d]
        v_blk = v_ref[...].astype(jnp.float32)
        scores = q_blk @ k_blk.T                              # [BQ, BK]
        if causal:
            scores = _causal_mask(qi, ki, scores, window)
        m_prev = m_ref[...]                                   # [BQ, 128]
        l_prev = l_ref[...]
        m_cur = jnp.broadcast_to(jnp.max(scores, -1, keepdims=True),
                                 (blk, 128))
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)                      # [BQ, 128]
        p = jnp.exp(scores - m_next[:, :1])                   # [BQ, BK]
        l_ref[...] = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, -1, keepdims=True), (blk, 128))
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + p @ v_blk

    @pl.when(ki == n_k - 1)
    def _flush():
        l = l_ref[:, :1]                                      # [BQ, 1]
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[:, :1] + jnp.log(l)).reshape(1, blk)


def _flash_fwd(q3, k3, v3, causal, scale, interpret, window=None):
    """q3/k3/v3: [bh, s, d] -> (o [bh, s, d], lse [bh, s] f32). window:
    sliding-window causal attention (keep q_pos - k_pos < window)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import BlockSpec
    from jax.experimental.pallas import tpu as pltpu

    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    bh, s, d = q3.shape
    blk = _block_for(s)
    nwin = _n_win(window, blk)
    n_q, n_k = s // blk, s // blk
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale, n_k=n_k,
                          d=d, blk=blk, window=window, nwin=nwin),
        grid=(bh, n_q, n_k),
        in_specs=[
            BlockSpec((None, blk, d), lambda b, qi, ki: (b, qi, 0)),
            BlockSpec((None, blk, d), _kv_index(causal, nwin)),
            BlockSpec((None, blk, d), _kv_index(causal, nwin)),
        ],
        out_specs=[
            BlockSpec((None, blk, d), lambda b, qi, ki: (b, qi, 0)),
            BlockSpec((None, 1, blk), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, d), jnp.float32),
            pltpu.VMEM((blk, 128), jnp.float32),
            pltpu.VMEM((blk, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q3, k3, v3)
    return o, lse[:, 0, :]


# ---------------- backward kernels -------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *, causal, scale, n_k, d, blk, window=None,
               nwin=None):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros((blk, d), jnp.float32)

    run = (ki <= qi) if causal else (ki >= 0)
    if nwin is not None:
        run &= (qi - ki) <= nwin

    @pl.when(run)
    def _step():
        q_blk = q_ref[...].astype(jnp.float32) * scale
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        do_blk = do_ref[...].astype(jnp.float32)              # [BQ, d]
        lse = lse_ref[...].reshape(blk, 1)
        delta = delta_ref[...].reshape(blk, 1)
        scores = q_blk @ k_blk.T                              # [BQ, BK]
        if causal:
            scores = _causal_mask(qi, ki, scores, window)
        p = jnp.exp(scores - lse)                             # [BQ, BK]
        dp = do_blk @ v_blk.T
        ds = p * (dp - delta)
        dq_acc_ref[...] += ds @ k_blk

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[...] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc_ref, dv_acc_ref, *, causal, scale, n_q, d, blk,
                window=None, nwin=None):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros((blk, d), jnp.float32)
        dv_acc_ref[...] = jnp.zeros((blk, d), jnp.float32)

    run = (qi >= ki) if causal else (qi >= 0)
    if nwin is not None:
        run &= (qi - ki) <= nwin

    @pl.when(run)
    def _step():
        q_blk = q_ref[...].astype(jnp.float32) * scale        # [BQ, d]
        k_blk = k_ref[...].astype(jnp.float32)                # [BK, d]
        v_blk = v_ref[...].astype(jnp.float32)
        do_blk = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...].reshape(blk, 1)
        delta = delta_ref[...].reshape(blk, 1)
        scores = q_blk @ k_blk.T                              # [BQ, BK]
        if causal:
            scores = _causal_mask(qi, ki, scores, window)
        p = jnp.exp(scores - lse)                             # [BQ, BK]
        dv_acc_ref[...] += p.T @ do_blk
        dp = do_blk @ v_blk.T
        ds = p * (dp - delta)
        dk_acc_ref[...] += ds.T @ q_blk  # q_blk carries the scale: dS^T (Q*scale)

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, o3, lse, do3, causal, scale, interpret,
               delta=None, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import BlockSpec
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q3.shape
    blk = _block_for(s)
    nwin = _n_win(window, blk)
    n_q, n_k = s // blk, s // blk
    if delta is None:  # ring callers precompute: o3/do3 are hop-invariant
        delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                        axis=-1)                              # [bh, s]
    lse2 = lse[:, None, :]                                    # [bh, 1, s]
    delta2 = delta[:, None, :]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale, n_k=n_k,
                          d=d, blk=blk, window=window, nwin=nwin),
        grid=(bh, n_q, n_k),
        in_specs=[
            BlockSpec((None, blk, d), lambda b, qi, ki: (b, qi, 0)),
            BlockSpec((None, blk, d), _kv_index(causal, nwin)),
            BlockSpec((None, blk, d), _kv_index(causal, nwin)),
            BlockSpec((None, blk, d), lambda b, qi, ki: (b, qi, 0)),
            BlockSpec((None, 1, blk), lambda b, qi, ki: (b, 0, qi)),
            BlockSpec((None, 1, blk), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_specs=BlockSpec((None, blk, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q3, k3, v3, do3, lse2, delta2)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, n_q=n_q,
                          d=d, blk=blk, window=window, nwin=nwin),
        grid=(bh, n_k, n_q),
        in_specs=[
            BlockSpec((None, blk, d), _q_index(causal, nwin)),
            BlockSpec((None, blk, d), lambda b, ki, qi: (b, ki, 0)),
            BlockSpec((None, blk, d), lambda b, ki, qi: (b, ki, 0)),
            BlockSpec((None, blk, d), _q_index(causal, nwin)),
            BlockSpec((None, 1, blk), _lse_index(causal, nwin)),
            BlockSpec((None, 1, blk), _lse_index(causal, nwin)),
        ],
        out_specs=[
            BlockSpec((None, blk, d), lambda b, ki, qi: (b, ki, 0)),
            BlockSpec((None, blk, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, d), jnp.float32),
            pltpu.VMEM((blk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(q3, k3, v3, do3, lse2, delta2)
    return dq, dk, dv


# ---------------- public API (custom VJP over [b, s, h, d]) -------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q3, k3, v3, causal, interpret, window=None):
    scale = 1.0 / math.sqrt(q3.shape[-1])
    o, _ = _flash_fwd(q3, k3, v3, causal, scale, interpret, window=window)
    return o


def _flash_fwd_rule(q3, k3, v3, causal, interpret, window=None):
    scale = 1.0 / math.sqrt(q3.shape[-1])
    o, lse = _flash_fwd(q3, k3, v3, causal, scale, interpret, window=window)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd_rule(causal, interpret, window, res, do3):
    q3, k3, v3, o3, lse = res
    scale = 1.0 / math.sqrt(q3.shape[-1])
    dq, dk, dv = _flash_bwd(q3, k3, v3, o3, lse, do3, causal, scale,
                            interpret, window=window)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _mesh_spec(mesh, b, h, manual=()):
    """PartitionSpec of a [b, s, h, d] operand over `mesh` by the
    framework's axis names (distributed/mesh.py): batch over the data axes
    ('dp', 'sharding'), heads over 'mp' — each only where it divides; any
    other axis computes replicated. seq and head_dim are never split, so
    the per-shard call meets the same supported() shape rules. Axes in
    `manual` are already split by an enclosing shard_map and are left out."""
    from jax.sharding import PartitionSpec as P

    def free(ax):
        return ax in mesh.axis_names and ax not in manual

    batch_axes, n = [], 1
    for ax in ("dp", "sharding"):
        if free(ax) and b % (n * mesh.shape[ax]) == 0:
            batch_axes.append(ax)
            n *= mesh.shape[ax]
    heads = "mp" if free("mp") and h % mesh.shape["mp"] == 0 else None
    return P(tuple(batch_axes) or None, None, heads, None)


def flash_attention(q, k, v, causal=False, interpret=False, window=None,
                    mesh=None):
    """q,k,v: [b, s, h, d] -> [b, s, h, d]. Differentiable (custom VJP).

    mesh: the device mesh of the enclosing multi-device jit, if any. A
    Mosaic kernel cannot be partitioned by XLA ("wrap the call in a
    shard_map" is the chip compiler's own error), so under a mesh of more
    than one device the call shard_maps itself: batch over the data axes,
    heads over 'mp' (:func:`_mesh_spec`), one kernel per shard, no
    communication — attention is independent per (batch, head). Called
    from inside a shard_map, it wraps only the axes that one left to XLA.

    window=W (requires causal=True) restricts attention to the last W
    tokens (Mistral-style sliding window): block pairs entirely outside
    the band are skipped — compute AND cache reads scale O(s * W) instead
    of O(s^2) for long sequences.

    The resolved FLAGS_flash_attention_block value joins the jit cache key
    (static `_blk`), so in-process set_flags sweeps retrace rather than
    silently reusing the old block's executable. Enclosing jits (e.g. a
    trainer's compiled train step) still bake the flag at THEIR build time —
    rebuild the trainer (or use a fresh process) when sweeping under one."""
    from ..flags import get_flag

    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if isinstance(window, bool):
            raise ValueError(f"window must be a positive int, got {window!r}")
        try:
            window = int(operator.index(window))  # accepts numpy ints
        except TypeError:
            raise ValueError(
                f"window must be a positive int, got {window!r}") from None
        if window < 1:
            raise ValueError(f"window must be a positive int, got {window!r}")
    call = functools.partial(_flash_attention_jit, causal=causal,
                             interpret=interpret, window=window,
                             _blk=get_flag("flash_attention_block", 0))
    if mesh is not None and mesh.size > 1:
        # axes an enclosing shard_map already made manual (the trainer's
        # localsgd/DGC/compressed steps, the pipeline) need no wrapping —
        # only the axes XLA would still have to partition over do
        manual = set(jax.sharding.get_abstract_mesh().manual_axes)
        auto = set(mesh.axis_names) - manual
        if auto:
            spec = _mesh_spec(mesh, q.shape[0], q.shape[2], manual)
            # check_vma off: pallas_call's out_shape carries no vma typing
            call = jax.shard_map(call, mesh=None if manual else mesh,
                                 axis_names=auto, in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False)
    return call(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "interpret", "_blk",
                                             "window"))
def _flash_attention_jit(q, k, v, causal, interpret, _blk, window=None):
    b, s, h, d = q.shape
    qh = jnp.swapaxes(q, 1, 2).reshape(b * h, s, d)
    kh = jnp.swapaxes(k, 1, 2).reshape(b * h, s, d)
    vh = jnp.swapaxes(v, 1, 2).reshape(b * h, s, d)
    out = _flash(qh, kh, vh, causal, interpret, window)
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)
