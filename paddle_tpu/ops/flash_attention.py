"""Pallas flash-attention (fwd + custom-VJP bwd) for TPU.

No reference equivalent (the reference composes attention from matmuls,
python/paddle/nn/layer/transformer.py:83); this is a TPU-native addition following
the blockwise online-softmax (FlashAttention-2) recipe from
/opt/skills/guides/pallas_guide.md: a 3-D grid (batch*heads, q blocks, kv blocks)
streams one [blk, d] K/V block through VMEM per step while (acc, m, l) persist in
VMEM scratch across the kv dimension — nothing scales with seq in VMEM, so 16k+
sequences fit. The forward also emits the per-row logsumexp; the backward
recomputes P = exp(S - L) blockwise (dq kernel and dk/dv kernel), never
materializing the [s, s] matrix in HBM.

Supported: head_dim % 64 == 0, seq % 128 == 0, fp32/bf16, seq >= 1024. Below
s=1024 the [s, s] materialization XLA does is assumed cheap enough that flash
doesn't pay. `interpret=True` runs the kernels on CPU.

What a tile step does follows what the call shows (dtype, causal, window, s, d),
nothing else (PERF.md section 6 "PR 35" has the measurements):

- Operands. The MXU is fed the blocks in their own type: bfloat16 blocks as
  loaded, float32 as float32 (which the MXU rounds to bfloat16 itself at default
  precision: a float32 product inside a Mosaic kernel matched the product of the
  rounded operands to 4e-6 and the exact one to 0.1). Scores, m, l, lse, delta,
  p, dp, dS and every accumulator are float32; p and dS are rounded to the
  operands' type once, where they become an operand of the second product. The
  scale is applied to the float32 scores and to dq and dk at the flush, never to
  q. In interpret mode operands are widened (`_mxu_dtype`), the same products.
- Tiles (`_tiles`). A causal grid step runs its tile only if some position of it
  is visible; the mask is applied only where the diagonal (or a window's far
  edge) crosses the tile; the first live tile of a row of tiles writes the
  accumulators, the others add to them (no zero fill, no rescale of nothing).
- The diagonal tile (`_parts`) is worked in strips of 128 that stop at the
  diagonal, so the corner no row may see is never computed: at s = 1,024 the
  3 live tiles of 512 compute 36 cells of 128 x 128, exactly what a grid of 128
  blocks would (`tile_counts`), in 4 grid steps instead of 64.
- The statistics. The forward keeps m lane-replicated and l as 128 partial sums
  a row (one sum at the flush); lse leaves as a row by one transpose. The dk/dv
  kernel works its tile transposed ([k, q]), so lse and delta are used as the
  rows they are stored as and neither P nor dS is transposed; the dq kernel
  turns the two rows into columns by one transpose each.

Block (`_block_for`): the largest of 512/256/128 that divides seq, for every
kernel, causal or not. A causal call needs no smaller block to skip masked
area, since the strips do that inside the tile, and smaller tiles lose to
per-step work. Device time a call, ms, fwd / dq / dkv, bfloat16, causal, head
dim 64, through FLAGS_flash_attention_block (my chip runs, PR 35, one TPU v5e):

    block   [64, 1024, 64] (gpt2-medium.train-1k)   [16, 8192, 64] (ROADMAP R-W3)
    512     0.181 / 0.190 / 0.245                    2.14 /  2.88 /  3.53
    256     0.402 / 0.343 / 0.457                    5.26 /  5.52 /  7.08
    128     1.091 / 0.881 / 0.914                   16.87 / 15.93 / 16.83
    (PR 34's kernels at 512: 0.458 / 0.241 / 0.354 and 4.08 / 2.93 / 4.04)

Unequal q and k blocks were swept too and lost everywhere (256 x 512 and
512 x 256 read 1.00 ms a call at s = 1,024 where 512 x 512 read 0.71), so the
kernels take one block.

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
    """The block of all three kernels: the largest of 512/256/128 that tiles
    seq exactly, whatever `causal` and the window are (the module docstring
    has the sweep). FLAGS_flash_attention_block forces a size for sweeps."""
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


#: rows of the strips a diagonal tile is worked in (one MXU tile)
_STRIP = 128


def _n_win(window, blk):
    """Largest block distance qi - ki at which a tile still holds a visible
    position (exact masking happens inside the kernel)."""
    return None if window is None else (window - 1 + blk - 1) // blk


def _tile_kind(q0, k0, blk, window):
    """(live, clear) of the causal tile whose first q position is q0 and
    first k position k0: live, some position of it is visible; clear, all
    are, so the mask would change nothing. Python ints or traced scalars."""
    live = k0 <= q0 + blk - 1
    clear = k0 + blk - 1 <= q0
    if window is not None:
        live &= k0 + blk - 1 > q0 - window
        clear &= q0 + blk - 1 - k0 < window
    return live, clear


def _parts(diagonal, blk, by="q"):
    """(q rows, k rows) of the pieces a tile is worked in. The tile the
    diagonal runs through is cut into strips that stop at the diagonal, so
    that the corner no row may see is never computed: by="q", strips of q
    rows with the k up to their own last; by="k", strips of k rows with the
    q from their own first. Any other tile is one piece."""
    if not diagonal or blk <= _STRIP:
        return [(slice(0, blk), slice(0, blk))]
    edges = range(0, blk, _STRIP)
    if by == "q":
        return [(slice(e, e + _STRIP), slice(0, e + _STRIP)) for e in edges]
    return [(slice(e, blk), slice(e, e + _STRIP)) for e in edges]


def tile_counts(s, causal, window=None):
    """What the grid of one (batch, head) computes against what the mask
    leaves, counted with the kernels' own predicates (pure arithmetic):
    grid steps, live tiles, tiles that apply the mask, and the 128 x 128
    cells computed and needed (a cell is needed if any of it is visible)."""
    blk = _block_for(s)
    n = s // blk
    out = {"block": blk, "grid_steps": n * n, "live_tiles": 0,
           "masked_tiles": 0, "cells_computed": 0}
    for qi in range(n):
        for ki in range(n):
            live, clear = _tile_kind(qi * blk, ki * blk, blk, window) \
                if causal else (True, True)
            if not live:
                continue
            out["live_tiles"] += 1
            out["masked_tiles"] += not clear
            out["cells_computed"] += sum(
                (r.stop - r.start) * (c.stop - c.start) for r, c in
                _parts(not clear and window is None, blk)) // (128 * 128)
    c = s // 128
    out["cells_needed"] = sum(
        bool(_tile_kind(i * 128, j * 128, 128, window)[0]) if causal else 1
        for i in range(c) for j in range(c))
    return out


# ---------------------------------------------------------------------------
# static audit manifest (analysis/pallas_audit.py, ISSUE 13)
# ---------------------------------------------------------------------------

#: representative supported configs (seq, head_dim, causal, window): the
#: s=1024 entry floor (gpt2-medium.train-1k's call), its non-causal arm, and
#: the 16k long-context windowed config
_AUDIT_CONFIGS = ((1024, 64, True, None), (1024, 64, False, None),
                  (16384, 64, True, 4096))


def audit_manifest():
    """Audit entries for the fwd/dq/dkv kernels: block sizes through the
    SAME _block_for the runtime uses, the type the MXU is fed in and the
    tiles computed against the tiles the mask leaves (pure arithmetic)."""
    entries = []
    for dtype in ("float32", "bfloat16"):
        for s, d, causal, window in _AUDIT_CONFIGS:
            blk = _block_for(s)
            tag = f"s={s},d={d},{dtype}," + (
                "full" if not causal else
                "causal" if window is None else f"window={window}")
            common = {"in_dtype": dtype, "mxu_dtype": dtype,
                      "acc_dtype": "float32", "matmul": True,
                      "grid": {"seq_q": (s, blk), "seq_k": (s, blk)},
                      "tiles": tile_counts(s, causal, window)}
            row = [{"name": "q", "block": (blk, d), "dtype": dtype},
                   {"name": "k", "block": (blk, d), "dtype": dtype},
                   {"name": "v", "block": (blk, d), "dtype": dtype}]
            back = row + [
                {"name": "do", "block": (blk, d), "dtype": dtype},
                {"name": "lse", "block": (1, blk), "dtype": "float32"},
                {"name": "delta", "block": (1, blk), "dtype": "float32"}]
            scratch = lambda name, width=d: {
                "name": f"{name}(scratch)", "block": (blk, width),
                "dtype": "float32", "stream": False}
            entries.append(dict(
                common, kernel=f"flash.fwd[{tag}]", op="flash_fwd",
                buffers=row + [
                    {"name": "o", "block": (blk, d), "dtype": dtype},
                    {"name": "lse", "block": (1, blk), "dtype": "float32"},
                    scratch("acc"), scratch("m", 128), scratch("l", 128)]))
            entries.append(dict(
                common, kernel=f"flash.dq[{tag}]", op="flash_dq",
                buffers=back + [
                    {"name": "dq", "block": (blk, d), "dtype": dtype},
                    scratch("dq_acc")]))
            entries.append(dict(
                common, kernel=f"flash.dkv[{tag}]", op="flash_dkv",
                buffers=back + [
                    {"name": "dk", "block": (blk, d), "dtype": dtype},
                    {"name": "dv", "block": (blk, d), "dtype": dtype},
                    scratch("dk_acc"), scratch("dv_acc")]))
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
    """K/V block map for (b, qi, ki) grids: a step outside the live band
    (causal ki > qi, or window ki < qi - n_win) aliases the nearest live
    block, so no new DMA is issued for it."""
    if not causal:
        return lambda b, qi, ki: (b, ki, 0)
    if n_win is None:
        return lambda b, qi, ki: (b, jnp.minimum(ki, qi), 0)
    return lambda b, qi, ki: (b, jnp.clip(ki, jnp.maximum(qi - n_win, 0),
                                          qi), 0)


def _q_index(causal, n_win=None, row=False):
    """Q/dO block map for (b, ki, qi) grids (row=True: the [1, s] lse and
    delta rows): steps outside the band alias into [ki, ki + n_win]."""
    if not causal:
        pick = lambda ki, qi: qi
    elif n_win is None:
        pick = lambda ki, qi: jnp.maximum(qi, ki)
    else:
        pick = lambda ki, qi: jnp.clip(qi, ki, ki + n_win)
    if row:
        return lambda b, ki, qi: (b, 0, pick(ki, qi))
    return lambda b, ki, qi: (b, pick(ki, qi), 0)


def _keep(shape, q0, k0, window, q_axis=0):
    """Causal (and sliding-window) keep-mask of a score tile whose first q
    position is q0 and first k position k0: k_pos <= q_pos, and with
    `window` also q_pos - k_pos < window. q_axis: the tile's axis that runs
    over q (1 in the dkv kernel's transposed tile)."""
    diff = (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    off = k0 - q0                       # q_pos - k_pos = diff - off
    keep = diff >= off
    if window is not None:
        keep &= diff < off + window
    return keep


def _tiles(causal, window, qi, ki, blk, tile, inner="k"):
    """Run `tile(masked, first)` for the grid step's tile if any of it is
    visible. masked: the diagonal (or the window's far edge) crosses the
    tile; a tile wholly inside the visible band skips the mask. first: it is
    the first live tile along the inner grid axis (`inner`: "k" for the
    (b, qi, ki) grids, "q" for dkv's (b, ki, qi)), which starts the
    accumulators where the others add to them: no zero fill, no rescale."""
    from jax.experimental import pallas as pl

    if not causal:
        first = (ki if inner == "k" else qi) == 0
        pl.when(first)(lambda: tile(False, True))
        pl.when(jnp.logical_not(first))(lambda: tile(False, False))
        return
    live, clear = _tile_kind(qi * blk, ki * blk, blk, window)
    if inner == "q":        # a k block's first live q block is its own
        first = qi == ki
    elif window is None:
        first = ki == 0
    else:
        first = ki == jnp.maximum(qi - _n_win(window, blk), 0)
    for masked in (False, True):
        for is_first in (False, True):
            cond = live & (jnp.logical_not(clear) if masked else clear) \
                & (first if is_first else jnp.logical_not(first))
            pl.when(cond)(functools.partial(tile, masked, is_first))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    """A product on the MXU, accumulated and returned in float32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scores(lhs, rhs, scale, q0, k0, window, masked, q_axis=0):
    """The tile's float32 scores, lhs @ rhs.T * scale, with the positions no
    row may see at _NEG where `masked` (q0, k0, q_axis: as `_keep`)."""
    scores = _dot(lhs, rhs, _NT) * scale
    if masked:
        scores = jnp.where(_keep(scores.shape, q0, k0, window, q_axis),
                           scores, _NEG)
    return scores


def _operand(x, dt, mxu):
    """x as an operand of a product: rounded to the call's type `dt` (a
    loaded block already is; p and dS are rounded here, once), in the type
    the MXU is fed in."""
    return x.astype(dt).astype(mxu)


def _mxu_dtype(dtype, interpret):
    """The type the MXU is fed in: the operands' own (bfloat16 blocks go in
    as loaded, float32 stays float32, which the MXU rounds to bfloat16
    itself at default precision). Off the chip (interpret mode on the CPU,
    whose runtime lacks the bf16 x bf16 -> f32 product at these shapes:
    distributed/moe.py:f32_operands) operands are widened, which gives the
    same products."""
    return jnp.float32 if interpret else dtype


# ---------------- forward kernel ---------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                causal, scale, n_k, d, blk, window, mxu):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    op = functools.partial(_operand, dt=q_ref.dtype, mxu=mxu)

    def _softmax(rows, scores, first):
        """The online-softmax statistics of q rows `rows`. m is held
        lane-replicated, [R, 128], and l as 128 partial sums a row (summed
        at the flush): neither costs a lane broadcast a tile."""
        nr, nc = scores.shape
        fold = lambda x, op: functools.reduce(
            op, [x[:, j:j + 128] for j in range(0, nc, 128)])
        m_cur = jnp.max(fold(scores, jnp.maximum), -1, keepdims=True)
        if first:
            m_next, alpha = jnp.broadcast_to(m_cur, (nr, 128)), None
        else:
            m_prev = m_ref[rows, :]                           # [R, 128]
            m_next = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_next)                  # [R, 128]
        p = jnp.exp(scores - jnp.tile(m_next, (1, nc // 128)))
        l_new = fold(p, jnp.add)
        l_ref[rows, :] = l_new if first else alpha * l_ref[rows, :] + l_new
        m_ref[rows, :] = m_next
        return p, alpha

    def _accumulate(rows, cols, p, alpha):
        pv = _dot(op(p), op(v_ref[cols, :]), _NN)
        if alpha is not None:
            wide = alpha[:, :d] if d <= 128 else jnp.tile(alpha, (1, d // 128))
            pv += acc_ref[rows, :] * wide
        acc_ref[rows, :] = pv

    def _tile(masked, first):
        # phase by phase over the tile's pieces (all the scores, then all
        # the statistics, then all the products): the order the compiler's
        # schedule overlaps best
        parts = _parts(masked and window is None, blk)
        scores = [_scores(op(q_ref[r, :]), op(k_ref[c, :]), scale,
                          qi * blk + r.start, ki * blk + c.start, window,
                          masked) for r, c in parts]              # [R, C]
        stats = [_softmax(r, x, first) for (r, _), x in zip(parts, scores)]
        for (r, c), (p, alpha) in zip(parts, stats):
            _accumulate(r, c, p, alpha)

    _tiles(causal, window, qi, ki, blk, _tile)

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.sum(l_ref[...], -1, keepdims=True)            # [BLK, 1]
        o_ref[...] = (acc_ref[...] * (1.0 / l)).astype(o_ref.dtype)
        # the [BLK] column as the [1, BLK] row it is stored as: one
        # transpose of the lane-replicated block
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1, :]


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
                          d=d, blk=blk, window=window,
                          mxu=_mxu_dtype(q3.dtype, interpret)),
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
               dq_acc_ref, *, causal, scale, n_k, d, blk, window, mxu):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    op = functools.partial(_operand, dt=q_ref.dtype, mxu=mxu)

    def _tile(masked, first):
        parts = _parts(masked and window is None, blk)
        # the [1, BLK] rows as lane-replicated columns: one transpose each
        col = lambda ref: jnp.broadcast_to(ref[...], (128, blk)).T
        lse, delta = col(lse_ref), col(delta_ref)             # [BLK, 128]

        def _ds(rows, cols):
            scores = _scores(op(q_ref[rows, :]), op(k_ref[cols, :]), scale,
                             qi * blk + rows.start, ki * blk + cols.start,
                             window, masked)                  # [R, C]
            wide = lambda x: jnp.tile(x[rows, :], (1, scores.shape[1] // 128))
            p = jnp.exp(scores - wide(lse))
            dp = _dot(op(do_ref[rows, :]), op(v_ref[cols, :]), _NT)
            return p * (dp - wide(delta))

        ds = [_ds(r, c) for r, c in parts]
        for (r, c), x in zip(parts, ds):
            dq = _dot(op(x), op(k_ref[c, :]), _NN)
            dq_acc_ref[r, :] = dq if first else dq_acc_ref[r, :] + dq

    _tiles(causal, window, qi, ki, blk, _tile)

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[...] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc_ref, dv_acc_ref, *, causal, scale, n_q, d, blk, window,
                mxu):
    """The tile is worked on transposed ([BK, BQ]: k along the sublanes, q
    along the lanes), so that lse and delta are used as the [1, BQ] rows
    they are stored as and both accumulating products are plain a @ b:
    neither P nor dS is ever transposed."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    op = functools.partial(_operand, dt=q_ref.dtype, mxu=mxu)

    def _tile(masked, first):
        parts = _parts(masked and window is None, blk, by="k")

        def _p(qs, ks):
            scores = _scores(op(k_ref[ks, :]), op(q_ref[qs, :]), scale,
                             qi * blk + qs.start, ki * blk + ks.start,
                             window, masked, q_axis=1)        # [K, Q]
            return jnp.exp(scores - lse_ref[:, qs])

        def _add(acc_ref, rows, x):
            acc_ref[rows, :] = x if first else acc_ref[rows, :] + x

        ps = [_p(qs, ks) for qs, ks in parts]
        for (qs, ks), p in zip(parts, ps):
            _add(dv_acc_ref, ks, _dot(op(p), op(do_ref[qs, :]), _NN))
        dss = [p * (_dot(op(v_ref[ks, :]), op(do_ref[qs, :]), _NT)
                    - delta_ref[:, qs]) for (qs, ks), p in zip(parts, ps)]
        for (qs, ks), ds in zip(parts, dss):
            _add(dk_acc_ref, ks, _dot(op(ds), op(q_ref[qs, :]), _NN))

    _tiles(causal, window, qi, ki, blk, _tile, inner="q")

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[...] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
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
    mxu = _mxu_dtype(q3.dtype, interpret)
    if delta is None:  # ring callers precompute: o3/do3 are hop-invariant
        delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                        axis=-1)                              # [bh, s]
    lse2 = lse[:, None, :]                                    # [bh, 1, s]
    delta2 = delta[:, None, :]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale, n_k=n_k,
                          d=d, blk=blk, window=window, mxu=mxu),
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

    q_map = _q_index(causal, nwin)
    row_map = _q_index(causal, nwin, row=True)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, n_q=n_q,
                          d=d, blk=blk, window=window, mxu=mxu),
        grid=(bh, n_k, n_q),
        in_specs=[
            BlockSpec((None, blk, d), q_map),
            BlockSpec((None, blk, d), lambda b, ki, qi: (b, ki, 0)),
            BlockSpec((None, blk, d), lambda b, ki, qi: (b, ki, 0)),
            BlockSpec((None, blk, d), q_map),
            BlockSpec((None, 1, blk), row_map),
            BlockSpec((None, 1, blk), row_map),
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
