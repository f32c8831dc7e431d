"""TPP-style Pallas micro-kernel registry (FLAGS_tpp_kernels).

Tensor Processing Primitives (arXiv:2104.05755) argues the hot ops XLA
fuses badly want a SMALL vocabulary of composable blocked primitives —
not one hand kernel per op. This module is that vocabulary for the
framework, Pallas-lowered. Every op takes ``interpret=``: True/False is
the caller's word, None (the default) asks core.device.on_tpu() at CALL
time — compiled on a TPU, interpreted elsewhere. Nothing is frozen when a
kernel is first built.

Micro-kernels — each compiled per (op, dtype, block shape) and cached
in the registry:

- ``matmul``        blocked matmul-accumulate: (M/bm, N/bn, K/bk) grid,
                    fp32 VMEM accumulator persisting across the K
                    steps, optional fused input-activation and
                    bias+activation epilogue (the TPP "BRGEMM + unary")
- ``bias_act``      fused bias + activation over row blocks (VPU)
- ``softmax_rows``  blocked softmax row-pass (stable: fp32 row max/sum)
- ``masked_reduce`` masked row reduce (sum|max)

Ported ops — the fusion-hostile GPT hot spots beyond
flash-attention/NMS (docs/PERF.md "TPP registry"); both are
``jax.custom_vjp`` (Pallas forward, reference-math backward) so the
trainer differentiates through them:

- ``ln_matmul``  the layernorm -> matmul prologue: rows are normalized
  in fp32 INSIDE the matmul kernel's x-block load, so the normalized
  activation never round-trips HBM between the two ops
- ``fused_mlp``  the GPT MLP block: matmul+bias feeding a second
  matmul whose x blocks are activated (gelu) on load — the hidden
  activation is the only HBM-materialized intermediate

``gpt_block_mlp`` composes them for models/gpt.py: ln_matmul covers
ln2+fc1, the fused_mlp tail covers gelu+fc2.

Every op call is metered (``tpp_kernel_calls_total{op}``, counted at
trace time — the PR 2 chokepoint semantics: once per compiled program)
and registered in the device cost registry (``trace.costs``
site="tpp") with analytic FLOPs/bytes so the MFU report can attribute
TPP-ported work. The module is imported ONLY when FLAGS_tpp_kernels
routes a model through it (gate-pinned by tests/test_async_gate.py).
"""
import functools

import jax
import jax.numpy as jnp

from .. import monitor as _monitor
from ..core.device import on_tpu
from ..trace import costs as _costs

__all__ = ["matmul", "bias_act", "softmax_rows", "masked_reduce",
           "ln_matmul", "fused_mlp", "gpt_block_mlp", "paged_attention",
           "paged_attention_ref", "registry_table", "pick_block",
           "supported_2d", "audit_manifest"]

_LN_EPS = 1e-5   # nn.LayerNorm's default epsilon (the only one GPT uses)

_CALLS = None


def _calls():
    global _CALLS
    if _CALLS is None:
        _CALLS = _monitor.counter(
            "tpp_kernel_calls_total",
            "TPP micro-kernel/port invocations by op (counted at trace "
            "time — once per compiled program, like the collective "
            "chokepoint meters)", labelnames=("op",))
    return _CALLS


def _resolve_interpret(interpret):
    """The caller's explicit True/False, else the platform test — now."""
    return (not on_tpu()) if interpret is None else bool(interpret)


#: candidate block edges, MXU/VPU-aligned first (128 is the MXU edge;
#: the smaller tails keep the tiny CI models on the kernel path in
#: interpret mode, where alignment affects nothing but tiling)
_BLOCK_EDGES = (256, 128, 64, 32, 16, 8)


def pick_block(dim):
    """Largest registry block edge dividing `dim` (None if indivisible —
    callers fall back to the dense path)."""
    for b in _BLOCK_EDGES:
        if dim % b == 0:
            return b
    return None


def supported_2d(m, k, n, dtype):
    """Can the registry tile an [m, k] @ [k, n] op? Returns the
    (bm, bn, bk) block shape, or None."""
    if str(dtype) not in ("float32", "bfloat16"):
        return None
    bm, bk, bn = pick_block(m), pick_block(k), pick_block(n)
    if bm is None or bk is None or bn is None:
        return None
    return (bm, bn, bk)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY = {}   # (op, dtype_str, block tuple) -> {"fn", "calls"}


def _kernel_entry(op, dtype, block, builder):
    key = (str(op), str(dtype), tuple(block))
    entry = _REGISTRY.get(key)
    if entry is None:
        entry = _REGISTRY[key] = {"fn": builder(), "calls": 0}
    return entry


def registry_table():
    """Snapshot of every built kernel: [{op, dtype, block, calls}] —
    the docs/PERF.md TPP registry table, live."""
    return [{"op": op, "dtype": dt, "block": list(blk),
             "calls": e["calls"]}
            for (op, dt, blk), e in sorted(_REGISTRY.items())]


def _note_call(entry, op, flops, nbytes):
    """Trace-time metering: count the call, land analytic FLOPs/bytes
    in the cost registry under site='tpp' (cumulative per op)."""
    entry["calls"] += 1
    if _monitor.is_enabled():
        _calls().labels(op=op).inc()
    _costs.record_manual("tpp", op, flops=flops, bytes_accessed=nbytes)


# ---------------------------------------------------------------------------
# static audit manifest (analysis/pallas_audit.py, ISSUE 13)
# ---------------------------------------------------------------------------

#: representative production shapes: the gpt2s hot path (hidden 768,
#: intermediate 3072, m = rows per kernel call). The manifest derives
#: blocks through the SAME pick_block/supported_2d the runtime uses, so
#: a block-table change flows straight into the lint-time budget check.
_AUDIT_SHAPES = ((512, 768, 3072), (512, 3072, 768))
_AUDIT_DTYPES = ("float32", "bfloat16")


def _matmul_entry(kernel, m, k, n, dtype, block, ln_prologue=False,
                  has_bias=True):
    bm, bn, bk = block
    bufs = [{"name": "x", "block": (bm, bk), "dtype": dtype}]
    if ln_prologue:
        bufs += [{"name": "gamma", "block": (1, bk), "dtype": dtype},
                 {"name": "beta", "block": (1, bk), "dtype": dtype}]
    bufs.append({"name": "w", "block": (bk, bn), "dtype": dtype})
    if has_bias:
        bufs.append({"name": "bias", "block": (1, bn), "dtype": dtype})
    bufs += [{"name": "out", "block": (bm, bn), "dtype": dtype},
             {"name": "acc(scratch)", "block": (bm, bn),
              "dtype": "float32", "stream": False}]
    return {"kernel": kernel, "op": kernel.split("[")[0],
            "in_dtype": dtype, "acc_dtype": "float32", "matmul": True,
            "grid": {"m": (m, bm), "n": (n, bn), "k": (k, bk)},
            "buffers": bufs}


def audit_manifest():
    """Declarative audit entries for every TPP kernel shape class —
    pure arithmetic mirroring the builders (nothing compiles)."""
    entries = []
    for dtype in _AUDIT_DTYPES:
        for m, k, n in _AUDIT_SHAPES:
            block = supported_2d(m, k, n, dtype)
            if block is None:
                continue
            entries.append(_matmul_entry(
                f"tpp.matmul[{m}x{k}x{n},{dtype}]", m, k, n, dtype,
                block))
        m, k, n = _AUDIT_SHAPES[0]
        bm, bn = pick_block(m), pick_block(n)
        # ln_matmul pins bk == k (LN row stats need the whole row)
        entries.append(_matmul_entry(
            f"tpp.ln_matmul[{m}x{k}x{n},{dtype}]", m, k, n, dtype,
            (bm, bn, k), ln_prologue=True))
        bm, bn = pick_block(m), pick_block(k)
        entries.append({
            "kernel": f"tpp.bias_act[{m}x{k},{dtype}]", "op": "bias_act",
            "in_dtype": dtype, "matmul": False,
            "grid": {"m": (m, bm), "n": (k, bn)},
            "buffers": [
                {"name": "x", "block": (bm, bn), "dtype": dtype},
                {"name": "bias", "block": (1, bn), "dtype": dtype},
                {"name": "out", "block": (bm, bn), "dtype": dtype}]})
        entries.append({
            "kernel": f"tpp.softmax_rows[{m}x{k},{dtype}]",
            "op": "softmax_rows", "in_dtype": dtype, "matmul": False,
            "grid": {"m": (m, bm)},
            "buffers": [
                {"name": "x", "block": (bm, k), "dtype": dtype},
                {"name": "out", "block": (bm, k), "dtype": dtype}]})
        entries.append({
            "kernel": f"tpp.masked_reduce[{m}x{k},{dtype}]",
            "op": "masked_reduce", "in_dtype": dtype, "matmul": False,
            "grid": {"m": (m, bm)},
            "buffers": [
                {"name": "x", "block": (bm, k), "dtype": dtype},
                {"name": "mask", "block": (bm, k), "dtype": "int32"},
                {"name": "out", "block": (bm, 1), "dtype": dtype}]})
    for B, H, hd, bs, maxb in _PAGED_AUDIT_SHAPES:
        for variant, page_dt in (("dense", "float32"), ("int8", "int8")):
            bufs = [
                {"name": "q", "block": (1, H, hd), "dtype": "float32"},
                {"name": "k_page", "block": (1, H, bs, hd),
                 "dtype": page_dt},
                {"name": "v_page", "block": (1, H, bs, hd),
                 "dtype": page_dt}]
            if variant == "int8":
                bufs += [{"name": "k_scales", "block": (1, H, bs, 1),
                          "dtype": "float32"},
                         {"name": "v_scales", "block": (1, H, bs, 1),
                          "dtype": "float32"}]
            bufs += [
                {"name": "out", "block": (1, H, hd), "dtype": "float32"},
                {"name": "m(scratch)", "block": (H, 1),
                 "dtype": "float32", "stream": False},
                {"name": "l(scratch)", "block": (H, 1),
                 "dtype": "float32", "stream": False},
                {"name": "acc(scratch)", "block": (H, hd),
                 "dtype": "float32", "stream": False}]
            entries.append({
                "kernel": f"tpp.paged_attention[{variant},B{B}xH{H}x"
                          f"{hd},bs{bs}x{maxb}]",
                "op": "paged_attention",
                "in_dtype": page_dt, "acc_dtype": "float32",
                "matmul": True,
                "grid": {"b": (B, 1), "j": (maxb, 1)},
                "buffers": bufs})
    return entries


# ---------------------------------------------------------------------------
# paged attention (the FLAGS_paged_kv decode kernel, ISSUE 18)
# ---------------------------------------------------------------------------

#: bundled paged_attention audit shapes: (B, H, hd, bs, maxb) — a
#: v5e-class serving point (128-lane head dim, 32-deep blocks so the
#: int8 page variant meets its 32-row sublane tile too)
_PAGED_AUDIT_SHAPES = ((16, 8, 128, 32, 16),)


def _paged_attention_kernel(tables_ref, lens_ref, *refs, bs, maxb, scale,
                            quantized):
    """One (b, j) grid step of the block-table decode attention: the
    scalar-prefetched table picked THIS j's physical frame (the K/V
    BlockSpec index_map reads tables_ref before the body runs), so the
    body only flash-accumulates one [KVh, bs, hd] block into the online
    softmax state (m/l/acc scratch, f32)."""
    import jax.experimental.pallas as pl

    idx = 0
    q_ref = refs[idx]; idx += 1
    k_ref = refs[idx]; idx += 1
    v_ref = refs[idx]; idx += 1
    ks_ref = vs_ref = None
    if quantized:
        ks_ref = refs[idx]; idx += 1
        vs_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    m_ref, l_ref, acc_ref = refs[idx], refs[idx + 1], refs[idx + 2]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)             # [H, hd]
    k = k_ref[0].astype(jnp.float32)             # [KVh, bs, hd]
    v = v_ref[0].astype(jnp.float32)
    if quantized:                                # int8 pages: row codec
        k = k * ks_ref[0].astype(jnp.float32)
        v = v * vs_ref[0].astype(jnp.float32)
    # the query rides a UNIT q dimension through both contractions:
    # Mosaic's dot needs a non-contracting lhs dimension ("hd,hcd->hc"
    # has none and is refused by the chip's compiler)
    s = jnp.einsum("hqd,hcd->hqc", q[:, None, :], k,
                   preferred_element_type=jnp.float32)[:, 0, :] * scale
    col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    s = jnp.where(col < lens_ref[b], s, -jnp.inf)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # a fully-masked block keeps m at -inf; substitute 0 so the exps
    # below see finite-minus-finite (they all collapse to exp(-inf)=0)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(m_prev - m_safe)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hqc,hcd->hqd", p[:, None, :], v,
        preferred_element_type=jnp.float32)[:, 0, :]
    m_ref[...] = m_new

    @pl.when(j == maxb - 1)
    def _writeback():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _build_paged_attention(dtype, shape_key, quantized):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, hd, bs, maxb = shape_key
    scale = 1.0 / (hd ** 0.5)

    def call(interpret, q, kp, vp, tables, lengths, k_scales=None,
             v_scales=None):
        B = q.shape[0]
        kern = functools.partial(
            _paged_attention_kernel, bs=bs, maxb=maxb, scale=scale,
            quantized=quantized)
        # the block table is the scalar-prefetch payload: the K/V specs'
        # index_map picks each step's PHYSICAL frame from it
        in_specs = [
            pl.BlockSpec((1, H, hd), lambda b, j, t, n: (b, 0, 0)),
            pl.BlockSpec((1, H, bs, hd),
                         lambda b, j, t, n: (t[b, j], 0, 0, 0)),
            pl.BlockSpec((1, H, bs, hd),
                         lambda b, j, t, n: (t[b, j], 0, 0, 0)),
        ]
        args = [q, kp, vp]
        if quantized:
            in_specs += [
                pl.BlockSpec((1, H, bs, 1),
                             lambda b, j, t, n: (t[b, j], 0, 0, 0)),
                pl.BlockSpec((1, H, bs, 1),
                             lambda b, j, t, n: (t[b, j], 0, 0, 0)),
            ]
            args += [k_scales, v_scales]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, maxb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, hd),
                                   lambda b, j, t, n: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, hd), jnp.float32)],
        )
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
            interpret=interpret,
        )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)

    return call


def paged_attention(q, kp, vp, tables, lengths, k_scales=None,
                    v_scales=None, interpret=None):
    """Block-table decode attention (one layer, one query per row).

    ``q`` [B, H, hd]; ``kp``/``vp`` [NB, H, bs, hd] physical KV frames;
    ``tables`` int [B, maxb] frame indices; ``lengths`` int [B] — row b
    attends columns ``0..lengths[b]-1`` of its logical cache. K/V blocks
    are gathered BY TABLE INDEX through scalar-prefetched BlockSpec
    index maps (never materializing the dense cache) and folded into an
    online-softmax f32 accumulator per row — the flash recipe over
    paged storage. With ``k_scales``/``v_scales`` ([NB, H, bs, 1] f32)
    the frames hold int8 pages (distributed/compress.py row codec) and
    dequantize on load; outputs then carry the codec's declared band vs
    the dense reference (:func:`paged_attention_ref` pins both paths)."""
    B, H, hd = q.shape
    NB, Hk, bs, hd_k = kp.shape
    if Hk != H or hd_k != hd:
        raise ValueError(
            f"paged_attention serves H == KVh (got q heads {H}, kv heads "
            f"{Hk}) and matching head dim (got {hd} vs {hd_k}) — grouped "
            "queries reshape outside the kernel")
    maxb = tables.shape[1]
    quantized = k_scales is not None
    if quantized != (v_scales is not None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    shape_key = (H, hd, bs, maxb)
    variant = "int8" if quantized else "dense"
    entry = _kernel_entry(
        f"paged_attention|{variant}", q.dtype, shape_key,
        lambda: _build_paged_attention(q.dtype, shape_key, quantized))
    item = jnp.dtype(q.dtype).itemsize
    page_item = 1 if quantized else jnp.dtype(kp.dtype).itemsize
    T = maxb * bs
    _note_call(entry, "paged_attention",
               4.0 * B * H * T * hd,
               (2 * B * H * hd * item              # q + out
                + 2 * B * maxb * H * bs * hd * page_item  # gathered pages
                + B * maxb * 4 + B * 4))           # tables + lengths
    return entry["fn"](_resolve_interpret(interpret), q, kp, vp, tables,
                       lengths, k_scales, v_scales)


def paged_attention_ref(q, kp, vp, tables, lengths, k_scales=None,
                        v_scales=None):
    """Pure-lax reference for :func:`paged_attention`: gather the pool
    through the tables into the dense layout, plain masked softmax
    attention in f32. The kernel must match within the declared band
    (f32 pages: online-softmax reassociation only; int8 pages add the
    row codec's quantization band)."""
    B, H, hd = q.shape
    maxb = tables.shape[1]
    bs = kp.shape[2]

    def dense(pool, scales):
        g = pool[tables].astype(jnp.float32)     # [B, maxb, H, bs, hd]
        if scales is not None:
            g = g * scales[tables].astype(jnp.float32)
        g = jnp.transpose(g, (0, 2, 1, 3, 4))
        return g.reshape(B, H, maxb * bs, hd)

    k = dense(kp, k_scales)
    v = dense(vp, v_scales)
    s = jnp.einsum("bhd,bhTd->bhT", q.astype(jnp.float32), k) \
        * (1.0 / (hd ** 0.5))
    cols = jnp.arange(maxb * bs)[None, None, :]
    s = jnp.where(cols < lengths[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum("bhT,bhTd->bhd", p, v).astype(q.dtype)


# ---------------------------------------------------------------------------
# activations (used inside kernel bodies — elementwise, K-block safe)
# ---------------------------------------------------------------------------

_ACTS = ("none", "gelu", "gelu_tanh", "relu")


def _apply_act(x, act):
    if act == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if act == "relu":
        return jnp.maximum(x, 0.0)
    return x


def _check_act(act, interpret, op):
    """Validate `act` for a kernel about to be lowered. Exact GELU is
    erf-based and this jax's Pallas TPU lowering has no erf/erfc — the
    compiled kernel raises BY NAME here instead of failing deep inside
    Mosaic (or, worse, quietly going dense)."""
    if act not in _ACTS:
        raise ValueError(f"act must be one of {_ACTS}, got {act!r}")
    if act == "gelu" and not interpret:
        raise NotImplementedError(
            f"tpp.{op}: exact (erf) GELU cannot be compiled for the TPU — "
            "the Pallas TPU lowering implements neither erf nor erfc. Use "
            "the tanh form (act='gelu_tanh' / GPTConfig(gelu_approx=True)) "
            "or leave FLAGS_tpp_kernels off for this model")


# ---------------------------------------------------------------------------
# matmul-accumulate (+ optional LN prologue / input act / bias+act epilogue)
# ---------------------------------------------------------------------------


def _matmul_kernel(*refs, k_steps, has_bias, ln_prologue, in_act, act):
    """One (i, j, ki) grid step: acc += f(x_blk) @ w_blk, with f the
    optional LN-normalize or input activation; bias + epilogue act land
    on the final K step's writeback."""
    import jax.experimental.pallas as pl

    idx = 0
    x_ref = refs[idx]; idx += 1
    if ln_prologue:
        g_ref = refs[idx]; idx += 1
        b2_ref = refs[idx]; idx += 1
    w_ref = refs[idx]; idx += 1
    bias_ref = None
    if has_bias:
        bias_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    acc_ref = refs[idx]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    if ln_prologue:
        # fp32 row stats over the FULL row (bk == K by construction)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) * (x - mu), axis=-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + _LN_EPS)
        x = x * g_ref[...].astype(jnp.float32) \
            + b2_ref[...].astype(jnp.float32)
    x = _apply_act(x, in_act)
    acc_ref[...] += jnp.dot(x, w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(ki == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + bias_ref[...].astype(jnp.float32)
        o_ref[...] = _apply_act(acc, act).astype(o_ref.dtype)


def _build_matmul(dtype, block, has_bias, ln_prologue, in_act, act):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bm, bn, bk = block

    def call(interpret, *call_args):
        # kernel-order args: x [, gamma, beta], w [, bias]
        it = iter(call_args)
        x = next(it)
        gamma = beta = None
        if ln_prologue:
            gamma, beta = next(it), next(it)
        w = next(it)
        bias = next(it) if has_bias else None
        m, k = x.shape
        n = w.shape[1]
        k_steps = k // bk
        kern = functools.partial(_matmul_kernel, k_steps=k_steps,
                                 has_bias=has_bias,
                                 ln_prologue=ln_prologue,
                                 in_act=in_act, act=act)
        in_specs = [pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki))]
        args = [x]
        if ln_prologue:
            in_specs += [
                pl.BlockSpec((1, bk), lambda i, j, ki: (0, ki)),
                pl.BlockSpec((1, bk), lambda i, j, ki: (0, ki)),
            ]
            args += [gamma.reshape(1, k), beta.reshape(1, k)]
        in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)))
        args.append(w)
        if has_bias:
            in_specs.append(pl.BlockSpec((1, bn),
                                         lambda i, j, ki: (0, j)))
            args.append(bias.reshape(1, n))
        return pl.pallas_call(
            kern,
            grid=(m // bm, n // bn, k_steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(*args)

    return call


def matmul(x, w, bias=None, in_act="none", act="none", block=None,
           _op="matmul", interpret=None):
    """Blocked matmul-accumulate: ``act(in_act(x) @ w + bias)``.
    x [m, k], w [k, n]; block=(bm, bn, bk) (auto-picked if None —
    raises when the shapes don't tile; check :func:`supported_2d`)."""
    interpret = _resolve_interpret(interpret)
    _check_act(in_act, interpret, _op), _check_act(act, interpret, _op)
    m, k = x.shape
    n = w.shape[1]
    block = block or supported_2d(m, k, n, x.dtype)
    if block is None:
        raise ValueError(
            f"tpp.matmul cannot tile [{m},{k}]@[{k},{n}] {x.dtype} — "
            "gate on supported_2d() and fall back to the dense path")
    key_op = (f"{_op}|bias={bias is not None}|in={in_act}|ep={act}")
    entry = _kernel_entry(key_op, x.dtype, block, lambda: _build_matmul(
        x.dtype, block, bias is not None, False, in_act, act))
    item = jnp.dtype(x.dtype).itemsize
    _note_call(entry, _op, 2.0 * m * k * n,
               (m * k + k * n + m * n + (n if bias is not None else 0))
               * item)
    args = (x, w) + ((bias,) if bias is not None else ())
    return entry["fn"](interpret, *args)


# ---------------------------------------------------------------------------
# bias + activation (VPU row blocks)
# ---------------------------------------------------------------------------


def _bias_act_kernel(x_ref, b_ref, o_ref, *, act):
    x = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = _apply_act(x, act).astype(o_ref.dtype)


def _build_bias_act(dtype, block, act):
    from jax.experimental import pallas as pl

    bm, bn = block

    def call(interpret, x, bias):
        m, n = x.shape
        return pl.pallas_call(
            functools.partial(_bias_act_kernel, act=act),
            grid=(m // bm, n // bn),
            in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                      pl.BlockSpec((1, bn), lambda i, j: (0, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), dtype),
            interpret=interpret,
        )(x, bias.reshape(1, n))

    return call


def bias_act(x, bias, act="gelu", interpret=None):
    """Fused ``act(x + bias)`` over [bm, bn] blocks. x [m, n], bias [n]."""
    interpret = _resolve_interpret(interpret)
    _check_act(act, interpret, "bias_act")
    m, n = x.shape
    bm, bn = pick_block(m), pick_block(n)
    if bm is None or bn is None:
        raise ValueError(f"tpp.bias_act cannot tile [{m},{n}]")
    entry = _kernel_entry(f"bias_act|{act}", x.dtype, (bm, bn),
                          lambda: _build_bias_act(x.dtype, (bm, bn), act))
    item = jnp.dtype(x.dtype).itemsize
    _note_call(entry, "bias_act", 2.0 * m * n, (2 * m * n + n) * item)
    return entry["fn"](interpret, x, bias)


# ---------------------------------------------------------------------------
# softmax row-pass
# ---------------------------------------------------------------------------


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x)
    o_ref[...] = (ex / jnp.sum(ex, axis=-1, keepdims=True)
                  ).astype(o_ref.dtype)


def _build_softmax(dtype, block):
    from jax.experimental import pallas as pl

    bm = block[0]

    def call(interpret, x):
        m, n = x.shape
        return pl.pallas_call(
            _softmax_kernel,
            grid=(m // bm,),
            in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, n), dtype),
            interpret=interpret,
        )(x)

    return call


def softmax_rows(x, interpret=None):
    """Stable row softmax over [bm, N] blocks (full row per grid step;
    fp32 max/sum internally). x [m, n]."""
    m, n = x.shape
    bm = pick_block(m)
    if bm is None:
        raise ValueError(f"tpp.softmax_rows cannot tile {m} rows")
    entry = _kernel_entry("softmax_rows", x.dtype, (bm, n),
                          lambda: _build_softmax(x.dtype, (bm, n)))
    item = jnp.dtype(x.dtype).itemsize
    _note_call(entry, "softmax_rows", 5.0 * m * n, 2 * m * n * item)
    return entry["fn"](_resolve_interpret(interpret), x)


# ---------------------------------------------------------------------------
# masked reduce
# ---------------------------------------------------------------------------


def _masked_reduce_kernel(x_ref, m_ref, o_ref, *, kind):
    x = x_ref[...].astype(jnp.float32)
    keep = m_ref[...] != 0
    if kind == "sum":
        o_ref[...] = jnp.sum(jnp.where(keep, x, 0.0), axis=-1,
                             keepdims=True).astype(o_ref.dtype)
    else:
        o_ref[...] = jnp.max(jnp.where(keep, x, -jnp.inf), axis=-1,
                             keepdims=True).astype(o_ref.dtype)


def _build_masked_reduce(dtype, block, kind):
    from jax.experimental import pallas as pl

    bm = block[0]

    def call(interpret, x, mask):
        m, n = x.shape
        return pl.pallas_call(
            functools.partial(_masked_reduce_kernel, kind=kind),
            grid=(m // bm,),
            in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                      pl.BlockSpec((bm, n), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, 1), dtype),
            interpret=interpret,
        )(x, mask)

    return call


def masked_reduce(x, mask, kind="sum", interpret=None):
    """Row-wise masked ``sum``/``max``: reduce x[i, j] over columns
    where mask[i, j] != 0. x [m, n] -> [m, 1]."""
    if kind not in ("sum", "max"):
        raise ValueError(f"kind must be sum|max, got {kind!r}")
    m, n = x.shape
    bm = pick_block(m)
    if bm is None:
        raise ValueError(f"tpp.masked_reduce cannot tile {m} rows")
    entry = _kernel_entry(f"masked_reduce|{kind}", x.dtype, (bm, n),
                          lambda: _build_masked_reduce(x.dtype, (bm, n),
                                                       kind))
    item = jnp.dtype(x.dtype).itemsize
    _note_call(entry, "masked_reduce", float(m * n),
               (2 * m * n + m) * item)
    return entry["fn"](_resolve_interpret(interpret), x,
                       mask.astype(jnp.int32))


# ---------------------------------------------------------------------------
# ported op: layernorm -> matmul prologue (ln_matmul)
# ---------------------------------------------------------------------------


def _ln_matmul_ref(x, gamma, beta, w, bias):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    xn = (x32 - mu) * jax.lax.rsqrt(var + _LN_EPS) * gamma + beta
    return (xn @ w.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _ln_matmul_fwd_kernel(x, gamma, beta, w, bias, interpret):
    m, k = x.shape
    n = w.shape[1]
    bm, bn = pick_block(m), pick_block(n)
    block = (bm, bn, k)   # LN stats need the full row: one K step
    entry = _kernel_entry("ln_matmul", x.dtype, block,
                          lambda: _build_matmul(x.dtype, block, True,
                                                True, "none", "none"))
    item = jnp.dtype(x.dtype).itemsize
    _note_call(entry, "ln_matmul", 2.0 * m * k * n + 8.0 * m * k,
               (m * k + k * n + m * n + 2 * k + n) * item)
    return entry["fn"](_resolve_interpret(interpret), x, gamma, beta, w,
                       bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ln_matmul(x, gamma, beta, w, bias, interpret=None):
    """Fused layernorm -> matmul prologue: ``LN(x; gamma, beta) @ w +
    bias`` with the normalized rows living only in VMEM. Differentiable
    (reference-math backward). Shapes: x [m, k], w [k, n]; m and n must
    tile (:func:`supported_2d` with bk == k)."""
    return _ln_matmul_fwd_kernel(x, gamma, beta, w, bias, interpret)


def _ln_matmul_vfwd(x, gamma, beta, w, bias, interpret):
    return _ln_matmul_fwd_kernel(x, gamma, beta, w, bias, interpret), \
        (x, gamma, beta, w, bias)


def _ln_matmul_vbwd(interpret, res, g):
    _, vjp = jax.vjp(_ln_matmul_ref, *res)
    return vjp(g)


ln_matmul.defvjp(_ln_matmul_vfwd, _ln_matmul_vbwd)


def ln_matmul_supported(m, k, n, dtype):
    """Tiling gate for the ln_matmul port (bk is pinned to k)."""
    return (str(dtype) in ("float32", "bfloat16")
            and pick_block(m) is not None and pick_block(n) is not None)


# ---------------------------------------------------------------------------
# ported op: the GPT fused MLP block (fused_mlp)
# ---------------------------------------------------------------------------


def _mlp_ref(x, w1, b1, w2, b2, approx):
    h = jax.nn.gelu((x.astype(jnp.float32) @ w1.astype(jnp.float32)
                     + b1.astype(jnp.float32)), approximate=approx)
    return (h @ w2.astype(jnp.float32)
            + b2.astype(jnp.float32)).astype(x.dtype)


def _mlp_fwd_kernels(x, w1, b1, w2, b2, approx, interpret):
    # leg 1: x @ w1 + b1 (pre-activation hidden — the one HBM
    # intermediate); leg 2: gelu fused into the second matmul's x-block
    # load, projection + bias on the way out
    h = matmul(x, w1, bias=b1, _op="fused_mlp", interpret=interpret)
    return _fused_tail_kernel(h, w2, b2, approx, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def fused_mlp(x, w1, b1, w2, b2, approx=False, interpret=None):
    """The GPT MLP block ``(gelu(x @ w1 + b1)) @ w2 + b2`` through two
    blocked kernels — gelu fused into the second matmul's block loads.
    Differentiable (reference-math backward). x [m, k]; both matmuls
    must tile (:func:`supported_2d`)."""
    return _mlp_fwd_kernels(x, w1, b1, w2, b2, approx, interpret)


def _mlp_vfwd(x, w1, b1, w2, b2, approx, interpret):
    return _mlp_fwd_kernels(x, w1, b1, w2, b2, approx, interpret), \
        (x, w1, b1, w2, b2)


def _mlp_vbwd(approx, interpret, res, g):
    _, vjp = jax.vjp(
        lambda x, w1, b1, w2, b2: _mlp_ref(x, w1, b1, w2, b2, approx),
        *res)
    return vjp(g)


fused_mlp.defvjp(_mlp_vfwd, _mlp_vbwd)


# ---------------------------------------------------------------------------
# the models/gpt.py hook
# ---------------------------------------------------------------------------


def gpt_block_mlp(x, ln, mlp, interpret=None):
    """The GPT block's MLP path ``fc2(gelu(fc1(LN(x))))`` through the
    two ported ops: ln_matmul covers ln2+fc1 (the layernorm->matmul
    prologue), the fused_mlp tail covers gelu+fc2. Takes the raw
    [b, s, h] array and the block's LayerNorm/GPTMLP layers; returns
    the [b, s, h] array, or None when the shapes/dtype don't tile (the
    caller falls back to the dense path). An exact-GELU MLP compiled for
    the TPU raises by name (:func:`_check_act`) — never None."""
    b, s, h = x.shape
    w1, b1 = mlp.fc1.weight._data, mlp.fc1.bias._data
    w2, b2 = mlp.fc2.weight._data, mlp.fc2.bias._data
    inter = w1.shape[1]
    m = b * s
    if not ln_matmul_supported(m, h, inter, x.dtype) \
            or supported_2d(m, inter, h, x.dtype) is None \
            or getattr(ln, "_epsilon", _LN_EPS) != _LN_EPS:
        return None
    interpret = _resolve_interpret(interpret)
    approx = bool(getattr(mlp, "_gelu_approx", False))
    _check_act("gelu_tanh" if approx else "gelu", interpret,
               "gpt_block_mlp")
    x2 = x.reshape(m, h)
    pre = ln_matmul(x2, ln.weight._data, ln.bias._data, w1, b1, interpret)
    out = _fused_tail(pre, w2, b2, approx, interpret)
    return out.reshape(b, s, h)


def _fused_tail_kernel(pre, w2, b2, approx, interpret):
    act = "gelu_tanh" if approx else "gelu"
    return matmul(pre, w2, bias=b2, in_act=act, _op="fused_mlp",
                  interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_tail(pre, w2, b2, approx, interpret):
    """gelu + projection half of the MLP block (the fused_mlp op
    applied after an ln_matmul prologue already produced the
    pre-activation hidden)."""
    return _fused_tail_kernel(pre, w2, b2, approx, interpret)


def _fused_tail_ref(pre, w2, b2, approx):
    h = jax.nn.gelu(pre.astype(jnp.float32), approximate=approx)
    return (h @ w2.astype(jnp.float32)
            + b2.astype(jnp.float32)).astype(pre.dtype)


def _fused_tail_vfwd(pre, w2, b2, approx, interpret):
    return _fused_tail_kernel(pre, w2, b2, approx, interpret), \
        (pre, w2, b2)


def _fused_tail_vbwd(approx, interpret, res, g):
    _, vjp = jax.vjp(
        lambda pre, w2, b2: _fused_tail_ref(pre, w2, b2, approx), *res)
    return vjp(g)


_fused_tail.defvjp(_fused_tail_vfwd, _fused_tail_vbwd)
