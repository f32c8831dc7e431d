"""Pallas attention of a prompt or a chunk in the naive form of multi-head
latent attention, with a key offset: the scores never leave fast memory
(models/axk1.py `_attend_naive`; docs/SERVING.md "State kinds").

The naive form up-projects a row's cached latents `c_kv` [S, r] to per-head
keys and values, `[k_nope_i | v_i] = c_kv W_kvb,i` (dn + dv channels a head),
and attends the t queries of H heads to them: scores `(q_nope_i . k_nope_i +
q_rope_i . k_rope) scale` in float32, `k_rope` one set of dr channels for all
heads, query i seeing columns 0..offset + i. The einsum loop over blocks of
1,024 columns writes each block's scores of all heads, `[H, t, 1024]` float32,
to HBM and reads them back for every pass of its softmax. This kernel never
forms them outside fast memory:

- ONE call a layer, a grid of (row, group of `GROUP` heads, key tile of `TILE`
  columns). The key tile's index map is clamped to the last live tile
  (`offset + t` columns, a prefetched scalar): the tiles past it are not
  fetched and their grid steps do nothing, so one compiled program serves
  every offset.
- A live grid step up-projects its latent tile by the group's slice of
  `W_kvb` into fast memory (bf16 operands, float32 accumulation, rounded to
  the cache's type as the einsum's `_dot` rounds it): the expansion never
  reaches HBM either.
- Then, head by head and in strips of `STRIP` query rows, the scores of both
  parts (the rotary part over the cache's channels after `c_kv`, the rotary
  64 and the zeros to the lane; the query is padded with zeros to them), the
  mask where the diagonal crosses the (strip, tile), a running softmax in
  float32 and the product with the values. A (strip, tile) no query of the
  strip sees is not computed; one all of it sees is not masked. The
  statistics start where the einsum loop's do (m at -1e30, the sums at 0)
  at the first tile, which every query sees (column 0).
- The statistics follow ops/flash_attention.py's tile step (PR 35): m held
  lane-replicated, l as 128 partial sums a row (summed at the flush), no
  `[rows, 1]` to `[1, rows]` turn. What changes with the head group alone
  (its slice of W_kvb, its results) is held in one buffer, so the call
  stays in the compiler's default 16 MiB of fast memory: a raised limit
  turns on XLA's prefetches into fast memory across the whole program that
  calls it, whose code then outgrows the einsum loop's (PERF.md, PR 39).

The arithmetic is the einsum loop's: bf16 operands into the MXU, float32
scores, max, sum and rescale, probabilities rounded to the values' type before
their product, a float32 accumulator, the result divided by the sum and
rounded to the queries' type. Nothing is skipped that a query sees, no
exponential is approximated; the sums run in another order (tiles of 512
against blocks of 1,024), as any two blockings of one softmax do.

Where it runs: a TPU, for a call `fits` takes (models/axk1.py asks
`live_only`): the chunked prefill of `a.x-k1.serve-backlog-8k` (1,024 queries
over a row of 8,192 columns) and a whole prompt of up to `MAX_ROWS` tokens in
whole tiles. Elsewhere the einsum loop runs, and it is the reference the
tests hold this kernel to (interpret mode).
"""
import functools

import jax
import jax.numpy as jnp

from ..core.device import on_tpu

#: the alignment the chip's tiles want of a channel count (tests lower it to
#: run the kernel at a CPU-sized model)
LANE = 128
#: cached columns a key tile: one up-projection, one fetch of the latents
TILE = 512
#: query rows a strip: the rows whose scores against a tile are live at once
STRIP = 256
#: heads a grid step: they share its latent tile; their slice of W_kvb, their
#: queries, results and running statistics are what fast memory holds
GROUP = 4
#: queries a call holds in fast memory (one chunk of the cell)
MAX_ROWS = 1024
#: the statistics' width: one vector register's lanes
_W = 128
_MASKED = -1e30     # the einsum loop's: finite, exp(_MASKED - m) is 0

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dims(q_nope, q_rope, lat, w_kvb):
    B, t, H, dn = q_nope.shape
    r = w_kvb.shape[0]
    dv = w_kvb.shape[1] // H - dn
    return B, t, H, dn, q_rope.shape[-1], dv, r, lat.shape[2], lat.shape[3]


def fits(q_nope, q_rope, lat, w_kvb):
    """Can `latent_prefill_attention` take this call? q_nope [B, t, H, dn],
    q_rope [B, t, H, dr], lat [L, B, S, W] (c_kv in the first r channels,
    the rotary dr after them, the rest zeros), w_kvb [r, H (dn + dv)]; one
    dtype of 2 or 4 bytes; channel counts in whole lanes; t in whole strips
    and at most MAX_ROWS; S in whole tiles; heads in whole groups."""
    if q_nope.ndim != 4 or q_rope.ndim != 4 or lat.ndim != 4 \
            or w_kvb.ndim != 2:
        return False
    B, t, H, dn = q_nope.shape
    if q_rope.shape[:3] != (B, t, H) or lat.shape[1] != B \
            or w_kvb.shape[1] % H:
        return False
    _, _, _, dn, dr, dv, r, S, W = _dims(q_nope, q_rope, lat, w_kvb)
    dtypes = {jnp.dtype(a.dtype) for a in (q_nope, q_rope, lat, w_kvb)}
    return (len(dtypes) == 1 and dtypes.pop().itemsize in (2, 4)
            and dv > 0 and all(n % LANE == 0 for n in (dn, dv, r, W))
            and r + dr <= W and t % STRIP == 0 and t <= MAX_ROWS
            and S % TILE == 0 and H % GROUP == 0)


def live_only(q_nope, q_rope, lat, w_kvb):
    """`fits`, on a TPU: on other platforms the einsum loop is what the
    compiler fuses best, and it is the kernel's reference."""
    return fits(q_nope, q_rope, lat, w_kvb) and on_tpu()


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _kernel(at_ref, qn_ref, qr_ref, lat_ref, w_ref, o_ref, kv_ref, acc_ref,
            m_ref, l_ref, *, scale, t, s_max, dn, dv, r, mxu):
    from jax.experimental import pallas as pl

    G = qn_ref.shape[0]
    j = pl.program_id(2)
    off = at_ref[1]
    last = (jnp.minimum(off + t, s_max) - 1) // TILE
    k0 = j * TILE
    dt = lat_ref.dtype
    op = lambda x: x.astype(dt).astype(mxu)
    sw = min(_W, TILE)
    fold = lambda x, f: functools.reduce(
        f, [x[:, c:c + sw] for c in range(0, x.shape[1], sw)])
    wide = lambda x, n: x[:, :n] if n <= sw else jnp.tile(x, (1, n // sw))

    def strip(h, rows, q0, masked):
        """Strip `rows` of head h against this tile."""
        sc = (_dot(op(qn_ref[h, rows, :]), op(kv_ref[h, :, :dn]), _NT)
              + _dot(op(qr_ref[h, rows, :]), op(lat_ref[:, r:]), _NT)) \
            * scale                                         # [STRIP, TILE]
        if masked:
            seen = (k0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                    <= q0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0))
            sc = jnp.where(seen, sc, _MASKED)
        m_prev = m_ref[h, rows, :]
        m_next = jnp.maximum(m_prev, jnp.max(fold(sc, jnp.maximum), -1,
                                             keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(sc - wide(m_next, sc.shape[1]))
        l_ref[h, rows, :] = l_ref[h, rows, :] * alpha + fold(p, jnp.add)
        m_ref[h, rows, :] = m_next
        acc_ref[h, rows, :] = acc_ref[h, rows, :] * wide(alpha, dv) + _dot(
            op(p), op(kv_ref[h, :, dn:]), _NN)

    def rows_of(s):
        return pl.ds(pl.multiple_of(s * STRIP, STRIP), STRIP)

    @pl.when(j <= last)
    def _walk():
        c = op(lat_ref[:, :r])

        def keys(h, carry):             # the group's keys and values
            w = w_ref[:, pl.ds(pl.multiple_of(h * (dn + dv), LANE),
                               dn + dv)]
            kv_ref[h] = _dot(c, op(w), _NN).astype(dt)
            return carry

        jax.lax.fori_loop(0, G, keys, 0)

        def each(i, carry):
            h, s = i // (t // STRIP), i % (t // STRIP)
            rows = rows_of(s)
            q0 = off + s * STRIP        # the strip's first query's column

            @pl.when(j == 0)            # the einsum loop's start: nothing
            def _start():               # seen, m at _MASKED
                m_ref[h, rows, :] = jnp.full((STRIP, sw), _MASKED,
                                             jnp.float32)
                l_ref[h, rows, :] = jnp.zeros((STRIP, sw), jnp.float32)
                acc_ref[h, rows, :] = jnp.zeros((STRIP, dv), jnp.float32)

            live = k0 <= q0 + STRIP - 1
            clear = k0 + TILE - 1 <= q0
            pl.when(clear)(functools.partial(strip, h, rows, q0, False))
            pl.when(live & jnp.logical_not(clear))(
                functools.partial(strip, h, rows, q0, True))
            return carry

        jax.lax.fori_loop(0, G * (t // STRIP), each, 0)

    @pl.when(j == last)
    def _flush():
        def each(i, carry):
            h, rows = i // (t // STRIP), rows_of(i % (t // STRIP))
            l = jnp.sum(l_ref[h, rows, :], -1, keepdims=True)
            o_ref[h, rows, :] = (acc_ref[h, rows, :] / l).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, G * (t // STRIP), each, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_prefill_attention(q_nope, q_rope, lat, i, w_kvb, offset, scale,
                              interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, t, H, dn, dr, dv, r, S, W = _dims(q_nope, q_rope, lat, w_kvb)
    G, sw = GROUP, min(_W, TILE)
    # head-major queries (the copy XLA makes of the slices anyway); the
    # rotary part padded with zeros to the cache's channels after c_kv
    qn = jnp.transpose(q_nope, (0, 2, 1, 3))
    qr = jnp.pad(jnp.transpose(q_rope, (0, 2, 1, 3)),
                 [(0, 0), (0, 0), (0, 0), (0, W - r - dr)])
    # the layer and the offset ride in as prefetched scalars: the cache is
    # read where it lies, one program serves every offset
    at = jnp.stack([i, offset]).astype(jnp.int32)

    def key_tile(b, g, j, a):
        return a[0], b, jnp.minimum(j, (jnp.minimum(a[1] + t, S) - 1)
                                    // TILE), 0

    # the group's queries stream in two buffers, its slice of W_kvb and its
    # results in one: the module docstring has why
    heads = lambda n, *mode: pl.BlockSpec(
        (None, G, t, n), lambda b, g, j, a: (b, g, 0, 0), *mode)
    once = pl.Buffered(1)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, t=t, s_max=S, dn=dn, dv=dv,
                          r=r, mxu=jnp.float32 if interpret else lat.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // G, S // TILE),
            in_specs=[heads(dn), heads(W - r),
                      pl.BlockSpec((None, None, TILE, W), key_tile),
                      pl.BlockSpec((r, G * (dn + dv)),
                                   lambda b, g, j, a: (0, g), once)],
            out_specs=heads(dv, once),
            scratch_shapes=[
                pltpu.VMEM((G, TILE, dn + dv), lat.dtype),   # keys, values
                pltpu.VMEM((G, t, dv), jnp.float32),         # running p @ v
                pltpu.VMEM((G, t, sw), jnp.float32),         # running max
                pltpu.VMEM((G, t, sw), jnp.float32)]),       # running sum
        out_shape=jax.ShapeDtypeStruct((B, H, t, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="latent_prefill_attention",
        interpret=interpret,
    )(at, qn, qr, lat, w_kvb)
    return jnp.transpose(out, (0, 2, 1, 3))


def latent_prefill_attention(q_nope, q_rope, lat, i, w_kvb, offset, scale,
                             interpret=None):
    """The naive form's attention of q_nope [B, t, H, dn] and q_rope [B, t,
    H, dr] over the latents of layer i of lat [L, B, S, W] up-projected by
    w_kvb [r, H (dn + dv)], query j seeing columns 0..offset + j (`i` and
    `offset` traced or Python integers): [B, t, H, dv] in q_nope's dtype.
    What models/axk1.py's einsum loop gives, with the scores and the keys
    and values in fast memory alone and no tile past the last live column
    fetched."""
    if interpret is None:
        interpret = not on_tpu()
    return _latent_prefill_attention(q_nope, q_rope, lat,
                                     jnp.asarray(i, jnp.int32), w_kvb,
                                     jnp.asarray(offset, jnp.int32),
                                     scale=float(scale),
                                     interpret=bool(interpret))


def audit_manifest():
    """The kernel at the benchmark's latent serving cell (a.x-k1: a chunk of
    1,024 queries of 64 heads over a row of 8,192 latent columns 640 wide,
    bf16): a head group's queries stream in two buffers, its slice of W_kvb
    and its results in one; a latent tile streams; the group's keys and
    values and the running statistics are scratch
    (analysis/pallas_audit.py). Within the compiler's default 16 MiB."""
    heads, t, t_max, width, r, dn, dv = 64, 1024, 8192, 640, 512, 128, 128
    group = lambda name, n, dtype="bfloat16", stream=True: {
        "name": name, "block": (GROUP, t, n), "dtype": dtype,
        "stream": stream}
    return [{
        "kernel": f"latent_prefill_attention.tiles[h={heads},t={t}]",
        "op": "latent_prefill_attention", "in_dtype": "bfloat16",
        "matmul": True, "acc_dtype": "float32",
        "grid": {"heads": (heads, GROUP), "t": (t_max, TILE),
                 "q": (t, STRIP)},
        "buffers": [
            group("q_nope", dn), group("q_rope", width - r),
            group("out", dv, stream=False),
            {"name": "latent_tile", "block": (TILE, width),
             "dtype": "bfloat16"},
            {"name": "w_kvb", "block": (r, GROUP * (dn + dv)),
             "dtype": "bfloat16", "stream": False},
            {"name": "kv", "block": (GROUP, TILE, dn + dv),
             "dtype": "bfloat16", "stream": False},
            group("acc", dv, "float32", False),
            group("m", _W, "float32", False),
            group("l", _W, "float32", False)]}]
