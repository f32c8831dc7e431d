"""Pallas attention of one decode step over the dense KV cache
[L, B, KVh, T, hd], reading only the columns that are live (models/gpt.py
_decode_fns.block; docs/SERVING.md "The dense cache on the chip").

A decode step has one query a row, and row b attends to columns 0..pos[b]
of its own row of layer i. The einsums contract with all T columns and mask
afterwards; this kernel fetches tiles 0..pos[b] // 128 of K and of V, from
the cache where and as it lies, and nothing beyond them. It sees a leaf
through `swapaxes(leaf, 3, 4)`, row-major [L, B, KVh, hd, T], which is the
stored T-minor layout (ops/kv_store.py has why), so the compiled step holds
no copy, slice or relayout of a cache layer.

One call walks the step's live (row, tile) pairs in one loop: the cache stays
in HBM, a tile of K and of V ([KVh, hd, 128] each) is fetched into one of two
buffers while the tile before it is worked on, rows follow one another
without a gap, and a dead tile costs nothing, not even a grid step. Both
products run on the matrix unit with the cache tile as the operand that is
loaded, as a matrix product of 32 rows loads its weights: the tile is
[KVh * hd, 128] as it lies, and the row's query is spread block-diagonally
over [heads, KVh * hd] (head h holds q[h] in lanes h * hd .. (h + 1) * hd and
zeros elsewhere), so that `Q @ K` gives every head's scores [heads, 128] and
`P @ V^T` every head's values [heads, KVh * hd], of which head h keeps its
own hd lanes. bf16 operands, float32 accumulation, an online softmax in
float32 over the tiles.

The layer index and `pos` ride in as prefetched scalars and the call is a jit
of its own, so the layers of a step share one traced kernel. The step's own
column is in the cache before the call (`_store` runs first): no row's
softmax is empty. A later change that hands the new column apart only has to
start the softmax from it.
"""
import functools
import math

import jax
import jax.numpy as jnp

from ..core.device import on_tpu
from .kv_store import LANE

#: two buffers each of a K and a V tile, the step's queries and results (two
#: buffers each, as the pipeline keeps them), the spread query and the
#: accumulator have to fit in fast memory
_VMEM_BUDGET = 12 << 20


def _heads_padded(kvh):
    return -(-kvh // 16) * 16      # a whole bf16 sublane tile of query rows


def fits(leaf, q):
    """Can `decode_attention` take this read? A plain (unquantized) cache
    side, one query a row, as many query heads as the cache has (grouped
    queries take the einsums), T a whole number of lane tiles, hd a whole
    number of sublane tiles and short of a lane tile (from 128 on the chip
    keeps a leaf hd-minor, and the T-minor view would be a relayout of the
    whole cache), and buffers that fit."""
    if isinstance(leaf, tuple):
        return False
    _, rows, kvh, t_max, hd = leaf.shape
    item = jnp.dtype(leaf.dtype).itemsize
    width = kvh * hd
    vmem = (4 * width * LANE * item + 4 * rows * width * 4
            + _heads_padded(kvh) * width * (item + 4))
    return (q.shape == (rows, kvh, 1, hd) and leaf.dtype == q.dtype
            and item in (2, 4) and t_max % LANE == 0
            and hd % (32 // item) == 0 and hd < LANE
            and vmem <= _VMEM_BUDGET)


def live_only(leaf, q):
    """`fits`, on a TPU: on other platforms the masked einsums over all T
    columns (models/gpt.py block) are what the compiler fuses best."""
    return fits(leaf, q) and on_tpu()


def _kernel(i_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, qblk, m_ref, l_ref, acc, *, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layer = i_ref[0]
    rows = q_ref.shape[0]
    _, kvh, hd, _ = kbuf.shape
    width = kvh * hd
    heads = qblk.shape[0]
    # head h owns lanes h * hd .. (h + 1) * hd of a [heads, KVh * hd] value
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 0) * hd
    own = (lane >= first) & (lane < first + hd)

    def last_tile(b):
        return pos_ref[b] // LANE

    def fetch(slot, b, j):
        at = pl.ds(pl.multiple_of(j * LANE, LANE), LANE)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, :, :, at],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, b, :, :, at],
                                      vbuf.at[slot], sem.at[1, slot]))

    total = jax.lax.fori_loop(0, rows, lambda b, n: n + last_tile(b) + 1, 0)
    for c in fetch(0, 0, 0):
        c.start()

    def tile(s, at):
        b, j = at
        slot = s % 2
        ends_row = j == last_tile(b)
        nxt = jnp.where(ends_row, b + 1, b), jnp.where(ends_row, 0, j + 1)

        @pl.when(s + 1 < total)
        def _():
            for c in fetch(1 - slot, *nxt):
                c.start()

        @pl.when(j == 0)
        def _():
            q = jnp.broadcast_to(q_ref[pl.ds(b, 1), :], (heads, width))
            qblk[...] = jnp.where(own, q, 0.0).astype(qblk.dtype)
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

        for c in fetch(slot, b, j):
            c.wait()
        k = kbuf[slot].reshape(width, LANE)
        v = vbuf[slot].reshape(width, LANE)
        sc = jnp.dot(qblk[...], k,
                     preferred_element_type=jnp.float32) * scale
        cols = j * LANE + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(cols <= pos_ref[b], sc, -jnp.inf)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(sc - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = alpha * acc[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(ends_row)
        def _():
            out = jnp.where(own, acc[...] / l_ref[...], 0.0)
            o_ref[pl.ds(b, 1), :] = jnp.sum(out, axis=0, keepdims=True)

        return nxt

    jax.lax.fori_loop(0, total, tile, (jnp.int32(0), jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_attention(kleaf, vleaf, q, i, pos, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, rows, kvh, t_max, hd = kleaf.shape
    width, heads = kvh * hd, _heads_padded(kvh)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    whole = pl.BlockSpec((rows, width), lambda g, i_ref, pos_ref: (0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[whole, in_hbm, in_hbm],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, kvh, hd, LANE), kleaf.dtype),
                pltpu.VMEM((2, kvh, hd, LANE), vleaf.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, width), kleaf.dtype),    # the spread q
                pltpu.VMEM((heads, 1), jnp.float32),        # running max
                pltpu.VMEM((heads, 1), jnp.float32),        # running sum
                pltpu.VMEM((heads, width), jnp.float32)]),  # running p @ v
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.float32),
        name="decode_attention",
        interpret=interpret,
    )(i,
      # kv_store's clamp: an idle row's stale position reads one tile of
      # columns nobody looks at, never a block outside the cache
      jnp.clip(pos, 0, t_max - 1).astype(jnp.int32),
      # rows of float32, so that one of them can be picked by its index
      q.reshape(rows, width).astype(jnp.float32),
      jnp.swapaxes(kleaf, 3, 4), jnp.swapaxes(vleaf, 3, 4))
    return out.astype(q.dtype).reshape(rows, kvh, 1, hd)


def decode_attention(kleaf, vleaf, q, i, pos, interpret=None):
    """softmax(q k^T / sqrt(hd)) v of row b's one query q[b] [KVh, 1, hd]
    over columns 0..pos[b] of row b of layer i of `kleaf` and `vleaf`
    [L, B, KVh, T, hd]: [B, KVh, 1, hd]. What the masked einsums over all T
    columns give, from the live tiles alone."""
    if interpret is None:
        interpret = not on_tpu()
    return _decode_attention(kleaf, vleaf, q, jnp.full((1,), i, jnp.int32),
                             pos, interpret=bool(interpret))


def audit_manifest():
    """The kernel at the benchmark's serving cell (gpt2-large: 32 rows, 20
    heads of 64, T 1024, bf16): tiles of K and of V stream through two
    buffers each; the step's queries and results, the spread query and the
    softmax's running state stay resident (analysis/pallas_audit.py)."""
    rows, kvh, hd, t_max = 32, 20, 64, 1024
    width, heads = kvh * hd, _heads_padded(kvh)
    tile = {"block": (kvh, hd, LANE), "dtype": "bfloat16"}
    return [{
        "kernel": f"decode_attention.live_tiles[kvh={kvh},hd={hd}]",
        "op": "decode_attention", "in_dtype": "bfloat16", "matmul": True,
        "acc_dtype": "float32",
        "grid": {"t": (t_max, LANE)},
        "buffers": [{"name": "q", "block": (rows, width),
                     "dtype": "float32", "stream": False},
                    {"name": "out", "block": (rows, width),
                     "dtype": "float32", "stream": False},
                    dict(tile, name="k_tile"), dict(tile, name="v_tile"),
                    {"name": "q_spread", "block": (heads, width),
                     "dtype": "bfloat16", "stream": False},
                    {"name": "acc", "block": (heads, width),
                     "dtype": "float32", "stream": False}]}]
