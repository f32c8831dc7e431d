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
column is among those read, so no row's softmax is empty: `decode_attention`
finds it in the cache (a store ran first), and `decode_attention_store` is
handed it and stores it as well. The T-minor layout makes the smallest thing
a store can rewrite the [KVh, hd, 128] block around pos[b] (ops/kv_store.py),
which is row b's last live tile: the loop has just fetched it, so it puts the
new key and value into column pos[b] % 128 of the two tiles in fast memory,
runs the products on the tiles so patched, and copies them back to where they
came from while the next tiles arrive (four buffers a side there, fetched two
tiles ahead; a buffer is fetched into again only once its write-back is done).
The tile is read once a step, not twice, and a layer of the step is one call
where it was three.
"""
import functools
import math

import jax
import jax.numpy as jnp

from ..core.device import on_tpu
from .kv_store import LANE

#: the buffers of K and of V tiles, the step's queries and results (and new
#: keys and values; two buffers each, as the pipeline keeps them), the spread
#: query and the accumulator (and the new column turned to the tile's
#: orientation) have to fit in fast memory
_VMEM_BUDGET = 12 << 20

#: tile buffers a side, and how many tiles ahead of the one worked on the
#: loop fetches. Where tiles are written back, two ahead into four buffers:
#: the buffer fetched into is that of the tile before last, so a write-back
#: has a whole tile's work to finish in before anything waits for it, and
#: the copies in and out keep the memory system as busy as the reads alone
#: keep it (3.83 ms a step of the benchmark's cell against 4.00 with one
#: ahead into three, and 3.81 for the copies with no work on them: PERF.md,
#: PR 37)
_SLOTS, _AHEAD = 2, 1
_SLOTS_STORE, _AHEAD_STORE = 4, 2


def _heads_padded(kvh):
    return -(-kvh // 16) * 16      # a whole bf16 sublane tile of query rows


def fits(leaf, q):
    """Can `decode_attention` (and `decode_attention_store`, whose buffers
    are the ones counted) take this read? A plain (unquantized) cache
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
    vmem = (2 * _SLOTS_STORE * width * LANE * item + 8 * rows * width * 4
            + _heads_padded(kvh) * width * (item + 4) + 2 * LANE * width * 4)
    return (q.shape == (rows, kvh, 1, hd) and leaf.dtype == q.dtype
            and item in (2, 4) and t_max % LANE == 0
            and hd % (32 // item) == 0 and hd < LANE
            and vmem <= _VMEM_BUDGET)


def live_only(leaf, q):
    """`fits`, on a TPU: on other platforms the masked einsums over all T
    columns (models/gpt.py block) are what the compiler fuses best."""
    return fits(leaf, q) and on_tpu()


def _kernel(i_ref, pos_ref, q_ref, *refs, scale, ahead, store):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if store:
        (kn_ref, vn_ref, k_hbm, v_hbm, o_ref, k_out, v_out,
         kbuf, vbuf, sem, qblk, m_ref, l_ref, acc, wsem, held) = refs
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, qblk, m_ref, l_ref, acc = refs
    layer = i_ref[0]
    rows = q_ref.shape[0]
    slots, kvh, hd, _ = kbuf.shape
    width = kvh * hd
    heads = qblk.shape[0]
    # head h owns lanes h * hd .. (h + 1) * hd of a [heads, KVh * hd] value
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 0) * hd
    own = (lane >= first) & (lane < first + hd)

    def last_tile(b):
        return pos_ref[b] // LANE

    def after(b, j):
        """The (row, tile) pair the walk takes after (b, j); past the last
        pair it stays in the last row, and is not used."""
        ends_row = j == last_tile(b)
        return (jnp.where(ends_row, jnp.minimum(b + 1, rows - 1), b),
                jnp.where(ends_row, 0, j + 1))

    def columns(j):
        return pl.ds(pl.multiple_of(j * LANE, LANE), LANE)

    def fetch(slot, b, j):
        return (pltpu.make_async_copy(k_hbm.at[layer, b, :, :, columns(j)],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, b, :, :, columns(j)],
                                      vbuf.at[slot], sem.at[1, slot]))

    def write_back(slot, b, j):
        return (pltpu.make_async_copy(kbuf.at[slot],
                                      k_out.at[layer, b, :, :, columns(j)],
                                      wsem.at[0, slot]),
                pltpu.make_async_copy(vbuf.at[slot],
                                      v_out.at[layer, b, :, :, columns(j)],
                                      wsem.at[1, slot]))

    def written_back(slot):
        """Wait until the tiles `slot` held are back in the cache, if they
        are on their way: only then may the slot be fetched into again."""
        @pl.when(held[slot] == 1)
        def _():
            for c in write_back(slot, 0, 0):     # (what is waited for is
                c.wait()                         # the slot's semaphore)
            held[slot] = 0

    if store:
        for slot in range(slots):
            held[slot] = 0
    total = jax.lax.fori_loop(0, rows, lambda b, n: n + last_tile(b) + 1, 0)
    ahead_at = jnp.int32(0), jnp.int32(0)    # the next pair to fetch
    for s in range(ahead):
        @pl.when(s < total)
        def _():
            for c in fetch(s, *ahead_at):
                c.start()
        ahead_at = after(*ahead_at)

    def tile(s, at):
        b, j, *ahead_at = at
        slot = s % slots
        ends_row = j == last_tile(b)

        @pl.when(s + ahead < total)
        def _():
            if store:
                written_back((s + ahead) % slots)
            for c in fetch((s + ahead) % slots, *ahead_at):
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

        if store:
            @pl.when(ends_row)
            def _():
                # kv_store's select: row b's KVh * hd new values lie along
                # the lanes and the tile wants each along its 128 columns.
                # Spread over the sublanes and transposed, every row of
                # the result is one value 128 times (f32 in between: exact)
                here = jax.lax.broadcasted_iota(
                    jnp.int32, (kvh, hd, LANE), 2) == pos_ref[b] % LANE
                for buf, new_ref in ((kbuf, kn_ref), (vbuf, vn_ref)):
                    new = jnp.broadcast_to(new_ref[pl.ds(b, 1), :],
                                           (LANE, width)).T
                    buf[slot] = jnp.where(
                        here, new.reshape(kvh, hd, LANE).astype(buf.dtype),
                        buf[slot])
                for c in write_back(slot, b, j):
                    c.start()
                held[slot] = 1

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

        return (*after(b, j), *after(*ahead_at))

    jax.lax.fori_loop(0, total, tile,
                      (jnp.int32(0), jnp.int32(0), *ahead_at))
    if store:
        for slot in range(slots):
            written_back(slot)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_attention(kleaf, vleaf, q, new, i, pos, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, rows, kvh, t_max, hd = kleaf.shape
    width, heads = kvh * hd, _heads_padded(kvh)
    store = new is not None
    slots, ahead = (_SLOTS_STORE, _AHEAD_STORE) if store else (_SLOTS, _AHEAD)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    whole = pl.BlockSpec((rows, width), lambda g, i_ref, pos_ref: (0, 0))
    # rows of float32, so that one of them can be picked by its index
    flat = [x.reshape(rows, width).astype(jnp.float32)
            for x in (q,) + (new or ())]
    stored = [jnp.swapaxes(kleaf, 3, 4), jnp.swapaxes(vleaf, 3, 4)]
    out, *stored = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(hd), ahead=ahead,
                          store=store),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[whole] * len(flat) + [in_hbm, in_hbm],
            out_specs=[whole] + ([in_hbm, in_hbm] if store else []),
            scratch_shapes=[
                pltpu.VMEM((slots, kvh, hd, LANE), kleaf.dtype),
                pltpu.VMEM((slots, kvh, hd, LANE), vleaf.dtype),
                pltpu.SemaphoreType.DMA((2, slots)),
                pltpu.VMEM((heads, width), kleaf.dtype),    # the spread q
                pltpu.VMEM((heads, 1), jnp.float32),        # running max
                pltpu.VMEM((heads, 1), jnp.float32),        # running sum
                pltpu.VMEM((heads, width), jnp.float32),    # running p @ v
            ] + ([pltpu.SemaphoreType.DMA((2, slots)),      # write-backs
                  pltpu.SMEM((slots,), jnp.int32)]          # ... under way
                 if store else [])),
        out_shape=[jax.ShapeDtypeStruct((rows, width), jnp.float32)]
        + ([jax.ShapeDtypeStruct(x.shape, x.dtype) for x in stored]
           if store else []),
        # the leaves, after the two scalars, the queries and the new values
        input_output_aliases={5: 1, 6: 2} if store else {},
        name="decode_attention_store" if store else "decode_attention",
        interpret=interpret,
    )(i,
      # kv_store's clamp: an idle row's stale position reads (and stores
      # into) one tile of columns nobody looks at, never a block outside
      # the cache
      jnp.clip(pos, 0, t_max - 1).astype(jnp.int32), *flat, *stored)
    out = out.astype(q.dtype).reshape(rows, kvh, 1, hd)
    return (out, *(jnp.swapaxes(x, 3, 4) for x in stored))


def decode_attention(kleaf, vleaf, q, i, pos, interpret=None):
    """softmax(q k^T / sqrt(hd)) v of row b's one query q[b] [KVh, 1, hd]
    over columns 0..pos[b] of row b of layer i of `kleaf` and `vleaf`
    [L, B, KVh, T, hd]: [B, KVh, 1, hd]. What the masked einsums over all T
    columns give, from the live tiles alone."""
    if interpret is None:
        interpret = not on_tpu()
    return _decode_attention(kleaf, vleaf, q, None,
                             jnp.full((1,), i, jnp.int32), pos,
                             interpret=bool(interpret))[0]


def decode_attention_store(kleaf, vleaf, q, k_new, v_new, i, pos,
                           interpret=None):
    """Row b of `k_new` and `v_new` [B, KVh, 1, hd] into column pos[b] of
    row b of layer i of `kleaf` and `vleaf`, in place where they are
    donated, and the attention of `q` over the columns up to it: (out,
    kleaf, vleaf), bit for bit what `kv_store.store_columns` of each and
    then `decode_attention` return, with each row's last live tile read
    once where they read it twice."""
    if interpret is None:
        interpret = not on_tpu()
    return _decode_attention(kleaf, vleaf, q, (k_new, v_new),
                             jnp.full((1,), i, jnp.int32), pos,
                             interpret=bool(interpret))


def audit_manifest():
    """The kernels at the benchmark's serving cell (gpt2-large: 32 rows, 20
    heads of 64, T 1024, bf16): tiles of K and of V stream through their
    buffers, and out again where the step's column is stored into them; the
    step's queries, new keys and values and results, the spread query and
    the softmax's running state stay resident (analysis/pallas_audit.py)."""
    rows, kvh, hd, t_max = 32, 20, 64, 1024
    width, heads = kvh * hd, _heads_padded(kvh)
    tile = {"block": (kvh, hd, LANE), "dtype": "bfloat16"}

    def step_rows(*names):
        return [{"name": n, "block": (rows, width), "dtype": "float32",
                 "stream": False} for n in names]

    state = [{"name": "q_spread", "block": (heads, width),
              "dtype": "bfloat16", "stream": False},
             {"name": "acc", "block": (heads, width),
              "dtype": "float32", "stream": False}]
    common = {"op": "decode_attention", "in_dtype": "bfloat16",
              "matmul": True, "acc_dtype": "float32",
              "grid": {"t": (t_max, LANE)}}
    return [
        dict(common,
             kernel=f"decode_attention.live_tiles[kvh={kvh},hd={hd}]",
             buffers=step_rows("q", "out")
             + [dict(tile, name="k_tile"), dict(tile, name="v_tile")]
             + state),
        dict(common,
             kernel=f"decode_attention.store[kvh={kvh},hd={hd}]",
             buffers=step_rows("q", "k_new", "v_new", "out")
             # (four buffers a side, of the kernel's own: not the
             # pipeline's two)
             + [{"name": n, "block": (_SLOTS_STORE, kvh, hd, LANE),
                 "dtype": "bfloat16", "stream": False}
                for n in ("k_tiles", "v_tiles")]
             + [{"name": "new_column", "block": (LANE, width),
                 "dtype": "float32", "stream": False}]
             + state)]
