"""Pallas attention of one decode step over a latent cache [L, B, T, W],
reading only the columns that are live and each of them once
(models/axk1.py `_attend_absorbed`; docs/SERVING.md "State kinds").

The absorbed form of multi-head latent attention is H query heads against ONE
shared row of latents: row b's query q[b] [H, W] (`W_UK` already inside it, the
rotary channels beside, zeros to the lane width) attends to columns 0..pos[b]
of `lat[i, b]`, and the same `[T, W]` row is key and value at once. The
einsums contract with all T columns twice (the scores, then the sum over the
latents) and keep `[B, H, T]` float32 scores between the two; this kernel
fetches tiles 0..pos[b] // TILE of the row, from the cache where and as it
lies, works both products on a tile while it is in fast memory, and fetches
nothing beyond them.

One call walks the step's live (row, tile) pairs in one loop, as
ops/decode_attention.py does: the cache, the queries and the results stay in
HBM; a tile `[TILE, W]`, a row's query and a row's result move through two
buffers each, the next one fetched while this one is worked on, rows follow
one another without a gap, a dead tile costs nothing. bf16 (or float32)
operands, float32 accumulation, an online softmax in float32 over the tiles.

The layer index and `pos` ride in as prefetched scalars and the call is a jit
of its own, so the layers of a step share one traced kernel. The step's own
column is in the cache before the call: no row's softmax is empty.
"""
import functools

import jax
import jax.numpy as jnp

from ..core.device import on_tpu

LANE = 128
#: cache columns a tile holds: 512 x 640 bf16 values are 640 KB a buffer
TILE = 512


def tile_of(t_max):
    """The tile the kernel walks a row of `t_max` columns in."""
    return TILE if t_max % TILE == 0 else None


def fits(lat, q):
    """Can `latent_decode_attention` take this read? One query a row of H
    heads over the cache's own width, a width in whole lanes, T in whole
    tiles, heads in whole sublane tiles, one dtype of 2 or 4 bytes."""
    _, rows, t_max, width = lat.shape
    item = jnp.dtype(lat.dtype).itemsize
    return (q.ndim == 3 and q.shape[0] == rows and q.shape[2] == width
            and q.dtype == lat.dtype and item in (2, 4)
            and width % LANE == 0 and tile_of(t_max) is not None
            and q.shape[1] % (32 // item) == 0)


def live_only(lat, q):
    """`fits`, on a TPU: on other platforms the masked einsums over all T
    columns are what the compiler fuses best."""
    return fits(lat, q) and on_tpu()


def _kernel(i_ref, pos_ref, q_hbm, lat_hbm, o_hbm, qbuf, kbuf, obuf, sem,
            m_ref, l_ref, acc, *, scale, tile):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layer = i_ref[0]
    rows = q_hbm.shape[0]

    def last_tile(b):
        return pos_ref[b] // tile

    def fetch_tile(slot, b, j):
        at = pl.ds(pl.multiple_of(j * tile, tile), tile)
        return pltpu.make_async_copy(lat_hbm.at[layer, b, at, :],
                                     kbuf.at[slot], sem.at[0, slot])

    def fetch_q(b):
        return pltpu.make_async_copy(q_hbm.at[b], qbuf.at[b % 2],
                                     sem.at[1, b % 2])

    def store_o(b):
        return pltpu.make_async_copy(obuf.at[b % 2], o_hbm.at[b],
                                     sem.at[2, b % 2])

    total = jax.lax.fori_loop(0, rows, lambda b, n: n + last_tile(b) + 1, 0)
    fetch_q(0).start()
    fetch_tile(0, 0, 0).start()

    def step(s, at):
        b, j = at
        slot = s % 2
        ends_row = j == last_tile(b)
        nxt = jnp.where(ends_row, b + 1, b), jnp.where(ends_row, 0, j + 1)

        @pl.when(s + 1 < total)
        def _():
            fetch_tile(1 - slot, *nxt).start()

            @pl.when(ends_row)
            def _():
                fetch_q(b + 1).start()

        @pl.when(j == 0)
        def _():
            fetch_q(b).wait()
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

        fetch_tile(slot, b, j).wait()
        q = qbuf[b % 2]                                     # [H, W]
        k = kbuf[slot]                                      # [tile, W]
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, tile]
        cols = j * tile + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(cols <= pos_ref[b], sc, -jnp.inf)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(sc - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = alpha * acc[...] + jnp.dot(
            p.astype(k.dtype), k, preferred_element_type=jnp.float32)

        @pl.when(ends_row)
        def _():
            @pl.when(b >= 2)
            def _():
                store_o(b - 2).wait()       # this buffer's last result is out

            obuf[b % 2] = (acc[...] / l_ref[...]).astype(obuf.dtype)
            store_o(b).start()

        return nxt

    jax.lax.fori_loop(0, total, step, (jnp.int32(0), jnp.int32(0)))
    for b in range(max(rows - 2, 0), rows):
        store_o(b).wait()


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_decode_attention(lat, q, i, pos, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, rows, t_max, width = lat.shape
    heads = q.shape[1]
    tile = tile_of(t_max)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[in_hbm, in_hbm],
            out_specs=in_hbm,
            scratch_shapes=[
                pltpu.VMEM((2, heads, width), q.dtype),     # a row's query
                pltpu.VMEM((2, tile, width), lat.dtype),    # a tile
                pltpu.VMEM((2, heads, width), q.dtype),     # a row's result
                pltpu.SemaphoreType.DMA((3, 2)),
                pltpu.VMEM((heads, 1), jnp.float32),        # running max
                pltpu.VMEM((heads, 1), jnp.float32),        # running sum
                pltpu.VMEM((heads, width), jnp.float32)]),  # running p @ lat
        out_shape=jax.ShapeDtypeStruct((rows, heads, width), q.dtype),
        name="latent_decode_attention",
        interpret=interpret,
    )(i,
      # an idle row's stale position reads one tile of columns nobody looks
      # at, never a block outside the cache
      jnp.clip(pos, 0, t_max - 1).astype(jnp.int32), q, lat)


def latent_decode_attention(lat, q, i, pos, scale, interpret=None):
    """softmax(q lat^T scale) lat of row b's H queries q[b] [H, W] over
    columns 0..pos[b] of row b of layer i of `lat` [L, B, T, W]: [B, H, W] in
    q's dtype. What the masked einsums over all T columns give, from the live
    tiles alone, each read once."""
    if interpret is None:
        interpret = not on_tpu()
    return _latent_decode_attention(lat, q, jnp.full((1,), i, jnp.int32),
                                    pos, scale=float(scale),
                                    interpret=bool(interpret))


def audit_manifest():
    """The kernel at the benchmark's latent serving cell (a.x-k1: 64 heads
    against a 640-wide latent row, bf16): a tile streams through two
    buffers; a row's query and result move through two each as well; the
    softmax's running state stays resident (analysis/pallas_audit.py)."""
    heads, width, t_max = 64, 640, 8192
    row = {"block": (heads, width), "dtype": "bfloat16"}
    return [{
        "kernel": f"latent_decode_attention.live_tiles[h={heads},w={width}]",
        "op": "latent_decode_attention", "in_dtype": "bfloat16",
        "matmul": True, "acc_dtype": "float32",
        "grid": {"t": (t_max, TILE)},
        "buffers": [dict(row, name="q_row"), dict(row, name="out_row"),
                    {"name": "latent_tile", "block": (TILE, width),
                     "dtype": "bfloat16"},
                    {"name": "acc", "block": (heads, width),
                     "dtype": "float32", "stream": False}]}]
