"""Pallas in-place store of one decode step's keys or values into the dense
KV cache [L, B, KVh, T, hd] (models/gpt.py _decode_fns; docs/SERVING.md "The
dense cache on the chip").

The chip keeps a cache leaf T-minor: hd = 64 in the lanes would pad to 128 and
double the cache, so T rides the lanes and a (16, 128) bf16 tile holds 16
values of hd for 128 columns. Row b's new token is one column, pos[b], of that
row: it lies in KVh * hd / 16 tiles. The kernel walks the rows, fetches the
128 columns around pos[b] of layer i, replaces the one column and writes the
block back where it came from; the rest of the cache is aliased through
untouched. Seen through `swapaxes(leaf, 3, 4)` the stored layout is plain
row-major [L, B, KVh, hd, T], which is what a Pallas operand has to be, and XLA
makes both transposes bitcasts: the compiled decode step holds no copy of a
cache layer (tests/test_chip_compile.py::TestDecodeStep).

The new values come in as they leave the qkv product, [B, KVh * hd] along the
lanes, and are turned to the block's orientation inside the kernel: handed over
as [B, KVh, hd, 1] they cost a lane-padded transpose a call, 1.9 ms of a 15.8
ms step (PERF.md, PR 28). The layer index rides in as a scalar and the call is
a jit of its own, so the stores of a step share one traced kernel.

Where a step's attention is ops/decode_attention.py, that kernel does this
store on the tile it has fetched anyway and this one is not called (PR 37);
it stays the store where the einsums read the cache: grouped queries, a
quantized cache's values leaf.
"""
import functools

import jax
import jax.numpy as jnp

from ..core.device import on_tpu

LANE = 128  # columns a block holds: one lane tile of the T-minor layout

#: what the kernel keeps in fast memory has to fit: the cache block in and
#: out, each double-buffered, the step's new values, and the transposed
#: f32 copy of a row of them
_VMEM_BUDGET = 12 << 20


def fits(leaf, val):
    """Can `store_columns` take this store? One new column a row, T a whole
    number of lane tiles, hd a whole number of sublane tiles (a quantized
    cache's [.., T, 1] scales are not: the select stores them) and short of
    a lane tile (from 128 on the chip keeps a leaf hd-minor, and the T-minor
    view this kernel stores through would copy the whole cache in and out:
    the same line ops/decode_attention.py draws), and a [KVh, hd, 128]
    block that fits."""
    _, rows, kvh, t_max, hd = leaf.shape
    item = jnp.dtype(leaf.dtype).itemsize
    vmem = kvh * hd * (4 * LANE * item + 2 * LANE * 4 + rows * 4)
    return (val.shape[2] == 1 and t_max % LANE == 0
            and hd % (32 // item) == 0 and hd < LANE
            and vmem <= _VMEM_BUDGET)


def in_place(leaf, val):
    """`fits`, on a TPU: on other platforms the select (models/gpt.py
    _row_update) compiles to the one pass over the layer it reads as."""
    return fits(leaf, val) and on_tpu()


def _kernel(i_ref, pos_ref, val_ref, cache_ref, out_ref):
    from jax.experimental import pallas as pl

    del i_ref  # read by the index maps
    b = pl.program_id(0)
    block = cache_ref[...]                       # [1, 1, KVh, hd, LANE]
    _, _, kvh, hd, _ = block.shape
    # row b's KVh * hd new values lie along the lanes; the block wants each
    # along its 128 columns. Spread over the sublanes and transposed, every
    # row of the result is one value 128 times (f32 in between: exact)
    row = val_ref[pl.ds(b, 1), :]                # [1, KVh * hd] f32
    new = jnp.broadcast_to(row, (LANE, kvh * hd)).T.reshape(kvh, hd, LANE)
    cols = jax.lax.broadcasted_iota(jnp.int32, block.shape, 4)
    out_ref[...] = jnp.where(cols == pos_ref[b] % LANE,
                             new.astype(block.dtype)[None, None], block)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _store_columns(leaf, val, i, pos, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, rows, kvh, t_max, hd = leaf.shape
    stored = jnp.swapaxes(leaf, 3, 4)            # [L, B, KVh, hd, T]
    block = pl.BlockSpec(
        (1, 1, kvh, hd, LANE),
        lambda b, i_ref, pos_ref: (i_ref[0], b, 0, 0, pos_ref[b] // LANE))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows,),
            in_specs=[pl.BlockSpec((rows, kvh * hd),
                                   lambda b, i_ref, pos_ref: (0, 0)),
                      block],
            out_specs=block),
        out_shape=jax.ShapeDtypeStruct(stored.shape, stored.dtype),
        input_output_aliases={3: 0},
        name="kv_store_columns",
        interpret=interpret,
    )(i,
      # dynamic_update_slice's clamp, so an idle row's stale position
      # can never index a block outside the cache
      jnp.clip(pos, 0, t_max - 1).astype(jnp.int32),
      val.reshape(rows, kvh * hd).astype(jnp.float32), stored)
    return jnp.swapaxes(out, 3, 4)


def store_columns(leaf, val, i, pos, interpret=None):
    """Row b of `val` [B, KVh, 1, hd] into column pos[b] of row b of layer i
    of `leaf` [L, B, KVh, T, hd], in place where `leaf` is donated. The same
    values a per-row dynamic_update_slice stores."""
    if interpret is None:
        interpret = not on_tpu()
    return _store_columns(leaf, val, jnp.full((1,), i, jnp.int32), pos,
                          interpret=bool(interpret))


def audit_manifest():
    """The kernel at the benchmark's serving cell (gpt2-large: 32 rows, 20
    heads of 64, T 1024, bf16): the cache block streams in and out,
    double-buffered; the step's new values stay resident
    (analysis/pallas_audit.py)."""
    rows, kvh, hd, t_max = 32, 20, 64, 1024
    block = {"block": (kvh, hd, LANE), "dtype": "bfloat16"}
    return [{
        "kernel": f"kv_store.columns[kvh={kvh},hd={hd}]", "op": "kv_store",
        "in_dtype": "bfloat16", "matmul": False,
        "grid": {"t": (t_max, LANE)},
        "buffers": [{"name": "val", "block": (rows, kvh * hd),
                     "dtype": "float32", "stream": False},
                    dict(block, name="cache_in"),
                    dict(block, name="cache_out")]}]
