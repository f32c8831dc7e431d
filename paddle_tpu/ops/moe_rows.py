"""The combine of the dropless expert layer's tiles (distributed/moe.py
`moe_dropless`): a tile's weighted results added into its tokens' rows of
the float32 result, each row read and written back by one DMA.

The result is laid out `[T, R, L]` (`slab_shape`: `L` the lane width, 128
where the width divides into it), so that a token's row is one contiguous
slab of whole tiles in HBM: a DMA moves it whole. A row of a `[T, d]` array
is a strided slice across `d / 128` tiles, which a kernel cannot slice (its
slices are whole sublane tiles) and which the chip's compiler scatters into
one row after another.

`scatter_add_rows(y3, rows, upd3)`: y3 with `upd3[j]` added to token
`rows[j]`'s slab; an out-of-range row (a tile's padding) is skipped. `rows`
must not repeat a token: a grid step reads its rows, adds and writes them
back as a whole. A tile's rows never repeat one (a token takes an expert
once).

A grid step moves `BLOCK` rows: all its reads are started before the first
is waited for, then all its writes. Its fast memory is two blocks of
updates (streamed) and one of rows read (scratch), 5.5 MiB at a width of
7,168: within the compiler's default 16 MiB (a raised limit turns on
prefetches across the whole calling program, PERF.md).

Where it runs: a TPU. Elsewhere `.at[].add` does the same, and the tests
hold the kernel to it in interpret mode.
"""
import functools

import jax
import jax.numpy as jnp

from ..core.device import on_tpu

#: rows a grid step moves
BLOCK = 64


def slab_shape(d):
    """(R, L): a row of d values as R sublanes of L lanes."""
    lanes = 128 if d % 128 == 0 else d
    return d // lanes, lanes


def _for_rows(rows_ref, base, block, n_real, fn):
    """fn(j, row) for the block's rows j that name a token."""
    from jax.experimental import pallas as pl

    def body(j, carry):
        row = rows_ref[base + j]

        @pl.when(row < n_real)
        def _():
            fn(j, row)

        return carry

    jax.lax.fori_loop(0, block, body, 0)


def _add_kernel(rows_ref, upd_ref, y_in, y_out, buf, sem, *, n_real, block):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    base = pl.program_id(0) * block

    def read(j, row):
        return pltpu.make_async_copy(y_in.at[row], buf.at[j], sem.at[0])

    def write(j, row):
        return pltpu.make_async_copy(buf.at[j], y_out.at[row], sem.at[1])

    _for_rows(rows_ref, base, block, n_real, lambda j, r: read(j, r).start())
    _for_rows(rows_ref, base, block, n_real, lambda j, r: read(j, r).wait())
    buf[...] += upd_ref[...]
    _for_rows(rows_ref, base, block, n_real, lambda j, r: write(j, r).start())
    _for_rows(rows_ref, base, block, n_real, lambda j, r: write(j, r).wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scatter_add_rows(y3, rows, upd3, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, R, L = y3.shape
    tm = rows.shape[0]
    block = BLOCK if tm % BLOCK == 0 else tm
    return pl.pallas_call(
        functools.partial(_add_kernel, n_real=T, block=block),
        out_shape=jax.ShapeDtypeStruct(y3.shape, y3.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tm // block,),
            in_specs=[pl.BlockSpec((block, R, L), lambda i, r: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((block, R, L), y3.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        input_output_aliases={2: 0}, name="moe_scatter_add_rows",
        interpret=interpret)(rows, upd3.astype(y3.dtype), y3)


def scatter_add_rows(y3, rows, upd3, interpret=None):
    """y3 [T, R, L] with upd3[j] added to token rows[j]'s slab (rows[j] >= T
    skipped; no token twice)."""
    if interpret is None and not on_tpu():
        return y3.at[rows].add(upd3.astype(y3.dtype), mode="drop",
                               indices_are_sorted=True, unique_indices=True)
    return _scatter_add_rows(y3, rows, upd3, interpret=bool(interpret))



def audit_manifest():
    """The kernel at the benchmark's latent serving cell (a.x-k1: a prefill
    chunk's tile of 128 rows of width 7,168 in float32, two grid steps of
    `BLOCK`): a block of updates streams in, the block of rows read is
    scratch; the result stays in HBM (analysis/pallas_audit.py). Within the
    compiler's default 16 MiB."""
    tm, (R, L) = 128, slab_shape(7168)
    return [{
        "kernel": f"moe_rows.scatter_add[tm={tm},d={R * L}]",
        "op": "moe_scatter_add_rows", "in_dtype": "float32",
        "matmul": False, "grid": {"rows": (tm, BLOCK)},
        "buffers": [
            {"name": "upd", "block": (BLOCK, R, L), "dtype": "float32"},
            {"name": "rows_read", "block": (BLOCK, R, L),
             "dtype": "float32", "stream": False}]}]
