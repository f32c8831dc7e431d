"""The dropless expert layer (distributed/moe.py `moe_dropless`) against a
float64 numpy sum over the held assignments, at the edges of its tiling: every
assignment held, none held, an expert's run that ends on a tile's last row or
one row past it, one token, tokens that fill no whole tile, and the held range
at the start, middle and end of the router's. Each case runs in both of the
layer's forms (`form`: a tile gathers its rows inside the loop, or slices them
from every padded row gathered before it), whichever its shape rule picks.

The program runs in float32 here (the CPU widens bfloat16 products); the sum
differs from the float64 one in the order of its additions and in float32's
rounding of products over 32-64 channels: 1e-5 against outputs of order 0.1
to 1 is some ten times that.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed import moe

ATOL = 1e-5


@pytest.fixture(params=["gathered_in_the_loop", "gathered_before_it"])
def form(request, monkeypatch):
    """The layer with its shape rule set to take one form at every tile."""
    least = 1 if request.param == "gathered_in_the_loop" else 1 << 30
    monkeypatch.setattr(moe, "IN_LOOP_GATHER_MIN_TILE", least)
    return request.param


def _rows_moved(form, rows, T, k, tm, count):
    """What the dispatch gathered plus what the combine added back."""
    padded = (-(-T * k // tm) + count) * tm
    return (rows if form == "gathered_in_the_loop" else padded) + rows


def _weights(count, d=32, f=16, seed=0):
    rng = np.random.default_rng(seed)
    return [(0.3 * rng.normal(size=s)).astype(np.float32)
            for s in ((count, d, f), (count, d, f), (count, f, d))]


def _random_routing(T, k, E, seed=1):
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((T, E)), axis=1)[:, :k].astype(np.int32)
    weights = rng.random((T, k)).astype(np.float32)
    return experts, weights / weights.sum(1, keepdims=True)


def _sum64(x, experts, weights, w, first=0):
    """sum over a token's held assignments of weight * down(silu(gate x) *
    up x), in float64."""
    x, weights = x.astype(np.float64), weights.astype(np.float64)
    wg, wu, wd = (a.astype(np.float64) for a in w)
    y = np.zeros_like(x)
    for e in range(wg.shape[0]):
        tok, j = np.nonzero(experts == first + e)
        h = x[tok] @ wg[e]
        out = ((h / (1 + np.exp(-h))) * (x[tok] @ wu[e])) @ wd[e]
        np.add.at(y, tok, weights[tok, j][:, None] * out)
    return y


def _run(x, experts, weights, w, held=None, num_experts=None, tile=None):
    y, counts = moe.moe_dropless(
        jnp.asarray(x), jnp.asarray(experts), jnp.asarray(weights),
        *map(jnp.asarray, w), held=held, num_experts=num_experts, tile=tile)
    return np.asarray(y), {n: int(v) for n, v in counts.items()}


def _check(form, x, experts, weights, w, tm, held=None, num_experts=None):
    first, count = held or (0, w[0].shape[0])
    y, counts = _run(x, experts, weights, w, held=held,
                     num_experts=num_experts, tile=tm)
    np.testing.assert_allclose(y, _sum64(x, experts, weights, w, first),
                               atol=ATOL)
    is_held = (experts >= first) & (experts < first + count)
    rows = tm * sum(-(-int(np.sum(experts == first + e)) // tm)
                    for e in range(count))
    assert counts["assignments_held"] == int(is_held.sum())
    assert counts["rows_computed"] == rows
    assert counts["rows_moved"] == _rows_moved(form, rows, x.shape[0],
                                               experts.shape[1], tm, count)
    assert counts["experts_touched"] == len(set(experts[is_held].tolist()))
    return y, counts


@pytest.mark.parametrize("k", [1, 4])
def test_every_token_on_one_held_expert(k, form):
    """Every assignment held and expert 5 in every token's k: its run is all
    T tokens, the worst case the tables are sized for."""
    T, E = 40, 8
    rng = np.random.default_rng(k)
    x = rng.normal(size=(T, 32)).astype(np.float32)
    others = [e for e in range(E) if e != 5]
    experts = np.asarray([[5] + list(rng.permutation(others)[:k - 1])
                          for _ in range(T)], np.int32)
    weights = np.full((T, k), 1.0 / k, np.float32)
    _, counts = _check(form, x, experts, weights, _weights(E), tm=8,
                       num_experts=E)
    assert counts["assignments_held"] == T * k


def test_every_token_on_experts_held_elsewhere(form):
    """A share that no token routes to: nothing computed, nothing added back,
    nothing gathered where the tiles gather their own rows."""
    T = 24
    x = np.random.default_rng(2).normal(size=(T, 32)).astype(np.float32)
    experts, weights = _random_routing(T, 4, 8)
    y, counts = _run(x, experts, weights, _weights(8), held=(8, 8),
                     num_experts=16, tile=8)
    assert not y.any()
    assert counts == {"assignments_held": 0, "rows_computed": 0,
                      "experts_touched": 0,
                      "rows_moved": _rows_moved(form, 0, T, 4, 8, 8)}
    assert (counts["rows_moved"] == 0) == (form == "gathered_in_the_loop")


@pytest.mark.parametrize("run", [16, 17, 15, 8, 1])
def test_a_run_ending_on_a_tile_boundary_and_one_row_past_it(run, form):
    """Expert 0 takes `run` tokens and expert 1 the rest of 33 (tiles of 8):
    a run that ends on a tile's last row, one row past it, one short."""
    T = 33
    x = np.random.default_rng(run).normal(size=(T, 32)).astype(np.float32)
    experts = np.stack([np.where(np.arange(T) < run, 0, 1),
                        np.full(T, 3)], 1).astype(np.int32)
    weights = np.full((T, 2), 0.5, np.float32)
    _check(form, x, experts, weights, _weights(4), tm=8, num_experts=4)


@pytest.mark.parametrize("T", [1, 5, 13, 37])
def test_few_tokens_and_tokens_that_fill_no_whole_tile(T, form):
    x = np.random.default_rng(T).normal(size=(T, 32)).astype(np.float32)
    experts, weights = _random_routing(T, 4, 8, seed=T)
    _check(form, x, experts, weights, _weights(8, seed=T), tm=8,
           num_experts=8)


@pytest.mark.parametrize("held", [(0, 4), (6, 4), (12, 4)])
def test_held_at_the_start_middle_and_end_of_the_range(held, form):
    T, E = 29, 16
    x = np.random.default_rng(5).normal(size=(T, 32)).astype(np.float32)
    experts, weights = _random_routing(T, 4, E, seed=5)
    _check(form, x, experts, weights, _weights(4, seed=held[0]), tm=8,
           held=held, num_experts=E)


def test_the_default_tile_and_a_bfloat16_input(form):
    """The tile `dropless_tile` picks, and bfloat16 activations and weights:
    the products take bfloat16 operands and accumulate in float32, the
    result stays float32."""
    T, E = 96, 32
    x = np.random.default_rng(9).normal(size=(T, 32)).astype(np.float32)
    experts, weights = _random_routing(T, 8, E, seed=9)
    w = _weights(6, seed=9)
    bf = [np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
          for a in [x] + w]
    y, counts = _run(jnp.asarray(x, jnp.bfloat16), experts, weights,
                     [jnp.asarray(a, jnp.bfloat16) for a in w],
                     held=(3, 6), num_experts=E)
    assert y.dtype == np.float32
    want = _sum64(bf[0], experts, weights, bf[1:], first=3)
    # the hidden activation is rounded to bfloat16 before the down product
    np.testing.assert_allclose(y, want, atol=2e-2 * np.abs(want).max())
    assert counts["rows_moved"] == _rows_moved(
        form, counts["rows_computed"], T, 8, moe.dropless_tile(T * 8, E), 6) > 0


def _lowered_layer(T, d=16, f=8, E=192, count=12):
    def layer(x, experts, weights, wg, wu, wd):
        return moe.moe_dropless(x, experts, weights, wg, wu, wd,
                                held=(0, count), num_experts=E)

    s = jax.ShapeDtypeStruct
    return jax.jit(layer).lower(
        s((T, d), jnp.float32), s((T, 8), jnp.int32), s((T, 8), jnp.float32),
        s((count, d, f), jnp.float32), s((count, d, f), jnp.float32),
        s((count, f, d), jnp.float32)).as_text()


def test_the_chunks_layout_moves_no_padded_buffer():
    """At the prefill chunk's ratio (1,024 tokens top-8 of 192 experts, 12
    held, tiles of 128) with a small width: the lowered program holds no
    array of the worst case's padded rows ((8,192 / 128 + 12) x 128 = 9,728)
    by the width, and sorts once (the assignments into expert order; no
    inverse permutation)."""
    assert moe.dropless_tile(1024 * 8, 192) == 128
    assert moe.dropless_tile(1024 * 8, 192) >= moe.IN_LOOP_GATHER_MIN_TILE
    text = _lowered_layer(1024)
    assert "tensor<9728x16x" not in text
    assert text.count("stablehlo.sort") == 1


def test_the_decode_steps_layout_gathers_its_padded_rows_once():
    """At the decode step's ratio (128 tokens, tiles of 16, under the shape
    rule's least tile) the padded rows, (1,024 / 16 + 12) x 16 = 1,216, are
    gathered once before the loop; still one sort and no gather of the
    assignments back."""
    assert moe.dropless_tile(128 * 8, 192) == 16 < moe.IN_LOOP_GATHER_MIN_TILE
    text = _lowered_layer(128)
    assert "tensor<1216x16xf32>" in text
    assert "tensor<1024x16xf32>" not in text
    assert text.count("stablehlo.sort") == 1


# -- the layer with the combine's kernel taken, in interpret mode -------------
def _routing_case(case):
    """(x, experts, weights, w, tm, held, num_experts) of one edge; x scaled
    so that the outputs stay of order 1 at every width."""
    x, *rest = _routing_edge(case)
    return (x * np.float32(np.sqrt(32 / x.shape[1])), *rest)


def _routing_edge(case):
    rng = np.random.default_rng(len(case))
    if case == "one_expert_takes_every_token":
        T, d, E, k = 40, 256, 8, 4
        others = [e for e in range(E) if e != 5]
        experts = np.asarray([[5] + list(rng.permutation(others)[:k - 1])
                              for _ in range(T)], np.int32)
        weights = np.full((T, k), 1.0 / k, np.float32)
        return (rng.normal(size=(T, d)).astype(np.float32), experts, weights,
                _weights(E, d=d), 8, None, E)
    if case == "a_run_one_row_past_a_tile":
        T, d = 33, 256
        experts = np.stack([np.where(np.arange(T) < 17, 0, 1),
                            np.full(T, 3)], 1).astype(np.int32)
        return (rng.normal(size=(T, d)).astype(np.float32), experts,
                np.full((T, 2), 0.5, np.float32), _weights(4, d=d), 8, None, 4)
    if case == "tokens_that_fill_no_whole_tile":
        T, d = 13, 128
        experts, weights = _random_routing(T, 4, 8, seed=T)
        return (rng.normal(size=(T, d)).astype(np.float32), experts, weights,
                _weights(8, d=d, seed=T), 8, None, 8)
    T, E = 29, 16                       # held in the middle, a width under a lane
    experts, weights = _random_routing(T, 4, E, seed=5)
    return (rng.normal(size=(T, 32)).astype(np.float32), experts, weights,
            _weights(4, seed=6), 8, (6, 4), E)


@pytest.mark.parametrize("case", [
    "one_expert_takes_every_token", "a_run_one_row_past_a_tile",
    "tokens_that_fill_no_whole_tile", "held_in_the_middle"])
def test_the_layer_with_the_combine_kernel_taken(case, form, monkeypatch):
    """The layer as a TPU runs it: the result laid out a token a slab, carried
    by the loop into the kernel, which adds each tile's weighted rows (its
    padding rows naming rows past T) and hands the slabs back; then read as
    [T, d]. The kernel runs in interpret mode."""
    from paddle_tpu.ops import moe_rows

    taken = []
    kernel = moe_rows.scatter_add_rows

    def interpreted(y3, rows, upd3, interpret=None):
        taken.append(y3.shape)
        return kernel(y3, rows, upd3, interpret=True)

    monkeypatch.setattr(moe_rows, "scatter_add_rows", interpreted)
    x, experts, weights, w, tm, held, E = _routing_case(case)
    _check(form, x, experts, weights, w, tm, held=held, num_experts=E)
    assert taken == [(x.shape[0],) + moe_rows.slab_shape(x.shape[1])]


# -- the combine's kernel (ops/moe_rows.py), in interpret mode -----------------
@pytest.mark.parametrize("T, R, L, tm, n", [
    (40, 2, 128, 16, 10),       # one grid step, padding rows after the tokens
    (300, 3, 128, 128, 125),    # two grid steps of 64 rows
    (50, 1, 32, 8, 8),          # a width under a lane: one slab of 32
    (12, 1, 128, 8, 0)])        # a tile of padding rows alone
def test_the_combine_kernel_is_an_add_at_its_rows(T, R, L, tm, n):
    from paddle_tpu.ops import moe_rows

    rng = np.random.default_rng(T)
    y = rng.normal(size=(T, R, L)).astype(np.float32)
    rows = np.concatenate([np.sort(rng.choice(T, n, replace=False)),
                           T + np.arange(tm - n)]).astype(np.int32)
    upd = rng.normal(size=(tm, R, L)).astype(np.float32)
    want = y.copy()
    want[rows[:n]] += upd[:n]
    for interpret in (True, None):      # the kernel; the XLA form off the chip
        got = moe_rows.scatter_add_rows(jnp.asarray(y), jnp.asarray(rows),
                                        jnp.asarray(upd), interpret=interpret)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_slab_shape():
    from paddle_tpu.ops import moe_rows

    assert moe_rows.slab_shape(7168) == (56, 128)
    assert moe_rows.slab_shape(4096) == (32, 128)
    assert moe_rows.slab_shape(32) == (1, 32)
