"""``ops.grouped_matmul`` under the Pallas interpreter: the grouped product
and its fused gate-and-up form against a float32 reference made group by
group, over operands poisoned wherever the kernel must not look (the
weights of experts without rows, the rows past ``sum(counts)``).  The
model's rule and the served streams are in ``tests/test_moe.py`` and
``tests/test_lfm2_serving.py``; the compile for the chip in
``tests/test_chip_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import grouped_matmul as gm


def _operands(m, K, N, counts, dtype, seed=0, n_weights=1):
    """Rows, weights and counts; NaN in every weight of an expert without
    rows and in every row past ``sum(counts)``."""
    counts = np.asarray(counts, np.int32)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((m, K)).astype(np.float32)
    x[counts.sum():] = np.nan
    ws = []
    for _ in range(n_weights):
        w = (rng.standard_normal((len(counts), K, N)) / np.sqrt(K)) \
            .astype(np.float32)
        w[counts == 0] = np.nan
        ws.append(jnp.asarray(w, dtype))
    return jnp.asarray(x, dtype), ws, jnp.asarray(counts)


def _reference(x, w, counts):
    """The grouped product in float32, group by group; zeros past the
    last group."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    at = 0
    for e, n in enumerate(np.asarray(counts)):
        out[at:at + n] = x[at:at + n] @ w[e]
        at += n
    return out


def _spread(m, E, busiest, seed=0):
    """``E`` counts that sum to ``m`` with one group of ``busiest``."""
    rng = np.random.default_rng(seed)
    rest = rng.multinomial(m - busiest, np.ones(E - 1) / (E - 1))
    return np.insert(rest, E // 2, busiest)


#: name -> (m, K, N, counts, tiles): the group sizes the kernel must get
#: right, at widths small enough for the interpreter
SIZES = {
    "all equal": (128, 256, 384, [16] * 8, None),
    "one expert takes every row": (128, 256, 384, [0, 0, 128, 0], None),
    "several empty experts, the first and the last among them":
        (128, 256, 384, [0, 5, 0, 40, 0, 83, 0, 0], None),
    "sizes off the sublane tile": (128, 256, 384,
                                   [1, 3, 11, 1, 3, 11, 50, 48], None),
    "rows past sum(counts) give zeros": (128, 256, 384, [7, 0, 30, 2], None),
    "no expert has a row": (32, 128, 128, [0, 0, 0], None),
    "a width that is not whole lane tiles": (12, 64, 48,
                                             [2, 0, 3, 1, 0, 4, 2, 0], None),
    "a group spanning two row tiles":
        (4096, 128, 256, _spread(4096, 16, 350), None),
    "groups spanning many row tiles, empty ones between": (
        512, 128, 256, [0, 300, 0, 0, 150, 1, 0, 40], (32, 64, 128)),
    "a row tile no group reaches": (256, 128, 128, [3, 60], (16, 64, 128)),
    "several weight tiles": (128, 128, 512, [30, 0, 98], (128, 128, 128)),
}


@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_product_is_the_reference_and_poison_stays_out(case):
    m, K, N, counts, tiles = SIZES[case]
    x, (w,), c = _operands(m, K, N, counts, jnp.float32)
    got = np.asarray(gm.grouped_dot(x, w, c, tiles=tiles, interpret=True))
    assert got.dtype == np.float32 and got.shape == (m, N)
    np.testing.assert_allclose(got, _reference(x, w, c), atol=2e-5)


#: the served shapes: a decode step's 128 assignments and a one-row
#: prefill's 4096 against both of the layer's matrices, at a reduced E
SHAPES = {
    "decode [2048, 1792]": (128, 2048, 1792, [40, 0, 11, 77]),
    "decode [1792, 2048]": (128, 1792, 2048, [1, 3, 0, 124]),
    "prefill [2048, 1792]": (4096, 2048, 1792, _spread(4096, 4, 1900)),
    "prefill [1792, 2048]": (4096, 1792, 2048, [0, 350, 3746, 0]),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_bfloat16_operands_accumulate_in_float32_as_ragged_dot_does(shape):
    m, K, N, counts = SHAPES[shape]
    x, (w,), c = _operands(m, K, N, counts, jnp.bfloat16)
    got = gm.grouped_dot(x, w, c, interpret=True)
    live = jnp.nan_to_num(x), jnp.nan_to_num(w)
    want = jax.lax.ragged_dot(*live, c, preferred_element_type=jnp.float32)
    rows = int(np.sum(counts))
    # the same bfloat16 products, summed in float32 in another order
    np.testing.assert_allclose(np.asarray(got)[:rows],
                               np.asarray(want)[:rows], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(got)[rows:].any()
    np.testing.assert_allclose(np.asarray(got), _reference(x, w, c),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["sizes off the sublane tile",
                                  "several empty experts, the first and "
                                  "the last among them",
                                  "groups spanning many row tiles, empty "
                                  "ones between", "several weight tiles"])
def test_the_fused_form_is_the_two_plain_calls(case, dtype):
    m, K, N, counts, tiles = SIZES[case]
    x, (w_gate, w_up), c = _operands(m, K, N, counts, dtype, n_weights=2)
    got = gm.grouped_swiglu(x, w_gate, w_up, c, out_dtype=dtype,
                            tiles=tiles, interpret=True)
    gate, up = (gm.grouped_dot(x, w, c, tiles=tiles, interpret=True)
                for w in (w_gate, w_up))
    want = (jax.nn.silu(gate) * up).astype(dtype)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("counts,tm,m", [
    ([4, 0, 11, 0, 113], 128, 128),
    ([0, 0, 128, 0], 128, 128),
    ([0, 300, 0, 0, 150, 1, 0, 40], 64, 512),
    ([3, 60], 64, 256),
    ([0, 0, 0], 32, 32),
])
def test_the_walk_names_each_touched_expert_once_a_row_tile(counts, tm, m):
    counts = np.asarray(counts)
    E = len(counts)
    weights, tile, first, end = (
        np.asarray(a) for a in gm._units(jnp.asarray(counts), m, tm))
    assert len(weights) == m // tm + E
    lo = np.cumsum(counts) - counts
    want = [(t, e, lo[e], lo[e] + counts[e]) for t in range(m // tm)
            for e in range(E)
            if max(t * tm, lo[e]) < min((t + 1) * tm, lo[e] + counts[e])]
    live = end > first
    assert list(zip(tile[live], weights[live], first[live], end[live])) \
        == want
    # every row tile is visited, in order, whether a group reaches it or not
    assert sorted(set(tile)) == list(range(m // tm)) \
        and (np.diff(tile) >= 0).all()
    # no expert without rows is ever named for its weights: the units with
    # no rows name the blocks of the unit before them, and copy nothing
    assert not counts.any() or (counts[weights] > 0).all()
    assert (weights[1:][~live[1:]] == weights[:-1][~live[1:]]).all() \
        or not counts.any()


@pytest.mark.parametrize("why,m,K,N,operands,want", [
    ("a decode step: one row tile, each expert's whole matrix a tile",
     128, 2048, 1792, 1, (128, 128, 1792)),
    ("its fused gate and up: two whole matrices a step",
     128, 2048, 1792, 2, (128, 128, 1792)),
    ("its down product", 128, 1792, 2048, 1, (128, 128, 2048)),
    ("a one-row prefill: row tiles of four sub-blocks",
     4096, 2048, 1792, 2, (128, 512, 1792)),
    ("a four-row prefill", 16384, 1792, 2048, 1, (128, 512, 2048)),
    ("rows that four sub-blocks do not divide", 640, 256, 256, 1,
     (128, 128, 256)),
    ("a handful of rows: one sub-block of packed sublane tiles",
     12, 64, 48, 1, (16, 16, 48)),
    ("matrices wider than a step may stream: lane tiles that divide N",
     128, 8192, 2048, 2, (128, 128, 512)),
])
def test_tiles_follow_from_the_shapes_of_the_call(why, m, K, N, operands,
                                                  want):
    assert gm._tiles(m, K, N, 2 * operands) == want, why


@pytest.mark.parametrize("why,make", [
    ("weights of another dtype than the rows", lambda x, w, c: (
        x, w.astype(jnp.bfloat16), c)),
    ("a contraction width the rows do not have", lambda x, w, c: (
        x[:, :-1], w, c)),
    ("counts for another number of experts", lambda x, w, c: (
        x, w, c[:-1])),
])
def test_operands_that_do_not_fit_are_refused(why, make):
    x, (w,), c = _operands(16, 32, 32, [4, 12], jnp.float32)
    with pytest.raises(ValueError, match="do not fit"):
        gm.grouped_dot(*make(x, w, c), interpret=True)


# ------------------------------------------------- the relu2 epilogue (PR 42)

@pytest.mark.parametrize("transposed", [False, True],
                         ids=["stored [E,K,N]", "stored [E,N,K]"])
@pytest.mark.parametrize("case,held", [
    ("sizes off the sublane tile", 128),
    # rows past the held experts' (assignments to experts this chip does
    # not hold) are NaN here and zeros in the output
    ("several empty experts, the first and the last among them", 96)])
def test_the_relu2_form_is_the_plain_call_squared(case, held, transposed):
    m, K, N, counts, tiles = SIZES[case]
    counts = np.asarray(counts) * held // m
    x, (w,), c = _operands(m, K, N, counts, jnp.float32, seed=3)
    want = np.square(np.maximum(_reference(x, w, c), 0.0))
    got = gm.grouped_relu2(
        x, jnp.swapaxes(w, 1, 2) if transposed else w, c,
        out_dtype=jnp.float32, transposed=transposed, tiles=tiles,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[int(counts.sum()):].any()


@pytest.mark.parametrize("transposed", [False, True],
                         ids=["stored [E,K,N]", "stored [E,N,K]"])
def test_the_gated_form_takes_its_weights_as_they_are_stored(transposed):
    m, K, N, counts, tiles = SIZES["sizes off the sublane tile"]
    x, (g, w), c = _operands(m, K, N, counts, jnp.float32, seed=4,
                             n_weights=2)
    gate = _reference(x, g, c)
    want = gate / (1.0 + np.exp(-gate)) * _reference(x, w, c)
    got = gm.grouped_swiglu(
        x, *(jnp.swapaxes(v, 1, 2) if transposed else v for v in (g, w)),
        c, out_dtype=jnp.float32, transposed=transposed, tiles=tiles,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_a_transposed_weight_of_the_wrong_shape_is_refused():
    x, (w,), c = _operands(32, 64, 128, [16, 16], jnp.float32)
    with pytest.raises(ValueError, match="do not fit"):
        gm.grouped_relu2(x, w, c, out_dtype=jnp.float32, transposed=True,
                         interpret=True)
