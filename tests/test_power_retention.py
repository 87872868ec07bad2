"""``ops/power_retention.py``: the feature map's identity, the three forms
of one function (attention, chunked, recurrent), padding that stays out of
the state, a call that continues a carried state and one that has none to
query, and the decode-step kernel (Pallas interpreter) against the
``jax.numpy`` arithmetic.  Everything float32 at highest precision: what
differs between the forms is the order of sums."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import power_retention as pr

EPS = 1e-6


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(B=2, T=37, H=4, Hkv=2, d=16, seed=0, gate_scale=2.0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, T, H, d))
    k = jax.random.normal(ks[1], (B, T, Hkv, d))
    v = jax.random.normal(ks[2], (B, T, Hkv, d))
    g = jax.nn.log_sigmoid(gate_scale * jax.random.normal(ks[3], (B, T, Hkv)))
    return q, k, v, g


def _recurrent(q, k, v, g, upto=None):
    B, T, H, d = q.shape
    S, z = pr.init_state(B, k.shape[2], d)
    ys = []
    for t in range(T if upto is None else upto):
        num, den, S, z = pr.retention_step_reference(
            S, z, q[:, t], k[:, t], v[:, t], g[:, t])
        ys.append(num / (den[..., None] + EPS))
    return jnp.stack(ys, 1), S, z


@pytest.mark.parametrize("d", [16, 128])
def test_feature_map_is_the_squared_product(d):
    a, b = jax.random.normal(jax.random.key(1), (2, 5, d))
    fa, fb = pr.phi(a), pr.phi(b)
    assert fa.shape == (5, pr.feature_dim(d))
    # float32 sums of 8704 terms of size ~1: 1e-5 relative
    np.testing.assert_allclose((fa * fb).sum(-1), (a * b).sum(-1) ** 2 / d,
                               rtol=2e-5, atol=1e-5)


def test_feature_count_is_the_tiled_symmetric_square():
    assert pr.feature_dim(128) == 8704          # 5.4 % over 128 * 129 / 2
    assert pr.feature_dim(16) == 192
    with pytest.raises(ValueError, match="multiple of the retention block"):
        pr.feature_dim(20)
    assert pr.state_bytes(16, 8, 128) == 16 * 8 * 129 * 8704 * 4


@pytest.mark.parametrize("chunk", [1, 8, 16, 64])
def test_chunked_form_is_the_attention_form(chunk):
    q, k, v, g = _inputs()
    want = pr.retention_attention(q, k, v, g, EPS)
    got, _, _ = pr.retention_chunked(*pr.init_state(2, 2, 16), q, k, v, g,
                                     EPS, chunk)
    # outputs are weighted means of unit normals; the forms differ in the
    # order of float32 sums over up to 37 terms
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_recurrent_form_is_the_attention_form_and_leaves_the_same_state():
    q, k, v, g = _inputs()
    want = pr.retention_attention(q, k, v, g, EPS)
    got, S, z = _recurrent(q, k, v, g)
    # a small normaliser (a query nearly orthogonal to every live key)
    # divides two sums that each carry 1e-6 of rounding
    np.testing.assert_allclose(got, want, atol=2e-4)
    _, S2, z2 = pr.retention_chunked(*pr.init_state(2, 2, 16), q, k, v, g,
                                     EPS, 8)
    np.testing.assert_allclose(S, S2, atol=5e-6)
    np.testing.assert_allclose(z, z2, atol=5e-6)


def test_a_chunked_block_continues_a_carried_state():
    q, k, v, g = _inputs()
    want = pr.retention_attention(q, k, v, g, EPS)
    _, S, z = pr.retention_chunked(*pr.init_state(2, 2, 16), q[:, :20],
                                   k[:, :20], v[:, :20], g[:, :20], EPS, 8)
    got, _, _ = pr.retention_chunked(S, z, q[:, 20:], k[:, 20:], v[:, 20:],
                                     g[:, 20:], EPS, 8)
    np.testing.assert_allclose(got, want[:, 20:], atol=2e-5)


@pytest.mark.parametrize("split", [1, 20, 36])
def test_a_call_split_in_two_is_the_one_call(split):
    """A carried call with ``lengths`` set: the second call's tokens reach
    the first's through the state alone, and a row whose valid tokens all
    lay in the first call (20 of them, split at 36: none left) hands its
    state on as it got it."""
    q, k, v, g = _inputs()
    lengths = jnp.asarray([20, 37])
    want, S, z = pr.retention_chunked(*pr.init_state(2, 2, 16), q, k, v, g,
                                      EPS, 8, lengths=lengths)
    a, b = (slice(None), slice(None, split)), (slice(None), slice(split, None))
    y1, S1, z1 = pr.retention_chunked(
        *pr.init_state(2, 2, 16), q[a], k[a], v[a], g[a], EPS, 8,
        lengths=jnp.minimum(lengths, split))
    y2, S2, z2 = pr.retention_chunked(
        S1, z1, q[b], k[b], v[b], g[b], EPS, 8,
        lengths=jnp.maximum(lengths - split, 0))
    got = jnp.concatenate([y1, y2], axis=1)
    for row, n in enumerate(lengths.tolist()):
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=2e-5)
    np.testing.assert_allclose(S2, S, atol=5e-6)
    np.testing.assert_allclose(z2, z, atol=5e-6)


def test_a_fresh_row_beside_a_carried_row():
    """One call whose incoming state is zero for row 0 and carried for row
    1: the state is queried (row 1 holds something) and adds nothing to
    row 0."""
    q, k, v, g = _inputs()
    want = pr.retention_attention(q, k, v, g, EPS)
    _, S, z = pr.retention_chunked(*pr.init_state(2, 2, 16), q[:, :20],
                                   k[:, :20], v[:, :20], g[:, :20], EPS, 8)
    S, z = S.at[0].set(0.0), z.at[0].set(0.0)
    got, S2, z2 = pr.retention_chunked(S, z, q[:, 20:], k[:, 20:], v[:, 20:],
                                       g[:, 20:], EPS, 8)
    alone = pr.retention_attention(q[:1, 20:], k[:1, 20:], v[:1, 20:],
                                   g[:1, 20:], EPS)
    np.testing.assert_allclose(got[0], alone[0], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1, 20:], atol=2e-5)
    _, S0, z0 = _recurrent(q[:1, 20:], k[:1, 20:], v[:1, 20:], g[:1, 20:])
    _, S1, z1 = _recurrent(q[1:], k[1:], v[1:], g[1:])
    np.testing.assert_allclose(S2, jnp.concatenate([S0, S1]), atol=5e-6)
    np.testing.assert_allclose(z2, jnp.concatenate([z0, z1]), atol=5e-6)


@pytest.mark.parametrize("T,chunk", [(37, 5), (37, 36), (9, 4), (8, 64)])
def test_a_last_block_shorter_than_the_chunk(T, chunk):
    """``T`` not a multiple of ``chunk`` (and a chunk longer than the
    call): the padded tail of the last block reaches neither the outputs
    nor the state."""
    q, k, v, g = _inputs(T=T)
    got, S, z = pr.retention_chunked(*pr.init_state(2, 2, 16), q, k, v, g,
                                     EPS, chunk)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, pr.retention_attention(q, k, v, g, EPS),
                               atol=2e-5)
    _, S2, z2 = _recurrent(q, k, v, g)
    np.testing.assert_allclose(S, S2, atol=5e-6)
    np.testing.assert_allclose(z, z2, atol=5e-6)


def _equations(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the programs its equations hold
    (a scan's body, a conditional's branches), each with the path of
    ``(primitive, branch)`` it lies under."""
    for eqn in jaxpr.eqns:
        yield inside, eqn
        for value in eqn.params.values():
            held = value if isinstance(value, (list, tuple)) else [value]
            for i, sub in enumerate(held):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(
                        sub, inside + ((eqn.primitive.name, i),))


def _products_of_phi_q(jaxpr, G, F):
    """Paths of the ``dot_general`` equations with an operand that has
    both an ``F``-sized and a ``G``-sized axis: ``phi(q)`` of the grouped
    query heads."""
    return [inside for inside, eqn in _equations(jaxpr)
            if eqn.primitive.name == "dot_general"
            and any(F in x.aval.shape and G in x.aval.shape
                    for x in eqn.invars)]


def test_phi_q_is_made_only_for_a_state_that_holds_something():
    """At the served heads' shapes (d = 128, F = 8704, G = 5 query heads a
    key/value head): the program holds the products of ``phi(q)`` with the
    state and with its normaliser once each, both inside the branch taken
    when the incoming normaliser is not all zero; the other branch and
    everything outside the conditional hold none, and a call that makes
    its zero state itself compiles to a program with no conditional and no
    ``[..., G, F]`` array at all."""
    B, T, H, Hkv, d, chunk = 1, 16, 10, 2, 128, 8
    G, F = H // Hkv, pr.feature_dim(d)
    q, k, v, g = _inputs(B=B, T=T, H=H, Hkv=Hkv, d=d)

    def given(S, z):
        return pr.retention_chunked(S, z, q, k, v, g, EPS, chunk)

    jaxpr = jax.make_jaxpr(given)(*pr.init_state(B, Hkv, d)).jaxpr
    conds = [inside for inside, eqn in _equations(jaxpr)
             if eqn.primitive.name == "cond"]
    assert conds == [(("scan", 0),)]
    # branch 1 is the true one: lax.cond orders them (false, true)
    assert _products_of_phi_q(jaxpr, G, F) == [(("scan", 0), ("cond", 1))] * 2

    def fresh():
        return pr.retention_chunked(*pr.init_state(B, Hkv, d), q, k, v, g,
                                    EPS, chunk)

    program = jax.jit(fresh).lower().compile().as_text()
    assert "conditional" not in program and f"{G},{F}]" not in program


def test_padding_enters_neither_the_state_nor_the_running_decay():
    q, k, v, g = _inputs()
    lengths = jnp.asarray([20, 37])
    got, S, z = pr.retention_chunked(*pr.init_state(2, 2, 16), q, k, v, g,
                                     EPS, 8, lengths=lengths)
    want = pr.retention_attention(q, k, v, g, EPS)
    np.testing.assert_allclose(got[0, :20], want[0, :20], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    _, S20, z20 = _recurrent(q[:1], k[:1], v[:1], g[:1], upto=20)
    np.testing.assert_allclose(S[0], S20[0], atol=5e-6)
    np.testing.assert_allclose(z[0], z20[0], atol=5e-6)


@pytest.mark.parametrize("tile_lanes", [2176, 8704])
def test_kernel_is_the_fallback_arithmetic(tile_lanes):
    """The Pallas kernel under the interpreter against the ``jax.numpy``
    step, on a state that is not empty; grouped heads (G = 2)."""
    B, H, Hkv, d = 2, 4, 2, 128
    ks = jax.random.split(jax.random.key(3), 6)
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, Hkv, d))
    v = jax.random.normal(ks[2], (B, Hkv, d))
    g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, Hkv)))
    S = jax.random.normal(ks[4], (B, Hkv, d, pr.feature_dim(d)))
    z = jnp.abs(jax.random.normal(ks[5], (B, Hkv, pr.feature_dim(d))))
    want = pr.retention_step_reference(S, z, q, k, v, g)
    got = pr.retention_step(S, z, q, k, v, g, use_kernel=True,
                            tile_lanes=tile_lanes, interpret=True)
    # num and den are sums of 8704 float32 products of size ~1 (values up
    # to ~30): the kernel adds them lane by lane, the einsum otherwise
    for w, x, tol in zip(want, got, (2e-4, 2e-4, 1e-5, 1e-5)):
        assert w.shape == x.shape
        np.testing.assert_allclose(x, w, atol=tol, rtol=1e-5)


def test_kernel_refuses_shapes_it_was_not_written_for():
    S, z = pr.init_state(1, 1, 16)
    a = jnp.zeros((1, 1, 16))
    with pytest.raises(ValueError, match="head_dim 128"):
        pr.retention_step(S, z, a, a, a, jnp.zeros((1, 1)), use_kernel=True)
