"""``ops.ssm``: the Mamba-2 recurrence's three forms are one function.  The
token-by-token recurrence written here from the equations (``S <- a S + dt
x B^T``, ``y = S C``, heads in groups that share B and C) is the
definition; the step (``jax.numpy`` form and the kernel under the Pallas
interpreter) and the chunked scan (any chunk, carried state, padded rows
with ``lengths``, a call split in two) are tested against it.  float32 at
highest precision on both sides: what differs is the order of sums."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import ssm

TOL = 5e-5


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(B=2, T=37, H=8, P=16, G=2, N=8, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (B, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 1.0),
        A=-jnp.exp(0.3 * jax.random.normal(k[2], (H,))),
        Bm=jax.random.normal(k[3], (B, T, G, N)),
        Cm=jax.random.normal(k[4], (B, T, G, N)),
        state=jax.random.normal(k[5], ssm.state_shape(B, H, P, G, N)))


def _recurrence(state, x, dt, A, Bm, Cm, lengths=None):
    """The definition, on ``[B, H, P, N]`` states of its own layout."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    S = np.asarray(state, np.float64).reshape(B, G, N, R, P) \
        .transpose(0, 1, 3, 4, 2).reshape(B, H, P, N)
    x, dt, A, Bm, Cm = (np.asarray(v, np.float64)
                        for v in (x, dt, A, Bm, Cm))
    ys = np.zeros((B, T, H, P))
    for b in range(B):
        for t in range(T if lengths is None else int(lengths[b])):
            for h in range(H):
                g = h // R
                S[b, h] = np.exp(dt[b, t, h] * A[h]) * S[b, h] \
                    + np.outer(dt[b, t, h] * x[b, t, h], Bm[b, t, g])
                ys[b, t, h] = S[b, h] @ Cm[b, t, g]
    back = S.reshape(B, G, R, P, N).transpose(0, 1, 4, 2, 3) \
        .reshape(state.shape)
    return ys, back


@pytest.mark.parametrize("chunk", [1, 8, 16, 64])
def test_chunked_scan_is_the_recurrence(chunk):
    v = _inputs()
    want, s_want = _recurrence(**v)
    got, s_got = ssm.ssm_chunked(v["state"], v["x"], v["dt"], v["A"],
                                 v["Bm"], v["Cm"], chunk)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(s_got, s_want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("T", [127, 128, 129])
def test_chunk_boundaries_at_the_served_chunk(T):
    """One token short of a chunk of 128, a whole chunk, one token over."""
    v = _inputs(B=1, T=T, H=2, P=8, G=1, N=4, seed=T)
    want, s_want = _recurrence(**v)
    got, s_got = ssm.ssm_chunked(v["state"], v["x"], v["dt"], v["A"],
                                 v["Bm"], v["Cm"], 128)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(s_got, s_want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jax.numpy", "kernel (interpreter)"])
def test_step_is_the_recurrence_and_leaves_the_same_state(use_kernel):
    # a group's heads x values are whole lane tiles: the kernel's shapes
    v = _inputs(B=3, T=5, H=8, P=64, G=2, N=16, seed=1)
    want, s_want = _recurrence(**v)
    S = v["state"]
    for t in range(5):
        y, S = ssm.ssm_step(S, v["x"][:, t], v["dt"][:, t],
                            jnp.exp(v["dt"][:, t] * v["A"]), v["Bm"][:, t],
                            v["Cm"][:, t], use_kernel=use_kernel)
        np.testing.assert_allclose(y, want[:, t], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(S, s_want, atol=TOL, rtol=TOL)


def test_kernel_is_the_jax_numpy_form_in_place():
    """Same arithmetic in the same order: the kernel under the interpreter
    and the ``jax.numpy`` form agree to a rounding of one fused
    multiply-add, and the kernel's state output is declared to alias its input."""
    v = _inputs(B=2, T=1, H=8, P=64, G=2, N=16, seed=2)
    args = (v["state"], v["x"][:, 0], v["dt"][:, 0],
            jnp.exp(v["dt"][:, 0] * v["A"]), v["Bm"][:, 0], v["Cm"][:, 0])
    y0, s0 = ssm.ssm_step_reference(*args)
    y1, s1 = ssm.ssm_step(*args, use_kernel=True)
    np.testing.assert_allclose(y1, y0, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(s1, s0, atol=2e-6, rtol=2e-6)
    # (the compile for the chip, ``tests/test_chip_compile.py``, holds the
    # donated state to be the output's buffer)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: ssm.ssm_step(*a, use_kernel=True))(*args))
    assert "tfos_ssm_step" in jaxpr and "input_output_aliases=((0, 0),)" \
        in jaxpr.replace("\n", " ")


def test_kernel_refuses_shapes_it_was_not_written_for():
    v = _inputs(B=1, T=1, H=8, P=16, G=2, N=8)      # 64 lanes a group
    with pytest.raises(ValueError, match="whole lane tiles"):
        ssm.ssm_step(v["state"], v["x"][:, 0], v["dt"][:, 0],
                     v["dt"][:, 0], v["Bm"][:, 0], v["Cm"][:, 0],
                     use_kernel=True)
    with pytest.raises(ValueError, match="multiple of the groups"):
        ssm.state_shape(1, 6, 16, 4, 8)


def test_padding_neither_decays_nor_feeds_the_state():
    """Rows of 37 and 20 valid tokens in one padded call: each row's state
    is the one after its own last valid token, whatever the pad holds."""
    v = _inputs()
    lengths = jnp.asarray([37, 20])
    _, s_want = _recurrence(**v, lengths=lengths)
    poisoned = dict(v, x=v["x"].at[1, 20:].set(1e6),
                    Bm=v["Bm"].at[1, 20:].set(1e6))
    got, s_got = ssm.ssm_chunked(poisoned["state"], poisoned["x"],
                                 poisoned["dt"], v["A"], poisoned["Bm"],
                                 v["Cm"], 8, lengths)
    np.testing.assert_allclose(s_got, s_want, atol=TOL, rtol=TOL)
    want, _ = _recurrence(**v)
    np.testing.assert_allclose(got[1, :20], want[1, :20], atol=TOL, rtol=TOL)


@pytest.mark.parametrize("split", [1, 20, 36])
def test_a_call_split_in_two_is_the_one_call(split):
    """A prompt admitted in slices: the second call starts from the state
    the first left."""
    v = _inputs()
    args = lambda lo, hi: (v["x"][:, lo:hi], v["dt"][:, lo:hi], v["A"],
                           v["Bm"][:, lo:hi], v["Cm"][:, lo:hi], 8)
    whole, s_whole = ssm.ssm_chunked(v["state"], *args(0, 37))
    first, s_mid = ssm.ssm_chunked(v["state"], *args(0, split))
    second, s_end = ssm.ssm_chunked(s_mid, *args(split, 37))
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(s_end, s_whole, atol=TOL, rtol=TOL)


def test_state_bytes_are_the_published_layers():
    # 64 heads x 64 x 128 float32 = 2,097,152 bytes a row a layer
    assert ssm.state_bytes(1, 64, 64, 128) == 2_097_152
    assert ssm.state_shape(32, 64, 64, 8, 128) == (32, 8, 128, 512)
