"""Mamba-1's selective scan (ops/selective_scan.py): the two Pallas kernels,
interpreted, against their plain twin, the recurrence one position at a
time, at lengths that are no multiple of a chunk, rows that end inside one,
channels of one block and of several, and idle slots; and the twin against
the equations written out in numpy.  Numbers here are about results, never
speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as ss


def _inputs(b, t, ch, n, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, t, ch)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, ch)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (n, ch))),
            jax.random.normal(k[3], (b, t, n)),
            jax.random.normal(k[4], (b, t, n)))


def test_the_twin_is_the_recurrence_written_out():
    u, dt, a, b, c = (np.asarray(x, np.float64)
                      for x in _inputs(1, 9, 128, 3, seed=0))
    s, ys = np.zeros((3, 128)), []
    for t in range(9):
        s = np.exp(dt[0, t] * a) * s + (dt[0, t] * u[0, t]) * b[0, t][:, None]
        ys.append(c[0, t] @ s)
    y, state = ss.selective_scan_jnp(*_inputs(1, 9, 128, 3, seed=0))
    np.testing.assert_allclose(y[0], np.stack(ys), atol=1e-5)
    np.testing.assert_allclose(state.reshape(3, 128), s, atol=1e-5)
    assert state.shape == (1,) + ss.state_shape(128, 3) == (1, 3, 1, 128)


@pytest.mark.parametrize("t,ch,n,lengths", [
    (150, 256, 4, (150, 77)),       # two chunks, a row that ends in the first
    (128, 1280, 16, (128, 1)),      # ten rows of channels: two blocks of five
    (37, 128, 2, (30, 37)),         # under a chunk
    (300, 1024, 3, (129, 256))])    # one block of eight rows; a chunk skipped
def test_chunk_kernel_interpreted_equals_its_twin(t, ch, n, lengths):
    args = _inputs(2, t, ch, n, seed=t)
    lengths = jnp.array(lengths)
    y_t, s_t = jax.jit(ss.selective_scan_jnp)(*args, lengths)
    y, s = jax.jit(lambda *a: ss.selective_scan_chunk_fwd(
        *a, interpret=True))(*args, lengths)
    for row, length in enumerate(lengths.tolist()):
        np.testing.assert_allclose(y[row, :length], y_t[row, :length],
                                   atol=1e-5)
    np.testing.assert_allclose(s, s_t, atol=1e-5)
    # a row's state is its state as of its length: the padding past it is
    # not read
    again = jax.jit(ss.selective_scan_jnp)(
        *(x[:1, :int(lengths[0])] if x.ndim == 3 else x for x in args))[1]
    np.testing.assert_allclose(s[:1], again, atol=1e-5)
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("slots,ch,n", [(11, 256, 4), (5, 1280, 16),
                                        (8, 128, 2)])
def test_step_kernel_interpreted_equals_its_twin_and_touches_one_layer(
        slots, ch, n):
    """Slots that are no whole blocks of eight, an idle slot (``dt`` 0)
    whose state stays to the bit, the other layers of the stack untouched;
    and a step is the chunked form's next position."""
    k = jax.random.split(jax.random.PRNGKey(slots), 2)
    state = jax.random.normal(k[0], (3, slots) + ss.state_shape(ch, n))
    u, dt, a, b, c = _inputs(slots, 1, ch, n, seed=3)
    u, dt, b, c = (x[:, 0] for x in (u, dt, b, c))
    dt = dt.at[3].set(0.0)
    s_t, y_t = ss.selective_scan_step_jnp(state, jnp.int32(1), u, dt, a, b, c)
    s, y = jax.jit(lambda *x: ss.selective_scan_step(*x, interpret=True))(
        state, jnp.int32(1), u, dt, a, b, c)
    np.testing.assert_allclose(y, y_t, atol=1e-5)
    np.testing.assert_allclose(s, s_t, atol=1e-6)
    np.testing.assert_array_equal(s[0], state[0])
    np.testing.assert_array_equal(s[2], state[2])
    np.testing.assert_array_equal(s[1, 3], state[1, 3])
    y_ref, s_ref = ss.selective_scan_jnp(
        u[:, None], dt[:, None], a, b[:, None], c[:, None],
        initial_state=state[1])
    np.testing.assert_allclose(y, y_ref[:, 0], atol=1e-5)
    np.testing.assert_allclose(s[1], s_ref, atol=1e-5)


def test_channels_that_are_no_whole_tiles_are_refused():
    with pytest.raises(ValueError, match="128 lanes"):
        ss.state_shape(96, 16)
