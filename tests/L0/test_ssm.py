"""The Mamba-2 recurrence's two forms (ISSUE 35, ``apex_tpu.ops.ssm``):
the in-place decode update (XLA route and the Pallas kernel in interpret
mode) and the chunked scan, against the equations in plain
``jax.numpy`` and against each other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (causal_conv, routing_override, ssd_chunk_scan,
                          ssm_decode_route, ssm_decode_update)

H, P, N, TAPS = 8, 16, 16, 4
CH = H * P + 2 * N
L, SLOTS = 3, 6


def weights(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        conv_w=jax.random.normal(k[0], (TAPS, CH)) * 0.5,
        conv_b=jax.random.normal(k[1], (CH,)) * 0.1,
        dt_bias=jax.random.normal(k[2], (H,)) - 1.0,
        a_log=jax.random.normal(k[3], (H,)) * 0.5,
        d_skip=1.0 + 0.1 * jax.random.normal(k[4], (H,)))


def stream(seed, s):
    k = jax.random.split(jax.random.PRNGKey(100 + seed), 2)
    return (jax.random.normal(k[0], (s, CH)),
            jax.random.normal(k[1], (s, H)))


def plain(w, xbc, dt, state=None, tail=None):
    """The equations as written, head major, token by token: returns
    (y [s, H * P], final state [H, P, N], final tail [TAPS - 1, CH])."""
    s = xbc.shape[0]
    tail = jnp.zeros((TAPS - 1, CH)) if tail is None else tail
    state = jnp.zeros((H, P, N)) if state is None else state
    seq = jnp.concatenate([tail, xbc])
    conv = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * seq[j:j + s] for j in range(TAPS)))
    x = conv[:, :H * P].reshape(s, H, P)
    b, c = conv[:, H * P:H * P + N], conv[:, H * P + N:]
    delta = jax.nn.softplus(dt + w["dt_bias"])
    decay = jnp.exp(-jnp.exp(w["a_log"]) * delta)
    ys = []
    for t in range(s):
        state = (decay[t][:, None, None] * state
                 + (delta[t][:, None] * x[t])[:, :, None] * b[t])
        ys.append(jnp.einsum("hpn,n->hp", state, c[t])
                  + w["d_skip"][:, None] * x[t])
    return jnp.stack(ys).reshape(s, H * P), state, seq[s:]


def to_pool(state):
    """[H, P, N], as the equations have it -> the module's [N, H * P]."""
    return state.transpose(2, 0, 1).reshape(N, H * P)


def chunked(w, xbc, dt, valid, state, tail, chunk):
    conv, tail = causal_conv(xbc, tail, valid, w["conv_w"], w["conv_b"])
    y, state = ssd_chunk_scan(
        conv[:, :H * P].reshape(-1, H, P), dt, w["a_log"],
        conv[:, H * P:H * P + N], conv[:, H * P + N:], w["d_skip"], state,
        valid, dt_bias=w["dt_bias"], chunk=chunk)
    return y, state, tail


def tail_of(conv, layer, slot):
    """A slot's tail as rows: the pool holds them end to end."""
    return conv[layer, slot].reshape(TAPS - 1, CH)


def pools(seed=7):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(k[0], (L, SLOTS, N, H * P)),
            jax.random.normal(k[1], (L, SLOTS, 1, (TAPS - 1) * CH)))


def update(w, ssm, conv, slots, xbc, dt, layer=1):
    return ssm_decode_update(ssm, conv, jnp.asarray(slots, jnp.int32), xbc,
                             dt, layer=layer, heads=H, **w)


# -- (c) the decode update against plain jax.numpy, in place ------------------

@pytest.mark.parametrize("route", ["xla", "decode"])
def test_decode_update_is_the_recurrence_in_place(route):
    w = weights()
    ssm, conv = pools()
    slots = [4, 2, 0, 0]                    # two real rows, two idle
    xbc, dt = stream(0, 4)
    with routing_override(decode=route):
        assert ssm_decode_route(ssm) == route
        y, ssm1, conv1 = jax.jit(
            lambda *a: update(w, *a))(ssm, conv, slots, xbc, dt)
    for row, slot in enumerate(slots[:2]):
        state0 = ssm[1, slot].reshape(N, H, P).transpose(1, 2, 0)
        want_y, want_s, want_t = plain(w, xbc[row:row + 1], dt[row:row + 1],
                                       state0, tail_of(conv, 1, slot))
        np.testing.assert_allclose(y[row], want_y[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(ssm1[1, slot], to_pool(want_s),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(tail_of(conv1, 1, slot), want_t)
    # other layers, and the slots no row names, keep their bytes; idle
    # rows touched the scratch slot alone
    untouched = np.ones((L, SLOTS), bool)
    untouched[1, [0, 2, 4]] = False
    for a, a1 in ((ssm, ssm1), (conv, conv1)):
        np.testing.assert_array_equal(np.asarray(a1)[untouched],
                                      np.asarray(a)[untouched])
    assert not np.array_equal(ssm1[1, 0], ssm[1, 0])


def test_decode_kernel_equals_the_xla_route():
    w = weights(1)
    ssm, conv = pools(8)
    xbc, dt = stream(1, 3)
    out = {}
    for route in ("xla", "decode"):
        with routing_override(decode=route):
            out[route] = update(w, ssm, conv, [1, 5, 3], xbc, dt, layer=2)
    for a, b in zip(out["xla"], out["decode"]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_auto_route_is_xla_off_the_tpu_and_where_tiles_are_not_whole():
    assert ssm_decode_route(jnp.zeros((1, 2, N, H * P))) == "xla"
    with routing_override(decode="decode"):
        assert ssm_decode_route(jnp.zeros((1, 2, N, 96))) == "xla"
        assert ssm_decode_route(jnp.zeros((1, 2, 12, 128))) == "xla"


def test_a_layer_outside_the_pool_is_refused():
    w = weights()
    ssm, conv = pools()
    xbc, dt = stream(0, 1)
    with pytest.raises(ValueError, match="layer"):
        update(w, ssm, conv, [1], xbc, dt, layer=L)


# -- (b) decode form equals chunked form --------------------------------------

@pytest.mark.parametrize("route", ["xla", "decode"])
@pytest.mark.parametrize("pad", [0, 5], ids=["whole", "front-padded"])
def test_decode_form_equals_chunked_form(pad, route):
    """Token by token through the pools, and the same tokens through
    the chunked scan in blocks of 8 (21 tokens: across two block
    boundaries), leave the same state, tail and outputs; so does the
    plain recurrence."""
    w = weights(2)
    n = 24 - pad
    xbc, dt = stream(2, n)
    ssm, conv = pools(9)
    ssm = ssm.at[0, 3].set(0.0)
    conv = conv.at[0, 3].set(0.0)
    ys = []
    with routing_override(decode=route):
        step = jax.jit(lambda *a: update(w, *a, layer=0))
        for t in range(n):
            y, ssm, conv = step(ssm, conv, [3], xbc[t:t + 1], dt[t:t + 1])
            ys.append(y[0])
    valid = jnp.arange(24) >= pad
    front = lambda a: jnp.pad(a, ((pad, 0), (0, 0)), constant_values=7.0)
    y_c, s_c, t_c = chunked(w, front(xbc), front(dt), valid,
                            jnp.zeros((N, H * P)),
                            jnp.zeros((TAPS - 1, CH)), chunk=8)
    want_y, want_s, want_t = plain(w, xbc, dt)
    for got, want in ((jnp.stack(ys), want_y), (y_c[pad:], want_y),
                      (ssm[0, 3], to_pool(want_s)), (s_c, to_pool(want_s))):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(tail_of(conv, 0, 3), want_t)
    np.testing.assert_array_equal(t_c, want_t)


def test_chunks_carry_state_and_tail():
    """Thirteen tokens as a chunk of 8 and then a front-padded chunk
    of the other 5: the second starts from the state and tail the first
    left, and together they are the plain recurrence."""
    w = weights(3)
    xbc, dt = stream(3, 13)
    want_y, want_s, want_t = plain(w, xbc, dt)
    all_valid = jnp.ones((8,), bool)
    y1, s1, t1 = chunked(w, xbc[:8], dt[:8], all_valid,
                         jnp.zeros((N, H * P)), jnp.zeros((TAPS - 1, CH)), 8)
    pad = 3
    front = lambda a: jnp.pad(a, ((pad, 0), (0, 0)), constant_values=-3.0)
    y2, s2, t2 = chunked(w, front(xbc[8:]), front(dt[8:]),
                         jnp.arange(8) >= pad, s1, t1, 8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2[pad:]]), want_y,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s2, to_pool(want_s), rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(t2, want_t)


def test_back_padding_passes_state_through():
    """A whole-row prefill is back-padded: the state and tail after the
    row are those after its last real token."""
    w = weights(4)
    xbc, dt = stream(4, 16)
    real = 10
    _, s_full, t_full = chunked(w, xbc, dt, jnp.arange(16) < real,
                                jnp.zeros((N, H * P)),
                                jnp.zeros((TAPS - 1, CH)), 8)
    _, want_s, want_t = plain(w, xbc[:real], dt[:real])
    np.testing.assert_allclose(s_full, to_pool(want_s), rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(t_full, want_t)


def test_a_short_run_keeps_the_older_tail():
    """Two real tokens after a tail of three: the new tail is the old
    tail's last row and the two."""
    w = weights(5)
    xbc, _ = stream(5, 8)
    tail = jnp.arange(3 * CH, dtype=jnp.float32).reshape(3, CH)
    _, new = causal_conv(xbc, tail, jnp.arange(8) >= 6, w["conv_w"],
                         w["conv_b"])
    np.testing.assert_array_equal(new, jnp.concatenate([tail[2:], xbc[6:]]))


def test_a_row_that_is_not_whole_blocks_is_refused():
    w = weights()
    xbc, dt = stream(0, 12)
    with pytest.raises(ValueError, match="whole blocks"):
        chunked(w, xbc, dt, jnp.ones((12,), bool), jnp.zeros((N, H * P)),
                jnp.zeros((TAPS - 1, CH)), chunk=8)
