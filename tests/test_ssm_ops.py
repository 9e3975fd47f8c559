"""The state-space recurrence's three forms (ops/ssm.py) against the
plainest one: a token-by-token walk of each sequence on its own, from a
zero state, in float32. The chunked scan runs over PACKED rows whose
segments start and end inside chunks, are 1 to 3 tokens long (shorter
than the conv) or carry their state across several chunks; the decode
kernel runs in interpret mode with dead lanes among the live ones."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops import ssm

H, P, N, G = 4, 8, 16, 2


def _inputs(T, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=-3.0, maxval=1.0))
    B = jax.random.normal(k[3], (T, G, N))
    C = jax.random.normal(k[4], (T, G, N))
    return x, dt, A, B, C


def _walk(x, dt, A, B, C):
    """One sequence token by token: ``(y [T, H, P], S after its last
    token)``."""
    rep = H // G
    S = np.zeros((H, P, N))
    ys = []
    for t in range(x.shape[0]):
        Bh = np.repeat(np.asarray(B[t]), rep, axis=0)
        Ch = np.repeat(np.asarray(C[t]), rep, axis=0)
        S = np.exp(np.asarray(dt[t]) * np.asarray(A))[:, None, None] * S \
            + (np.asarray(dt[t])[:, None] * np.asarray(x[t]))[:, :, None] \
            * Bh[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", S, Ch))
    return np.stack(ys), S


# segment lengths packed in order, then padding: boundaries inside
# chunks of 8, sequences of 1-3 tokens, one carried across three chunks
LAYOUTS = {"inside_chunks": [5, 9, 3, 14, 1],
           "short": [1, 2, 3, 1, 3, 2],
           "across_chunks": [27, 2, 4]}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_chunked_scan_equals_the_token_walk_of_each_segment(layout):
    lengths, Q, T = LAYOUTS[layout], 8, 40
    x, dt, A, B, C = _inputs(T)
    seg = np.zeros(T, np.int32)
    starts = np.cumsum([0] + lengths[:-1])
    for r, (s, n) in enumerate(zip(starts, lengths)):
        seg[s:s + n] = r + 1
    seg = jnp.asarray(seg)
    with jax.default_matmul_precision("highest"):
        y, carry = jax.jit(lambda *a: ssm.chunked_scan(*a, chunk=Q))(
            x, dt, A, B, C, seg)
        for s, n in zip(starts, lengths):
            want_y, want_s = _walk(x[s:s + n], dt[s:s + n], A, B[s:s + n],
                                   C[s:s + n])
            np.testing.assert_allclose(y[s:s + n], want_y, rtol=1e-4,
                                       atol=1e-4)
            got_s = ssm.final_state(carry, seg, jnp.int32(s + n - 1),
                                    chunk=Q)
            np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=1e-4)


def test_chunked_scan_does_not_depend_on_the_chunk():
    T = 37   # not a whole number of chunks
    x, dt, A, B, C = _inputs(T, seed=1)
    seg = jnp.asarray(np.repeat([1, 2, 3], [11, 20, 6]).astype(np.int32))
    outs = [ssm.chunked_scan(x, dt, A, B, C, seg, chunk=Q)[0]
            for Q in (4, 8, 16, 64)]
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], rtol=1e-4, atol=1e-4)


def test_segment_conv_restarts_at_each_segment_and_its_tail_is_the_state():
    T, Cc, K = 12, 6, 4
    xbc = jax.random.normal(jax.random.PRNGKey(2), (T, Cc))
    w = jax.random.normal(jax.random.PRNGKey(3), (K, Cc))
    b = jax.random.normal(jax.random.PRNGKey(4), (Cc,))
    seg = jnp.asarray([1, 1, 2, 2, 2, 2, 2, 3, 4, 4, 4, 0], jnp.int32)
    got = ssm.segment_conv(xbc, seg, w, b)
    for s, n in ((0, 2), (2, 5), (7, 1), (8, 3)):
        alone = np.concatenate([np.zeros((K - 1, Cc)), xbc[s:s + n]])
        want = sum(np.asarray(w[k]) * alone[k:k + n] for k in range(K)) + b
        np.testing.assert_allclose(got[s:s + n], want, rtol=1e-5, atol=1e-5)
        tail = ssm.conv_tail(xbc, seg, jnp.int32(s + n - 1), K - 1)
        np.testing.assert_allclose(tail, alone[-(K - 1):], atol=0)


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 1], [0] * 6, [1] * 6],
                         ids=["some_dead", "none_live", "all_live"])
def test_decode_kernel_matches_jnp_in_interpret_mode(live):
    """The kernel's state rows and ``y`` equal the jnp form's; dead
    lanes' rows and the spare row keep their bits."""
    b, Nk = 6, 128   # the kernel's state: whole lane tiles
    k = jax.random.split(jax.random.PRNGKey(5), 7)
    state = jax.random.normal(k[0], (b + 1, H, P, Nk)).astype(jnp.bfloat16)
    x = jax.random.normal(k[1], (b, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (b, H)))
    A = -jnp.exp(jax.random.normal(k[3], (H,)))
    B = jax.random.normal(k[4], (b, G, Nk)).astype(jnp.bfloat16)
    C = jax.random.normal(k[5], (b, G, Nk)).astype(jnp.bfloat16)
    D = jax.random.normal(k[6], (H,))
    live = jnp.asarray(live, bool)
    want_s, want_y = ssm.ssm_decode_update(state, x, dt, A, B, C, D, live,
                                           impl="jnp")
    got_s, got_y = ssm.ssm_decode_update(state, x, dt, A, B, C, D, live,
                                         impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s[:b], np.float32),
                                  np.asarray(want_s[:b], np.float32))
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(got_s[:b][dead], np.float32),
                                  np.asarray(state[:b][dead], np.float32))
    assert not np.asarray(got_y)[dead].any()
    # a live lane's step is the recurrence's
    if np.asarray(live).any():
        i = int(np.argmax(np.asarray(live)))
        rep = H // G
        s0 = np.asarray(state[i], np.float32)
        step = np.exp(np.asarray(dt[i] * A))[:, None, None] * s0 \
            + (np.asarray(dt[i])[:, None] * np.asarray(x[i], np.float32)
               )[:, :, None] * np.repeat(np.asarray(B[i], np.float32),
                                         rep, axis=0)[:, None, :]
        np.testing.assert_allclose(np.asarray(got_s[i], np.float32), step,
                                   rtol=1e-2, atol=1e-2)


def test_decode_impl_is_a_demand():
    with pytest.raises(ValueError, match="unknown ssm decode impl"):
        ssm.decode_resolved(P, 128, "triton")
    with pytest.raises(ValueError, match="cannot be honoured"):
        ssm.decode_resolved(P, 16, "pallas")
    assert ssm.decode_resolved(P, 128) == "jnp"   # the CPU
