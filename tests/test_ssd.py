"""``ops/ssd.py``: the chunkwise state-space scan against the one-token step,
token by token; the convolution with bias against its four-term sum; the
router's two scorings against a top-k by hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssd
from paddle_tpu.ops.moe_dropless import route_topk


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _case(seed, T, H=6, P=8, G=2, N=16, dt_hi=0.5):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    return dict(x=f(T, H, P), dt=rng.uniform(1e-3, dt_hi, size=(T, H)).astype(np.float32),
                A=-rng.uniform(1.0, 16.0, size=(H,)).astype(np.float32), B=f(T, G, N), C=f(T, G, N),
                D=f(H), state=f(H, P, N))


def _token_by_token(c, state, n=None):
    """``ssd_step`` over the run's rows one at a time (a batch of one)."""
    s, ys = jnp.asarray(state)[None], []
    for t in range(c["x"].shape[0] if n is None else n):
        y, s = ssd.ssd_step(c["x"][None, t], c["dt"][None, t], c["A"], c["B"][None, t], c["C"][None, t], c["D"], s)
        ys.append(np.asarray(y[0]))
    return np.stack(ys), np.asarray(s[0])


def test_one_step_is_the_recurrence_written_out():
    c = _case(0, 1)
    y, s = ssd.ssd_step(c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"], c["state"][None])
    for h in range(6):
        g = h // 3                                                          # head h takes group h // (H / G)
        want = np.exp(c["dt"][0, h] * c["A"][h]) * c["state"][h] + c["dt"][0, h] * np.outer(c["x"][0, h], c["B"][0, g])
        np.testing.assert_allclose(np.asarray(s[0, h]), want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y[0, h]), want @ c["C"][0, g] + c["D"][h] * c["x"][0, h], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("T,chunk,dt_hi", [(64, 16, 0.5), (50, 16, 0.5), (16, 16, 0.1), (7, 16, 0.5), (96, 32, 8.0)])
def test_chunked_scan_is_the_step_token_by_token_with_a_carried_state(T, chunk, dt_hi):
    """Lengths that are and are not multiples of the inner chunk, a run
    shorter than it, a state handed in, and a decay strong enough
    (``exp(-128)`` a token) that a division by a cumulative decay would
    overflow."""
    c = _case(T + chunk, T, dt_hi=dt_hi)
    want_y, want_s = _token_by_token(c, c["state"])
    y, s = ssd.ssd_chunked(c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"], c["state"], chunk=chunk)
    assert np.isfinite(np.asarray(y)).all() and _rel(y, want_y) < 1e-5 and _rel(s, want_s) < 1e-5


@pytest.mark.parametrize("n_valid", [0, 5, 16, 37, 48])
def test_rows_past_the_last_valid_one_leave_the_state_alone(n_valid):
    c = _case(3, 48)
    y, s = ssd.ssd_chunked(c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"], c["state"], jnp.int32(n_valid), chunk=16)
    if n_valid == 0:
        np.testing.assert_array_equal(np.asarray(s), c["state"])            # bitwise
        return
    want_y, want_s = _token_by_token(c, c["state"], n_valid)
    assert _rel(s, want_s) < 1e-5 and _rel(np.asarray(y)[:n_valid], want_y) < 1e-5
    # whole inner chunks of padding after the last valid row: the state that leaves the last chunk with a valid row, bitwise
    if n_valid % 16 == 0:
        _, short = ssd.ssd_chunked(*(c[k][:n_valid] for k in ("x", "dt")), c["A"], c["B"][:n_valid], c["C"][:n_valid], c["D"],
                                   c["state"], chunk=16)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(short))


def test_an_inactive_slot_keeps_its_state_bitwise():
    c = _case(4, 3)
    state = np.stack([c["state"]] * 3)
    active = jnp.asarray([True, False, True])
    _, s = ssd.ssd_step(c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"], state, active)
    np.testing.assert_array_equal(np.asarray(s[1]), state[1])
    assert not np.array_equal(np.asarray(s[0]), state[0])


def test_two_calls_hand_the_state_and_the_tail_across_the_seam():
    """A run cut in two, each part convolved from the tail the part before
    left and scanned from the state it left, is the run in one call."""
    rng = np.random.default_rng(5)
    T, cut, K, H, P, G, N = 57, 24, 4, 6, 8, 2, 16
    ch = H * P + 2 * G * N
    raw = rng.normal(size=(T, ch)).astype(np.float32)
    w, b = rng.normal(size=(K, ch)).astype(np.float32) * 0.5, rng.normal(size=(ch,)).astype(np.float32) * 0.1
    c = _case(6, T)

    def run(rows, dt, tail, state):
        window = jnp.concatenate([tail, rows], axis=0)
        xbc = ssd.causal_conv(window, w, b)
        x, B, C = xbc[:, :H * P].reshape(-1, H, P), xbc[:, H * P:H * P + G * N].reshape(-1, G, N), xbc[:, H * P + G * N:].reshape(-1, G, N)
        y, state = ssd.ssd_chunked(x, dt, c["A"], B, C, c["D"], state, chunk=16)
        return y, state, window[-(K - 1):]

    zero_tail, zero_state = jnp.zeros((K - 1, ch), jnp.float32), jnp.zeros((H, P, N), jnp.float32)
    want_y, want_s, want_tail = run(raw, c["dt"], zero_tail, zero_state)
    y1, s1, t1 = run(raw[:cut], c["dt"][:cut], zero_tail, zero_state)
    y2, s2, t2 = run(raw[cut:], c["dt"][cut:], t1, s1)
    assert _rel(np.concatenate([y1, y2]), want_y) < 1e-5 and _rel(s2, want_s) < 1e-5
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(want_tail))
    # without the tail the first K - 1 rows after the seam are another convolution's
    y2_cold, _, _ = run(raw[cut:], c["dt"][cut:], zero_tail, s1)
    assert _rel(y2_cold[:K - 1], want_y[cut:cut + K - 1]) > 1e-2 and _rel(y2_cold[K - 1:], want_y[cut + K - 1:]) > 1e-4


def test_the_convolution_with_bias_is_its_four_term_sum():
    rng = np.random.default_rng(7)
    T, K, ch = 9, 4, 5
    x, w, b = rng.normal(size=(T, ch)), rng.normal(size=(K, ch)), rng.normal(size=(ch,))
    padded = np.concatenate([np.zeros((K - 1, ch)), x])
    want = np.stack([b + w[0] * padded[t] + w[1] * padded[t + 1] + w[2] * padded[t + 2] + w[3] * padded[t + 3] for t in range(T)])
    want = want / (1.0 + np.exp(-want))                                     # SiLU
    got = ssd.causal_conv(jnp.asarray(padded, jnp.float32), jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    assert _rel(ssd.causal_conv(jnp.asarray(padded, jnp.float32), jnp.asarray(w, jnp.float32), jnp.zeros((ch,))), want) > 1e-2
    # a batch of one-token windows, as a decode step has them
    step = ssd.causal_conv(jnp.asarray(np.stack([padded[t:t + K] for t in range(T)]), jnp.float32), jnp.asarray(w, jnp.float32),
                           jnp.asarray(b, jnp.float32))
    np.testing.assert_allclose(np.asarray(step[:, 0]), want, rtol=1e-5, atol=1e-6)


def test_the_registry_counts_one_selection_a_set_of_shapes():
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops import registry

    assert registry.implementations("ssd_step") == ["lax"] and registry.implementations("ssd_chunked") == ["lax"]
    registry.clear_cache("ssd_chunked")
    metrics.reset_counters("kernels.ssd_chunked.")
    c = _case(8, 32)
    for _ in range(2):
        ssd.ssd_chunked(c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"], c["state"], chunk=16)
    assert metrics.counters("kernels.ssd_chunked.") == {"kernels.ssd_chunked.picked": 1, "kernels.ssd_chunked.fallback": 0}


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax_topk"])
def test_the_routers_two_scorings_against_a_top_k_by_hand(scoring):
    rng = np.random.default_rng(9)
    T, D, E, k = 12, 16, 24, 5
    x, w = rng.normal(size=(T, D)).astype(np.float32), rng.normal(size=(D, E)).astype(np.float32)
    weights, experts = route_topk(jnp.asarray(x), jnp.asarray(w), top_k=k, scale=2.5, scoring=scoring)
    logits = x.astype(np.float64) @ w.astype(np.float64)
    for t in range(T):
        order = np.argsort(-logits[t])[:k]                                  # both scorings rise with the logit
        assert list(np.asarray(experts[t])) == list(order)
        if scoring == "sigmoid":
            score = 1.0 / (1.0 + np.exp(-logits[t, order]))
            want = score / score.sum()
        else:
            e = np.exp(logits[t, order] - logits[t, order].max())
            want = e / e.sum()                                              # a softmax over the chosen logits, not over all
        np.testing.assert_allclose(np.asarray(weights[t]), 2.5 * want, rtol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        route_topk(jnp.asarray(x), jnp.asarray(w), top_k=k, scoring="softmax")
