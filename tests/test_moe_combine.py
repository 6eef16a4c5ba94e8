"""``ops/moe_combine.py`` on the CPU: the slot-major ``lax`` form and the
kernel (Pallas interpret mode) against the three lines ``dropless_experts``
held before PR 37, kept here as the reference; the rows of no run poisoned;
and the registry's selection counters. Whether the chip's compiler takes the
kernel is ``test_chip_compile.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.ops import moe_combine as mc
from paddle_tpu.ops import registry
from paddle_tpu.ops.moe_dropless import dropless_experts, route_topk

E, D = 24, 128
HELD = {"all": (0, E), "a_proper_subset": (5, 9), "none_of_the_chosen": (E - 4, 4)}     # (first, count) of the held experts


@pytest.fixture
def interpret():
    prior = mc.set_interpret(True)
    yield
    mc.set_interpret(prior)


def _token_major(y, order, mine, weights):
    """The way back as it was: gather in pair order, ``[T, k, D]``, a sum over each token's ``k``."""
    T, k = mine.shape
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
    y = jnp.take(y, inverse, axis=0).reshape(T, k, -1)
    return jnp.sum(jnp.where(mine[..., None], y * weights[..., None], 0.0), axis=1)


def _case(T, k, held, poison=np.nan):
    """``(y, order, mine, weights)`` as ``dropless_experts`` hands them over: the held pairs' rows first, by expert;
    the rows past them — which the grouped matmul leaves unspecified — poisoned."""
    first, count = HELD[held]
    rng = np.random.default_rng(T * 100 + k)
    experts = np.stack([rng.choice(E, size=k, replace=False) for _ in range(T)]).astype(np.int32)
    if held == "none_of_the_chosen":
        experts %= first
    mine = (experts >= first) & (experts < first + count)
    order = np.argsort(np.where(mine, experts - first, count).reshape(-1), kind="stable").astype(np.int32)
    y = rng.normal(size=(T * k, D)).astype(np.float32)
    y[int(mine.sum()):] = poison
    weights = rng.random((T, k)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (y, order, mine, weights))


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("T", [5, 40, 64])
@pytest.mark.parametrize("k", [8, 10])
def test_slot_major_combine_is_the_token_major_one(k, T, held):
    args = _case(T, k, held)
    got = np.asarray(jax.jit(mc.combine_lax)(*args))
    assert got.shape == (T, D) and got.dtype == np.float32 and np.all(np.isfinite(got))       # a where, not a product with zero
    np.testing.assert_array_equal(got, np.asarray(_token_major(*_case(T, k, held, poison=0.0))))     # k terms, in slot order, both
    if held == "none_of_the_chosen":
        assert not got.any()


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("T", [5, 40, 64])
@pytest.mark.parametrize("k", [8, 10])
def test_kernel_combine_is_the_token_major_one(interpret, k, T, held):
    args = _case(T, k, held)
    registry.clear_cache("moe_combine")
    metrics.reset_counters("kernels.moe_combine.")
    got = np.asarray(jax.jit(lambda *a: mc.combine(*a))(*args))       # a new function: traced, so selected, again
    assert metrics.counters("kernels.moe_combine.") == {"kernels.moe_combine.picked": 1, "kernels.moe_combine.fallback": 0}
    assert got.shape == (T, D) and got.dtype == np.float32 and np.all(np.isfinite(got))
    want = np.asarray(_token_major(*_case(T, k, held, poison=0.0)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max())        # the k terms in expert order
    registry.clear_cache("moe_combine")


def test_a_long_run_of_kept_rows_spans_several_blocks(interpret):
    args = _case(256, 8, "all")                                   # 2,048 rows in blocks of 256
    assert mc._rows(256 * 8) == 256
    got, want = np.asarray(jax.jit(mc.combine_rows)(*args)), np.asarray(mc.combine_lax(*args))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("why,y,T,k", [
    ("a width that is no lane tile", ((320, 96), jnp.float32), 40, 8),
    ("a bfloat16 result", ((320, 128), jnp.bfloat16), 40, 8),
    ("a result that does not fit VMEM", ((1 << 16, 2048), jnp.float32), 1 << 13, 8),
    ("pairs that do not fit the scalar memory", ((1 << 17, 256), jnp.float32), 1 << 14, 8),
])
def test_the_kernel_declines(interpret, why, y, T, k):
    y, order = jax.ShapeDtypeStruct(*y), jax.ShapeDtypeStruct((T * k,), jnp.int32)
    mine, weights = jax.ShapeDtypeStruct((T, k), jnp.bool_), jax.ShapeDtypeStruct((T, k), jnp.float32)
    assert not mc.combine_available(y, order, mine, weights), why
    registry.clear_cache("moe_combine")
    metrics.reset_counters("kernels.moe_combine.")
    assert registry.select("moe_combine", y, order, mine, weights).fn is mc.combine_lax
    assert metrics.counters("kernels.moe_combine.") == {"kernels.moe_combine.picked": 0, "kernels.moe_combine.fallback": 1}
    registry.clear_cache("moe_combine")


def test_the_kernel_declines_on_the_cpu_and_under_a_mesh(monkeypatch):
    import paddle_tpu as paddle

    args = (jax.ShapeDtypeStruct((320, 128), jnp.float32), jax.ShapeDtypeStruct((320,), jnp.int32),
            jax.ShapeDtypeStruct((40, 8), jnp.bool_), jax.ShapeDtypeStruct((40, 8), jnp.float32))
    assert not mc.combine_available(*args)                       # the CPU
    monkeypatch.setattr(paddle.device, "is_tpu", lambda: True)
    assert mc.combine_available(*args)
    monkeypatch.setattr(mc, "_under_mesh", lambda: True)
    assert not mc.combine_available(*args)


@pytest.mark.parametrize("k", [8, 10])
def test_dropless_experts_with_the_kernel_is_dropless_experts_without(k):
    """The whole layer, float32, a proper subset held: the grouped matmul's real unspecified rows (``ragged_dot``
    leaves zeros there on the CPU), the kernel picked once for the program."""
    rng = np.random.default_rng(k)
    T, F, held = 40, 64, (5, 9)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    gate_up = jnp.asarray(rng.normal(size=(held[1], D, 2 * F)) * 0.1, jnp.float32)
    down = jnp.asarray(rng.normal(size=(held[1], F, D)) * 0.1, jnp.float32)
    weights, experts = route_topk(x, jnp.asarray(rng.normal(size=(D, E)), jnp.float32), top_k=k)
    layer = lambda: dropless_experts(x, weights, experts, gate_up, down, held=held, n_experts=E)  # noqa: E731
    want, want_stats = layer()
    registry.clear_cache("moe_combine")
    metrics.reset_counters("kernels.moe_combine.")
    prior = mc.set_interpret(True)
    try:
        got, got_stats = layer()
    finally:
        mc.set_interpret(prior)
    assert metrics.counters("kernels.moe_combine.")["kernels.moe_combine.picked"] == 1
    np.testing.assert_array_equal(np.asarray(got_stats), np.asarray(want_stats))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6 * float(jnp.abs(want).max()))
    registry.clear_cache("moe_combine")
