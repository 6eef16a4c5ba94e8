"""``ops/grouped_matmul.py`` on the CPU (Pallas interpret mode) against
``lax.ragged_dot`` in float32, at the Solar cell's two ``(K, N)`` and every
row tile the shape rule can return; and the registry's selection counters.
Whether the chip's compiler takes the kernel is ``test_chip_compile.py``'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.ops import grouped_matmul as gm
from paddle_tpu.ops import registry
from paddle_tpu.ops.moe_dropless import dropless_experts, route_topk

M, G = 1024, 6
SHAPES = {"gate_up": (4096, 2560), "down": (1280, 4096)}       # the cell's two projections
RUN_OF_TILE = {16: 1.6, 32: 3.2, 64: 6.4, 128: 25.6}           # an expected run for each row tile the rule returns:
                                                               # 64 and 128 slots decoding, a 256- and a 1,024-token chunk
SIZES = {
    "runs_of_0_1_3_26_600": [0, 1, 3, 26, 600, 0],             # sum < M, the long run over many tiles
    "runs_straddle_tile_edges": [14, 5, 30, 17, 0, 63],        # starts at 14, 19, 49, 66: none on an edge of 16 to 128
    "several_groups_in_one_tile": [2, 3, 1, 4, 2, 1],
    "empty_groups_between_hit_ones": [0, 7, 0, 0, 9, 0],
    "sum_equals_M": [100, 24, 300, 0, 88, 512],
    "sum_is_zero": [0, 0, 0, 0, 0, 0],
}


@pytest.fixture
def interpret():
    prior = gm.set_interpret(True)
    yield
    gm.set_interpret(prior)


@functools.lru_cache(maxsize=None)
def _operands(which):
    K, N = SHAPES[which]
    key = jax.random.PRNGKey(K)
    lhs = jax.random.normal(key, (M, K), jnp.bfloat16)
    rhs = (jax.random.normal(jax.random.fold_in(key, 1), (G, K, N), jnp.float32) * 0.05).astype(jnp.bfloat16)
    return lhs, rhs


@functools.lru_cache(maxsize=None)
def _want(which, case):
    lhs, rhs = _operands(which)
    return np.asarray(jax.lax.ragged_dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32),
                                         jnp.asarray(SIZES[case], jnp.int32), precision="highest"))


@functools.lru_cache(maxsize=None)
def _kernel(expected_run):
    return jax.jit(functools.partial(gm.grouped_matmul, expected_run=expected_run))


@pytest.mark.parametrize("case", sorted(SIZES))
@pytest.mark.parametrize("tm", sorted(RUN_OF_TILE))
@pytest.mark.parametrize("which", sorted(SHAPES))
def test_grouped_matmul_is_ragged_dot_on_the_rows_of_a_group(interpret, which, tm, case):
    K, N = SHAPES[which]
    lhs, rhs = _operands(which)
    tiling = gm.plan(M, K, N, RUN_OF_TILE[tm], lhs.dtype)
    assert tiling[0] == tm and K % tiling[1] == 0 and N % tiling[2] == 0 and tiling[1] * tiling[2] * 2 <= gm._BLOCK_BYTES
    got = np.asarray(_kernel(RUN_OF_TILE[tm])(lhs, rhs, jnp.asarray(SIZES[case], jnp.int32)))
    n = sum(SIZES[case])                                       # rows past the last run are unspecified
    want = _want(which, case)
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got[:n], want[:n], rtol=0, atol=2e-5 * K ** 0.5)


@pytest.mark.parametrize("tk", [1280, 640, 256])
def test_a_split_contraction_accumulates_in_float32(interpret, tk):
    lhs, rhs = _operands("down")
    case = "runs_straddle_tile_edges"
    got = np.asarray(gm.grouped_matmul(lhs, rhs, jnp.asarray(SIZES[case], jnp.int32), expected_run=3.2, tiling=(16, tk, 512)))
    n = sum(SIZES[case])
    np.testing.assert_allclose(got[:n], _want("down", case)[:n], rtol=0, atol=2e-5 * 1280 ** 0.5)


@pytest.mark.parametrize("why,lhs,rhs,run", [
    ("float32 operands", ((64, 128), jnp.float32), ((4, 128, 128), jnp.float32), 3.0),
    ("rows the tile does not divide", ((72, 128), jnp.bfloat16), ((4, 128, 128), jnp.bfloat16), 40.0),
    ("a width that is no lane tile", ((64, 128), jnp.bfloat16), ((4, 128, 96), jnp.bfloat16), 3.0),
    ("a contraction that is no lane tile", ((64, 96), jnp.bfloat16), ((4, 96, 128), jnp.bfloat16), 3.0),
])
def test_the_kernel_declines(interpret, why, lhs, rhs, run):
    assert not gm.grouped_matmul_available(jax.ShapeDtypeStruct(*lhs), jax.ShapeDtypeStruct(*rhs), expected_run=run), why


def test_the_kernel_declines_on_the_cpu_and_under_a_mesh(monkeypatch):
    import paddle_tpu as paddle

    a, b = jax.ShapeDtypeStruct((64, 128), jnp.bfloat16), jax.ShapeDtypeStruct((4, 128, 128), jnp.bfloat16)
    assert not gm.grouped_matmul_available(a, b, expected_run=3.0)                 # the CPU
    monkeypatch.setattr(paddle.device, "is_tpu", lambda: True)
    assert gm.grouped_matmul_available(a, b, expected_run=3.0)
    monkeypatch.setattr(gm, "_under_mesh", lambda: True)
    assert not gm.grouped_matmul_available(a, b, expected_run=3.0)


def _two_layers(x, router, gate_up, down):
    """Two expert layers of one program, as a decode step has four."""
    for _ in range(2):
        w, idx = route_topk(x, router, top_k=4)
        y, _ = dropless_experts(x, w, idx, gate_up, down, held=(2, 4), n_experts=router.shape[-1])
        x = x + y.astype(x.dtype)
    return x


def test_one_selection_is_counted_per_compiled_program():
    rng = np.random.default_rng(0)
    D, F, E = 128, 128, 16
    router = jnp.asarray(rng.normal(size=(D, E)), jnp.bfloat16)
    gate_up = jnp.asarray(rng.normal(size=(4, D, 2 * F)) * 0.1, jnp.bfloat16)
    down = jnp.asarray(rng.normal(size=(4, F, D)) * 0.1, jnp.bfloat16)
    x = lambda T: jnp.asarray(rng.normal(size=(T, D)), jnp.bfloat16)  # noqa: E731
    count = lambda: {k.rsplit(".", 1)[1]: v for k, v in metrics.counters("kernels.grouped_matmul.").items()}  # noqa: E731
    registry.clear_cache("grouped_matmul")
    metrics.reset_counters("kernels.grouped_matmul.")
    prior = gm.set_interpret(True)
    try:
        jax.jit(_two_layers).lower(x(16), router, gate_up, down)
        assert count() == {"picked": 1, "fallback": 0}           # two layers, four grouped matmuls: one selection
        jax.jit(_two_layers).lower(x(64), router, gate_up, down)
        assert count() == {"picked": 2, "fallback": 0}           # a program of another batch: one more
        jax.jit(_two_layers).lower(x(64), router, gate_up, down)
        assert count() == {"picked": 2, "fallback": 0}           # the same program again: none
    finally:
        gm.set_interpret(prior)
    jax.jit(_two_layers).lower(x(32), router, gate_up, down)       # the CPU, no interpreter: the kernel declines
    assert count() == {"picked": 2, "fallback": 1}
    registry.clear_cache("grouped_matmul")
