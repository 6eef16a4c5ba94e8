"""The aliased decode-attention kernel (``ops/decode_attention.py``) in
interpret mode against the lax formulation it replaces, which this file keeps
as its own plain reference; and the registry's choice between the two.

Interpret mode checks the kernel's math and its DMA bookkeeping (which block
is fetched, which tile is written back, what stays untouched). What the
chip's compiler says of it — layouts, VMEM, the alias — is
``tests/test_chip_compile.py``'s part.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import gpt
from paddle_tpu.observability import metrics
from paddle_tpu.ops import decode_attention as da
from paddle_tpu.ops import registry


@pytest.fixture(autouse=True)
def _no_mesh_left_over():
    """The kernel declines under a fleet mesh, and the registry remembers its choice by whether there is one: none
    may be left over from an earlier test file of this worker (several initialise the fleet and leave it so)."""
    from paddle_tpu.distributed import fleet

    prev, fleet._hcg = fleet._hcg, None
    registry.clear_cache("decode_attention")
    yield
    fleet._hcg = prev
    registry.clear_cache("decode_attention")


@pytest.fixture
def interpret():
    prior = da.set_interpret(True)
    yield
    da.set_interpret(prior)


def _reference(q, k, v, cache_k, cache_v, pos, active, layer):
    """Plain lax: cut the layer out, write each slot's window at its
    position where the slot is active, attend row j up to ``pos + j`` with an
    f32 softmax, put the layer back."""
    lk, lv = cache_k[layer], cache_v[layer]

    def write(c, u, p, a):
        cur = jax.lax.dynamic_slice(c, (0, p, 0), u.shape)
        return jax.lax.dynamic_update_slice(c, jnp.where(a, u, cur), (0, p, 0))

    lk = jax.vmap(write)(lk, k, pos, active)
    lv = jax.vmap(write)(lv, v, pos, active)
    b, _, W, dh = q.shape
    S = lk.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q * jnp.asarray(1.0 / dh ** 0.5, q.dtype), lk,
                        preferred_element_type=jnp.float32)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (b, W, S), 2)
    q_pos = pos[:, None, None] + jax.lax.broadcasted_iota(jnp.int32, (b, W, S), 1)
    scores = jnp.where((k_pos <= q_pos)[:, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(lv.dtype)
    att = jnp.einsum("bhqk,bhkd->bhqd", p, lv, preferred_element_type=jnp.float32)
    return att.astype(q.dtype), cache_k.at[layer].set(lk), cache_v.at[layer].set(lv)


def _case(L, B, H, S, dh, W, dtype, seed=0):
    rng = np.random.default_rng(seed)
    make = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    return (make(B, H, W, dh), make(B, H, W, dh), make(B, H, W, dh),
            make(L, B, H, S, dh), make(L, B, H, S, dh))


def _both(q, k, v, ck, cv, pos, active, layer):
    pos, active = jnp.asarray(pos, jnp.int32), jnp.asarray(active)
    want = _reference(q, k, v, ck, cv, pos, active, layer)
    got = jax.jit(da.decode_attention, static_argnums=7)(q, k, v, ck, cv, pos, active, layer)
    return want, got, np.asarray(active)


# depths: 0, the last row, inside the first block, a block's last row and the
# next block's first, one that is no multiple of any tile; the last slot idle
_DEPTHS = {1: [0, 511, 37, 255, 256, 300], 4: [0, 508, 14, 253, 126, 300]}


@pytest.mark.parametrize("window", [1, 4])
@pytest.mark.parametrize("head", [64, 128])
def test_kernel_matches_the_lax_formulation(interpret, monkeypatch, head, window):
    """bf16, heads 64 (S in the lanes: the kernel sees the cache transposed)
    and 128, W=1 and a W=4 window that straddles a tile and a block; blocks
    of 256 rows so that a slot spans more than one."""
    monkeypatch.setattr(da, "_BLOCK_BYTES", 2 * 256 * head * 2)
    H, S = 2, 512
    assert da._plan(H, S, head, jnp.bfloat16)[1] == 256
    q, k, v, ck, cv = _case(3, 6, H, S, head, window, jnp.bfloat16)
    (att0, k0, v0), (att1, k1, v1), live = _both(
        q, k, v, ck, cv, _DEPTHS[window], [True] * 5 + [False], layer=1)
    assert bool((k0 == k1).all()) and bool((v0 == v1).all())       # every row of every layer, bitwise
    assert bool((k1[1, 5] == ck[1, 5]).all()) and bool((v1[1, 5] == cv[1, 5]).all())   # the idle slot
    assert not bool((k1[1, :5] == ck[1, :5]).all())                # and the live ones were written
    err = np.abs(np.asarray(att0, np.float32) - np.asarray(att1, np.float32))[live]
    assert err.max() <= 2.0 ** -6, err.max()      # outputs of size ~1 in bf16, the softmax normalised in another order
    assert not np.asarray(att1, np.float32)[~live].any()            # an idle slot attends nothing


@pytest.mark.parametrize("head", [64, 128])
def test_kernel_one_slot_wide(interpret, head):
    """B=1: the benchmark's probe attends a cache one slot wide."""
    q, k, v, ck, cv = _case(2, 1, 4, 256, head, 1, jnp.bfloat16, seed=1)
    (att0, k0, v0), (att1, k1, v1), _ = _both(q, k, v, ck, cv, [205], [True], layer=0)
    assert bool((k0 == k1).all()) and bool((v0 == v1).all())
    np.testing.assert_allclose(np.asarray(att1, np.float32), np.asarray(att0, np.float32), atol=2.0 ** -7)


def test_kernel_float32_and_no_gate(interpret):
    """float32 caches (8-row tiles) and ``active=None``: every slot written."""
    q, k, v, ck, cv = _case(2, 3, 2, 128, 128, 2, jnp.float32, seed=2)
    pos = jnp.asarray([0, 126, 77], jnp.int32)
    want = _reference(q, k, v, ck, cv, pos, jnp.ones((3,), bool), 1)
    got = jax.jit(da.decode_attention, static_argnums=7)(q, k, v, ck, cv, pos, None, 1)
    assert bool((want[1] == got[1]).all()) and bool((want[2] == got[2]).all())
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ registry
_BF16 = jax.ShapeDtypeStruct((2, 4, 4, 256, 64), jnp.bfloat16)


def _choice(cache=_BF16, packed=False, window=1):
    registry.clear_cache("decode_attention")
    try:
        return registry.select("decode_attention", cache, packed=packed, window=window).name
    finally:
        registry.clear_cache("decode_attention")


@pytest.mark.parametrize("where,want", [
    ("cpu", "xla"), ("tpu", "pallas_aliased"), ("interpret", "pallas_aliased"),
    ("tpu_pack", "xla"), ("tpu_mesh", "xla"), ("tpu_ragged", "xla"), ("tpu_int8", "xla")])
def test_registry_choice(monkeypatch, where, want):
    """The kernel on a TPU (or under the interpreter) for a plain-array cache
    it can tile, with no mesh; the lax formulation for everything else — the
    CPU, an int8 pack, a mesh, a context that is no multiple of the tile (an
    engine with a draft model reserves S + spec_k rows)."""
    from paddle_tpu.distributed import fleet

    if where.startswith("tpu"):
        monkeypatch.setattr(paddle.device, "is_tpu", lambda: True)
    if where == "interpret":
        monkeypatch.setattr(da, "_INTERPRET", True)
    if where == "tpu_mesh":
        monkeypatch.setattr(type(fleet), "multi_device_mesh", property(lambda self: object()))
    cache = {"tpu_ragged": jax.ShapeDtypeStruct((2, 4, 4, 260, 64), jnp.bfloat16),
             "tpu_int8": jax.ShapeDtypeStruct(_BF16.shape, jnp.int8)}.get(where, _BF16)
    assert _choice(cache, packed=where == "tpu_pack") == want


def _tiny_engine(**kw):
    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(7)
    model = GPTForPretraining(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                                        max_seq_len=128))
    model.eval()
    return DecodeEngine(model, max_batch_slots=3, max_seq_len=128, prefill_chunk=16, **kw)


@pytest.mark.parametrize("fuse", [1, 3])
def test_engine_serves_the_same_tokens_through_the_kernel(fuse):
    """The engine's decode program (``decode_fn``, and the fused scan over the
    same body) with the kernel in it serves the tokens of the lax program,
    counts one ``picked`` selection per compiled program, and leaves the
    lax program's rows in the cache: the first layer's bitwise (the
    projection that makes them is not the kernel's), the later layers' within
    float32 rounding of an attention output normalised in another order."""
    ids = np.random.default_rng(3).integers(0, 97, (3, 21)).astype(np.int32)
    registry.clear_cache("decode_attention")
    plain = _tiny_engine(fuse=fuse)
    want = plain.generate(ids, max_new_tokens=12)
    prior = da.set_interpret(True)
    try:
        metrics.reset_counters("kernels.decode_attention.")
        fused = _tiny_engine(fuse=fuse)
        got = fused.generate(ids, max_new_tokens=12)
        counts = metrics.counters("kernels.decode_attention.")
    finally:
        da.set_interpret(prior)
        registry.clear_cache("decode_attention")
    np.testing.assert_array_equal(got, want)
    assert counts["kernels.decode_attention.picked"] == 1
    assert counts.get("kernels.decode_attention.fallback", 0) == 0
    np.testing.assert_array_equal(np.asarray(fused._ck[0, :, :, :32]), np.asarray(plain._ck[0, :, :, :32]))
    np.testing.assert_array_equal(np.asarray(fused._cv[0, :, :, :32]), np.asarray(plain._cv[0, :, :, :32]))
    np.testing.assert_allclose(np.asarray(fused._ck[1, :, :, :32]), np.asarray(plain._ck[1, :, :, :32]),
                               rtol=1e-4, atol=1e-5)


def test_int8_pack_keeps_the_lax_program(interpret):
    """A ``kv_dtype="int8"`` engine declines even where the kernel is on."""
    registry.clear_cache("decode_attention")
    metrics.reset_counters("kernels.decode_attention.")
    engine = _tiny_engine(kv_dtype="int8")
    engine.generate(np.arange(10, dtype=np.int32)[None], max_new_tokens=4)
    counts = metrics.counters("kernels.decode_attention.")
    assert counts.get("kernels.decode_attention.picked", 0) == 0
    assert counts["kernels.decode_attention.fallback"] >= 1
    assert gpt._decode_attention_impl(engine._ck, 1).fallback
