"""EVA attention's decode step (``ops/eva_attention.py``): the Pallas kernel in
interpret mode against its ``lax`` form, and both against the step written
out in numpy — the ring row written, a closing chunk's summary, one softmax
over the live summary and ring rows — at live counts of none, of whole tiles
and of partial ones in each range; and the half-split rotation
(``ops/rope.py``) against its definition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import eva_attention as eva
from paddle_tpu.ops import registry, rope

L, B, H, W, R, d, CHUNK = 2, 5, 2, 32, 48, 128, 4


def _written_out(q, k, v, rk, rv, sk, sv, pos, active, layer, pool, n_sum, n_ring):
    """The step by its definition, float64: the buffers after it and the attention of every slot."""
    rk, rv, sk, sv = (np.array(a, np.float64) for a in (rk, rv, sk, sv))
    att = np.zeros((B, H, d))
    for b in range(B):
        if not active[b]:
            continue
        t = int(pos[b])
        rk[layer, b, :, t % W], rv[layer, b, :, t % W] = k[b], v[b]
        if t % CHUNK == CHUNK - 1:
            first = (t % W) // CHUNK * CHUNK
            kc, vc = rk[layer, b, :, first:first + CHUNK], rv[layer, b, :, first:first + CHUNK]       # [H, C, d]
            logits = np.einsum("hcd,hd->hc", kc, pool)
            alpha = np.exp(logits - logits.max(-1, keepdims=True))
            alpha /= alpha.sum(-1, keepdims=True)
            sk[layer, b, :, t // CHUNK] = np.einsum("hc,hcd->hd", alpha, kc)
            sv[layer, b, :, t // CHUNK] = np.einsum("hc,hcd->hd", alpha, vc)
        keys = np.concatenate([sk[layer, b, :, :n_sum[b]], rk[layer, b, :, :n_ring[b]]], axis=1)
        vals = np.concatenate([sv[layer, b, :, :n_sum[b]], rv[layer, b, :, :n_ring[b]]], axis=1)
        s = np.einsum("hd,hrd->hr", q[b], keys) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        att[b] = np.einsum("hr,hrd->hd", p / p.sum(-1, keepdims=True), vals)
    return att, rk, rv, sk, sv


# per slot: (position, summaries attended, ring rows attended, active). Slot 0 closes a chunk and attends no summary;
# 1 a partial tile of each range; 2 whole tiles of both and closes a chunk; 3 is inactive; 4 a summary count past one
# streamed block and the ring's last row, closing the window's last chunk
CASES = [(3, 0, 4, True), (45, 13, 14, True), (71, 16, 8, True), (50, 9, 5, False), (63, 22, 32, True)]


def _inputs(dtype, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    q, k, v = f(B, H, d), f(B, H, d), f(B, H, d)
    rk, rv, sk, sv = f(L, B, H, W, d), f(L, B, H, W, d), f(L, B, H, R, d), f(L, B, H, R, d)
    pool = 0.2 * f(H, d)
    pos, n_sum, n_ring, active = (np.asarray(c) for c in zip(*CASES))
    as_dt = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    return ((as_dt(q), as_dt(k), as_dt(v), as_dt(rk), as_dt(rv), as_dt(sk), as_dt(sv), jnp.asarray(pos, jnp.int32),
             jnp.asarray(active), jnp.int32(1), jnp.asarray(pool), jnp.asarray(n_sum, jnp.int32), jnp.asarray(n_ring, jnp.int32)),
            dict(pos=pos, n_sum=n_sum, n_ring=n_ring, active=active))


@pytest.fixture
def interpret():
    prior = eva.set_interpret(True)
    registry.clear_cache("eva_decode")
    yield
    eva.set_interpret(prior)
    registry.clear_cache("eva_decode")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_and_lax_form_agree_with_the_step_written_out(interpret, dtype):
    args, meta = _inputs(dtype)
    as64 = lambda a: np.asarray(jnp.asarray(a, jnp.float32), np.float64)  # noqa: E731
    # the written-out step sees the rows as the buffers hold them (the new row rounded to their dtype)
    want = _written_out(*(as64(a) for a in args[:7]), meta["pos"], meta["active"], 1, as64(args[10]), meta["n_sum"], meta["n_ring"])
    assert registry.select("eva_decode", *args, chunk=CHUNK).name == "pallas_aliased"
    outs = {"pallas": jax.jit(lambda *a: eva.eva_decode_pallas(*a, chunk=CHUNK))(*args),
            "lax": jax.jit(lambda *a: eva.eva_decode_lax(*a, chunk=CHUNK))(*args)}
    tol = 2e-6 if dtype == "float32" else 2e-2
    for name, got in outs.items():
        att, *bufs = (as64(a) for a in got)
        scale = np.abs(want[0]).max()
        assert np.abs(att - want[0]).max() < tol * 10 * scale, name
        for g, w, orig in zip(bufs, want[1:], args[3:7]):
            assert np.abs(g - w).max() <= tol * np.abs(w).max(), name
            # every row the step did not write is bitwise as it was: the other layer, the inactive slot, other rows
            touched = np.abs(g - as64(orig)).max(axis=-1) > 0
            assert not touched[0].any() and not touched[1, 3].any(), name
    # the kernel and the lax form: the same ring bitwise; the summaries and the attention to rounding (sums in another
    # order, an online softmax against one)
    for a, b in zip(outs["pallas"][1:3], outs["lax"][1:3]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(outs["pallas"][3:], outs["lax"][3:]):
        assert np.abs(as64(a) - as64(b)).max() <= tol * np.abs(as64(b)).max()
    assert np.abs(as64(outs["pallas"][0]) - as64(outs["lax"][0])).max() < tol * 10 * np.abs(want[0]).max()
    assert np.all(as64(outs["pallas"][0])[3] == 0) and np.all(as64(outs["lax"][0])[3] == 0)            # an inactive slot gives zeros


def test_a_summary_is_written_only_where_a_chunk_closes(interpret):
    args, meta = _inputs("float32", seed=1)
    _, _, _, sk, _ = jax.jit(lambda *a: eva.eva_decode_pallas(*a, chunk=CHUNK))(*args)
    changed = np.argwhere(np.abs(np.asarray(sk) - np.asarray(args[5])).max(axis=(2, 4)) > 0)
    closing = [(1, b, p // CHUNK) for b, (p, _, _, a) in enumerate(CASES) if a and p % CHUNK == CHUNK - 1]
    assert sorted(map(tuple, changed)) == closing


def test_the_registry_picks_the_lax_form_off_the_tpu():
    registry.clear_cache("eva_decode")
    args, _ = _inputs("bfloat16")
    assert registry.select("eva_decode", *args, chunk=CHUNK).name == "lax"
    assert registry.implementations("eva_decode") == ["pallas_aliased", "lax"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_half_split_rotation_is_its_definition(dtype):
    rng = np.random.default_rng(4)
    dim, theta = 16, 100000.0
    x = rng.standard_normal((6, 3, dim)).astype(np.float32)
    positions = np.array([0, 1, 7, 2047, 2048, 32767])
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = positions[:, None, None] * inv                                                     # [6, 1, dim/2]
    want = np.empty_like(x, dtype=np.float64)
    for i in range(dim // 2):                                                                  # pair (i, i + dim/2)
        a, b = x[..., i], x[..., i + dim // 2]
        want[..., i] = a * np.cos(angle[..., i]) - b * np.sin(angle[..., i])
        want[..., i + dim // 2] = b * np.cos(angle[..., i]) + a * np.sin(angle[..., i])
    cos, sin = rope.rope_angles(jnp.asarray(positions), rope.yarn_inv_freq(dim, theta))
    registry.clear_cache("rope")
    got = rope.rotate(jnp.asarray(x, dtype), cos[:, None], sin[:, None], pairs="half")
    assert got.dtype == jnp.dtype(dtype)
    # float32 angles: one rounding of t * inv_freq, 2e-3 rad at the last position for the fastest pair
    assert np.abs(np.asarray(got, np.float64) - want).max() < (6e-3 if dtype == "float32" else 3e-2)
    assert registry.select("rope", jnp.asarray(x, dtype), cos[:, None], sin[:, None], pairs="half").name == "lax_half_split"
    # the interleaved pairs are another rotation, and remain the default
    inter = rope.rotate(jnp.asarray(x), cos[:, None], sin[:, None])
    assert registry.select("rope", jnp.asarray(x), cos[:, None], sin[:, None]).name == "lax_interleaved"
    assert np.abs(np.asarray(inter) - want).max() > 0.1
    with pytest.raises(ValueError):
        rope.rotate(jnp.asarray(x), cos[:, None], sin[:, None], pairs="adjacent")
