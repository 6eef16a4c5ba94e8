"""GigaChat 3.5 through the serving engine, against the plain reference of
``benchmark/families/gigachat3_5.py``: tiny widths, seeded weights, float32,
the CPU. The uncut tiny model and one of its sixteen shares run the same code."""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.models import gigachat3_5 as g35
from paddle_tpu.ops import mla_attention, rope
from paddle_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step
from paddle_tpu.ops.moe_dropless import dropless_experts, gated_ffn, route_topk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARES = 16
UNCUT = {
    "family": "gigachat3_5", "source": "test", "model_type": "gigachat3_5",
    "vocab_size": 128, "max_position_embeddings": 512, "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_attention_heads": 4, "n_shared_experts": 1, "n_routed_experts": 32, "routed_scaling_factor": 2.5,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "norm_topk_prob": True, "rope_interleave": True,
    "rms_norm_eps": 1e-6, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "layernorm_gating_weight": 2, "use_mla_scaling_factor": True, "full_attention_layers": [1],
    "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-6, "swiglu_limit": 10,
    "reduced": [],
}
SHARE = dict(UNCUT, vocab_size=8, n_routed_experts=2, held_experts=[6, 2], reduced=["vocab_size", "n_routed_experts"],
             published={"n_routed_experts": 32, "vocab_size": 128},
             deployment="16 chips share every expert layer's experts and the vocabulary: 2 of 32 experts, 8 of 128 rows each")
CONFIGS = {"uncut": UNCUT, "share": SHARE}


@pytest.fixture(scope="module")
def family():
    return manifest.load_module(REPO, "benchmark", "families", "gigachat3_5")


@pytest.fixture(scope="module")
def models(family):
    """The uncut tiny model, and share 3 of 16 cut out of *its* weights."""
    full = g35.GigaChat35ForCausalLM(g35.GigaChat35Config.from_config_file(UNCUT), seed=11, dtype="float32")
    cut = family.share_weights(family.dims(UNCUT), full.weights, 3, SHARES)
    on_device = lambda v: tuple(jnp.asarray(a) for a in v) if isinstance(v, tuple) else jnp.asarray(v)  # noqa: E731
    share = g35.GigaChat35ForCausalLM(g35.GigaChat35Config.from_config_file(SHARE), weights={k: on_device(v) for k, v in cut.items()})
    return {"uncut": full, "share": share}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def test_reference_is_independent_of_the_program(family):
    text = open(family.__file__).read()
    body = text[text.index("# ---------------------------------------------------------------- reference"):
                text.index("# ---------------------------------------------------------------- required bytes")]
    assert "paddle_tpu" not in body and '"highest"' in body
    assert family.share_dims(family.dims(UNCUT), 3, SHARES) == family.dims(SHARE)
    cfg = g35.GigaChat35Config.from_config_file(SHARE)
    assert cfg.weight_shapes() == family.weight_shapes(SHARE)                  # one layout, written twice
    assert cfg.softmax_scale() == pytest.approx(family.softmax_scale(family.dims(SHARE))) == pytest.approx(24 ** -0.5 * 1.2079 ** 2, rel=1e-4)
    np.testing.assert_allclose(cfg.inv_freq(), family.rope_inv_freq(family.dims(SHARE)), rtol=1e-6)


# ------------------------------------------------ (a) the system against the reference
@pytest.mark.parametrize("path", ["bucketed", "chunked_padded_final", "engine_prefill_decode"])
@pytest.mark.parametrize("which", ["uncut", "share"])
def test_system_logits_agree_with_the_reference(family, models, which, path):
    config, model = CONFIGS[which], models[which]
    z = family.dims(config)
    rng = np.random.default_rng(5)
    n = 37
    ids = rng.integers(0, z["V"], (n + 6,)).astype(np.int32)
    want = np.asarray(family.reference_logits(config, model.weights, ids))
    dec = model.decoder()
    p = dec.params()
    cache = dec.alloc(3, 64)
    if path == "bucketed":
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n] = ids[:n]
        last, _ = dec.prefill(p, cache, jnp.asarray(padded), jnp.int32(n), jnp.int32(1))
        assert _rel(last[0], want[n - 1]) < 2e-5
        assert _rel(np.asarray(model(ids[None])._value)[0], want) < 2e-5          # the model's own forward too
    elif path == "chunked_padded_final":
        C = 16                                                                     # 37 = 16 + 16 + 5 of a padded 16
        for start in (0, 16):
            _, cache = dec.chunk(p, cache, jnp.asarray(ids[None, start:start + C]), jnp.int32(2), jnp.int32(start))
        final = np.zeros((1, C), np.int32)
        final[0, :n - 32] = ids[32:n]
        last, cache = dec.chunk(p, cache, jnp.asarray(final), jnp.int32(2), jnp.int32(32), last_row=jnp.int32(n - 33))
        assert _rel(last[0], want[n - 1]) < 2e-5
        # the padding of the final chunk left state and tail alone, and its rows lie past the slot's position: a decode step agrees
        step, _, _ = dec.decode(p, cache, jnp.asarray([0, 0, ids[n]], jnp.int32), jnp.asarray([0, 0, n], jnp.int32),
                                jnp.asarray([False, False, True]))
        assert _rel(step[2], want[n]) < 2e-5
    else:
        engine = DecodeEngine(model, max_batch_slots=3, max_seq_len=64, prefill_chunk=16)
        first, _ = engine.prefill(ids[:n], 1, max_new_tokens=8)
        served = [int(first)]
        for _ in range(5):
            toks, _, _ = engine.decode_step()
            served.append(int(toks[1]))
        seq = np.concatenate([ids[:n], np.asarray(served, np.int32)])
        rows = np.asarray(family.reference_logits(config, model.weights, seq))[n - 1:-1]
        assert [int(np.argmax(r)) for r in rows] == served


# ------------------------------------------------ (b) the share adds up
def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_reference_layer(family, models):
    """Over all sixteen shares of one tiny expert layer: the routed partial
    results, with the shared expert and the router counted once, sum to what
    the uncut reference gives for the whole layer — clamps and scale included."""
    z = dict(family.dims(UNCUT), limit=0.3)                               # a limit low enough to bind at these weights
    full = models["uncut"].weights
    rng = np.random.default_rng(9)
    x = rng.normal(size=(24, z["D"])).astype(np.float32)
    layer = 2
    lw = {k: full[k][layer] for k in ("router", "experts_gate_up", "experts_down", "shared_gate_up", "shared_down")}
    with jax.default_matmul_precision("highest"):
        want, _ = family.reference_moe(z, lw, jnp.asarray(x))
        unclamped, _ = family.reference_moe(dict(z, limit=None), lw, jnp.asarray(x))
        total = np.zeros_like(np.asarray(want))
        for share in range(SHARES):
            cfg = g35.GigaChat35Config.from_config_file(dict(SHARE, held_experts=[share * 2, 2], swiglu_limit=z["limit"]))
            w = family.share_weights(z, full, share, SHARES)
            weights, experts = route_topk(jnp.asarray(x), full["router"][layer], top_k=cfg.num_experts_per_tok,
                                          scale=cfg.routed_scaling_factor)
            y, _ = dropless_experts(jnp.asarray(x), weights, experts, jnp.asarray(w["experts_gate_up"][layer]),
                                    jnp.asarray(w["experts_down"][layer]), held=cfg.held_experts, n_experts=cfg.router_experts,
                                    limit=cfg.swiglu_limit)
            total += np.asarray(y)
        total += np.asarray(gated_ffn(jnp.asarray(x), full["shared_gate_up"][layer], full["shared_down"][layer], z["limit"]))
    assert _rel(total, want) < 2e-5
    assert _rel(unclamped, want) > 1e-3                                   # the clamp is computed, and here it binds


def test_the_clamp_of_a_gated_ffn_defaults_to_none_and_clamps_both_factors():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(6, 8)) * 5, jnp.float32)
    gate_up, down = jnp.asarray(rng.normal(size=(8, 10)), jnp.float32), jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    h = np.asarray(x) @ np.asarray(gate_up)
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    want = (silu(np.minimum(h[:, :5], 3.0)) * np.clip(h[:, 5:], -3.0, 3.0)) @ np.asarray(down)
    with jax.default_matmul_precision("highest"):
        assert _rel(gated_ffn(x, gate_up, down, 3.0), want) < 1e-5
        assert _rel(gated_ffn(x, gate_up, down), (silu(h[:, :5]) * h[:, 5:]) @ np.asarray(down)) < 1e-5


# ------------------------------------------------ (c) latent attention: absorbed = un-absorbed; the kernel = lax
def _latent_case(seed, B=3, S=64, H=4, rank=16, dr=8, dn=16, dv=16, width=None):
    rng = np.random.default_rng(seed)
    width = width or rank + dr
    cache = np.zeros((B, S, width), np.float32)
    cache[:, :, :rank + dr] = rng.normal(size=(B, S, rank + dr))
    return dict(rng=rng, cache=cache, q_nope=rng.normal(size=(B, H, dn)).astype(np.float32),
                q_rope=rng.normal(size=(B, H, dr)).astype(np.float32), row=rng.normal(size=(B, rank + dr)).astype(np.float32),
                w_uk=rng.normal(size=(rank, H, dn)).astype(np.float32), w_uv=rng.normal(size=(rank, H, dv)).astype(np.float32),
                pos=np.asarray([5, 40, 63, 33][:B], np.int32), rank=rank, dr=dr, width=width)


def test_absorbed_decode_equals_unabsorbed_attention():
    """``q' = W_uk^T q_nope`` over the cached latents and ``W_uv`` after the
    weighted sum, against keys and values expanded from the latents."""
    c = _latent_case(1)
    rank, dr, B = c["rank"], c["dr"], 3
    active = np.asarray([True, True, True])
    with jax.default_matmul_precision("highest"):
        q = jnp.concatenate([jnp.einsum("bhd,chd->bhc", c["q_nope"], c["w_uk"]), jnp.asarray(c["q_rope"])], axis=-1)
        o_lat, cache = mla_attention.latent_decode_lax(q, jnp.asarray(c["row"]), jnp.asarray(c["cache"]), jnp.asarray(c["pos"]),
                                                       jnp.asarray(active), rank=rank)
        got = np.asarray(jnp.einsum("bhc,chd->bhd", o_lat, c["w_uv"]))
        for b in range(B):
            n = int(c["pos"][b]) + 1
            rows = np.array(c["cache"][b, :n])
            rows[n - 1] = c["row"][b]                                     # write before attend
            k_nope, v = np.einsum("sc,chd->shd", rows[:, :rank], c["w_uk"]), np.einsum("sc,chd->shd", rows[:, :rank], c["w_uv"])
            scores = np.einsum("hd,shd->hs", c["q_nope"][b], k_nope) + np.einsum("hr,sr->hs", c["q_rope"][b], rows[:, rank:])
            prob = np.exp(scores - scores.max(-1, keepdims=True))
            want = np.einsum("hs,shd->hd", prob / prob.sum(-1, keepdims=True), v)
            assert _rel(got[b], want) < 2e-5
            np.testing.assert_array_equal(np.asarray(cache[b, n - 1]), c["row"][b])


@pytest.mark.parametrize("dtype,width", [("float32", 24), ("float32", 128), ("bfloat16", 128)])
def test_latent_decode_kernel_is_the_lax_form_and_leaves_an_inactive_slot_alone(dtype, width):
    """Interpret mode: rows padded to lanes or not, a slot in the first block,
    one past a block's edge, one at the context's last row, one inactive."""
    from paddle_tpu.observability import metrics

    c = _latent_case(2, B=4, S=128, width=width)
    rank = c["rank"]
    dt = jnp.dtype(dtype)
    pos, active = jnp.asarray([5, 64, 127, 33], jnp.int32), jnp.asarray([True, True, True, False])
    pad = lambda a: jnp.pad(jnp.asarray(a), ((0, 0),) * (a.ndim - 1) + ((0, width - a.shape[-1]),)).astype(dt)  # noqa: E731
    q = pad(np.concatenate([c["rng"].normal(size=(4, 4, rank)).astype(np.float32), c["q_rope"]], axis=-1) * 0.3)
    row, cache = pad(c["row"]), jnp.asarray(c["cache"], dt)
    want_o, want_cache = mla_attention.latent_decode_lax(q, row, cache, pos, active, rank=rank)
    metrics.reset_counters("kernels.mla_decode.")
    prior = mla_attention.set_interpret(True)
    try:
        got_o, got_cache = mla_attention.decode(q, row, cache, pos, active, rank=rank)
    finally:
        mla_attention.set_interpret(prior)
    assert metrics.counters("kernels.mla_decode.")["kernels.mla_decode.picked"] == 1
    assert _rel(got_o.astype(jnp.float32), want_o.astype(jnp.float32)) < (2e-5 if dtype == "float32" else 2 ** -7)
    np.testing.assert_array_equal(np.asarray(got_cache.astype(jnp.float32)), np.asarray(want_cache.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(got_cache[3].astype(jnp.float32)), np.asarray(cache[3].astype(jnp.float32)))
    assert not np.asarray(got_o[3].astype(jnp.float32)).any()


def test_blocked_prefill_is_whole_context_attention():
    """A chunk at ``start`` against the slot's rows, in blocks of 16 of a
    context of 64: the un-absorbed scores of the whole context at once."""
    c = _latent_case(3)
    rank, C, start, slot = c["rank"], 16, 32, 1
    q_nope, q_rope = (c["rng"].normal(size=(C, 4, d)).astype(np.float32) for d in (16, c["dr"]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mla_attention.latent_prefill(jnp.asarray(q_nope), jnp.asarray(q_rope), jnp.asarray(c["cache"]), jnp.asarray(c["w_uk"]),
                                                      jnp.asarray(c["w_uv"]), jnp.int32(slot), jnp.int32(start), rank=rank, block=16))
    rows = c["cache"][slot, :start + C]
    k_nope, v = np.einsum("sc,chd->shd", rows[:, :rank], c["w_uk"]), np.einsum("sc,chd->shd", rows[:, :rank], c["w_uv"])
    scores = np.einsum("qhd,shd->hqs", q_nope, k_nope) + np.einsum("qhr,sr->hqs", q_rope, rows[:, rank:])
    scores = np.where((np.arange(start + C)[None] <= start + np.arange(C)[:, None])[None], scores, -np.inf)
    prob = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("hqs,shd->qhd", prob / prob.sum(-1, keepdims=True), v)
    assert _rel(got, want) < 2e-5


# ------------------------------------------------ (d) rotary positions
def test_yarn_frequencies_and_the_interleaved_rotation():
    inv = rope.yarn_inv_freq(64, 1e5, 8, 32, 1, 32768)
    plain = 1e5 ** (-np.arange(0, 64, 2) / 64)
    turns = 32768 * plain / (2 * math.pi)                                  # over the original context
    assert inv.dtype == np.float32 and inv.shape == (32,)
    np.testing.assert_allclose(inv[turns > 40], plain[turns > 40], rtol=1e-6)             # fast pairs keep their frequency
    np.testing.assert_allclose(inv[turns < 0.9], plain[turns < 0.9] / 8, rtol=1e-6)       # slow pairs are stretched by the factor
    assert np.all(np.diff(inv) < 0) and np.all(inv <= plain * (1 + 1e-6)) and np.all(inv >= plain / 8 * (1 - 1e-6))
    np.testing.assert_allclose(rope.yarn_inv_freq(64, 1e5), plain, rtol=1e-6)
    assert rope.yarn_mscale(8, 1) == pytest.approx(1.2079, abs=1e-4) and rope.yarn_mscale(1, 1) == 1.0
    # pairs (2i, 2i + 1) turn by t * inv_freq[i]: complex multiplication, and a dot product sees t - s only
    rng = np.random.default_rng(0)
    x, y = rng.normal(size=(2, 5, 3, 64)).astype(np.float32)
    t, s = np.asarray([0, 1, 7, 100, 3000]), np.asarray([3, 4, 10, 103, 3003])
    rx = np.asarray(rope.rotate(jnp.asarray(x), *(a[:, None] for a in rope.rope_angles(jnp.asarray(t), inv))))
    want = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(1j * t[:, None, None] * inv.astype(np.float64))
    np.testing.assert_allclose(rx[..., 0::2], want.real, atol=2e-3)       # a float32 angle of 3,000 rad is good to 2e-4
    np.testing.assert_allclose(rx[..., 1::2], want.imag, atol=2e-3)
    np.testing.assert_allclose(rx[:3], np.stack([want.real, want.imag], -1).reshape(x.shape)[:3], atol=2e-5)
    ry0 = np.asarray(rope.apply_rope(jnp.asarray(y), *(a[:, None] for a in rope.rope_angles(jnp.asarray(t), inv))))
    ry3 = np.asarray(rope.apply_rope(jnp.asarray(y), *(a[:, None] for a in rope.rope_angles(jnp.asarray(s), inv))))
    rx3 = np.asarray(rope.apply_rope(jnp.asarray(x), *(a[:, None] for a in rope.rope_angles(jnp.asarray(s), inv))))
    np.testing.assert_allclose((rx * ry0).sum(-1), (rx3 * ry3).sum(-1), atol=2e-3)
    np.testing.assert_array_equal(rx[0], x[0])                             # position 0 turns nothing


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_cached_rows_do_not_depend_on_where_a_prompt_is_split(family, models, chunk):
    """The rotated key is cached: a prompt fed in chunks of 8, 16 or 32 leaves
    the rows the reference computes for the whole prompt (position ``start +
    i`` in a chunk), and a decode step at the slot's position appends its own."""
    model = models["share"]
    z = family.dims(SHARE)
    ids = np.random.default_rng(8).integers(0, z["V"], (49,)).astype(np.int32)
    ref = family.reference_forward(SHARE, model.weights, ids)
    engine = DecodeEngine(model, max_batch_slots=2, max_seq_len=64, prefill_chunk=chunk)
    engine.prefill(ids[:48], 1, max_new_tokens=4)
    engine._tok = engine._tok.at[1].set(int(ids[48]))                      # the step consumes the reference's 49th token
    engine.decode_step()
    rows = np.asarray(engine._cache[0][1, :49, :z["rank"] + z["dr"]])
    assert _rel(rows[:, z["rank"]:], np.asarray(ref["latent"])[:, z["rank"]:]) < 2e-5      # the rotated key
    assert _rel(rows[:, :z["rank"]], np.asarray(ref["latent"])[:, :z["rank"]]) < 2e-5      # the normalised latent
    assert not np.asarray(engine._cache[0][1, :, z["rank"] + z["dr"]:]).any()              # the lanes' padding stays zero
    assert engine._specs[0].shape == (2, 64, 128) and engine.latent_bytes_per_slot() == engine.kv_bytes_per_slot() == 64 * 128 * 4


# ------------------------------------------------ (e) the delta rule: scalar decay, shared key heads
@pytest.mark.parametrize("tokens,chunk,decay_hi", [(192, 64, 1.6), (48, 16, 8.0), (64, 64, 0.05)])
def test_scalar_decay_and_shared_key_heads_equal_the_per_channel_form_given_a_broadcast_decay(tokens, chunk, decay_hi):
    rng = np.random.default_rng(tokens + chunk)
    Hk, Hv, dk, dv = 2, 6, 16, 24
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q, k = (unit(rng.normal(size=(Hk, tokens, dk))).astype(np.float32) for _ in range(2))
    v = rng.normal(size=(Hv, tokens, dv)).astype(np.float32)
    log_alpha = -rng.uniform(1e-3, decay_hi, size=(Hv, tokens, 1)).astype(np.float32)
    beta = rng.uniform(0, 1, size=(Hv, tokens)).astype(np.float32)
    state = rng.normal(size=(Hv, dk, dv)).astype(np.float32)
    every = lambda a: np.repeat(a, Hv // Hk, axis=0)  # noqa: E731     a key head, then its value heads
    wide = np.broadcast_to(log_alpha, (Hv, tokens, dk))
    want_o, want_s = delta_rule_chunked(every(q), every(k), v, wide, beta, state, chunk=chunk)
    got_o, got_s = delta_rule_chunked(q, k, v, log_alpha, beta, state, chunk=chunk)
    assert _rel(got_o, want_o) < 1e-5 and _rel(got_s, want_s) < 1e-5
    s, w, outs = jnp.asarray(state), jnp.asarray(state), []
    for t in range(tokens):
        o, s = delta_rule_step(q[:, t], k[:, t], v[:, t], log_alpha[:, t], beta[:, t], s)
        o_wide, w = delta_rule_step(every(q)[:, t], every(k)[:, t], v[:, t], wide[:, t], beta[:, t], w)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(o_wide))   # a broadcast is the same arithmetic
        outs.append(np.asarray(o))
    assert _rel(got_o, np.stack(outs, 1)) < 1e-5 and _rel(got_s, s) < 1e-5
    # alpha = 1 and beta = 0 leave the state bitwise alone
    _, s3 = delta_rule_chunked(q, k, v, np.zeros_like(log_alpha), np.zeros_like(beta), state, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(s3), state)


# ------------------------------------------------ (f) slots
@pytest.mark.parametrize("case", ["reused_slot_is_a_fresh_slot", "neighbours_do_not_change_a_slot", "an_inactive_slot_keeps_its_buffers"])
def test_slot_state(models, case):
    model = models["share"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 8, (n,)).astype(np.int32) for n in (21, 40, 9)]

    def serve(engine, prompt, slot, others=()):
        for other_slot, other in others:
            engine.prefill(other, other_slot, max_new_tokens=12)
        first, _ = engine.prefill(prompt, slot, max_new_tokens=10)
        toks = [int(first)]
        for _ in range(6):
            out, _, _ = engine.decode_step()
            toks.append(int(out[slot]))
        state = tuple(np.take(np.asarray(buf), slot, axis=spec.slot_axis)
                      for buf, spec in zip(engine._cache, engine._specs) if spec.reset_at_admission)
        return toks, state

    make = lambda: DecodeEngine(model, max_batch_slots=3, max_seq_len=64, prefill_chunk=16)  # noqa: E731
    if case == "an_inactive_slot_keeps_its_buffers":
        engine = make()
        engine.prefill(prompts[1], 1, max_new_tokens=20)
        engine.prefill(prompts[2], 2, max_new_tokens=3)                 # slot 2 stops after three tokens; slot 0 was never used
        for _ in range(4):
            engine.decode_step()
        before = [np.asarray(buf) for buf in engine._cache]
        assert not engine._active_np[2] and not engine._active_np[0] and engine._active_np[1]
        engine.decode_step()
        for spec, was, now in zip(engine._specs, before, engine._cache):
            for slot in (0, 2):                                          # latent rows, state and tail, bitwise
                np.testing.assert_array_equal(np.take(np.asarray(now), slot, axis=spec.slot_axis), np.take(was, slot, axis=spec.slot_axis))
            assert not np.array_equal(np.take(np.asarray(now), 1, axis=spec.slot_axis), np.take(was, 1, axis=spec.slot_axis))
        return
    want, want_state = serve(make(), prompts[0], 1)
    if case == "reused_slot_is_a_fresh_slot":
        engine = make()
        serve(engine, prompts[1], 1)                    # leaves state and tail in slot 1
        engine.free_slot(1)
        got, got_state = serve(engine, prompts[0], 1)   # admission zeroes them inside the first prefill program
        assert [s.reset_at_admission for s in engine._specs] == [False] + [True] * 8    # latent rows; 4 states, 4 tails
        assert [s.name for s in engine._specs][:2] == ["latent0", "state0"] and engine._specs[1].dtype == "float32"
    else:
        got, got_state = serve(make(), prompts[0], 1, others=[(0, prompts[1]), (2, prompts[2])])
    assert got == want
    for g, w in zip(got_state, want_state):
        np.testing.assert_array_equal(g, w)


def test_the_engine_counts_what_the_step_routed_and_what_a_slot_holds(models):
    from paddle_tpu.observability import metrics, spans

    engine = DecodeEngine(models["share"], max_batch_slots=2, max_seq_len=64)
    engine.prefill(np.arange(1, 20, dtype=np.int32) % 8, 0, max_new_tokens=8)
    metrics.reset_counters("infer.moe.")
    engine.decode_step()
    counted = metrics.counters("infer.moe.")
    assert counted["infer.moe.assignments_local"] == int(engine.last_stats[0]) >= 0
    assert counted["infer.moe.experts_hit"] == int(engine.last_stats[1]) <= 2 * 4
    step = [s for s in spans.recent() if s.name == "infer.decode_step"][-1]
    assert step.attrs == {"assignments_local": int(engine.last_stats[0]), "experts_hit": int(engine.last_stats[1])}
    assert engine.state_bytes_per_slot() == 4 * (4 * 16 * 16 * 4 + 3 * (2 * 2 * 16 + 4 * 16) * 4)
    assert metrics.gauges("infer.")["infer.latent_bytes_per_slot"] == engine.latent_bytes_per_slot() == 64 * 128 * 4
    # a key/value model's slots hold no latent rows
    from paddle_tpu.models import solar_open2 as so2
    import test_solar_open2 as solar

    other = DecodeEngine(so2.SolarOpen2ForCausalLM(so2.SolarOpen2Config.from_config_file(solar.SHARE), seed=1, dtype="float32"),
                         max_batch_slots=2, max_seq_len=64)
    assert other.latent_bytes_per_slot() == 0 and other.kv_bytes_per_slot() > 0


@pytest.mark.parametrize("kwargs,what", [(dict(prefill_chunk=16, prefix_cache_mb=1), "prefix_cache_mb"),
                                         (dict(draft={"vocab_size": 8, "hidden_size": 32, "num_layers": 1,
                                                      "num_heads": 2, "max_seq_len": 64}), "draft"),
                                         (dict(kv_dtype="int8"), "int8")])
def test_engine_refuses_what_rests_on_cached_rows(models, kwargs, what):
    with pytest.raises(NotImplementedError, match=what):
        DecodeEngine(models["share"], max_batch_slots=2, max_seq_len=64, **kwargs)


def test_the_scheduler_serves_the_model_with_run_ahead_on(family, models):
    """Through ``ContinuousBatchingScheduler``, whose tick launches a decode
    step before it pulls the last: the tokens of two requests are the
    reference's greedy continuation."""
    from paddle_tpu.inference import ContinuousBatchingScheduler

    model = models["share"]
    engine = DecodeEngine(model, max_batch_slots=2, max_seq_len=64, prefill_chunk=16)
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 8, (n,)).astype(np.int32) for n in (19, 33)]
    rids = [sched.submit(p, max_new_tokens=6) for p in prompts]
    done = sched.run()
    assert all(done[r].status == "finished" and len(done[r].tokens) == 6 for r in rids) and engine._inflight is None
    for prompt, req in zip(prompts, (done[r] for r in rids)):
        seq = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        rows = np.asarray(family.reference_logits(SHARE, model.weights, seq))[len(prompt) - 1:-1]
        assert [int(np.argmax(r)) for r in rows] == [int(t) for t in req.tokens]
