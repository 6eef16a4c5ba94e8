"""Solar Open 2 through the serving engine, against the plain reference of
``benchmark/families/solar_open2.py``: tiny widths, seeded weights, float32,
the CPU. The uncut tiny model and one of its two shares run the same code."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.models import solar_open2 as so2
from paddle_tpu.ops.delta_rule import _unit_lower_inverse, delta_rule_chunked, delta_rule_step
from paddle_tpu.ops.moe_dropless import dropless_experts, gated_ffn, route_topk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARES = 2
UNCUT = {
    "family": "solar_open2", "source": "test", "model_type": "solar_open2",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
    "vocab_size": 128, "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "max_position_embeddings": 512,
    "gqa_layers": [0], "kda_allow_neg_eigval": True, "n_routed_experts": 16, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 4, "reduced": [],
    "assumed": {"low_rank": 8},
}
SHARE = dict(UNCUT, num_attention_heads=2, num_key_value_heads=1, vocab_size=64, n_routed_experts=8, held_experts=[8, 8],
             linear_attn_config=dict(UNCUT["linear_attn_config"], num_heads=2),
             reduced=["num_attention_heads", "num_key_value_heads", "vocab_size", "n_routed_experts", "linear_attn_config"],
             published={"n_routed_experts": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
                        "linear_attn_config": UNCUT["linear_attn_config"]},
             deployment="2 chips share every layer: half the heads, experts and vocabulary each")
CONFIGS = {"uncut": UNCUT, "share": SHARE}


@pytest.fixture(scope="module")
def family():
    return manifest.load_module(REPO, "benchmark", "families", "solar_open2")


@pytest.fixture(scope="module")
def models(family):
    """The uncut tiny model, and share 1 of 2 cut out of *its* weights."""
    full = so2.SolarOpen2ForCausalLM(so2.SolarOpen2Config.from_config_file(UNCUT), seed=11, dtype="float32")
    cut = family.share_weights(family.dims(UNCUT), full.weights, 1, SHARES)
    share = so2.SolarOpen2ForCausalLM(so2.SolarOpen2Config.from_config_file(SHARE),
                                      weights={k: jnp.asarray(v) for k, v in cut.items()})
    return {"uncut": full, "share": share}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def test_reference_is_independent_of_the_program(family):
    text = open(family.__file__).read()
    body = text[text.index("# ---------------------------------------------------------------- reference"):
                text.index("# ---------------------------------------------------------------- required bytes")]
    assert "paddle_tpu" not in body
    assert family.share_dims(family.dims(UNCUT), 1, SHARES) == family.dims(SHARE)


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
        # the padding of the final chunk left state and tail alone: a decode step from here agrees
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
@pytest.mark.parametrize("layer", ["gqa", "linear", "experts"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference_layer(family, models, layer):
    """Over all shares of one tiny layer: the mixers' partial results, and the
    routed partial results with the shared expert and the router counted once,
    sum to what the uncut reference gives for the whole layer."""
    z = family.dims(UNCUT)
    full = {k: np.asarray(v) for k, v in models["uncut"].weights.items()}
    rng = np.random.default_rng(9)
    s = 24
    x = rng.normal(size=(s, z["D"])).astype(np.float32)
    f32 = lambda d: {k: jnp.asarray(v, jnp.float32) for k, v in d.items()}  # noqa: E731
    with jax.default_matmul_precision("highest"):
        if layer == "gqa":
            want, _, _ = family.reference_gqa(z, f32({k: v[0] for k, v in full.items() if k.startswith("attn_")}), x)
        elif layer == "linear":
            want, _ = family.reference_linear(z, f32({k: v[0] for k, v in full.items() if k.startswith("lin_")}), x)
        else:
            want, _ = family.reference_moe(z, f32({k: full[k][1] for k in ("router", "experts_gate_up", "experts_down",
                                                                           "shared_gate_up", "shared_down")}), x)
        total = np.zeros_like(np.asarray(want))
        for share in range(SHARES):
            cfg = so2.SolarOpen2Config.from_config_file(dict(SHARE, held_experts=[share * 8, 8]))
            w = {k: jnp.asarray(v) for k, v in family.share_weights(z, full, share, SHARES).items()}
            if layer == "gqa":
                scratch = jnp.zeros((1, 1, cfg.num_key_value_heads, s, cfg.head_dim), jnp.float32)
                y, _, _ = so2._gqa_chunk(cfg, so2._layer(w, "attn_", 0), jnp.asarray(x), scratch, scratch, 0, 0, jnp.int32(0))
            elif layer == "linear":
                H, d = cfg.linear_num_heads, cfg.linear_head_dim
                y, _, _ = so2._linear_chunk(cfg, so2._layer(w, "lin_", 0), jnp.asarray(x), jnp.zeros((H, d, d), jnp.float32),
                                            jnp.zeros((3, 3 * H * d), jnp.float32), jnp.int32(s))
            else:
                weights, experts = route_topk(jnp.asarray(x), w["router"][1], top_k=cfg.num_experts_per_tok)
                y, _ = dropless_experts(jnp.asarray(x), weights, experts, w["experts_gate_up"][1], w["experts_down"][1],
                                        held=cfg.held_experts)
            total += np.asarray(y)
        if layer == "experts":                        # what every chip computes alike, once
            total += np.asarray(gated_ffn(jnp.asarray(x), jnp.asarray(full["shared_gate_up"][1]), jnp.asarray(full["shared_down"][1])))
    assert _rel(total, want) < 2e-5


# ------------------------------------------------ (c) chunkwise delta rule = the recurrence
def _delta_rule_inputs(tokens, chunk, beta_hi, decay_hi, cos):
    """Unit queries and keys whose neighbours lie at a cosine of ``cos`` (a shared direction as long as a noise row, and
    nothing drawn for it at 0); ``beta`` up to ``beta_hi``, ``-log_alpha`` up to ``decay_hi``."""
    rng = np.random.default_rng(tokens + chunk)
    H, dk, dv = 3, 16, 24
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    shared = lambda: np.sqrt(cos * dk) * unit(rng.normal(size=(H, 1, dk))) if cos else 0.0  # noqa: E731
    q, k = (unit(shared() + np.sqrt(1 - cos) * rng.normal(size=(H, tokens, dk))).astype(np.float32) for _ in range(2))
    v = rng.normal(size=(H, tokens, dv)).astype(np.float32)
    log_alpha = -rng.uniform(1e-3, decay_hi, size=(H, tokens, dk)).astype(np.float32)
    beta = rng.uniform(0, beta_hi, size=(H, tokens)).astype(np.float32)
    state = rng.normal(size=(H, dk, dv)).astype(np.float32)
    return q, k, v, log_alpha, beta, state


@pytest.mark.parametrize("tokens,chunk,beta_hi,decay_hi,cos,bound", [
    (192, 64, 2.0, 1.6, 0.0, 1e-5), (192, 64, 1.0, 0.05, 0.0, 1e-5), (48, 16, 2.0, 8.0, 0.0, 1e-5), (64, 64, 2.0, 0.5, 0.0, 1e-5),
    # keys that share a direction, hardly any decay: over five seeds row substitution (the parent's) reads 4e-7 to 1.2e-6 here and
    # the halved inverse 5e-7 to 1.3e-6, where the doubling product (I - m)(I + m^2)(I + m^4)... reads 7e-6, 2e-4, 8e9 and 6e27
    (48, 16, 2.0, 0.01, 0.5, 2e-6), (48, 16, 2.0, 0.01, 0.9, 2e-6), (192, 64, 2.0, 0.01, 0.5, 2e-6), (192, 64, 2.0, 0.01, 0.9, 2e-6)])
def test_chunkwise_delta_rule_is_the_token_by_token_recurrence(tokens, chunk, beta_hi, decay_hi, cos, bound):
    """Including ``beta > 1`` (negative eigenvalues), a decay strong enough to
    underflow a cumulative product, a state carried over three chunks, and
    neighbouring keys at a cosine of ``cos``: the powers of the chunk's
    triangular matrix then grow before they vanish."""
    q, k, v, log_alpha, beta, state = _delta_rule_inputs(tokens, chunk, beta_hi, decay_hi, cos)
    s, outs = jnp.asarray(state), []
    for t in range(tokens):
        o, s = delta_rule_step(q[:, t], k[:, t], v[:, t], log_alpha[:, t], beta[:, t], s)
        outs.append(np.asarray(o))
    o2, s2 = delta_rule_chunked(q, k, v, log_alpha, beta, state, chunk=chunk)
    assert _rel(o2, np.stack(outs, 1)) < bound and _rel(s2, s) < bound
    if beta_hi > 1:
        assert float(beta.max()) > 1.0
    if cos:
        assert abs(float(np.sum(k[:, 1:] * k[:, :-1], axis=-1).mean()) - cos) < 0.05
    # alpha = 1 and beta = 0 leave the state bitwise alone
    _, s3 = delta_rule_chunked(q, k, v, np.zeros_like(log_alpha), np.zeros_like(beta), state, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(s3), state)


@pytest.mark.parametrize("c", [16, 32, 64, 24])
def test_the_halved_inverse_of_a_chunks_unit_triangular_matrix_is_the_float64_solve(c):
    """``(I + m)^-1`` as the chunkwise form builds ``m`` (``beta`` 1 to 2, keys
    at a neighbour cosine of 0.9), over two batch axes, times a right-hand side:
    ``numpy.linalg.solve`` in float64. 24 halves to 3, an odd block."""
    rng = np.random.default_rng(c)
    batch, n = (3, 2), 40
    k = np.sqrt(0.9) * rng.normal(size=batch + (1, 16)) + np.sqrt(0.1) * rng.normal(size=batch + (c, 16))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = rng.uniform(1.0, 2.0, size=batch + (c, 1))
    m = np.tril(beta * (k @ np.swapaxes(k, -1, -2)), -1)
    rhs = rng.normal(size=batch + (c, n))
    want = np.linalg.solve(np.eye(c) + m, rhs)
    inv = _unit_lower_inverse(jnp.asarray(m, jnp.float32))
    assert inv.shape == batch + (c, c) and inv.dtype == jnp.float32
    assert not np.asarray(jnp.triu(inv, 1)).any() and (np.asarray(jnp.diagonal(inv, axis1=-2, axis2=-1)) == 1.0).all()
    got = jnp.matmul(inv, jnp.asarray(rhs, jnp.float32), precision=jax.lax.Precision.HIGHEST)
    assert _rel(got, want) < 2e-6
    # a row of m that is zero (a padded position: beta = 0) is that row of the identity, exactly
    m[..., c // 2 + 1, :] = 0.0
    inv = np.asarray(_unit_lower_inverse(jnp.asarray(m, jnp.float32)))
    np.testing.assert_array_equal(inv[..., c // 2 + 1, :], np.broadcast_to(np.eye(c, dtype=np.float32)[c // 2 + 1], batch + (c,)))


# ------------------------------------------------ (d) slots
@pytest.mark.parametrize("case", ["reused_slot_is_a_fresh_slot", "neighbours_do_not_change_a_slot"])
def test_slot_state(models, case):
    model = models["share"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (21, 40, 9)]

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
    want, want_state = serve(make(), prompts[0], 1)
    if case == "reused_slot_is_a_fresh_slot":
        engine = make()
        serve(engine, prompts[1], 1)                    # leaves state and tail in slot 1
        engine.free_slot(1)
        got, got_state = serve(engine, prompts[0], 1)   # admission zeroes them inside the first prefill program
        assert [s.reset_at_admission for s in engine._specs] == [False, False] + [True] * 6    # k, v; 3 states, 3 tails
    else:
        got, got_state = serve(make(), prompts[0], 1, others=[(0, prompts[1]), (2, prompts[2])])
    assert got == want
    for g, w in zip(got_state, want_state):
        np.testing.assert_array_equal(g, w)


# ------------------------------------------------ (e) dropless
@pytest.mark.parametrize("case", ["all_to_one_held_expert", "counts_equal_numpy", "picked_and_declined_paths_agree"])
def test_dropless_routing(case):
    rng = np.random.default_rng(2)
    T, D, F, E, k, held = 40, 32, 16, 12, 3, (4, 6)
    if case == "picked_and_declined_paths_agree":
        T, D, F, k = 64, 128, 128, 4         # bf16 at widths the grouped-matmul kernel tiles: 256 pairs, tiles of 64 rows
    dtype = jnp.bfloat16 if case == "picked_and_declined_paths_agree" else jnp.float32
    x = jnp.asarray(rng.normal(size=(T, D)), dtype)
    gate_up = jnp.asarray(rng.normal(size=(held[1], D, 2 * F)) * 0.1, dtype)
    down = jnp.asarray(rng.normal(size=(held[1], F, D)) * 0.1, dtype)
    if case == "all_to_one_held_expert":
        experts = jnp.full((T, k), 6, jnp.int32).at[:, 1].set(0).at[:, 2].set(11)      # one held, two absent
        weights = jnp.full((T, k), 1.0 / k, jnp.float32)
    else:
        weights, experts = route_topk(x, jnp.asarray(rng.normal(size=(D, E)), dtype), top_k=k)
    y, stats = dropless_experts(x, weights, experts, gate_up, down, held=held, n_experts=E)
    idx, w = np.asarray(experts), np.asarray(weights)
    mine = (idx >= held[0]) & (idx < held[0] + held[1])
    assert [int(v) for v in stats] == [int(mine.sum()), len(np.unique(idx[mine]))]
    if case == "picked_and_declined_paths_agree":
        from paddle_tpu.observability import metrics
        from paddle_tpu.ops import grouped_matmul

        metrics.reset_counters("kernels.grouped_matmul.")
        prior = grouped_matmul.set_interpret(True)
        try:
            y_picked, stats_picked = dropless_experts(x, weights, experts, gate_up, down, held=held, n_experts=E)
        finally:
            grouped_matmul.set_interpret(prior)
        assert metrics.counters("kernels.grouped_matmul.")["kernels.grouped_matmul.picked"] == 1
        np.testing.assert_array_equal(np.asarray(stats_picked), np.asarray(stats))
        assert _rel(y_picked, y) < 2 ** -8      # the same bf16 operands summed in float32, in another order at most
        x, gate_up, down = (a.astype(jnp.float32) for a in (x, gate_up, down))
    with jax.default_matmul_precision("highest"):
        want = np.zeros((T, D), np.float32)
        for e in np.unique(idx[mine]):
            w_e = np.where(idx == e, w, 0.0).sum(-1)
            want += w_e[:, None] * np.asarray(gated_ffn(x, gate_up[e - held[0]], down[e - held[0]]))
    assert _rel(y, want) < (2e-5 if dtype == jnp.float32 else 2 ** -7)   # bf16: the activation between the projections
    if case == "all_to_one_held_expert":
        assert int(stats[0]) == T and int(stats[1]) == 1            # every token's pair computed: nothing dropped
        assert np.all(np.abs(np.asarray(y)).sum(-1) > 0)


def test_the_engine_counts_what_the_step_routed(models):
    from paddle_tpu.observability import metrics, spans

    engine = DecodeEngine(models["share"], max_batch_slots=2, max_seq_len=64)
    engine.prefill(np.arange(1, 20, dtype=np.int32), 0, max_new_tokens=8)
    metrics.reset_counters("infer.moe.")
    engine.decode_step()
    counted = metrics.counters("infer.moe.")
    assert counted["infer.moe.assignments_local"] == int(engine.last_stats[0]) > 0
    assert 0 < counted["infer.moe.experts_hit"] == int(engine.last_stats[1]) <= 8 * 4
    step = [s for s in spans.recent() if s.name == "infer.decode_step"][-1]
    assert step.attrs == {"assignments_local": int(engine.last_stats[0]), "experts_hit": int(engine.last_stats[1])}
    assert engine.state_bytes_per_slot() == 3 * (2 * 16 * 16 * 4 + 3 * 3 * 2 * 16 * 4)
    assert engine.kv_bytes_per_slot() == 2 * 1 * 1 * 64 * 16 * 4


def test_fused_decode_is_the_single_step_and_sums_its_counters(models):
    served, counted = [], []
    for fuse in (1, 2):
        engine = DecodeEngine(models["share"], max_batch_slots=2, max_seq_len=64)
        first, _ = engine.prefill(np.arange(1, 20, dtype=np.int32), 0, max_new_tokens=9)
        toks, stats = [int(first)], np.zeros(2, np.int64)
        for _ in range(4 // fuse):
            out, _, _ = engine.decode_step(fuse=fuse)
            toks += [int(t) for t in np.atleast_2d(out)[:, 0]]
            stats += np.asarray(engine.last_stats)
        served.append(toks)
        counted.append(stats.tolist())
    assert served[0] == served[1] and counted[0] == counted[1]


# ------------------------------------------------ (f) what a recurrent model's engine refuses
@pytest.mark.parametrize("kwargs,what", [(dict(prefill_chunk=16, prefix_cache_mb=1), "prefix_cache_mb"),
                                         (dict(draft={"vocab_size": 64, "hidden_size": 32, "num_layers": 1,
                                                      "num_heads": 2, "max_seq_len": 64}), "draft"),
                                         (dict(kv_dtype="int8"), "int8")])
def test_engine_refuses_what_rests_on_cached_rows(models, kwargs, what):
    with pytest.raises(NotImplementedError, match=what):
        DecodeEngine(models["share"], max_batch_slots=2, max_seq_len=64, **kwargs)


def test_a_chunk_that_does_not_divide_the_context_is_refused(models):
    with pytest.raises(ValueError, match="must divide"):
        DecodeEngine(models["share"], max_batch_slots=2, max_seq_len=64, prefill_chunk=24)


# ------------------------------------------------ the GQA group through the decode kernel
def test_decode_attention_kernel_takes_a_group_of_query_heads():
    """Eight query heads on one key/value head ride the kernel's padded window
    rows: interpret mode against the model's lax write-and-attend."""
    from paddle_tpu.ops import decode_attention as da

    rng = np.random.default_rng(4)
    Lg, B, Hkv, G, S, d = 2, 3, 1, 8, 64, 128
    ck, cv = (jnp.asarray(rng.normal(size=(Lg, B, Hkv, S, d)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, Hkv, G, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, Hkv, 1, d)), jnp.float32) for _ in range(2))
    pos, active = jnp.asarray([5, 40, 17], jnp.int32), jnp.asarray([True, True, False])
    want, wk, wv = so2._gqa_write_attend(q, k, v, ck, cv, pos, active, 1)
    prior = da.set_interpret(True)
    try:
        got, gk, gv = da.decode_attention(q, k, v, ck, cv, pos, active, 1, group=G)
    finally:
        da.set_interpret(prior)
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    assert _rel(np.asarray(got)[:2], np.asarray(want)[:2]) < 1e-5 and not np.asarray(got)[2].any()
