"""Granite 4.0-H through the serving engine, against the plain reference of
``benchmark/families/granite_moe_hybrid.py``: tiny widths, seeded weights,
float32, the CPU. The uncut tiny model and one of its two shares run the same
code."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.models import granite_moe_hybrid as gmh
from paddle_tpu.ops.moe_dropless import dropless_experts, gated_ffn, route_topk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARES = 2
UNCUT = {
    "family": "granite_moe_hybrid", "source": "test", "model_type": "granitemoehybrid",
    "vocab_size": 128, "max_position_embeddings": 512, "hidden_size": 64, "intermediate_size": 24, "shared_intermediate_size": 48,
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention", "mamba"], "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "num_local_experts": 12, "num_experts_per_tok": 4, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0625, "logits_scaling": 16, "rms_norm_eps": 1e-5, "reduced": [],
}
SHARE = dict(UNCUT, vocab_size=64, num_local_experts=6, router_experts=12, held_experts=[6, 6],
             reduced=["vocab_size", "num_local_experts"], published={"num_local_experts": 12, "vocab_size": 128},
             deployment="2 chips share every expert layer's experts and the table: 6 of 12 experts, 64 of 128 rows each")
CONFIGS = {"uncut": UNCUT, "share": SHARE}


@pytest.fixture(scope="module")
def family():
    return manifest.load_module(REPO, "benchmark", "families", "granite_moe_hybrid")


@pytest.fixture(scope="module")
def models(family):
    """The uncut tiny model, and share 1 of 2 cut out of *its* weights."""
    full = gmh.GraniteMoeHybridForCausalLM(gmh.GraniteMoeHybridConfig.from_config_file(UNCUT), seed=11, dtype="float32")
    cut = family.share_weights(family.dims(UNCUT), full.weights, 1, SHARES)
    on_device = lambda v: tuple(jnp.asarray(a) for a in v) if isinstance(v, tuple) else jnp.asarray(v)  # noqa: E731
    share = gmh.GraniteMoeHybridForCausalLM(gmh.GraniteMoeHybridConfig.from_config_file(SHARE),
                                            weights={k: on_device(v) for k, v in cut.items()})
    return {"uncut": full, "share": share}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def test_reference_is_independent_of_the_program(family):
    text = open(family.__file__).read()
    body = text[text.index("# ---------------------------------------------------------------- reference"):
                text.index("# ---------------------------------------------------------------- required bytes")]
    assert "paddle_tpu" not in body and '"highest"' in body and "lax.scan(token" in body
    assert family.share_dims(family.dims(UNCUT), 1, SHARES) == family.dims(SHARE)
    cfg = gmh.GraniteMoeHybridConfig.from_config_file(SHARE)
    assert cfg.weight_shapes() == family.weight_shapes(SHARE)                  # one layout, written twice
    assert (cfg.gqa_layers, cfg.linear_layers, cfg.conv_channels) == ((2,), (0, 1, 3), 8 * 16 + 2 * 2 * 16)
    assert cfg.attention_multiplier == 0.0625 != cfg.head_dim ** -0.5          # the multiplier, not d^-1/2


def test_seeded_weights_keep_the_decay_near_one(models):
    w = models["uncut"].weights
    dt = np.log1p(np.exp(np.asarray(w["ssm_dt_bias"])))                        # softplus
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    a = np.exp(np.asarray(w["ssm_a_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and np.all(np.asarray(w["ssm_d"]) == 1.0)
    assert w["ssm_a_log"].dtype == w["ssm_dt_bias"].dtype == jnp.float32 and "head" not in w      # one table


# ------------------------------------------------ (a) the system against the reference, in logits
@pytest.mark.parametrize("path", ["bucketed", "chunked_padded_final", "chunked_attending_in_blocks_of_16"])
@pytest.mark.parametrize("which", ["uncut", "share"])
def test_forwards_agree_with_the_reference(family, models, which, path, monkeypatch):
    if path == "chunked_attending_in_blocks_of_16":
        # a chunk's attention visits the context a block at a time: here four blocks of a context of 64, the last never read
        monkeypatch.setattr(gmh, "_ATTN_BLOCK", 16)
    config, model = CONFIGS[which], models[which]
    z = family.dims(config)
    n = 37
    ids = np.random.default_rng(5).integers(0, z["V"], (n + 6,)).astype(np.int32)
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
        return
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


@pytest.mark.parametrize("n", [53, 21, 9], ids=["several_chunks", "a_chunk_and_a_final_chunk", "a_final_chunk_alone"])
@pytest.mark.parametrize("which", ["uncut", "share"])
def test_prefill_by_chunks_then_decode_through_the_engine_agrees_in_logits(family, models, which, n):
    """The engine hands out tokens; the logits behind each are the program's
    decode forward on the engine's own buffers *before* the step that consumes
    the token, as the cell's check reads them."""
    config, model = CONFIGS[which], models[which]
    z = family.dims(config)
    prompt = np.random.default_rng(n).integers(0, z["V"], (n,)).astype(np.int32)
    engine = DecodeEngine(model, max_batch_slots=3, max_seq_len=64, prefill_chunk=16)
    first, _ = engine.prefill(prompt, 1, max_new_tokens=8)
    served, probed = [int(first)], []
    for _ in range(5):
        logits, _, _ = gmh.decode_probe(model.cfg, engine._params, engine._cache, engine._tok, engine._pos, engine._active)
        probed.append(np.asarray(logits[1]))
        toks, _, _ = engine.decode_step()
        served.append(int(toks[1]))
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])
    want = np.asarray(family.reference_logits(config, model.weights, seq))
    assert [int(np.argmax(r)) for r in want[n - 1:-1]] == served
    assert max(_rel(g, w) for g, w in zip(probed, want[n:])) < 2e-5


def test_a_wrong_multiplier_or_a_dropped_bias_is_far_from_the_reference(family, models):
    """Each of the family's own factors is computed: with one off, the same
    comparison reads hundreds of times what it reads sound (1e-7; the scores
    of a tiny model at N(0, 0.02) are small, so their scale moves least)."""
    model = models["uncut"]
    ids = np.random.default_rng(6).integers(0, 128, (24,)).astype(np.int32)
    want = np.asarray(family.reference_logits(UNCUT, model.weights, ids))
    for wrong in (dict(attention_multiplier=0.25), dict(residual_multiplier=1.0), dict(embedding_multiplier=1.0)):
        other = gmh.GraniteMoeHybridForCausalLM(gmh.GraniteMoeHybridConfig.from_config_file(dict(UNCUT, **wrong)), weights=model.weights)
        assert _rel(np.asarray(other(ids[None])._value)[0], want) > 5e-5, wrong
    scaled = gmh.GraniteMoeHybridForCausalLM(gmh.GraniteMoeHybridConfig.from_config_file(dict(UNCUT, logits_scaling=1.0)), weights=model.weights)
    assert _rel(np.asarray(scaled(ids[None])._value)[0] / 16.0, want) < 2e-5     # logits / 16, and nothing else
    no_bias = dict(model.weights, ssm_conv_bias=jnp.zeros_like(model.weights["ssm_conv_bias"]) + 0.5)
    other = gmh.GraniteMoeHybridForCausalLM(model.cfg, weights=no_bias)
    assert _rel(np.asarray(other(ids[None])._value)[0], want) > 1e-3


# ------------------------------------------------ (b) the two shares add up
def test_the_two_shares_add_up_to_the_uncut_layer_and_vocabulary(family, models):
    """Share 0's and share 1's routed parts, with the shared MLP counted once,
    sum to what the uncut reference gives for ``Routed + Shared``; and the two
    slices' logits, side by side, are the uncut reference's."""
    z = family.dims(UNCUT)
    full = models["uncut"].weights
    x = np.random.default_rng(9).normal(size=(24, z["D"])).astype(np.float32)
    layer = 1
    lw = {k: full[k][layer] for k in ("router", "experts_gate_up", "experts_down", "shared_gate_up", "shared_down")}
    with jax.default_matmul_precision("highest"):
        want, _ = family.reference_moe(z, lw, jnp.asarray(x))
        total = np.zeros_like(np.asarray(want))
        for share in range(SHARES):
            w = family.share_weights(z, full, share, SHARES)
            weights, experts = route_topk(jnp.asarray(x), full["router"][layer], top_k=z["top_k"], scoring="softmax_topk")
            y, stats = dropless_experts(jnp.asarray(x), weights, experts, jnp.asarray(w["experts_gate_up"][layer]),
                                        jnp.asarray(w["experts_down"][layer]), held=(share * 6, 6), n_experts=12)
            # the reference given the share computes the same part (without the shared MLP)
            part, _ = family.reference_moe(family.share_dims(z, share, SHARES), {**lw, "experts_gate_up": w["experts_gate_up"][layer],
                                                                              "experts_down": w["experts_down"][layer]}, jnp.asarray(x), shared=False)
            assert _rel(y, part) < 2e-5 and 0 < int(stats[0]) < 24 * 4
            total += np.asarray(y)
        total += np.asarray(gated_ffn(jnp.asarray(x), full["shared_gate_up"][layer], full["shared_down"][layer]))
        assert _rel(total, want) < 2e-5
        h = jnp.asarray(np.random.default_rng(10).normal(size=(7, z["D"])).astype(np.float32))
        slices = [family.reference_head(family.share_dims(z, s, SHARES), family.share_weights(z, full, s, SHARES), h) for s in range(SHARES)]
        np.testing.assert_allclose(np.concatenate([np.asarray(s) for s in slices], axis=-1), np.asarray(family.reference_head(z, full, h)),
                                   rtol=1e-5, atol=1e-6)
    # and the program's head over a slice is the reference's over that slice
    share = models["share"]
    got = gmh._head(share.cfg, share.weights, h)
    assert _rel(got, slices[1]) < 2e-5 and got.shape == (7, 64)


# ------------------------------------------------ (c) slots
@pytest.mark.parametrize("case", ["reused_slot_is_a_fresh_slot", "neighbours_do_not_change_a_slot", "an_inactive_slot_keeps_its_buffers"])
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
            for slot in (0, 2):                                          # key/value rows, state and tail, bitwise
                np.testing.assert_array_equal(np.take(np.asarray(now), slot, axis=spec.slot_axis), np.take(was, slot, axis=spec.slot_axis))
            # (a greedy tiny model repeats its token, and three equal inputs in a row are the tail they replace)
            if not spec.name.startswith("conv_tail"):
                assert not np.array_equal(np.take(np.asarray(now), 1, axis=spec.slot_axis), np.take(was, 1, axis=spec.slot_axis))
        return
    want, want_state = serve(make(), prompts[0], 1)
    if case == "reused_slot_is_a_fresh_slot":
        engine = make()
        serve(engine, prompts[1], 1)                    # leaves state and tail in slot 1
        engine.free_slot(1)
        got, got_state = serve(engine, prompts[0], 1)   # admission zeroes them inside the first prefill program
        assert [s.reset_at_admission for s in engine._specs] == [False, False] + [True] * 6    # k, v; 3 states, 3 tails
        assert [s.name for s in engine._specs] == ["k", "v", "ssm_state0", "ssm_state1", "ssm_state2", "conv_tail0", "conv_tail1", "conv_tail2"]
        assert engine._specs[2].dtype == "float32" and engine._specs[2].shape == (3, 8, 16, 16) and engine._specs[5].shape == (3, 3, 192)
    else:
        got, got_state = serve(make(), prompts[0], 1, others=[(0, prompts[1]), (2, prompts[2])])
    assert got == want
    for g, w in zip(got_state, want_state):
        np.testing.assert_array_equal(g, w)


def test_the_engine_counts_what_the_step_routed_and_what_a_slot_holds(models):
    from paddle_tpu.observability import metrics, spans

    engine = DecodeEngine(models["share"], max_batch_slots=2, max_seq_len=64)
    engine.prefill(np.arange(1, 20, dtype=np.int32) % 64, 0, max_new_tokens=8)
    metrics.reset_counters("infer.moe.")
    engine.decode_step()
    counted = metrics.counters("infer.moe.")
    assert counted["infer.moe.assignments_local"] == int(engine.last_stats[0]) >= 0
    assert counted["infer.moe.experts_hit"] == int(engine.last_stats[1]) <= 4 * 6
    step = [s for s in spans.recent() if s.name == "infer.decode_step"][-1]
    assert step.attrs == {"assignments_local": int(engine.last_stats[0]), "experts_hit": int(engine.last_stats[1])}
    # three Mamba layers' ssm_state (8 x 16 x 16 float32) and conv_tail (3 x 192 float32 here); one layer's keys and values
    gauges = metrics.gauges("infer.")
    assert gauges["infer.state_bytes_per_slot"] == engine.state_bytes_per_slot() == 3 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    assert gauges["infer.kv_bytes_per_slot"] == engine.kv_bytes_per_slot() == 2 * 2 * 64 * 16 * 4
    assert engine.latent_bytes_per_slot() == 0


@pytest.mark.parametrize("kwargs,what", [(dict(prefill_chunk=16, prefix_cache_mb=1), "prefix_cache_mb"),
                                         (dict(draft={"vocab_size": 64, "hidden_size": 32, "num_layers": 1,
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
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (19, 33)]
    rids = [sched.submit(p, max_new_tokens=6) for p in prompts]
    done = sched.run()
    assert all(done[r].status == "finished" and len(done[r].tokens) == 6 for r in rids) and engine._inflight is None
    for prompt, req in zip(prompts, (done[r] for r in rids)):
        seq = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        rows = np.asarray(family.reference_logits(SHARE, model.weights, seq))[len(prompt) - 1:-1]
        assert [int(np.argmax(r)) for r in rows] == [int(t) for t in req.tokens]
