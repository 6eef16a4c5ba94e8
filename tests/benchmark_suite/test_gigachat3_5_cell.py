"""The cell ``gigachat3.5-432b-a28b.serve-longdoc``: its entries in the real
manifest, and the family driven through the closed loop at tiny sizes on the
CPU — ``correct`` true as it is, false with a fault planted in the program."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import device, manifest, peaks, trace
from benchmark.harness.records import Records
from benchmark.layer_metrics import _program

import tiny_root

REPO = tiny_root.REPO
CELL = "gigachat3.5-432b-a28b.serve-longdoc"
SOLAR = "solar-open2-250b.serve-reasoning"
NEW_READERS = ("decode_latent_ms", "decode_latent_roofline")
PREFILL_SIDE = ("sched_queue_p50_ms", "prefill_share_pct")
# https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json as the builder's catalog gives it
SOURCE = json.loads("""
{"vocab_size": 128256, "max_position_embeddings": 262144, "hidden_size": 7168, "intermediate_size": 18432,
"moe_intermediate_size": 2048, "num_hidden_layers": 40, "nextn_is_sparse": false, "num_attention_heads": 64,
"n_shared_experts": 1, "n_routed_experts": 256, "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
"qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
"num_experts_per_tok": 8, "first_k_dense_replace": 3, "norm_topk_prob": true, "rope_interleave": true,
"num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000, "rope_scaling": {"beta_fast":
32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 32768, "type":
"yarn"}, "attention_bias": false, "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
"layernorm_gating_weight": 2, "gated_attention": true, "use_shared_expert_sigmoid": false, "use_mla_scaling_factor": true,
"linear_attention_type": "GigaChat35GatedDeltaNet", "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
"linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
"linear_num_value_heads": 64, "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "linear_sigmoid_gate_scale": 2,
"linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10, "tie_word_embeddings": false, "num_nextn_predict_layers": 2,
"model_type": "gigachat3_5", "tf_legacy_loss": false}
""")
TINY = {
    "family": "gigachat3_5", "source": "test", "model_type": "gigachat3_5",
    "vocab_size": 64, "max_position_embeddings": 512, "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_attention_heads": 4, "n_shared_experts": 1, "n_routed_experts": 8, "routed_scaling_factor": 2.5,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "norm_topk_prob": True, "rope_interleave": True,
    "rms_norm_eps": 1e-6, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "layernorm_gating_weight": 2, "use_mla_scaling_factor": True, "full_attention_layers": [1],
    "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-6, "swiglu_limit": 10,
    "held_experts": [8, 8], "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 16},
    "deployment": "2 chips share every expert layer's experts, 8 of 16 each",
    "serving": {"dtype": "float32", "slots": 4, "context": 128, "prefill_chunk": 16, "fuse": 1,
                "prefix_cache_mb": 0, "replicas": 1, "max_queue_depth": 64},
}


@pytest.fixture(scope="module")
def real():
    return manifest.load_manifest(REPO)


def test_the_real_manifest_holds_the_configuration_and_its_cell(real):
    assert manifest.check_manifest(real, REPO) == []
    cell = manifest.resolve_cell(real, CELL, REPO)
    assert cell.chips == 1 and cell.config["family"] == "gigachat3_5" and cell.traffic["driver"] == "serve_closed_loop"
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    solar = [m["name"] for m in manifest.resolve_cell(real, SOLAR, REPO).per_layer]
    # what Solar's cell reports, the two new readers, and two readers of what sets a closed loop's tail: the wait for a
    # slot and the prefill programs' share of the device
    assert set(names) == set(solar) | set(NEW_READERS) | set(PREFILL_SIDE) and len(names) == len(set(names)) == 20
    assert all(m["moves"] == "itl_p95_ms" for m in cell.per_layer)
    # ``decode_unscoped_ms`` sums every part but attn, mlp and head: here it would read linear, routed and latent a second
    # time; ``prefill_cache_ms`` reads a part ``cache`` that this family does not have (its row write belongs to ``latent``);
    # ``ttft_*`` and ``gen_late_ms`` count from a request's submission, and a closed loop submits its first 48 at once
    # when set-up starts: over half of a window's requests would read their place in set-up's queue (PERF.md §7)
    assert not {"decode_unscoped_ms", "prefill_cache_ms", "ttft_p50_ms", "ttft_p95_ms", "ttft_mean_ms", "gen_late_ms"} & set(names)
    by_name = {group: {e["name"]: e for e in real[group]} for group in ("configs", "workloads", "per_layer")}
    assert all(by_name["per_layer"][name]["workloads"] == [CELL] for name in NEW_READERS)
    assert by_name["workloads"][CELL]["config"] == "gigachat3.5-432b-a28b" and by_name["workloads"][CELL]["traffic"] == "longdoc-saturated"
    assert by_name["configs"]["gigachat3.5-432b-a28b"]["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "full_attention_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    # one chip's share: every key of the source's config.json as published but the six reduced, whose published values the
    # file keeps; all 64 heads, the router's 256 outputs and 8 a token
    cfg, z = cell.config, cell.family.dims(cell.config)
    assert {k for k, v in SOURCE.items() if cfg.get(k) != v} == set(cfg["reduced"])
    assert cfg["published"] == {k: SOURCE[k] for k in cfg["reduced"]}
    assert (z["D"], z["H"], z["rank"], z["dr"], z["Hk"], z["Hv"], z["F"], z["Fd"], z["top_k"]) == (7168, 64, 512, 64, 32, 64, 2048, 18432, 8)
    assert z["E"] == 256 and z["held"] == (0, 16) and z["L"] == 4 and z["layers"] == 5 and z["full"] == (1,) and z["dense"] == 1
    assert cell.family.param_count(cfg) == pytest.approx(4.73e9, rel=0.01)
    assert all(k in cfg for k in ("published", "deployment", "held_here", "assumed")) and cfg["num_nextn_predict_layers"] == 0
    assert cfg["serving"]["context"] % cfg["serving"]["prefill_chunk"] == 0 and cfg["serving"]["slots"] == 48
    assert set(cfg["serving"]) == {"dtype", "slots", "context", "prefill_chunk", "fuse", "prefix_cache_mb", "replicas", "max_queue_depth"}


def test_solars_entries_are_what_its_pr_left_by_name(real):
    """Everything PR 30's ``test_the_real_manifest_holds_the_configuration_and_its_cell`` holds of Solar's cell, looked up
    by name: that test also holds Solar's entries to the *end* of the manifest's lists, which no PR that appends a cell
    can keep and none but a ``benchmark`` PR may edit (``tests/conftest.py`` has the mark)."""
    cell = manifest.resolve_cell(real, SOLAR, REPO)
    assert cell.chips == 1 and cell.config["family"] == "solar_open2" and cell.traffic["driver"] == "serve_closed_loop"
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    readers = {"decode_linear_attn_ms", "decode_routed_ms", "routed_experts_hit_pct", "decode_routed_roofline"}
    assert readers <= set(names) and "decode_unscoped_ms" not in names and "prefill_cache_ms" not in names
    assert all(m["moves"] == "itl_p95_ms" for m in cell.per_layer) and len(names) == 16
    assert SOLAR in [w["name"] for w in real["workloads"]] and "solar-open2-250b" in [c["name"] for c in real["configs"]]
    assert readers <= {m["name"] for m in real["per_layer"]}
    cfg, z = cell.config, cell.family.dims(cell.config)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) == (4096, 128, 1280, 8)
    assert cfg["linear_attn_config"]["head_dim"] == 128 and z["E"] == 320 and z["held"] == (0, 40) and z["R"] == 128
    assert cell.family.param_count(cfg) * 2 / 1e9 == pytest.approx(5.7, abs=0.05)           # GB of weights held
    assert cfg["serving"]["context"] % cfg["serving"]["prefill_chunk"] == 0


def test_the_traffic_file_holds_the_parameters_asked_for(real):
    t = manifest.resolve_cell(real, CELL, REPO).traffic
    assert t["clients"] == "slots" and t["stream_seed"] == 34 and t["warmup_ticks"] == 24 and t["max_total_tokens"] == 32768
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 6144, "sigma": 0.8, "min": 1024, "max": 24576}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.5, "min": 512, "max": 8192}
    assert (t["first_request"]["prompt_base"], t["first_request"]["prompt_step"]) == (1024, 320)
    driver = manifest.load_module(REPO, "benchmark", "drivers", "serve_closed_loop")
    lists = driver.client_lists(t, 48)
    assert sum(l[0][0] for l in lists) == 48 * 1024 + 320 * 47 * 48 // 2 == 410112                # set-up's prefill
    assert all(p + o <= 32768 and p >= 1024 for l in lists for p, o in l)


def test_bytes_and_operations_of_a_decode_step_from_shapes_and_counts(real):
    cell = manifest.resolve_cell(real, CELL, REPO)
    fam, cfg = cell.family, cell.config
    assert fam.expert_bytes(cfg) == 3 * 7168 * 2048 * 2
    assert fam.latent_row_bytes(cfg) == 1152 and fam.latent_row_flops(cfg) == 139264
    live = 48 * 10000.0
    everything = fam.decode_step_bytes(cfg, live_rows=live)                                      # no count: every held expert
    experts = 4 * 16 * fam.expert_bytes(cfg)
    state = 2 * 48 * 4 * (64 * 128 * 128 * 4 + 3 * 16384 * 2)
    rest = everything - experts - live * 1152 - state
    # the mixers' weights 2.21 GB, the dense FFN 0.79, the shared experts 0.35, routers and head 0.24
    assert rest == pytest.approx(3.60e9, rel=0.01)
    # memory binds the latent attention: 121 operations a byte against the chip's ridge of 240
    floor = fam.latent_step_floor_s(cfg, live, peaks.PEAKS["TPU v5 lite"])
    assert floor == pytest.approx(live * 1152 / 819e9) and floor > live * 139264 / 197e12


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A root of new files only: the tiny configuration beside links to the real code."""
    root = tiny_root.make(tmp_path_factory.mktemp("bench_gigachat"))
    with open(os.path.join(root, "benchmark", "configs", "tiny-gigachat.json"), "w") as f:
        json.dump(TINY, f)
    m = manifest.load_manifest(root)
    cell = "tiny-gigachat.closed"
    m["configs"].append({"name": "tiny-gigachat", "source": "test", "file": "benchmark/configs/tiny-gigachat.json",
                         "reduced": ["n_routed_experts"], "why": "test"})
    m["workloads"].append({"name": cell, "config": "tiny-gigachat", "traffic": "tiny-closed", "chips": 1, "why": "test"})
    real = manifest.load_manifest(REPO)
    for group in ("end_to_end", "per_layer"):
        for entry, was in zip(m[group], real[group]):
            if CELL in was.get("workloads", ()):
                entry["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    assert manifest.check_manifest(m, root) == []
    return root, m, cell


def _drive(tiny, tmp_path, seconds=0.6):
    root, m, name = tiny
    cell = manifest.resolve_cell(m, name, root)
    records = Records(cell=cell, seed=3000000019, seconds=seconds, chips=1, peaks=peaks.PEAKS["TPU v5 lite"])
    cell.driver.run(records, jax.devices()[:1], process_start=time.perf_counter(), trace_on=False, trace_dir=str(tmp_path))
    return cell, records


def test_the_family_drives_the_closed_loop_and_is_correct(tiny, monkeypatch, tmp_path):
    from paddle_tpu.observability import introspect, metrics

    cell, records = _drive(tiny, tmp_path, seconds=1.0)
    monkeypatch.setattr(device, "describe", lambda devs, trace=None: {"platform": "cpu", "kind": "cpu", "count": 1})
    line = bench_run.result_line(cell, records, jax.devices()[:1], trace_on=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_rel_rms", "cache_rel_rms", "state_rel_rms", "token_below_best", "routing_below_kth",
                                     "state_on_bf16_grid", "compiles_in_window"}
    assert records.check["positions"] == 51 and records.check["slots_decoding"] == 4 and records.check["logit_rel_rms"] < 1e-4 and records.check["cache_rel_rms"] < 1e-5
    assert sum(records.tick_admitted[i] for i in records.inside(records.tick_end)) > 0          # slots were refilled
    # a slot's rows: 128 x (16 + 8 padded to 128 lanes) float32, all of them latent; its state: 4 layers x (4 x 16 x 16 x 4 + 3 x 128 x 4)
    gauges = metrics.gauges("infer.")
    assert gauges["infer.latent_bytes_per_slot"] == gauges["infer.kv_bytes_per_slot"] == 128 * 128 * 4
    assert gauges["infer.state_bytes_per_slot"] == 4 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    layer = {k: v["value"] for k, v in bench_run.compute_metrics(cell, cell.per_layer, records).items()}
    assert 0 < layer["routed_experts_hit_pct"] <= 100
    # no device trace on the CPU: one made from the decode program the run compiled, 1 ms an op that carries a scope
    scopes = introspect.op_scopes()[cell.family.SCOPES_OF_PROGRAM[cell.family.DECODE_PROGRAM]]
    runs = 5
    ticks = records.inside(records.tick_end)[-runs:]
    records.traced = (ticks[0], ticks[-1])
    records.trace = trace.TraceSummary(
        window_ns=(0.0, 1e9), devices=[0], busy_ns={0: runs * len(scopes) * 1e6}, op_ns={}, gap_ns={}, collective_ns={},
        collective_exposed_ns={}, modules={"jit_decode_fn": [len(scopes) * 1e6] * runs},
        op_ns_by_program={"jit_decode_fn": {f"{op} fusion f32[4]": runs * 1e6 for op in scopes}})
    got = {k: v["value"] for k, v in bench_run.compute_metrics(cell, cell.per_layer, records).items()}
    parts = {part: sum(1 for path in scopes.values() if _program.part_of(path, cell.family.PART_OF_SCOPE) == part)
             for part in ("attn", "latent", "linear", "routed", "mlp", "head_loss")}
    assert all(parts.values())
    assert got["decode_latent_ms"] == pytest.approx(parts["latent"]) and got["decode_attn_ms"] == pytest.approx(parts["attn"])
    assert got["decode_linear_attn_ms"] == pytest.approx(parts["linear"]) and got["decode_routed_ms"] == pytest.approx(parts["routed"])
    assert got["decode_mlp_ms"] == pytest.approx(parts["mlp"]) and got["decode_head_ms"] == pytest.approx(parts["head_loss"])
    decoding = [i for i in ticks if records.tick_decoding[i]]
    live = sum(records.tick_live_rows[i] for i in decoding) / len(decoding)
    assert got["decode_latent_roofline"] == pytest.approx(100.0 * live * (16 + 8) * 2 / 819e9 / (parts["latent"] * 1e-3))
    hit = cell.family.experts_hit_per_step(records)
    assert 0 < hit <= 4 * 8
    want = hit * cell.family.expert_bytes(cell.config) + 4 * 64 * 16 * 2
    assert got["decode_routed_roofline"] == pytest.approx(100.0 * want / 819e9 / (parts["routed"] * 1e-3))
    assert got["decode_step_roofline"] > 0 and set(cell.readers) >= set(got) and len(got) >= 10


def test_the_new_readers_find_nothing_in_a_family_without_a_latent_part(real, tmp_path):
    """On the parent's program, or in a cell of another family, the two readers return None and raise nothing."""
    solar = manifest.resolve_cell(real, SOLAR, REPO)
    records = Records(cell=solar, seed=1, seconds=1.0, chips=1, peaks=peaks.PEAKS["TPU v5 lite"])
    readers = [manifest.load_module(REPO, "benchmark", "layer_metrics", name) for name in NEW_READERS]
    assert [r.read(records) for r in readers] == [None, None]                                   # no trace
    records.trace = trace.TraceSummary(window_ns=(0.0, 1e9), devices=[0], busy_ns={0: 1e6}, op_ns={}, gap_ns={}, collective_ns={},
                                       collective_exposed_ns={}, modules={"jit_decode_fn": [1e6]}, op_ns_by_program={})
    assert [r.read(records) for r in readers] == [None, None]                                   # no latent part, no floor


@pytest.mark.parametrize("fault", ["state_not_reset_at_admission", "rotation_off_by_one_position", "latent_rows_held_in_float8",
                                   "state_held_in_bfloat16", "post_norms_scaled_by_two"])
def test_a_planted_fault_is_seen(tiny, monkeypatch, tmp_path, fault):
    """The family's four controls (``planted``: what ``python3 -m benchmark.families.gigachat3_5 <control> ...`` runs on
    the chip), and a norm's scale off by two: each planted from outside the program, each not correct."""
    from paddle_tpu.inference import aot_cache
    from paddle_tpu.models import gigachat3_5 as g35
    from paddle_tpu.ops import rope

    family = manifest.resolve_cell(tiny[1], tiny[2], tiny[0]).family
    planted_at = lambda: (g35._mla_project, rope.rope_angles, g35._admitting, g35.delta_rule_step, g35.delta_rule_chunked,  # noqa: E731
                          aot_cache.cache_dir)
    sound = planted_at()
    if fault in family.CONTROLS:
        with family.planted(fault):
            assert aot_cache.cache_dir() is None          # a planted program neither loads a sound executable nor leaves its own
            _, records = _drive(tiny, tmp_path, seconds=0.3)
        assert planted_at() == sound                      # and nothing stays planted
    else:
        layers = g35._layers

        def without_post(cfg, p, *a, **k):
            doubled = {name: p[name] * 0 + 30.0 for name in ("norm_post1", "norm_post2")}   # 2 sigmoid(30) = 2, not 1
            return layers(cfg, dict(p, **doubled), *a, **k)

        monkeypatch.setattr(g35, "_layers", without_post)
        _, records = _drive(tiny, tmp_path, seconds=0.3)
    assert records.check["correct"] is False
    assert any(number > limit for number, limit in records.check["compared"].values())
    assert np.isfinite(records.check["logit_rel_rms"])
    if fault == "rotation_off_by_one_position":
        # rotary scores depend on differences of positions: the logits do not see a common shift, the cached key does
        assert records.check["logit_rel_rms"] < 1e-4 and records.check["cache_rel_rms"] > 0.1
        latent, key = zip(*records.check["cache_rel_rms_latent_and_key_by_prompt"])
        assert max(latent) < 1e-5 and min(key) > 0.1
    if fault == "latent_rows_held_in_float8":
        assert records.check["cache_rel_rms"] > 1e-2
    if fault == "state_not_reset_at_admission":
        assert records.check["state_rel_rms"] > family.SERVE_STATE_REL_RMS          # not correct by the state's own limit
    if fault == "state_held_in_bfloat16":
        # every element of the state a bfloat16 number; the comparison with the reference alone reads 2.9e-3 here
        assert records.check["compared"]["state_on_bf16_grid"][0] == 1.0
