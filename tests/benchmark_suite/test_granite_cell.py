"""The cell ``granite-4.0-h-small.serve-rag``: its entries in the real manifest
(by name), its sizes against the issue's table, and the family driven through
the closed loop at tiny sizes on the CPU — ``correct`` true as it is, false
with a fault planted in the program."""
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
CELL, CONFIG = "granite-4.0-h-small.serve-rag", "granite-4.0-h-small"
GIGA = "gigachat3.5-432b-a28b.serve-longdoc"
NEW_READERS = ("decode_ssm_ms", "decode_ssm_roofline", "prefill_ssm_ms", "prefill_routed_ms", "prefill_ssm_roofline")
SHARED_READERS = ("decode_step_ms", "decode_step_roofline", "serve_device_idle_pct", "fleet_self_ms", "sched_self_ms", "decode_launch_ms",
                  "engine_decode_step_ms", "decode_attn_ms", "decode_mlp_ms", "decode_head_ms", "decode_routed_ms",
                  "decode_routed_roofline", "routed_experts_hit_pct", "prefill_chunk_ms", "prefill_share_pct", "sched_queue_p50_ms")
# https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json as the builder's catalog gives it
SOURCE = json.loads("""
{"attention_bias": false, "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
"intermediate_size": 768, "layer_types": ["mamba", "mamba", "mamba", "mamba", "mamba", "attention", "mamba", "mamba", "mamba", "mamba",
"mamba", "mamba", "mamba", "mamba", "mamba", "attention", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba",
"attention", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "attention", "mamba", "mamba", "mamba",
"mamba"], "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": true, "mamba_d_conv": 4, "mamba_d_head": 64,
"mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": false,
"max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm", "num_attention_heads": 32,
"num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72,
"position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": null, "rope_theta": 10000,
"shared_intermediate_size": 1536, "tie_word_embeddings": true, "vocab_size": 100352}
""")
TINY = {
    "family": "granite_moe_hybrid", "source": "test", "model_type": "granitemoehybrid",
    "vocab_size": 64, "max_position_embeddings": 512, "hidden_size": 64, "intermediate_size": 24, "shared_intermediate_size": 48,
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention", "mamba"], "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "num_local_experts": 6, "router_experts": 12, "held_experts": [6, 6], "num_experts_per_tok": 4,
    # (the embedding's factor 2, not 12: at width 64 twelve embeddings would drown what the layers add to the stream)
    "embedding_multiplier": 2, "residual_multiplier": 0.22, "attention_multiplier": 0.0625, "logits_scaling": 16, "rms_norm_eps": 1e-5,
    "reduced": ["num_local_experts"], "published": {"num_local_experts": 12},
    "deployment": "2 chips share every expert layer's experts, 6 of 12 each",
    "serving": {"dtype": "float32", "slots": 4, "context": 128, "prefill_chunk": 16, "fuse": 1,
                "prefix_cache_mb": 0, "replicas": 1, "max_queue_depth": 64},
}


@pytest.fixture(scope="module")
def real():
    return manifest.load_manifest(REPO)


def test_the_real_manifest_holds_the_configuration_the_cell_and_the_five_readers_by_name(real):
    assert manifest.check_manifest(real, REPO) == []
    assert len(real["workloads"]) == 7 and sum(w["chips"] == 4 for w in real["workloads"]) == 1
    by_name = {group: {e["name"]: e for e in real[group]} for group in ("configs", "workloads", "per_layer", "end_to_end")}
    cell = manifest.resolve_cell(real, CELL, REPO)
    assert cell.chips == 1 and cell.config["family"] == "granite_moe_hybrid" and cell.traffic["driver"] == "serve_closed_loop"
    assert by_name["workloads"][CELL]["config"] == CONFIG and by_name["workloads"][CELL]["traffic"] == "rag-saturated"
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]           # ``out_tok_s`` stays off (PERF.md §7)
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(NEW_READERS) | set(SHARED_READERS) and len(names) == len(set(names)) == 21
    # a saturated device leaves no gap of 0.1 ms in some traced windows, and the reader then has nothing to read (PERF.md §7, PR 36)
    assert CELL not in by_name["per_layer"]["idle_in_program_spans_pct"]["workloads"]
    assert all(m["moves"] == "itl_p95_ms" for m in cell.per_layer)
    assert all(by_name["per_layer"][name]["workloads"] == [CELL] and by_name["per_layer"][name]["source"] == "device_trace"
               for name in NEW_READERS)
    assert by_name["per_layer"]["decode_ssm_roofline"]["unit"] == by_name["per_layer"]["prefill_ssm_roofline"]["unit"] == "%"
    # PERF.md §7 (PR 34) has why each of these misreads one chip's share in a closed loop
    assert not {"decode_unscoped_ms", "prefill_cache_ms", "ttft_p50_ms", "ttft_p95_ms", "ttft_mean_ms", "gen_late_ms", "out_tok_s"} & set(names)
    assert by_name["configs"][CONFIG]["reduced"] == cell.config["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert by_name["configs"][CONFIG]["source"] == cell.config["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    # the cells that were there report what they reported: the GigaChat cell's readers are Solar's, its own two and the prefill side's
    assert len(manifest.resolve_cell(real, GIGA, REPO).per_layer) == 20 and not set(NEW_READERS) & {
        m["name"] for m in manifest.resolve_cell(real, GIGA, REPO).per_layer}


def test_the_configuration_is_the_sources_at_published_widths_and_the_issues_table(real):
    cell = manifest.resolve_cell(real, CELL, REPO)
    cfg, fam = cell.config, cell.family
    # every key of the source's config.json as published but the four reduced, whose published values the file keeps
    assert {k for k, v in SOURCE.items() if cfg.get(k) != v} == set(cfg["reduced"])
    assert cfg["published"] == {k: SOURCE[k] for k in cfg["reduced"]}
    assert cfg["layer_types"] == SOURCE["layer_types"][:10] and cfg["num_hidden_layers"] == 10      # one whole period, in its published order
    assert (cfg["num_local_experts"], cfg["router_experts"], cfg["held_experts"], cfg["vocab_size"]) == (36, 72, [0, 36], 50176)
    assert all(k in cfg for k in ("published", "deployment", "held_here", "assumed"))
    assert cfg["serving"] == {"dtype": "bfloat16", "slots": 40, "context": 8192, "prefill_chunk": 1024, "fuse": 1, "prefix_cache_mb": 0,
                              "replicas": 1, "max_queue_depth": 4096}
    z = fam.dims(cfg)
    assert (z["D"], z["H"], z["P"], z["N"], z["G"], z["K"], z["Hq"], z["Hkv"], z["d"], z["F"], z["Fs"], z["top_k"]) == (
        4096, 128, 64, 128, 1, 4, 32, 8, 128, 768, 1536, 10)
    assert z["E"] == 72 and z["held"] == (0, 36) and z["L"] == z["layers"] == 10 and z["attn"] == (5,)
    # the issue's table, parameter for parameter
    assert fam.ssm_weight_count(cfg) == 68_681_728 + 42_240 + 384 + 8_192 + 33_554_432 == 102_286_976
    shapes = fam.weight_shapes(cfg)
    count = lambda *names: sum(int(np.prod(shapes[n])) for n in names)  # noqa: E731
    assert count("attn_q", "attn_kv", "attn_out") == 41_943_040
    assert count("experts_gate_up", "experts_down") == 10 * 339_738_624
    assert count("shared_gate_up", "shared_down", "router", "norm1", "norm2") == 10 * 19_177_472
    assert count("embed", "final_norm") == 205_524_992
    assert fam.param_count(cfg) == 4_757_211_776
    assert fam.slot_bytes(cfg) == {"state": 37_748_736, "tail": 456_192, "kv": 33_554_432} and sum(fam.slot_bytes(cfg).values()) == 71_759_360
    # the two floors: nine mixers' weights and every decoding slot's state and tails read and written, at 819 GB/s ...
    v5e = peaks.PEAKS["TPU v5 lite"]
    assert fam.ssm_step_floor_s(cfg, 40, v5e) == pytest.approx((9 * 102_286_976 * 2 + 40 * 9 * (2 * 4_194_304 + 2 * 50_688)) / 819e9)
    # ... and a chunk's projections (204.5 MFLOP a token a layer) with the recurrence's least, at 197 TFLOP/s
    assert fam.ssm_chunk_floor_s(cfg, 1024, v5e) == pytest.approx(1024 * 9 * (204_472_320 + 4 * 64 * 128 * 128) / 197e12)
    assert fam.expert_bytes(cfg) == 3 * 4096 * 768 * 2
    everything = fam.decode_step_bytes(cfg, live_rows=40 * 4000.0)                               # no count: every held expert
    assert everything == pytest.approx(2 * 4_757_211_776 + 40 * 4000 * 4096 + 2 * 40 * (37_748_736 + 456_192))


def test_the_traffic_file_holds_the_parameters_asked_for(real):
    t = manifest.resolve_cell(real, CELL, REPO).traffic
    assert t["clients"] == "slots" and t["stream_seed"] == 36 and t["warmup_ticks"] == 24 and t["max_total_tokens"] == 8192
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 3072, "sigma": 0.6, "min": 512, "max": 7168}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 32, "max": 768}
    assert (t["first_request"]["prompt_base"], t["first_request"]["prompt_step"], t["first_request"]["output_share"]) == (512, 160, "(client+1)/clients")
    driver = manifest.load_module(REPO, "benchmark", "drivers", "serve_closed_loop")
    lists = driver.client_lists(t, 40)
    assert sum(l[0][0] for l in lists) == 40 * 512 + 160 * 39 * 40 // 2 == 145280                   # set-up's prefill
    assert all(p + o <= 8192 and p >= 512 for l in lists for p, o in l)
    chunks = [-(-p // 1024) for l in lists for p, _ in l[1:]]
    assert 3.5 < sum(chunks) / len(chunks) < 4.5                                                   # about four chunks a refill


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A root of new files only: the tiny configuration beside links to the real code."""
    root = tiny_root.make(tmp_path_factory.mktemp("bench_granite"))
    with open(os.path.join(root, "benchmark", "configs", "tiny-granite.json"), "w") as f:
        json.dump(TINY, f)
    m = manifest.load_manifest(root)
    cell = "tiny-granite.closed"
    m["configs"].append({"name": "tiny-granite", "source": "test", "file": "benchmark/configs/tiny-granite.json",
                         "reduced": ["num_local_experts"], "why": "test"})
    m["workloads"].append({"name": cell, "config": "tiny-granite", "traffic": "tiny-closed", "chips": 1, "why": "test"})
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

    before = {path: os.stat(os.path.join(REPO, "benchmark", path)).st_mtime_ns
              for path in ("run.py", "drivers/serve_closed_loop.py", "drivers/_serving.py", "harness/manifest.py", "layer_metrics/_program.py")}
    cell, records = _drive(tiny, tmp_path, seconds=1.0)
    monkeypatch.setattr(device, "describe", lambda devs, trace=None: {"platform": "cpu", "kind": "cpu", "count": 1})
    line = bench_run.result_line(cell, records, jax.devices()[:1], trace_on=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_rel_rms", "cache_rel_rms", "cache_row_rel_rms", "state_rel_rms", "tail_rel_rms", "token_below_best",
                                     "routing_below_kth", "state_on_bf16_grid", "compiles_in_window"}
    check = records.check
    assert check["positions"] == 51 and check["slots_decoding"] == 4 and check["prompt_lengths"] == [77, 31, 8]
    assert check["logit_rel_rms"] < 1e-4 and check["cache_row_rel_rms"] < 1e-4 and check["state_rel_rms"] < 1e-4 and check["tail_rel_rms"] < 1e-5
    assert sum(records.tick_admitted[i] for i in records.inside(records.tick_end)) > 0          # slots were refilled
    # a slot's rows: one attention layer x keys and values x 2 heads x 128 x 16 float32; its state: 3 layers x (8 x 16 x 16 x 4 + 3 x 160 x 4)
    gauges = metrics.gauges("infer.")
    assert gauges["infer.kv_bytes_per_slot"] == 2 * 2 * 128 * 16 * 4 and gauges["infer.latent_bytes_per_slot"] == 0
    assert gauges["infer.state_bytes_per_slot"] == 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    layer = {k: v["value"] for k, v in bench_run.compute_metrics(cell, cell.per_layer, records).items()}
    assert 0 < layer["routed_experts_hit_pct"] <= 100
    # no device trace on the CPU: one made from the programs the run compiled, 1 ms an op that carries a scope
    fam = cell.family
    scopes = {program: introspect.op_scopes()[fam.SCOPES_OF_PROGRAM[program]] for program in (fam.DECODE_PROGRAM,) + fam.CHUNK_PROGRAMS}
    runs = 5
    ticks = records.inside(records.tick_end)[-runs:]
    records.traced = (ticks[0], ticks[-1])
    modules = {f"jit_{program}": [len(ops) * 1e6] * runs for program, ops in scopes.items()}
    records.trace = trace.TraceSummary(
        window_ns=(0.0, 1e9), devices=[0], busy_ns={0: sum(sum(d) for d in modules.values())}, op_ns={}, gap_ns={}, collective_ns={},
        collective_exposed_ns={}, modules=modules,
        op_ns_by_program={f"jit_{program}": {f"{op} fusion f32[4]": runs * 1e6 for op in ops} for program, ops in scopes.items()})
    got = {k: v["value"] for k, v in bench_run.compute_metrics(cell, cell.per_layer, records).items()}
    assert set(got) == set(NEW_READERS) | set(SHARED_READERS)
    parts = {program: {part: sum(1 for path in ops.values() if _program.part_of(path, fam.PART_OF_SCOPE) == part)
                       for part in ("ssm", "attn", "routed", "mlp", "head_loss")} for program, ops in scopes.items()}
    decode = parts[fam.DECODE_PROGRAM]
    assert all(decode.values()) and all(parts[p]["ssm"] and parts[p]["routed"] for p in fam.CHUNK_PROGRAMS)
    assert got["decode_ssm_ms"] == pytest.approx(decode["ssm"]) and got["decode_attn_ms"] == pytest.approx(decode["attn"])
    assert got["decode_routed_ms"] == pytest.approx(decode["routed"]) and got["decode_mlp_ms"] == pytest.approx(decode["mlp"])
    assert got["decode_head_ms"] == pytest.approx(decode["head_loss"])
    chunk_mean = lambda part: sum(parts[p][part] for p in fam.CHUNK_PROGRAMS) / 2  # noqa: E731   as many executions of each
    assert got["prefill_ssm_ms"] == pytest.approx(chunk_mean("ssm")) and got["prefill_routed_ms"] == pytest.approx(chunk_mean("routed"))
    decoding = [records.tick_decoding[i] for i in ticks if records.tick_decoding[i]]
    v5e = peaks.PEAKS["TPU v5 lite"]
    assert got["decode_ssm_roofline"] == pytest.approx(
        100.0 * fam.ssm_step_floor_s(cell.config, sum(decoding) / len(decoding), v5e) / (decode["ssm"] * 1e-3))
    assert got["prefill_ssm_roofline"] == pytest.approx(100.0 * fam.ssm_chunk_floor_s(cell.config, 16, v5e) / (chunk_mean("ssm") * 1e-3))
    assert got["decode_step_roofline"] > 0 and got["decode_routed_roofline"] > 0
    # ... with no file of ``benchmark/`` changed
    assert before == {path: os.stat(os.path.join(REPO, "benchmark", path)).st_mtime_ns for path in before}


def test_the_new_readers_find_nothing_in_a_family_without_a_state_space_part(real):
    """On the parent's program, or in a cell of another family, the five readers return None and raise nothing."""
    giga = manifest.resolve_cell(real, GIGA, REPO)
    records = Records(cell=giga, seed=1, seconds=1.0, chips=1, peaks=peaks.PEAKS["TPU v5 lite"])
    readers = [manifest.load_module(REPO, "benchmark", "layer_metrics", name) for name in NEW_READERS]
    assert [r.read(records) for r in readers] == [None] * 5                                     # no trace
    records.trace = trace.TraceSummary(window_ns=(0.0, 1e9), devices=[0], busy_ns={0: 1e6}, op_ns={}, gap_ns={}, collective_ns={},
                                       collective_exposed_ns={}, modules={"jit_decode_fn": [1e6]}, op_ns_by_program={})
    assert [r.read(records) for r in readers] == [None] * 5                                     # no ssm part, no floors


@pytest.mark.parametrize("fault", ["state_held_in_bfloat16", "state_not_reset_at_admission", "conv_tail_not_handed_over",
                                   "conv_tail_held_in_float8", "gate_after_the_norm"])
def test_a_planted_fault_is_seen(tiny, monkeypatch, tmp_path, fault):
    """The family's four controls (``planted``: what ``python3 -m benchmark.families.granite_moe_hybrid <control> ...``
    runs on the chip), and the gate applied after the norm instead of before it: each planted from outside the program,
    each not correct."""
    from paddle_tpu.inference import aot_cache
    from paddle_tpu.models import granite_moe_hybrid as gmh

    family = manifest.resolve_cell(tiny[1], tiny[2], tiny[0]).family
    planted_at = lambda: (gmh.ssd_step, gmh.ssd_chunked, gmh._admitting, gmh._ssm_chunk, gmh._ssm_decode, aot_cache.cache_dir)  # noqa: E731
    sound = planted_at()
    if fault in family.CONTROLS:
        with family.planted(fault):
            assert aot_cache.cache_dir() is None          # a planted program neither loads a sound executable nor leaves its own
            _, records = _drive(tiny, tmp_path, seconds=0.3)
        assert planted_at() == sound                      # and nothing stays planted
    else:
        import jax.numpy as jnp

        def norm_then_gate(cfg, lp, y, z, dtype):
            o = gmh._rms_norm(y.reshape(y.shape[0], -1), lp["ssm_norm"], cfg.rms_norm_eps) * jax.nn.silu(z)
            return jnp.matmul(o.astype(dtype), lp["ssm_out"])

        monkeypatch.setattr(gmh, "_ssm_out", norm_then_gate)
        _, records = _drive(tiny, tmp_path, seconds=0.3)
    check = records.check
    assert check["correct"] is False
    assert any(number > limit for number, limit in check["compared"].values())
    assert np.isfinite(check["logit_rel_rms"])
    if fault == "state_held_in_bfloat16":
        # every element of the state a bfloat16 number
        assert check["compared"]["state_on_bf16_grid"][0] == 1.0 and 1e-4 < check["state_rel_rms"]
    if fault == "state_not_reset_at_admission":
        assert max(check["state_rel_rms"], check["cache_row_rel_rms"]) > 1e-2
    if fault == "conv_tail_held_in_float8":
        # three bits of mantissa: 2^-4 / sqrt(3) of a number, on the tail itself
        assert 1e-2 < check["tail_rel_rms"] < 0.1 and check["tail_rel_rms"] > family.SERVE_TAIL_REL_RMS
    if fault == "conv_tail_not_handed_over":
        # the three rows behind each seam are another convolution's: a few rows of a prompt, far off; the tail a final chunk
        # leaves is its own rows' and is where it should be
        assert check["cache_row_rel_rms"] > family.SERVE_CACHE_ROW_REL_RMS and check["tail_rel_rms"] < 1e-5
        assert check["cache_row_rel_rms_by_prompt"][2] < 1e-4                   # a final chunk alone has no seam
