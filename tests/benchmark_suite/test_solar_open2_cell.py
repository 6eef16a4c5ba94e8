"""The cell ``solar-open2-250b.serve-reasoning``: its entries in the real
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
CELL = "solar-open2-250b.serve-reasoning"
NEW_READERS = ("decode_linear_attn_ms", "decode_routed_ms", "routed_experts_hit_pct", "decode_routed_roofline")
TINY = {
    "family": "solar_open2", "source": "test", "model_type": "solar_open2",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 2, "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 2, "head_dim": 16, "num_key_value_heads": 1,
    "vocab_size": 64, "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "max_position_embeddings": 512,
    "gqa_layers": [0], "kda_allow_neg_eigval": True, "n_routed_experts": 8, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 4, "held_experts": [8, 8],
    "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 16},
    "deployment": "2 chips share every layer's experts, 8 of 16 each", "assumed": {"low_rank": 8},
    "serving": {"dtype": "float32", "slots": 4, "context": 128, "prefill_chunk": 16, "fuse": 1,
                "prefix_cache_mb": 0, "replicas": 1, "max_queue_depth": 64},
}


@pytest.fixture(scope="module")
def real():
    return manifest.load_manifest(REPO)


def test_the_real_manifest_holds_the_configuration_and_its_cell(real):
    assert manifest.check_manifest(real, REPO) == []
    cell = manifest.resolve_cell(real, CELL, REPO)
    assert cell.chips == 1 and cell.config["family"] == "solar_open2" and cell.traffic["driver"] == "serve_closed_loop"
    # judged on the gaps between tokens; ``out_tok_s`` spread 1.8 % and 3.3 % over two sets of six seeds on the chip,
    # over half its 3 % bound, so the cell does not report it, nor the three per-layer metrics that move it (PERF.md §6)
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(names) and "decode_unscoped_ms" not in names and "prefill_cache_ms" not in names
    assert all(m["moves"] == "itl_p95_ms" for m in cell.per_layer) and len(names) == 16
    assert real["workloads"][-1]["name"] == CELL and real["configs"][-1]["name"] == "solar-open2-250b"   # appended
    assert [m["name"] for m in real["per_layer"][-4:]] == list(NEW_READERS)
    # one chip's share: every width the source's, the counts held here reduced
    cfg, z = cell.config, cell.family.dims(cell.config)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) == (4096, 128, 1280, 8)
    assert cfg["linear_attn_config"]["head_dim"] == 128 and z["E"] == 320 and z["held"] == (0, 40) and z["R"] == 128
    assert cell.family.param_count(cfg) * 2 / 1e9 == pytest.approx(5.7, abs=0.05)           # GB of weights held
    assert cfg["serving"]["context"] % cfg["serving"]["prefill_chunk"] == 0


def test_the_traffic_file_holds_the_parameters_asked_for(real):
    t = manifest.resolve_cell(real, CELL, REPO).traffic
    assert t["clients"] == "slots" and t["stream_seed"] == 30 and t["warmup_ticks"] == 24 and t["max_total_tokens"] == 16384
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.6, "min": 512, "max": 8192}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 6144}
    assert (t["first_request"]["prompt_base"], t["first_request"]["prompt_step"]) == (512, 96)
    driver = manifest.load_module(REPO, "benchmark", "drivers", "serve_closed_loop")
    lists = driver.client_lists(t, 128)
    assert sum(l[0][0] for l in lists) == 845824                                             # set-up's prefill
    assert all(p + o <= 16384 and p >= 512 for l in lists for p, o in l)


def test_bytes_of_a_decode_step_from_shapes_and_counts(real):
    cell = manifest.resolve_cell(real, CELL, REPO)
    fam, cfg = cell.family, cell.config
    assert fam.expert_bytes(cfg) == 3 * 4096 * 1280 * 2
    everything = fam.decode_step_bytes(cfg, live_rows=128 * 4096.0)                          # no count: every held expert
    experts = 4 * 40 * fam.expert_bytes(cfg)
    rows = 2 * 128 * 128 * 4096 * 2
    state = 2 * 128 * 3 * (8 * 128 * 128 * 4 + 3 * 3 * 1024 * 2)
    rest = everything - experts - rows - state
    assert rest == pytest.approx(0.474e9, rel=0.01)                                          # mixers, shared experts, routers, head


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A root of new files only: the tiny configuration beside links to the real code."""
    root = tiny_root.make(tmp_path_factory.mktemp("bench_solar"))
    with open(os.path.join(root, "benchmark", "configs", "tiny-solar.json"), "w") as f:
        json.dump(TINY, f)
    m = manifest.load_manifest(root)
    cell = "tiny-solar.closed"
    m["configs"].append({"name": "tiny-solar", "source": "test", "file": "benchmark/configs/tiny-solar.json",
                         "reduced": ["n_routed_experts"], "why": "test"})
    m["workloads"].append({"name": cell, "config": "tiny-solar", "traffic": "tiny-closed", "chips": 1, "why": "test"})
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
    from paddle_tpu.observability import introspect

    cell, records = _drive(tiny, tmp_path, seconds=1.0)
    monkeypatch.setattr(device, "describe", lambda devs, trace=None: {"platform": "cpu", "kind": "cpu", "count": 1})
    line = bench_run.result_line(cell, records, jax.devices()[:1], trace_on=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_rel_rms", "cache_rel_rms", "state_rel_rms", "token_below_best", "routing_below_kth",
                                     "state_on_bf16_grid", "compiles_in_window"}
    assert records.check["positions"] == 34 and records.check["logit_rel_rms"] < 1e-4
    assert sum(records.tick_admitted[i] for i in records.inside(records.tick_end)) > 0          # slots were refilled
    # what needs no trace: the program's count of the experts hit, a step
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
             for part in ("attn", "linear", "routed", "mlp", "head_loss")}
    assert all(parts.values())
    assert got["decode_linear_attn_ms"] == pytest.approx(parts["linear"]) and got["decode_routed_ms"] == pytest.approx(parts["routed"])
    assert got["decode_attn_ms"] == pytest.approx(parts["attn"]) and got["decode_mlp_ms"] == pytest.approx(parts["mlp"])
    hit = cell.family.experts_hit_per_step(records)
    assert 0 < hit <= 4 * 8
    want = hit * cell.family.expert_bytes(cell.config) + 4 * 64 * 16 * 2
    assert got["decode_routed_roofline"] == pytest.approx(100.0 * want / 819e9 / (parts["routed"] * 1e-3))
    assert got["decode_step_roofline"] > 0


@pytest.mark.parametrize("fault", ["state_not_reset_at_admission", "beta_without_the_factor_two", "state_held_in_bfloat16"])
def test_a_planted_fault_is_seen(tiny, monkeypatch, tmp_path, fault):
    from paddle_tpu.models import solar_open2 as so2

    if fault == "state_not_reset_at_admission":
        # the first prefill program of a slot no longer starts from zeros: what the last request left is read
        monkeypatch.setattr(so2, "_admitting", lambda start: False)
    elif fault == "beta_without_the_factor_two":
        init = so2.SolarOpen2Config.__init__

        def without(self, **kw):
            init(self, **dict(kw, allow_neg_eigval=False))

        monkeypatch.setattr(so2.SolarOpen2Config, "__init__", without)
    else:
        root, m, name = tiny
        cell = manifest.resolve_cell(m, name, root)
        cell.config["serving"]["state_dtype"] = "bfloat16"
        records = Records(cell=cell, seed=3000000019, seconds=0.3, chips=1, peaks=peaks.PEAKS["TPU v5 lite"])
        cell.driver.run(records, jax.devices()[:1], process_start=time.perf_counter(), trace_on=False, trace_dir=str(tmp_path))
        # the limits are set for bfloat16 weights at the published widths, on the chip (PERF.md §6); here, in float32
        # over some fifty tokens, the state's own comparison has to read the rounding (as it is: 1.8e-7)
        assert records.check["state_rel_rms"] > 1e-3 and records.check["logit_rel_rms"] > 1e-4
        assert records.check["correct"] is False and records.check["compared"]["state_on_bf16_grid"] == [1.0, 0.5]
        return
    _, records = _drive(tiny, tmp_path, seconds=0.3)
    assert records.check["correct"] is False
    assert any(number > limit for number, limit in records.check["compared"].values())
    assert np.isfinite(records.check["logit_rel_rms"])
