"""The cell ``evabyte-6.5b.serve-bytegen``: its entries in the real manifest (by
name), its sizes against hand arithmetic at the published widths, and the
family driven through the closed loop at tiny sizes on the CPU — ``correct``
true as it is, false with each of its controls planted.

Also, by name, what two accepted tests of ``test_itl_readers.py`` hold and an
eighth cell trips where they pin a count or the end of a list
(``len(real["workloads"]) == 7``, ``real["per_layer"][-4:]``): everything else
either asserts is asserted here (``tests/conftest.py`` has the two marks)."""
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

import test_granite_cell as granite
import tiny_root

REPO = tiny_root.REPO
CELL, CONFIG = "evabyte-6.5b.serve-bytegen", "evabyte-6.5b"
GIGA, SOLAR = "gigachat3.5-432b-a28b.serve-longdoc", "solar-open2-250b.serve-reasoning"
NEW_READERS = ("decode_eva_ms", "decode_eva_roofline", "eva_summary_rows_pct")
SHARED_READERS = ("decode_step_ms", "decode_step_roofline", "serve_device_idle_pct", "fleet_self_ms", "sched_self_ms",
                  "sched_queue_p50_ms", "decode_launch_ms", "engine_decode_step_ms", "decode_attn_ms", "decode_mlp_ms",
                  "decode_head_ms", "prefill_share_pct")
GAP_READERS = ("sched_itl_p95_ms", "itl_tail_chunks", "chunk_gaps_pct", "multi_chunk_gaps_pct")
GAP_CELLS = [granite.CELL, "gpt2-medium.serve-chat", "cerebras-gpt-1.3b.serve-longgen"]
# https://huggingface.co/EvaByte/EvaByte/blob/main/config.json as published
SOURCE = json.loads("""
{"attention_bias": false, "attention_class": "eva", "chunk_size": 16, "fp32_ln": false, "fp32_logits": true,
"fp32_skip_add": true, "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": null, "init_fn": "v2",
"init_std": 0.01275, "intermediate_size": 11008, "lazy_init": true, "max_position_embeddings": 32768,
"max_seq_length": 32768, "mixedp_attn": true, "model_type": "evabyte", "norm_add_unit_offset": true,
"num_attention_heads": 32, "num_chunks": null, "num_hidden_layers": 32, "num_key_value_heads": 32, "num_pred_heads": 8,
"rms_norm_eps": 1e-05, "rope_scaling": null, "rope_theta": 100000, "tie_word_embeddings": false, "vocab_size": 320,
"window_size": 2048}
""")
TINY = {
    "family": "evabyte", "source": "test", "model_type": "evabyte", "hidden_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 320, "window_size": 32,
    "chunk_size": 4, "rope_theta": 100000, "rms_norm_eps": 1e-5, "max_position_embeddings": 128, "init_std": 0.01275,
    "reduced": ["num_hidden_layers"], "published": {"num_hidden_layers": 4},
    "deployment": "2 chips hold the depth as pipeline stages of 2 layers",
    "serving": {"dtype": "float32", "slots": 4, "context": 128, "prefill_chunk": 16, "fuse": 1,
                "prefix_cache_mb": 0, "replicas": 1, "max_queue_depth": 64},
}


@pytest.fixture(scope="module")
def real():
    return manifest.load_manifest(REPO)


def test_the_real_manifest_holds_the_configuration_the_cell_and_the_three_readers_by_name(real):
    assert manifest.check_manifest(real, REPO) == []
    by_name = {group: {e["name"]: e for e in real[group]} for group in ("configs", "workloads", "per_layer", "end_to_end")}
    cell = manifest.resolve_cell(real, CELL, REPO)
    assert cell.chips == 1 and cell.config["family"] == "evabyte" and cell.traffic["driver"] == "serve_closed_loop"
    assert by_name["workloads"][CELL]["config"] == CONFIG and by_name["workloads"][CELL]["traffic"] == "bytegen-saturated"
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]                 # ``out_tok_s`` stays off
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(NEW_READERS) | set(SHARED_READERS) and len(names) == len(set(names)) == 15
    assert all(m["moves"] == "itl_p95_ms" for m in cell.per_layer)
    for name, unit, better, source, layer in (("decode_eva_ms", "ms", "lower", "device_trace", "kernels"),
                                              ("decode_eva_roofline", "%", "higher", "device_trace", "kernels"),
                                              ("eva_summary_rows_pct", "%", "higher", "program_counter", "KV cache")):
        assert by_name["per_layer"][name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                                              "moves": "itl_p95_ms", "workloads": [CELL]}
    assert names[-3:] == list(NEW_READERS) and [m["name"] for m in real["per_layer"][-3:]] == list(NEW_READERS)  # appended
    assert real["workloads"][-1]["name"] == CELL and real["configs"][-1]["name"] == CONFIG
    # not the token gaps' four (their test lists three cells), nor what misreads a saturated closed loop, nor
    # ``prefill_chunk_ms``: the traffic admits the same lengths at the same ticks at every seed, and no refill falls in the
    # traced last 5 s of a 51-s window, so it finds no chunk to read there (PERF.md §7)
    assert not (set(GAP_READERS) | {"prefill_chunk_ms", "idle_in_program_spans_pct", "decode_unscoped_ms", "prefill_cache_ms", "ttft_p50_ms",
                                    "ttft_p95_ms", "ttft_mean_ms", "gen_late_ms", "out_tok_s"}) & set(names)
    assert by_name["configs"][CONFIG]["reduced"] == cell.config["reduced"] == ["num_hidden_layers", "num_pred_heads"]
    assert by_name["configs"][CONFIG]["source"] == cell.config["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"


def test_what_the_two_pinned_itl_tests_hold_holds_by_name(real):
    """``test_itl_readers.py``'s ``test_the_four_entries_are_appended_and_list_three_cells`` and
    ``test_granites_entries_are_what_its_pr_left_by_name_and_these_four`` but the count of cells and the end of the
    list of readers: the four token-gap readers' fields and their three cells; Granite's entries; one chip in four;
    the other cells' readers as they were."""
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1 and len(real["workloads"]) == 8
    by_name = {group: {e["name"]: e for e in real[group]} for group in ("configs", "workloads", "per_layer", "end_to_end")}
    for name, unit, source in zip(GAP_READERS, ("ms", "chunks", "%", "%"), ("program_span",) + ("program_counter",) * 3):
        assert by_name["per_layer"][name] == {"name": name, "unit": unit, "better": "lower", "source": source, "layer": "scheduler",
                                              "moves": "itl_p95_ms", "workloads": GAP_CELLS}
        assert callable(manifest.load_module(REPO, "benchmark", "layer_metrics", name).read)
    for cell in GAP_CELLS:
        assert set(GAP_READERS) <= {m["name"] for m in manifest.resolve_cell(real, cell, REPO).per_layer}
    names = [m["name"] for m in real["per_layer"]]
    assert names.index(GAP_READERS[0]) == len(names) - 7 and names[-7:-3] == list(GAP_READERS)   # theirs, then this cell's
    # Granite's cell
    g = manifest.resolve_cell(real, granite.CELL, REPO)
    assert g.chips == 1 and g.config["family"] == "granite_moe_hybrid" and g.traffic["driver"] == "serve_closed_loop"
    assert by_name["workloads"][granite.CELL]["config"] == granite.CONFIG and by_name["workloads"][granite.CELL]["traffic"] == "rag-saturated"
    assert [m["name"] for m in g.end_to_end] == ["itl_p95_ms", "setup_s"]
    gnames = [m["name"] for m in g.per_layer]
    assert set(gnames) == set(granite.NEW_READERS) | set(granite.SHARED_READERS) | set(GAP_READERS) and len(gnames) == len(set(gnames)) == 25
    assert granite.CELL not in by_name["per_layer"]["idle_in_program_spans_pct"]["workloads"]
    assert all(m["moves"] == "itl_p95_ms" for m in g.per_layer)
    assert all(by_name["per_layer"][name]["workloads"] == [granite.CELL] and by_name["per_layer"][name]["source"] == "device_trace"
               for name in granite.NEW_READERS)
    assert by_name["per_layer"]["decode_ssm_roofline"]["unit"] == by_name["per_layer"]["prefill_ssm_roofline"]["unit"] == "%"
    assert not {"decode_unscoped_ms", "prefill_cache_ms", "ttft_p50_ms", "ttft_p95_ms", "ttft_mean_ms", "gen_late_ms", "out_tok_s"} & set(gnames)
    assert by_name["configs"][granite.CONFIG]["reduced"] == g.config["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert by_name["configs"][granite.CONFIG]["source"] == g.config["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    # the cells that were there report what they reported: GigaChat's twenty and Solar's sixteen, none of Granite's, of the
    # token gaps' or of this cell's
    for other, count in ((GIGA, 20), (SOLAR, 16)):
        theirs = {m["name"] for m in manifest.resolve_cell(real, other, REPO).per_layer}
        assert len(theirs) == count and not (set(granite.NEW_READERS) | set(GAP_READERS) | set(NEW_READERS)) & theirs


@pytest.fixture
def ring():
    """``ring(cell, notes)``: records of a window of one tick a decode step, the program's ring of span records holding
    one ``infer.decode_step`` record a step with ``notes`` (the ring is put back after the test)."""
    from paddle_tpu.observability import spans

    saved = list(spans._RING)

    def fill(cell, notes):
        records = Records(cell=cell, seed=1, seconds=1.0, chips=1, peaks=peaks.PEAKS["TPU v5 lite"])
        records.window_open, records.window_close = 1.0, 2.0 + len(notes)
        records.tick_end = [1.5 + k for k in range(len(notes))]
        spans._RING.clear()
        for k, attrs in enumerate(notes):
            s = spans.Span("infer.decode_step", attrs=dict(attrs))
            s.start_ns, s.end_ns, s.span_id, s.parent_id = int((1.1 + k) * 1e9), int((1.4 + k) * 1e9), f"e{k}", None
            spans._RING.append(s)
        return records

    yield fill
    spans._RING.clear()
    spans._RING.extend(saved)


def test_the_configuration_is_the_sources_at_published_widths_and_its_arithmetic(real, ring):
    cell = manifest.resolve_cell(real, CELL, REPO)
    cfg, fam = cell.config, cell.family
    # every key of the source's config.json as published but the two reduced, whose published values the file keeps
    assert {k for k, v in SOURCE.items() if cfg.get(k, "missing") != v} == set(cfg["reduced"])
    assert cfg["published"] == {k: SOURCE[k] for k in cfg["reduced"]} == {"num_hidden_layers": 32, "num_pred_heads": 8}
    assert (cfg["num_hidden_layers"], cfg["num_pred_heads"]) == (8, 1)
    assert all(k in cfg for k in ("published", "deployment", "held_here", "assumed"))
    assert set("abcde") == {v[1] for v in cfg["assumed"].values() if v.startswith("(")}
    assert cfg["serving"] == {"dtype": "bfloat16", "slots": 16, "context": 32768, "prefill_chunk": 1024, "fuse": 1,
                              "prefix_cache_mb": 0, "replicas": 1, "max_queue_depth": 4096}
    z = fam.dims(cfg)
    assert (z["D"], z["L"], z["H"], z["d"], z["F"], z["V"], z["W"], z["C"]) == (4096, 8, 32, 128, 11008, 320, 2048, 16)
    # the stage's arithmetic, parameter for parameter: a layer is attention + MLP + norms + phi, and the stage holds eight
    shapes = fam.weight_shapes(cfg)
    count = lambda *names: sum(int(np.prod(shapes[n])) for n in names)  # noqa: E731
    assert count("attn_qkv", "attn_out") == 8 * 67_108_864 and count("mlp_gate_up", "mlp_down") == 8 * 135_266_304
    assert count("norm1", "norm2") == 8 * 8_192 and count("eva_phi") == 8 * 4_096
    assert count("embed", "head", "final_norm") == 2_625_536
    assert fam.param_count(cfg) == 8 * 202_387_456 + 2_625_536 == 1_621_725_184
    # a row is a key and a value of 32 x 128 in bfloat16; a slot holds a ring of 2,048 and a table of 32,768 / 16 a layer
    assert fam.row_bytes(cfg) == 16_384 and fam.slot_bytes(cfg) == 8 * (2048 + 2048) * 16_384 == 536_870_912
    # the EVA core's floor: the live rows and summaries the program counted (here two traced steps), x 16,384 bytes x 8
    # layers, with the rows and summaries written, at 819 GB/s; the decode step's: that and every weight once
    records = ring(cell, [dict(eva_ring_rows=9_000, eva_summary_rows=5_000, eva_rows_written=16, eva_summaries_written=1),
                                         dict(eva_ring_rows=9_016, eva_summary_rows=5_000, eva_rows_written=16, eva_summaries_written=3)])
    v5e = peaks.PEAKS["TPU v5 lite"]
    assert fam.eva_rows_per_step(records) == (9_008, 5_000, 16, 2)
    assert fam.eva_step_floor_s(cfg, records, v5e) == pytest.approx(8 * (9_008 + 5_000 + 16 + 2) * 16_384 / 819e9)
    assert fam.decode_step_bytes(cfg, 16 * 10_000.0, records) == pytest.approx(2 * 1_621_725_184 + 8 * 14_026 * 16_384)
    assert fam.decode_step_bytes(cfg, 16 * 10_000.0) == 2 * 1_621_725_184       # nothing counted: the weights alone
    empty = ring(cell, [])
    assert fam.eva_step_floor_s(cfg, empty, v5e) is None and fam.eva_rows_per_step(empty) is None


def test_the_traffic_file_holds_the_parameters_asked_for(real):
    t = manifest.resolve_cell(real, CELL, REPO).traffic
    assert t["clients"] == "slots" and t["stream_seed"] == 40 and t["warmup_ticks"] == 24 and t["max_total_tokens"] == 32768
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 6144, "sigma": 0.6, "min": 2048, "max": 16384}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024, "max": 12288}
    assert (t["first_request"]["prompt_base"], t["first_request"]["prompt_step"], t["first_request"]["output_share"]) == (2048, 1280, "(client+1)/clients")
    driver = manifest.load_module(REPO, "benchmark", "drivers", "serve_closed_loop")
    lists = driver.client_lists(t, 16)
    assert sum(l[0][0] for l in lists) == 16 * 2048 + 1280 * 15 * 16 // 2 == 186_368                 # set-up's prefill
    assert all(p + o <= 32768 and p >= 2048 for l in lists for p, o in l)
    # the slots sit thousands of bytes deep: a refill's prompt spans three windows on the median
    prompts = [p for l in lists for p, _ in l[1:]]
    assert 5000 < float(np.median(prompts)) < 7500


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A root of new files only: the tiny configuration beside links to the real code."""
    root = tiny_root.make(tmp_path_factory.mktemp("bench_evabyte"))
    with open(os.path.join(root, "benchmark", "configs", "tiny-evabyte.json"), "w") as f:
        json.dump(TINY, f)
    m = manifest.load_manifest(root)
    cell = "tiny-evabyte.closed"
    m["configs"].append({"name": "tiny-evabyte", "source": "test", "file": "benchmark/configs/tiny-evabyte.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({"name": cell, "config": "tiny-evabyte", "traffic": "tiny-closed", "chips": 1, "why": "test"})
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

    before = {path: os.stat(os.path.join(REPO, "benchmark", path)).st_mtime_ns
              for path in ("run.py", "drivers/serve_closed_loop.py", "drivers/_serving.py", "harness/manifest.py", "layer_metrics/_program.py")}
    cell, records = _drive(tiny, tmp_path, seconds=1.0)
    monkeypatch.setattr(device, "describe", lambda devs, trace=None: {"platform": "cpu", "kind": "cpu", "count": 1})
    line = bench_run.result_line(cell, records, jax.devices()[:1], trace_on=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_rel_rms", "cache_rel_rms", "summary_rel_rms", "summary_row_rel_rms", "token_below_best",
                                     "compiles_in_window"}
    check = records.check
    # the tiny context of 128 and chunks of 16: 104 bytes (three windows and a partial one), 89 (seven before the boundary
    # at 96) and 8 (a final chunk alone); 17 positions each, all four slots decoding
    assert check["prompt_lengths"] == [104, 89, 8] and check["positions"] == 51 and check["slots_decoding"] == 4
    assert check["logit_rel_rms"] < 1e-4 and check["cache_rel_rms"] < 1e-5 and check["summary_row_rel_rms"] < 1e-5
    assert sum(records.tick_admitted[i] for i in records.inside(records.tick_end)) > 0          # slots were refilled
    layer = {k: v["value"] for k, v in bench_run.compute_metrics(cell, cell.per_layer, records).items()}
    assert 0 < layer["eva_summary_rows_pct"] < 100                                               # no trace needed
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
                       for part in ("eva", "attn", "mlp", "head_loss")} for program, ops in scopes.items()}
    decode = parts[fam.DECODE_PROGRAM]
    assert all(decode.values()) and all(parts[p]["eva"] and parts[p]["attn"] for p in fam.CHUNK_PROGRAMS)
    assert got["decode_eva_ms"] == pytest.approx(decode["eva"]) and got["decode_attn_ms"] == pytest.approx(decode["attn"])
    assert got["decode_mlp_ms"] == pytest.approx(decode["mlp"]) and got["decode_head_ms"] == pytest.approx(decode["head_loss"])
    v5e = peaks.PEAKS["TPU v5 lite"]
    floor = fam.eva_step_floor_s(cell.config, records, v5e)
    ring, summaries, written, closed = fam.eva_rows_per_step(records)
    # two layers, a key and a value of 4 heads x 64 a row, counted at bfloat16's two bytes whatever the tiny run's dtype
    assert floor == pytest.approx(2 * (ring + summaries + written + closed) * 2 * 4 * 64 * 2 / 819e9)
    assert got["decode_eva_roofline"] == pytest.approx(100.0 * floor / (decode["eva"] * 1e-3))
    assert got["decode_step_roofline"] > 0
    # ... with no file of ``benchmark/`` changed
    assert before == {path: os.stat(os.path.join(REPO, "benchmark", path)).st_mtime_ns for path in before}


def test_the_new_readers_find_nothing_in_a_family_without_an_eva_part(real):
    """On the parent's program, or in a cell of another family, the three readers return None and raise nothing."""
    giga = manifest.resolve_cell(real, GIGA, REPO)
    records = Records(cell=giga, seed=1, seconds=1.0, chips=1, peaks=peaks.PEAKS["TPU v5 lite"])
    readers = [manifest.load_module(REPO, "benchmark", "layer_metrics", name) for name in NEW_READERS]
    assert [r.read(records) for r in readers] == [None] * 3                                      # no trace
    records.trace = trace.TraceSummary(window_ns=(0.0, 1e9), devices=[0], busy_ns={0: 1e6}, op_ns={}, gap_ns={}, collective_ns={},
                                       collective_exposed_ns={}, modules={"jit_decode_fn": [1e6]}, op_ns_by_program={})
    assert [r.read(records) for r in readers] == [None] * 3                                      # no eva part, no floor


@pytest.mark.parametrize("fault", ["summaries_dropped", "summaries_mean_pooled", "summary_of_stale_rows", "rows_held_in_float8"])
def test_a_planted_fault_is_seen(tiny, tmp_path, fault):
    """The family's four controls (``planted``: what ``python3 -m benchmark.families.evabyte <control> ...`` runs on
    the chip), each planted from outside the program, each not correct; and nothing stays planted."""
    from paddle_tpu.inference import aot_cache
    from paddle_tpu.models import evabyte as eb

    family = manifest.resolve_cell(tiny[1], tiny[2], tiny[0]).family
    planted_at = lambda: (eb._ring_write, eb._summarise, eb.eva_decode, eb._pool, eb._summaries_attended, aot_cache.cache_dir)  # noqa: E731
    sound = planted_at()
    with family.planted(fault):
        assert aot_cache.cache_dir() is None          # a planted program neither loads a sound executable nor leaves its own
        _, records = _drive(tiny, tmp_path, seconds=0.3)
    assert planted_at() == sound
    check = records.check
    print(fault, check["compared"])
    assert check["correct"] is False
    assert any(number > limit for number, limit in check["compared"].values())
    assert np.isfinite(check["logit_rel_rms"])
