"""The four readers of the program's own token gaps (PR 38: ``sched_itl_p95_ms``, ``itl_tail_chunks``,
``chunk_gaps_pct``, ``multi_chunk_gaps_pct``) on hand-made span records and once through the tiny closed loop on the
CPU, beside the outside ``itl_p95_ms``; and, by name, what ``test_granite_cell.py`` holds of Granite's cell where it
also pins the cell's readers to the twenty-one PR 36 left — which no PR that gives the cell a reader can keep and
none but a ``benchmark`` PR may edit (``tests/conftest.py`` has the two marks)."""
import os
import types

import jax
import pytest

import test_granite_cell as granite                  # its constants, its tiny root and its drive; none of its tests
import tiny_root
from benchmark import run as bench_run
from benchmark.harness import device, manifest, peaks, stats, trace
from benchmark.layer_metrics import _program
from paddle_tpu.observability import introspect, metrics, spans

REPO = tiny_root.REPO
READERS = ("sched_itl_p95_ms", "itl_tail_chunks", "chunk_gaps_pct", "multi_chunk_gaps_pct")
CELL, CONFIG, GIGA, SOLAR = granite.CELL, granite.CONFIG, granite.GIGA, "solar-open2-250b.serve-reasoning"
LISTED = [CELL, "gpt2-medium.serve-chat", "cerebras-gpt-1.3b.serve-longgen"]
MS = 1_000_000
tiny = granite.tiny                                  # the module-scoped root with ``tiny-granite.closed``


def reader(name):
    return manifest.load_module(REPO, "benchmark", "layer_metrics", name)


def _span(name, start_ms, end_ms, sid, parent=None, **attrs):
    s = spans.Span(name, attrs=attrs or None)
    s.start_ns, s.end_ns, s.span_id, s.parent_id = int(start_ms * MS), int(end_ms * MS), sid, parent
    return s


def _tick(n, end_ms, gaps_ms=None, before=None):
    """A scheduler tick that ended at ``end_ms`` with the decode step's record under it: ``gaps_ms`` = the tick's
    distinct ``[gap in ms, count]`` or ``[gap in ms, count, chunks inside]`` (None: the parent's program, which notes
    none), ``before`` = the prefill programs the engine queued before the pulled step, which is what ran inside a gap
    that says nothing else (None: a tick without a decode step)."""
    noted = {} if gaps_ms is None else {"gaps": [[int(g[0] * MS), g[1], g[2] if len(g) > 2 else before] for g in gaps_ms]}
    out = [_span("infer.sched.step", end_ms - 10, end_ms, f"s{n}", f"f{n}", **noted)]
    if before is not None:
        out.insert(0, _span("infer.decode_step", end_ms - 9, end_ms - 1, f"d{n}", f"s{n}", experts_hit=7))
    return out


# tick 0 ended before the window opened; 100 gaps in the window: 80 x 10 ms, 1 x 12, 10 x 30, 6 x 50, 3 x 70. Tick 3's
# step had one chunk queued before it and tick 4's two: so have the gaps between two pulls, while a request's first gap in
# the same tick (9 ms, 8 ms: its own prefill ran before its first token) holds none, and says so itself
STAIR = [(0, 90, [[900, 5]], 7), (1, 110, [[10, 60]], 0), (2, 120, [[10, 20], [12, 1]], 0), (3, 150, [[30, 10], [9, 1, 0]], 1),
         (4, 200, [[50, 6], [8, 1, 0]], 2), (5, 270, [[70, 3]], 3), (6, 280, [], None)]


@pytest.fixture
def ring():
    saved = list(spans._RING)
    spans._RING.clear()

    def fill(ticks):
        spans._RING.clear()
        spans._RING.extend(s for tick in ticks for s in _tick(*tick))
        return types.SimpleNamespace(window_open=0.099, window_close=0.299, trace=None)

    yield fill
    spans._RING.clear()
    spans._RING.extend(saved)


@pytest.mark.parametrize("name, want", [
    ("sched_itl_p95_ms", 50.0),                    # of 102: order statistics 95 and 96 (95.95) are both 50 ms
    ("itl_tail_chunks", 2.0),                      # ranks 95.88 to 97.92 of 102 lie in the six 50-ms gaps (ranks 93 to 99): two chunks,
                                                   # not the 2.33 that the nine gaps at or above the p95 hold on average
    ("chunk_gaps_pct", 100 * 19 / 102),            # 10 + 6 + 3 of 102: the two first gaps hold none
    ("multi_chunk_gaps_pct", 100 * 9 / 102),       # 6 + 3
])
def test_the_readers_weigh_each_distinct_gap_by_its_count(ring, name, want):
    assert reader(name).read(ring(STAIR)) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("long_ones, want", [
    (10, 2.0),       # ranks 94 to 96 of 100 lie among the ten long gaps
    (5, 1.0),        # the p95 sits on the edge: one rank among the short gaps (no chunk), one among the long (two chunks)
    (3, 0.0),        # the p95 sits at the bare tick, and the chunk gaps lie above it
])
def test_the_tail_is_the_band_around_the_p95_and_not_all_above_it(ring, long_ones, want):
    records = ring([(1, 150, [[10, 100 - long_ones]], 0), (2, 250, [[50, long_ones - 1], [500, 1, 7]], 2)])
    assert reader("itl_tail_chunks").read(records) == pytest.approx(want, abs=1e-9)


def test_the_weighted_p95_is_the_percentile_of_the_gaps_written_out(ring):
    ticks = [(n, 100 + n, [[3 + (n * 7) % 11, 1 + n % 5], [40 + n, 1]], n % 3) for n in range(1, 60)]
    written_out = [g for _, _, gaps, _ in ticks for g, c in gaps for _ in range(c)]
    assert reader("sched_itl_p95_ms").read(ring(ticks)) == pytest.approx(stats.percentile(written_out, 95.0))


@pytest.mark.parametrize("name", ["itl_tail_chunks", "chunk_gaps_pct", "multi_chunk_gaps_pct"])
def test_a_window_whose_gaps_hold_no_chunk_reads_zero_and_not_none(ring, name):
    records = ring([(n, end, [g[:2] for g in gaps], 0 if before is not None else None) for n, end, gaps, before in STAIR])
    assert reader(name).read(records) == 0.0 and reader("sched_itl_p95_ms").read(records) == pytest.approx(50.0)


@pytest.mark.parametrize("name", READERS)
def test_the_parents_records_carry_no_gaps_and_read_none(ring, name, monkeypatch):
    assert reader(name).read(ring([(n, end, None, before) for n, end, _, before in STAIR])) is None
    assert reader(name).read(ring([(6, 280, [], None)])) is None          # the attributes, and a window without a token
    assert reader(name).read(ring([])) is None
    monkeypatch.delattr(spans, "recent")                                  # a program without a ring
    assert reader(name).read(ring(STAIR)) is None


# ------------------------------------------------------ the real manifest
@pytest.fixture(scope="module")
def real():
    return manifest.load_manifest(REPO)


def test_the_four_entries_are_appended_and_list_three_cells(real):
    assert manifest.check_manifest(real, REPO) == []
    assert [m["name"] for m in real["per_layer"][-4:]] == list(READERS)
    for m, unit, source in zip(real["per_layer"][-4:], ("ms", "chunks", "%", "%"), ("program_span",) + ("program_counter",) * 3):
        assert m == {"name": m["name"], "unit": unit, "better": "lower", "source": source, "layer": "scheduler",
                     "moves": "itl_p95_ms", "workloads": LISTED}
    for cell in LISTED:
        assert set(READERS) <= {m["name"] for m in manifest.resolve_cell(real, cell, REPO).per_layer}
    for name in READERS:
        assert callable(reader(name).read)


def test_granites_entries_are_what_its_pr_left_by_name_and_these_four(real):
    """Everything ``test_granite_cell.py::test_the_real_manifest_holds_the_configuration_the_cell_and_the_five_readers_by_name``
    holds, but the count: the cell's readers are PR 36's twenty-one and these four, no other."""
    assert manifest.check_manifest(real, REPO) == []
    assert len(real["workloads"]) == 7 and sum(w["chips"] == 4 for w in real["workloads"]) == 1
    by_name = {group: {e["name"]: e for e in real[group]} for group in ("configs", "workloads", "per_layer", "end_to_end")}
    cell = manifest.resolve_cell(real, CELL, REPO)
    assert cell.chips == 1 and cell.config["family"] == "granite_moe_hybrid" and cell.traffic["driver"] == "serve_closed_loop"
    assert by_name["workloads"][CELL]["config"] == CONFIG and by_name["workloads"][CELL]["traffic"] == "rag-saturated"
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert len(granite.NEW_READERS) + len(granite.SHARED_READERS) == 21
    assert set(names) == set(granite.NEW_READERS) | set(granite.SHARED_READERS) | set(READERS) and len(names) == len(set(names)) == 25
    assert CELL not in by_name["per_layer"]["idle_in_program_spans_pct"]["workloads"]
    assert all(m["moves"] == "itl_p95_ms" for m in cell.per_layer)
    assert all(by_name["per_layer"][name]["workloads"] == [CELL] and by_name["per_layer"][name]["source"] == "device_trace"
               for name in granite.NEW_READERS)
    assert by_name["per_layer"]["decode_ssm_roofline"]["unit"] == by_name["per_layer"]["prefill_ssm_roofline"]["unit"] == "%"
    assert not {"decode_unscoped_ms", "prefill_cache_ms", "ttft_p50_ms", "ttft_p95_ms", "ttft_mean_ms", "gen_late_ms", "out_tok_s"} & set(names)
    assert by_name["configs"][CONFIG]["reduced"] == cell.config["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert by_name["configs"][CONFIG]["source"] == cell.config["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    # the cells that were there report what they reported: GigaChat's twenty and Solar's sixteen, none of Granite's, none of these
    for other, count in ((GIGA, 20), (SOLAR, 16)):
        theirs = {m["name"] for m in manifest.resolve_cell(real, other, REPO).per_layer}
        assert len(theirs) == count and not (set(granite.NEW_READERS) | set(READERS)) & theirs


# ------------------------------------------- the tiny closed loop, on the CPU
def test_the_tiny_closed_loop_reads_the_gaps_the_benchmark_stamps(tiny, monkeypatch, tmp_path):
    """Everything ``test_granite_cell.py::test_the_family_drives_the_closed_loop_and_is_correct`` holds, but that the
    per-layer line is PR 36's twenty-one readers and no other: it is those and these four, and the inside p95 lies
    within 5 % of the outside one."""
    before = {path: os.stat(os.path.join(REPO, "benchmark", path)).st_mtime_ns
              for path in ("run.py", "drivers/serve_closed_loop.py", "drivers/_serving.py", "harness/manifest.py", "layer_metrics/_program.py")}
    evicted = metrics.counter("trace.spans_evicted")
    cell, records = granite._drive(tiny, tmp_path, seconds=1.5)
    assert metrics.counter("trace.spans_evicted") == evicted                                    # the window is whole
    monkeypatch.setattr(device, "describe", lambda devs, trace=None: {"platform": "cpu", "kind": "cpu", "count": 1})
    line = bench_run.result_line(cell, records, jax.devices()[:1], trace_on=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_rel_rms", "cache_rel_rms", "cache_row_rel_rms", "state_rel_rms", "tail_rel_rms", "token_below_best",
                                     "routing_below_kth", "state_on_bf16_grid", "compiles_in_window"}
    check = records.check
    assert check["positions"] == 51 and check["slots_decoding"] == 4 and check["prompt_lengths"] == [77, 31, 8]
    assert check["logit_rel_rms"] < 1e-4 and check["cache_row_rel_rms"] < 1e-4 and check["state_rel_rms"] < 1e-4 and check["tail_rel_rms"] < 1e-5
    inside = records.inside(records.tick_end)
    assert sum(records.tick_admitted[i] for i in inside) > 0                                    # slots were refilled
    gauges = metrics.gauges("infer.")
    assert gauges["infer.kv_bytes_per_slot"] == 2 * 2 * 128 * 16 * 4 and gauges["infer.latent_bytes_per_slot"] == 0
    assert gauges["infer.state_bytes_per_slot"] == 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    layer = {k: v["value"] for k, v in bench_run.compute_metrics(cell, cell.per_layer, records).items()}
    assert 0 < layer["routed_experts_hit_pct"] <= 100
    # the four need no trace. The program's ticks saw what the benchmark's log saw ...
    ticks = [s for s in _program.window_spans(records) if s.name == _program.SCHED_TICK]
    assert len(ticks) == len(inside) and all(set(t.attrs) == {"gaps"} for t in ticks)
    # ... and the gaps the program stamps where the tokens arrive are the gaps the benchmark stamps after the tick
    assert sum(count for t in ticks for _, count, _ in t.attrs["gaps"]) == len(records.itl_samples())
    # (the benchmark's wrapper counts a call that only pulls a deferred first token as a dispatch; the engine does not)
    most = max(inside_gap for t in ticks for _, _, inside_gap in t.attrs["gaps"])
    assert 0 < most <= 2 * max(records.tick_prefill_dispatches[i] for i in inside)
    assert layer["sched_itl_p95_ms"] == pytest.approx(line["metrics"]["itl_p95_ms"]["value"], rel=0.05)
    assert 0 < layer["chunk_gaps_pct"] <= 100 and 0 <= layer["multi_chunk_gaps_pct"] <= layer["chunk_gaps_pct"]
    assert 0 <= layer["itl_tail_chunks"] <= most
    # no device trace on the CPU: one made from the programs the run compiled, 1 ms an op that carries a scope
    fam = cell.family
    scopes = {program: introspect.op_scopes()[fam.SCOPES_OF_PROGRAM[program]] for program in (fam.DECODE_PROGRAM,) + fam.CHUNK_PROGRAMS}
    runs = 5
    last = inside[-runs:]
    records.traced = (last[0], last[-1])
    modules = {f"jit_{program}": [len(ops) * 1e6] * runs for program, ops in scopes.items()}
    records.trace = trace.TraceSummary(
        window_ns=(0.0, 1e9), devices=[0], busy_ns={0: sum(sum(d) for d in modules.values())}, op_ns={}, gap_ns={}, collective_ns={},
        collective_exposed_ns={}, modules=modules,
        op_ns_by_program={f"jit_{program}": {f"{op} fusion f32[4]": runs * 1e6 for op in ops} for program, ops in scopes.items()})
    got = {k: v["value"] for k, v in bench_run.compute_metrics(cell, cell.per_layer, records).items()}
    assert set(got) == set(granite.NEW_READERS) | set(granite.SHARED_READERS) | set(READERS)
    assert all(got[name] == layer[name] for name in READERS)                                    # a trace changes none of the four
    parts = {program: {part: sum(1 for path in ops.values() if _program.part_of(path, fam.PART_OF_SCOPE) == part)
                       for part in ("ssm", "attn", "routed", "mlp", "head_loss")} for program, ops in scopes.items()}
    decode = parts[fam.DECODE_PROGRAM]
    assert all(decode.values()) and all(parts[p]["ssm"] and parts[p]["routed"] for p in fam.CHUNK_PROGRAMS)
    assert got["decode_ssm_ms"] == pytest.approx(decode["ssm"]) and got["decode_attn_ms"] == pytest.approx(decode["attn"])
    assert got["decode_routed_ms"] == pytest.approx(decode["routed"]) and got["decode_mlp_ms"] == pytest.approx(decode["mlp"])
    assert got["decode_head_ms"] == pytest.approx(decode["head_loss"])
    chunk_mean = lambda part: sum(parts[p][part] for p in fam.CHUNK_PROGRAMS) / 2  # noqa: E731   as many executions of each
    assert got["prefill_ssm_ms"] == pytest.approx(chunk_mean("ssm")) and got["prefill_routed_ms"] == pytest.approx(chunk_mean("routed"))
    decoding = [records.tick_decoding[i] for i in last if records.tick_decoding[i]]
    v5e = peaks.PEAKS["TPU v5 lite"]
    assert got["decode_ssm_roofline"] == pytest.approx(
        100.0 * fam.ssm_step_floor_s(cell.config, sum(decoding) / len(decoding), v5e) / (decode["ssm"] * 1e-3))
    assert got["prefill_ssm_roofline"] == pytest.approx(100.0 * fam.ssm_chunk_floor_s(cell.config, 16, v5e) / (chunk_mean("ssm") * 1e-3))
    assert got["decode_step_roofline"] > 0 and got["decode_routed_roofline"] > 0
    # ... with no file of ``benchmark/`` changed
    assert before == {path: os.stat(os.path.join(REPO, "benchmark", path)).st_mtime_ns for path in before}
