"""The one span record (PR 27): the ring keeps start, end and parent;
``self_time`` subtracts overlapping children once; with ``FLAGS_monitor=0``
nothing is allocated; a scheduler tick yields the documented span tree; the
tick's records carry the token gap and what the device ran inside it (PR 38);
and ``introspect.op_scopes()`` joins a compiled train step's HLO instructions
to the model parts' ``jax.named_scope`` names."""
import gc
import re
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining, GPTPretrainingCriterion
from paddle_tpu.observability import introspect, spans, trace

KW = dict(max_batch_slots=2, max_seq_len=64, prefill_chunk=8, fuse=1)


def _made(name, start, end, sid, parent=None):
    s = spans.Span(name)
    s.start_ns, s.end_ns, s.span_id, s.parent_id = start, end, sid, parent
    return s


# --------------------------------------------------------------- the record
def test_ring_keeps_start_end_parent_and_attrs():
    t0 = time.perf_counter_ns()
    with spans.span("t.ring.outer", slots=3) as outer:
        with spans.span("t.ring.inner") as inner:
            pass
    got = {s.name: s for s in spans.recent(since_ns=t0)}
    assert set(got) == {"t.ring.outer", "t.ring.inner"}
    assert got["t.ring.inner"].parent_id == outer.span_id and outer.parent_id is None
    assert t0 <= outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert outer.attrs == {"slots": 3} and inner.attrs is None
    assert outer.seconds == (outer.end_ns - outer.start_ns) / 1e9
    assert spans.recent(since_ns=outer.end_ns) == []            # since is exclusive, on the end
    assert [s.name for s in spans.recent(since_ns=t0, until_ns=inner.end_ns)] == ["t.ring.inner"]


def test_ring_is_bounded():
    assert spans._RING.maxlen == spans.RING_CAPACITY >= 64 * 1024


def test_what_falls_out_of_a_full_ring_is_counted(monkeypatch):
    from collections import deque

    from paddle_tpu.observability import metrics

    assert "trace.spans_evicted" in metrics.OBS_COUNTERS and spans.RING_CAPACITY == 4 * 65536   # four of the busiest window
    monkeypatch.setattr(spans, "_RING", deque(maxlen=4))
    monkeypatch.setattr(spans, "RING_CAPACITY", 4)
    before = metrics.counter("trace.spans_evicted")
    for i in range(4):
        with spans.span(f"t.evict.{i}"):
            pass
    assert metrics.counter("trace.spans_evicted") == before and len(spans._RING) == 4
    for i in range(4, 7):
        with spans.span(f"t.evict.{i}"):
            pass
    assert metrics.counter("trace.spans_evicted") == before + 3
    assert [s.name for s in spans.recent()] == [f"t.evict.{i}" for i in range(3, 7)]


@pytest.mark.parametrize("children, want", [
    ([], 100),                                     # no child: all of it
    ([(10, 30)], 80),
    ([(10, 30), (50, 60)], 70),                    # disjoint children add
    ([(10, 40), (30, 60)], 50),                    # overlapping children count once
    ([(10, 60), (20, 30)], 50),                    # one child inside another
    ([(90, 140)], 90),                             # a child that outlives the parent is clipped
])
def test_self_time_subtracts_the_union_of_the_children(children, want):
    records = [_made("p", 0, 100, "p")] + [_made("c", a, b, f"c{i}", "p") for i, (a, b) in enumerate(children)]
    records.append(_made("other", 5, 95, "x", "someone-else"))
    got = spans.self_time(records)
    assert got["p"] == want
    assert got["x"] == 90


def test_monitor_off_allocates_nothing():
    paddle.set_flags({"FLAGS_monitor": False})
    try:
        before = len(spans._RING)
        a, b = spans.span("t.off"), spans.span("t.off", slots=1)
        assert a is b is spans._NULL is trace._NULL
        with a as sp:
            assert sp.seconds is None and sp.span_id is None
        assert trace.trace_span("t.off") is spans._NULL
        assert len(spans._RING) == before and spans._TLS.open == []
    finally:
        paddle.set_flags({"FLAGS_monitor": True})


def test_one_span_class_and_one_stack():
    """``trace_span`` and ``span_event`` build the same record as ``span``
    and nest on the same thread-local stack."""
    t0 = time.perf_counter_ns()
    tid = trace.new_trace_id("t")
    with spans.span("t.one.tick") as tick:                  # no trace: an integer id
        with trace.trace_span("t.one.traced", trace_id=tid, step=4) as traced:
            assert spans._TLS.open == [tick, traced]
            assert trace.current_trace() == tid and trace.current_span() == traced.span_id
            with spans.span("t.one.child") as child:        # inherits the trace
                pass
            sid = trace.span_event("t.one.event", trace_id=None, seconds=0.25, chunk=2)
    assert type(traced) is type(tick) is spans.Span
    assert isinstance(tick.span_id, int) and isinstance(traced.span_id, str) and len(traced.span_id) == 16
    assert traced.parent_id == tick.span_id and child.parent_id == traced.span_id and child.trace_id == tid
    event = next(s for s in spans.recent(since_ns=t0) if s.name == "t.one.event")
    assert event.span_id == sid and event.parent_id == traced.span_id and event.trace_id == tid
    assert event.end_ns - event.start_ns == 250_000_000 and event.attrs == {"chunk": 2}
    assert spans._TLS.open == [] and trace.current_trace() is None


# ------------------------------------------------------- the serving tick
@pytest.fixture(scope="module")
def fleet():
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig.tiny())
    model.eval()
    return paddle.inference.ServingFleet(model, replicas=1, **KW)


def _tree(records):
    """{name: parent's name} and the records by name (last of each)."""
    by_id = {s.span_id: s for s in records}
    return ({s.name: (by_id[s.parent_id].name if s.parent_id in by_id else None) for s in records},
            {s.name: s for s in records})


def test_a_tick_yields_the_span_tree(fleet):
    rng = np.random.default_rng(3)
    fleet.submit(rng.integers(0, 512, (11,)).astype("int32"), max_new_tokens=8, seed=0)
    fleet.step()                                    # chunk 1 of 2: prefill only
    t0 = time.perf_counter_ns()
    fleet.step()                                    # final chunk, then the first decode step
    parents, by_name = _tree(spans.recent(since_ns=t0))
    engine_and_up = {n: p for n, p in parents.items() if n.startswith("infer.") and n != "infer.compile"}
    assert engine_and_up == {
        "infer.fleet.step": None,
        "infer.sched.step": "infer.fleet.step",
        "infer.sched.admit": "infer.sched.step",
        "infer.sched.prefill": "infer.sched.step",
        "infer.prefill_chunk": "infer.sched.prefill",
        "infer.decode_step": "infer.sched.step",
        "infer.decode_launch": "infer.decode_step",
        "infer.decode_sync": "infer.decode_step",
        "infer.sched.drain": "infer.sched.step",
    }
    step, launch, sync, drain = (by_name[n] for n in
                                 ("infer.decode_step", "infer.decode_launch", "infer.decode_sync", "infer.sched.drain"))
    assert step.start_ns <= launch.start_ns <= launch.end_ns <= sync.start_ns <= sync.end_ns <= step.end_ns
    assert step.end_ns <= drain.start_ns and drain.attrs == {"slots": 1}
    # a request of a trace keeps its own per-chunk span, under the tick's prefill span
    assert parents["serving.prefill_chunk"] == "infer.sched.prefill"
    assert by_name["serving.prefill_chunk"].trace_id is not None


def test_a_pure_decode_tick_has_no_prefill_child(fleet):
    t0 = time.perf_counter_ns()
    fleet.step()
    names = [s.name for s in spans.recent(since_ns=t0)]
    assert sorted(names) == sorted([
        "infer.fleet.step", "infer.sched.step", "infer.sched.admit", "infer.sched.prefill",
        "infer.decode_step", "infer.decode_launch", "infer.decode_sync", "infer.sched.drain"])
    fleet.run()


def test_a_run_ahead_tick_keeps_the_tree_and_pulls_the_step_before(fleet):
    """PR 33: the tick launches its decode step and pulls the one the tick before launched. Same spans, same nesting:
    ``infer.decode_launch`` is this tick's dispatch, ``infer.decode_sync`` the pull of the step before."""
    from paddle_tpu.observability import metrics

    engine = next(iter(fleet.replicas.values())).engine
    fleet.submit(np.random.default_rng(4).integers(0, 512, (5,)).astype("int32"), max_new_tokens=6, seed=0)
    fleet.step()                                    # the one-chunk prefill and the first decode step: the pipe fills
    in_flight = engine._inflight.report
    before = metrics.counters("infer.decode")
    t0 = time.perf_counter_ns()
    fleet.step()
    parents, by_name = _tree(spans.recent(since_ns=t0))
    assert {n: p for n, p in parents.items() if n.startswith("infer.")} == {
        "infer.fleet.step": None,
        "infer.sched.step": "infer.fleet.step",
        "infer.sched.admit": "infer.sched.step",
        "infer.sched.prefill": "infer.sched.step",
        "infer.decode_step": "infer.sched.step",
        "infer.decode_launch": "infer.decode_step",
        "infer.decode_sync": "infer.decode_step",
        "infer.sched.drain": "infer.sched.step",
    }
    assert by_name["infer.decode_launch"].end_ns <= by_name["infer.decode_sync"].start_ns
    assert engine._inflight is not None and engine._inflight.report is not in_flight   # pulled: the step before's
    after = metrics.counters("infer.decode")
    assert after["infer.decode_ahead"] == before["infer.decode_ahead"] + 1
    assert after["infer.decode_dispatches"] == before["infer.decode_dispatches"] + 1
    fleet.run()
    assert engine._inflight is None


# ----------------------------------------------------------- the token gap
PREFILL_PROGRAMS = ("prefill", "prefill_chunk", "prefill_final")
DRAFT = GPTConfig(vocab_size=512, hidden_size=32, num_layers=1, num_heads=2, max_seq_len=128)
ENGINES = {
    "run_ahead": {},                                  # the scheduler's own order: launch step k, pull step k - 1
    "synchronous": {},                                # the same engine, each call pulling the step it launched
    "fuse3": {"fuse": 3},                             # a stack of three a pull: declines to run ahead
    "draft": {"draft": DRAFT, "spec_k": 3},           # a draft's accepted run a pull: declines too
}


def _served(kind):
    """Four prompts through a scheduler on three slots with the order of the engine's dispatches logged from outside:
    one of three chunks decodes throughout; four ticks on a one-chunk prompt and a four-chunk prompt are admitted
    together (the first's only chunk is launched ahead of the second's first, in one tick); a three-chunk prompt
    waits for a slot. One entry a tick: what the log says was launched and pulled, what the engine says of the step
    it pulled last, how many tokens the drain appended, and the tick's span records; and for each request where its
    last prefill program and the step that brought its second token lie in the log."""
    from paddle_tpu.inference import ContinuousBatchingScheduler, DecodeEngine
    from paddle_tpu.observability import metrics

    paddle.seed(0)
    model = GPTForPretraining(GPTConfig.tiny())
    model.eval()
    engine = DecodeEngine(model, **{**KW, "max_batch_slots": 3, **ENGINES[kind]})
    if kind == "synchronous":
        ahead = engine.decode_step
        engine.decode_step = lambda ahead=False, _step=ahead: _step()
    log, owner, dispatch, prefill_step = [], {}, engine._dispatch, engine.prefill_step

    def logged(which, *args, **kwargs):
        log.append(which)
        return dispatch(which, *args, **kwargs)

    def owned(job):                                             # which slot's each prefill program was
        at = len(log)
        out = prefill_step(job)
        if len(log) > at:
            owner[len(log) - 1] = job.slot
        return out

    engine._dispatch, engine.prefill_step = logged, owned
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(38)
    prompts = {0: [(19, 40)], 4: [(5, 8), (27, 5)], 5: [(20, 4)]}       # submitted before tick: (prompt tokens, new tokens)
    observed = metrics.histogram("serving.itl_seconds").count
    requests, ticks = {}, []
    queued_at, pulled_at = (0, None), 0               # the prefill programs before the launch of the step in flight and where it is in the log; before the step pulled last
    last_program, second = {}, {}                     # rid -> log index of its last prefill program; (tick, log index of the step) of its second token
    for n in range(200):
        for tokens, new in prompts.get(n, ()):
            rid = sched.submit(rng.integers(0, 512, (tokens,)).astype("int32"), max_new_tokens=new)
            requests[rid] = sched.queue[-1]
        if n > 5 and not (sched.queue or sched.prefilling or sched.running):
            break
        in_flight, at, t0 = engine._inflight is not None, len(log), time.perf_counter_ns()
        had = {rid: len(r.tokens) for rid, r in requests.items()}
        sched.step()
        drained = sum(len(r.tokens) - had[rid] - (not had[rid] and bool(r.tokens)) for rid, r in requests.items())
        launched = [i for i in range(at, len(log)) if log[i] not in PREFILL_PROGRAMS]
        assert len(launched) <= 1 and all(w.startswith(("decode", "spec_decode")) for w in log[at:] if w not in PREFILL_PROGRAMS)
        want = pulled = None                                    # what the pulled step's record should say, from the log
        if kind == "run_ahead" and in_flight:
            want, pulled = queued_at[0] - pulled_at, queued_at[1]
            pulled_at = queued_at[0]
        if launched:
            queued_at = (sum(w in PREFILL_PROGRAMS for w in log[:launched[0]]), launched[0])
            if kind != "run_ahead":
                want, pulled = queued_at[0] - pulled_at, launched[0]
                pulled_at = queued_at[0]
        for rid, r in requests.items():
            if r.tokens and rid not in last_program:
                last_program[rid] = max(i for i, slot in owner.items() if slot == r.slot)
            if len(r.tokens) > 1 and rid not in second:
                second[rid] = (n, pulled)
        ticks.append({"want": want, "launched_prefill": sum(w in PREFILL_PROGRAMS for w in log[at:]), "drained": drained,
                      "pulled_at": engine.pulled_at, "records": {s.name: s for s in spans.recent(since_ns=t0)}})
    first_gaps = {rid: {"tick": second[rid][0], "chunks": sum(w in PREFILL_PROGRAMS for w in log[last_program[rid] + 1:second[rid][1]])}
                  for rid in requests}
    return ticks, requests, metrics.histogram("serving.itl_seconds").count - observed, log, first_gaps


@pytest.fixture(scope="module", params=list(ENGINES))
def served(request):
    return (request.param,) + _served(request.param)


def test_a_pulled_step_says_what_was_queued_before_it(served):
    """``engine.pulled_at`` moves, with each step the engine pulls, by the prefill programs the log has between the
    launch of the step pulled before and this step's launch; the chunk count has that one home and no span attribute."""
    kind, ticks, done, _, log, _ = served
    assert sum(w in PREFILL_PROGRAMS for w in log) == 3 + 1 + 4 + 3 and "prefill_final" in log and "prefill_chunk" in log
    moved, was = 0, 0
    for tick in ticks:
        step = tick["records"].get("infer.decode_step")
        assert step is None or not step.attrs                   # this model's decoder counts nothing, and the engine notes nothing else
        assert tick["pulled_at"] - was == (tick["want"] or 0)
        moved += tick["want"] is not None
        was = tick["pulled_at"]
    wants = [t["want"] for t in ticks if t["want"] is not None]
    assert moved == len(wants) > 10 and wants[0] == 3 and max(wants[1:]) == 2 and sum(wants) == 11 == was
    # under run-ahead a chunk delays the tokens pulled one tick after the tick that launched it
    if kind == "run_ahead":
        held = [i for i, t in enumerate(ticks) if t["want"]]
        assert len(held) > 3 and all(ticks[i - 1]["launched_prefill"] == ticks[i]["want"] for i in held[1:])


def test_a_ticks_gaps_count_its_tokens_and_sum_to_the_requests_own(served):
    kind, ticks, done, observed, _, _ = served
    records = [t["records"]["infer.sched.step"] for t in ticks]
    assert all(set(r.attrs) == {"gaps"} for r in records)       # the one thing a tick notes: what a committed reader reads
    # every token the drain appended has a gap, each distinct gap of a tick is listed once, shortest first
    for r, tick in zip(records, ticks):
        gaps = r.attrs["gaps"]
        assert sum(n for _, n, _ in gaps) == tick["drained"] and [(g, c) for g, _, c in gaps] == sorted({(g, c) for g, _, c in gaps})
        assert all(isinstance(g, int) and g >= 0 and n >= 1 and c >= 0 for g, n, c in gaps)
    assert sum(t["drained"] for t in ticks) == sum(len(q.tokens) - 1 for q in done.values()) == observed
    # the gaps of a request sum to its last arrival less its first token, and no gap is longer than a request's longest
    in_all = sum(g * n for r in records for g, n, _ in r.attrs["gaps"])
    assert abs(in_all - sum(q.last_token_ns - round(q.first_token_ts * 1e9) for q in done.values())) <= len(done)
    assert max(g for r in records for g, _, _ in r.attrs["gaps"]) == max(q.max_gap_ns for q in done.values())
    assert all(q.max_gap_seconds == q.max_gap_ns / 1e9 > 0 for q in done.values())
    # an arrival is the end of the pull (``infer.decode_sync``) of the tick that drained it: the same instant as in a device trace
    ends = {t["records"]["infer.decode_sync"].end_ns for t in ticks if "infer.decode_sync" in t["records"]}
    assert all(q.last_token_ns in ends for q in done.values())
    zeros = [(n, c) for r in records for g, n, c in r.attrs["gaps"] if g == 0]
    if kind in ("fuse3", "draft"):
        assert zeros and all(c == 0 for _, c in zeros)      # tokens that one pull brings are 0 apart, are counted, and nothing ran between them
    else:
        assert not zeros and all(len(r.attrs["gaps"]) <= 3 for r in records)      # the shared gap, and each new slot's own


def test_each_gap_says_how_many_prefill_programs_ran_inside_it(served):
    """The gap between two pulls holds what the engine queued between the two steps' launches;
    a request's first gap holds only what was dispatched after its own last prefill program — the log has both."""
    kind, ticks, done, _, _, first_gaps = served
    arrivals, shared = {}, 0
    for n, tick in enumerate(ticks):
        sync = tick["records"].get("infer.decode_sync")
        if tick["want"] is None:
            continue
        gaps = {g: c for g, _, c in tick["records"]["infer.sched.step"].attrs["gaps"] if g}
        between = sync.end_ns - arrivals[max(arrivals)] if arrivals else None
        if between in gaps:                                     # some slot decoded in both pulls
            assert gaps[between] == tick["want"]
            shared += 1
        arrivals[n] = sync.end_ns
    assert shared > 10
    for rid, r in done.items():
        tick = ticks[first_gaps[rid]["tick"]]
        mine = [c for g, _, c in tick["records"]["infer.sched.step"].attrs["gaps"]
                if g and abs(g - (arrivals[first_gaps[rid]["tick"]] - round(r.first_token_ts * 1e9))) <= 1]
        assert mine == [first_gaps[rid]["chunks"]], (rid, mine, first_gaps[rid])
    # the one-chunk prompt's first gap holds the chunk launched behind its own in the same tick; the prompt admitted with
    # it was still prefilling then, and its own first gap holds nothing though the step that ended it had a chunk before it
    assert [first_gaps[rid]["chunks"] for rid in sorted(done)][:2] == [0, 1]
    late = first_gaps[sorted(done)[2]]
    assert late["chunks"] < ticks[late["tick"]]["want"]


def test_the_token_gap_is_exported_and_in_the_finished_event(served):
    from paddle_tpu.observability import metrics, monitor

    _, _, done, observed, _, _ = served
    assert "serving.itl_seconds" in metrics.KNOWN_HISTOGRAMS and observed > 0
    text = metrics.prometheus_text(prefix="serving.itl")
    assert "# TYPE paddle_tpu_serving_itl_seconds_seconds histogram" in text and 'le="0.001"' in text
    summary = metrics.snapshot()["histograms"]["serving.itl_seconds"]
    assert summary["count"] >= observed and summary["p50"] is not None
    events = {e["id"]: e for e in monitor().events("request") if e["status"] == "finished"}
    last = events[max(done)]
    assert last["max_gap_seconds"] == done[max(done)].max_gap_seconds > 0
    assert not any(key.startswith("itl") for key in last)        # the registry's percentiles are the exporter's, not a request's
    assert not any(key.startswith("stall") for e in monitor().events("request") for key in e)     # the field that read launches is gone


def test_a_histogram_takes_a_value_with_its_count():
    from paddle_tpu.observability import metrics

    h = metrics.Histogram(bounds=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5, 40)
    assert (h.count, h.bucket_counts, h.sum) == (41, [1, 40, 0], pytest.approx(20.05)) and 0.1 < h.percentile(50) <= 0.5
    metrics.observe("t.gap.weighted", 2.0, 3)
    assert metrics.histogram("t.gap.weighted").count == 3 and metrics.histogram("t.gap.weighted").sum == 6.0
    del metrics._HISTOGRAMS["t.gap.weighted"]


def test_monitor_off_stamps_no_token():
    """``FLAGS_monitor=0``: the same tokens, and no stamp, no note, no observation."""
    from paddle_tpu.inference import ContinuousBatchingScheduler, DecodeEngine
    from paddle_tpu.observability import metrics

    paddle.seed(0)
    model = GPTForPretraining(GPTConfig.tiny())
    model.eval()
    prompt = np.random.default_rng(5).integers(0, 512, (19,)).astype("int32")

    def serve():
        engine = DecodeEngine(model, **KW)
        sched = ContinuousBatchingScheduler(engine)
        rid = sched.submit(prompt, max_new_tokens=6)
        return sched.run()[rid], engine

    on, _ = serve()
    paddle.set_flags({"FLAGS_monitor": False})
    try:
        ring, observed = len(spans._RING), metrics.histogram("serving.itl_seconds").count
        off, engine = serve()
        assert len(spans._RING) == ring and metrics.histogram("serving.itl_seconds").count == observed
    finally:
        paddle.set_flags({"FLAGS_monitor": True})
    assert off.tokens == on.tokens and len(off.tokens) == 6
    assert (off.last_token_ns, off.max_gap_ns, off.max_gap_seconds, engine.arrived_ns) == (0, 0, None, 0)
    assert on.last_token_ns > 0 and on.max_gap_ns > 0 and off.first_token_ts > 0
    assert engine.prefill_programs == engine.pulled_at == 3                    # integers, counted either way


@pytest.mark.parametrize("name", ["infer.tokens_per_decode_dispatch", "serving.prefill_stall_seconds"])
def test_unread_histograms_are_gone(fleet, name):
    from paddle_tpu.observability import metrics

    assert name not in metrics.KNOWN_HISTOGRAMS and name not in metrics.histograms()


# ------------------------------------------------------------- op_scopes
@pytest.fixture(scope="module")
def step_scopes():
    """The scopes of a tiny train step, read after the step is deleted."""
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig.tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, opt, GPTPretrainingCriterion(), amp_level="O2")
    ids = np.random.default_rng(0).integers(0, 512, (2, 17)).astype("int32")
    loss = float(step(ids[:, :-1], ids[:, 1:])["loss"])
    assert np.isfinite(loss)
    del step, model, opt
    gc.collect()
    return introspect.op_scopes()["train_step/step"]


def _scopes_in(op_name):
    return set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", op_name))


@pytest.mark.parametrize("scope", ["norm", "attn_qkv", "attn_core", "attn_out", "mlp", "embed", "head_loss",
                                   "optimizer", "amp_cast"])
def test_op_scopes_names_every_part(step_scopes, scope):
    hits = [name for name, op_name in step_scopes.items() if scope in _scopes_in(op_name)]
    assert hits, scope
    # transposed (backward) ops carry the scope of the forward code, except for the update itself
    if scope not in ("optimizer", "embed"):
        assert any("transpose" in step_scopes[n] for n in hits), scope


def test_op_scopes_reaches_the_matmuls(step_scopes):
    """Every dot of the step (or the fusion that holds it) is named after a part."""
    parts = {"attn_qkv", "attn_core", "attn_out", "mlp", "head_loss"}
    dots = {n: s for n, s in step_scopes.items() if s.endswith("dot_general")}
    assert len(dots) >= 12
    assert all(_scopes_in(s) & parts for s in dots.values()), [s for s in dots.values() if not _scopes_in(s) & parts]


def test_op_scopes_parses_once_and_drops_the_handle(step_scopes):
    assert isinstance(introspect._PROGRAMS["train_step/step"], dict)
    assert introspect.op_scopes()["train_step/step"] is step_scopes


def test_parse_op_names_reads_hlo_text():
    text = '''HloModule jit__step, is_scheduled=true
%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(_step)/jit(main)/mlp/mul" source_file="x.py" source_line=3}
}
ENTRY %main (a: f32[8]) -> (f32[8], f32[8]) {
  %a = f32[8]{0} parameter(0), metadata={op_name="state['params']"}
  %fusion.939 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jit(main)/transpose(jvp(norm))/mul" source_file="x.py"}
  %copy.2 = f32[8]{0} copy(%a)
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%fusion.939, %copy.2)
}'''
    assert introspect.parse_op_names(text) == {
        "multiply.3": "jit(_step)/jit(main)/mlp/mul", "a": "state['params']",
        "fusion.939": "jit(_step)/jit(main)/transpose(jvp(norm))/mul"}


def test_decode_programs_are_noted_too(fleet):
    scopes = introspect.op_scopes()
    assert {"infer/decode", "infer/prefill_chunk", "infer/prefill_final"} <= set(scopes)
    words = set().union(*(_scopes_in(s) for s in scopes["infer/decode"].values()))
    assert {"cache_write", "cache_read", "attn_core", "mlp", "norm"} <= words
