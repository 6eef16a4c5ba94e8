"""The one span record (PR 27): the ring keeps start, end and parent;
``self_time`` subtracts overlapping children once; with ``FLAGS_monitor=0``
nothing is allocated; a scheduler tick yields the documented span tree; and
``introspect.op_scopes()`` joins a compiled train step's HLO instructions to
the model parts' ``jax.named_scope`` names."""
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
