"""The readers of the program's own spans and scopes (PR 27) on hand-made
records: ring entries for the serving tick, ``op_ns`` and scopes for the train
step, ``gap_ns`` for the idle attribution — and what they do on a program that
has neither a ring nor ``op_scopes`` (they return None)."""
import types

import pytest

import tiny_root
from benchmark.harness import manifest
from paddle_tpu.observability import introspect, spans

MS = 1_000_000   # the hand-made spans are written in milliseconds


def reader(name):
    return manifest.load_module(tiny_root.REPO, "benchmark", "layer_metrics", name)


def _span(name, start_ms, end_ms, sid, parent=None):
    s = spans.Span(name)
    s.start_ns, s.end_ns, s.span_id, s.parent_id = int(start_ms * MS), int(end_ms * MS), sid, parent
    return s


def _tick(t, n, prefill_chunks=0, fleet_self=0.5, drain=1.0, launch=1.0, decode=50.0):
    """One fleet tick starting at ``t`` ms: admit 1 ms, each prefill chunk 4 ms
    plus 0.5 ms of the scheduler's own, launch + sync = ``decode``."""
    out, at = [], t + fleet_self / 2
    sched_start = at
    out.append(_span("infer.sched.admit", at, at + 1.0, f"a{n}", f"s{n}"))
    at += 1.0
    p0 = at
    for c in range(prefill_chunks):
        out.append(_span("infer.prefill_chunk", at + 0.5, at + 4.5, f"c{n}.{c}", f"p{n}"))
        out.append(_span("serving.prefill_chunk", at + 0.4, at + 4.5, f"e{n}.{c}", f"p{n}"))   # the request's own record
        at += 4.5
    out.append(_span("infer.sched.prefill", p0, at, f"p{n}", f"s{n}"))
    out.append(_span("infer.decode_launch", at + 0.1, at + 0.1 + launch, f"l{n}", f"d{n}"))
    out.append(_span("infer.decode_sync", at + 0.1 + launch, at + decode, f"y{n}", f"d{n}"))
    out.append(_span("infer.decode_step", at, at + decode, f"d{n}", f"s{n}"))
    at += decode
    out.append(_span("infer.sched.drain", at, at + drain, f"r{n}", f"s{n}"))
    at += drain
    out.append(_span("infer.sched.step", sched_start, at, f"s{n}", f"f{n}"))
    out.append(_span("infer.fleet.step", t, at + fleet_self / 2, f"f{n}"))
    return out


@pytest.fixture
def ring():
    """Five hand-made ticks in the ring, long before any real span; the window
    holds ticks 1..4 (tick 0 ends before it opens, tick 4 is the last inside)."""
    saved = list(spans._RING)
    spans._RING.clear()
    made = (_tick(0, 0, decode=70.0)
            + _tick(100, 1)                                   # pure decode: 50 ms
            + _tick(200, 2, prefill_chunks=2, drain=2.0)      # two chunks: the decode step waits for them
            + _tick(300, 3, decode=52.0, launch=2.0, fleet_self=0.7)
            + _tick(400, 4, decode=54.0, launch=3.0, fleet_self=0.9))
    spans._RING.extend(made)
    yield types.SimpleNamespace(window_open=0.099, window_close=0.499, trace=None)
    spans._RING.clear()
    spans._RING.extend(saved)


@pytest.mark.parametrize("name, want", [
    ("fleet_self_ms", 0.6),              # ticks 1..4: 0.5, 0.5, 0.7, 0.9
    ("sched_self_ms", 2.0),              # admit 1 + drain 1 (tick 2: 1 + 2 chunks' 0.5 + drain 2 = 4): 2, 4, 2, 2
    ("decode_launch_ms", 1.5),           # 1, 1, 2, 3
    ("engine_decode_step_ms", 52.0),     # ticks 1, 3, 4 (tick 2 dispatched prefill): 50, 52, 54
])
def test_span_readers_on_hand_made_ticks(ring, name, want):
    assert reader(name).read(ring) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("name", ["fleet_self_ms", "sched_self_ms", "decode_launch_ms", "engine_decode_step_ms"])
def test_span_readers_return_none_without_a_ring(ring, name, monkeypatch):
    monkeypatch.delattr(spans, "recent")                 # the parent commit's program
    assert reader(name).read(ring) is None


def test_span_readers_return_none_on_an_empty_window(ring):
    ring.window_open, ring.window_close = 5.0, 6.0
    assert reader("fleet_self_ms").read(ring) is None and reader("engine_decode_step_ms").read(ring) is None


class _Trace:
    def __init__(self, op_ns, runs, gap_ns=None):
        self.op_ns, self.gap_ns, self._runs = op_ns, gap_ns or {}, runs

    def module_like(self, part):
        assert part == "_step"
        return ("jit__step", self._runs) if self._runs else (None, [])


@pytest.fixture
def traced(monkeypatch):
    scopes = {
        "fusion.1": "jit(_step)/jit(main)/attn_qkv/dot_general",
        "custom-call.2": "jit(_step)/jit(main)/transpose(jvp(attn_core))/pallas_call",
        "fusion.3": "jit(_step)/jit(main)/transpose(jvp(attn_out))/dot_general",
        "fusion.4": "jit(_step)/jit(main)/checkpoint/rematted_computation/mlp/tanh",
        "fusion.5": "jit(_step)/jit(main)/transpose(jvp(norm))/jit(layer_norm_fused)/mul",
        "fusion.6": "jit(_step)/jit(main)/head_loss/dot_general",
        "fusion.7": "jit(_step)/jit(main)/transpose(jvp(embed))/scatter-add",
        "fusion.8": "jit(_step)/jit(main)/optimizer/jit(norm)/sqrt",     # the outermost scope decides
        "fusion.9": "jit(_step)/jit(main)/transpose(jvp(amp_cast))/convert_element_type",
        "all-reduce.1": "jit(_step)/jit(main)/transpose(jvp(mlp))/dot_general",
        "copy.11": "jit(_step)/jit(main)/while/body/dynamic_slice",
    }
    monkeypatch.setitem(introspect._PROGRAMS, "train_step/step", scopes)
    op_ms = {"fusion.1 fusion bf16[8,1024,3072]": 10, "custom-call.2 custom-call (bf16[8,16,1024,64], +2)": 20,
             "fusion.3 fusion bf16[8,1024,1024]": 6, "fusion.4 fusion bf16[8,1024,4096]": 40,
             "fusion.5 fusion (bf16[1024], +2)": 8, "fusion.6 fusion bf16[8,1024,50304]": 12,
             "fusion.7 fusion f32[50304,1024]": 2, "fusion.8 fusion f32[24,1024,4096]": 14,
             "fusion.9 fusion bf16[24,1024,4096]": 4, "all-reduce.1 all-reduce f32[1024]": 3,
             "copy.11 copy bf16[1024,1024]": 5, "fusion.99 fusion f32[8]": 1}      # 99: the program has no such op
    cell = types.SimpleNamespace(family=types.SimpleNamespace(TRAIN_PROGRAM="_step"))
    return types.SimpleNamespace(cell=cell, trace=_Trace({k: 2.0 * v * MS for k, v in op_ms.items()}, [1.0, 1.0]))


@pytest.mark.parametrize("name, want", [
    ("train_attn_ms", 36.0), ("train_mlp_ms", 40.0), ("train_norm_ms", 8.0), ("train_head_loss_ms", 14.0),
    ("train_optimizer_ms", 18.0), ("train_unscoped_ms", 9.0),     # the collective, the unscoped copy, the unknown op
])
def test_train_step_by_model_part(traced, name, want):
    assert reader(name).read(traced) == pytest.approx(want)


@pytest.mark.parametrize("broken", ["no trace", "no op_scopes", "no such program", "no executions"])
def test_train_part_readers_return_none_when_there_is_nothing_to_join(traced, monkeypatch, broken):
    if broken == "no trace":
        traced.trace = None
    elif broken == "no op_scopes":
        monkeypatch.delattr(introspect, "op_scopes")     # the parent commit's program
    elif broken == "no such program":
        monkeypatch.delitem(introspect._PROGRAMS, "train_step/step")
    else:
        traced.trace._runs = []
    assert reader("train_attn_ms").read(traced) is None and reader("train_unscoped_ms").read(traced) is None


def test_idle_share_inside_the_programs_spans():
    gaps = {"infer.decode_launch": 30.0, "infer.sched.drain": 20.0, "infer.fleet.step": 10.0, "bench.tick": 5.0,
            "bench.log": 15.0, "no_span": 20.0, "short_gaps": 1000.0}
    records = types.SimpleNamespace(trace=_Trace({}, [], gaps))
    assert reader("idle_in_program_spans_pct").read(records) == pytest.approx(60.0)
    assert reader("idle_in_program_spans_pct").read(types.SimpleNamespace(trace=None)) is None
    assert reader("idle_in_program_spans_pct").read(types.SimpleNamespace(trace=_Trace({}, [], {"short_gaps": 3.0}))) is None
