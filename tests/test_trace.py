"""Cross-process observability plane (PR 14): deterministic trace ids,
exception-safe spans, the fleet kill->requeue trace reconstruction, merged
multi-process timelines over a real TCPStore, the live metrics exporter,
and the crash flight recorder."""
import json
import os
import subprocess
import sys
import textwrap
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.observability import exporter, flightrec, metrics, trace
from paddle_tpu.observability.__main__ import (
    analyze_merged,
    chrome_trace_doc,
    main as obs_main,
)
from paddle_tpu.testing import chaos

# same engine spec as tests/test_fleet.py: identical fingerprints share the
# module-scoped AOT store, so every fleet in the file compiles once
KW = dict(max_batch_slots=2, max_seq_len=64, prefill_chunk=8, fuse=2)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module", autouse=True)
def aot_dir(tmp_path_factory):
    prev = paddle.get_flags("FLAGS_compile_cache_dir")["FLAGS_compile_cache_dir"]
    d = tmp_path_factory.mktemp("trace_aot")
    paddle.set_flags({"FLAGS_compile_cache_dir": str(d)})
    yield str(d)
    paddle.set_flags({"FLAGS_compile_cache_dir": prev})


@pytest.fixture
def run_log_dir(tmp_path):
    prev = paddle.get_flags("FLAGS_run_log_dir")["FLAGS_run_log_dir"]
    paddle.set_flags({"FLAGS_run_log_dir": str(tmp_path)})
    obs.monitor().clear()
    yield tmp_path
    obs.monitor().flush()
    paddle.set_flags({"FLAGS_run_log_dir": prev})
    obs.monitor().close()


def _read_log(tmp_path):
    obs.monitor().flush()
    events = []
    for f in sorted(tmp_path.glob("run-*.jsonl")):
        events.extend(json.loads(l) for l in f.read_text().splitlines() if l)
    return events


def _trace_ids(ev):
    tids = [ev["trace"]] if ev.get("trace") else []
    tids.extend(t for t in (ev.get("traces") or []) if t)
    return tids


def _label(ev):
    if ev.get("event") == "span":
        return ev.get("name")
    if ev.get("event") == "fleet":
        return f"fleet.{ev.get('kind')}"
    return ev.get("event")


# ------------------------------------------------------- deterministic ids
class TestTraceIds:
    def test_ids_replay_bitwise_under_same_seed(self):
        paddle.seed(1234)
        trace._GENS.clear()
        a = [trace.new_trace_id("t") for _ in range(4)]
        paddle.seed(1234)
        trace._GENS.clear()
        b = [trace.new_trace_id("t") for _ in range(4)]
        assert a == b
        assert len(set(a)) == 4
        assert all(len(t) == 16 for t in a)

    def test_ranks_decorrelate(self, monkeypatch):
        paddle.seed(1234)
        trace._GENS.clear()
        rank0 = [trace.new_trace_id("t") for _ in range(4)]
        monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
        paddle.seed(1234)
        trace._GENS.clear()
        rank1 = [trace.new_trace_id("t") for _ in range(4)]
        trace._GENS.clear()
        assert set(rank0).isdisjoint(rank1)

    def test_disabled_allocates_nothing(self):
        paddle.set_flags({"FLAGS_trace": False})
        try:
            assert trace.new_trace_id("t") is None
            assert trace.span_event("s", trace_id="deadbeef") is None
            sp = trace.trace_span("s")
            assert sp is trace._NULL
        finally:
            paddle.set_flags({"FLAGS_trace": True})


# ---------------------------------------------------- exception-safe spans
class TestSpanExceptionSafety:
    def test_trace_span_raising_body_still_closes(self, run_log_dir):
        paddle.seed(0)
        tid = trace.new_trace_id("t")
        before = metrics.histogram("t.boom").count
        with pytest.raises(RuntimeError, match="kaboom"):
            with trace.trace_span("t.boom", trace_id=tid):
                raise RuntimeError("kaboom")
        # stack uncorrupted, histogram recorded, event carries error=true
        assert trace.current_trace() is None
        assert trace.current_span() is None
        assert metrics.histogram("t.boom").count == before + 1
        spans = [e for e in _read_log(run_log_dir)
                 if e.get("event") == "span" and e.get("name") == "t.boom"]
        assert spans and spans[0]["error"] is True
        assert spans[0]["trace"] == tid

    def test_nesting_survives_inner_raise(self, run_log_dir):
        paddle.seed(0)
        tid = trace.new_trace_id("t")
        with trace.trace_span("t.outer", trace_id=tid) as outer:
            try:
                with trace.trace_span("t.inner"):
                    raise ValueError("inner")
            except ValueError:
                pass
            # the outer span is the ambient context again
            assert trace.current_span() == outer.span_id
        assert trace.current_span() is None
        evs = {e["name"]: e for e in _read_log(run_log_dir)
               if e.get("event") == "span"}
        assert evs["t.inner"]["error"] is True
        assert evs["t.inner"]["parent"] == outer.span_id
        assert evs["t.outer"]["error"] is False

    def test_obs_span_raising_body_chrome_and_histogram(self, tmp_path):
        before = metrics.histogram("t.sp.err").count
        prof = profiler.Profiler(timer_only=True)
        prof.start()
        with pytest.raises(ValueError):
            with obs.span("t.sp.err") as sp:
                raise ValueError("x")
        with obs.span("t.sp.after"):
            pass
        prof.stop()
        assert sp.error is True and sp.seconds is not None
        assert metrics.histogram("t.sp.err").count == before + 1
        out = prof.export(tmp_path / "trace.json")
        names = {e.get("name") for e in json.load(open(out))["traceEvents"]}
        # the raising span closed its RecordEvent: both spans exported
        assert "t.sp.err" in names and "t.sp.after" in names

    def test_error_spans_reach_chrome_trace_args(self, run_log_dir):
        paddle.seed(0)
        tid = trace.new_trace_id("t")
        with pytest.raises(RuntimeError):
            with trace.trace_span("t.chrome.err", trace_id=tid):
                raise RuntimeError("x")
        doc = chrome_trace_doc(str(run_log_dir))
        rows = [e for e in doc["traceEvents"]
                if e.get("name") == "t.chrome.err"]
        assert rows and rows[0]["args"]["error"] is True
        assert rows[0]["args"]["trace"] == tid


# ---------------------------------------- fleet: one trace id, end to end
class TestFleetTracePath:
    def test_kill_requeue_reconstructs_full_path(self, model, run_log_dir):
        """PR-14 acceptance: one trace_id follows a request through
        submit -> route -> prefill -> decode -> kill -> requeue ->
        delivery, reconstructed from the merged run logs."""
        flightrec.reset()
        paddle.seed(0)
        rng = np.random.default_rng(42)
        prompts = [rng.integers(0, 512, (n,)).astype("int32")
                   for n in (5, 9, 3, 12, 7, 11)]
        with chaos.inject(FLAGS_chaos_replica_kill_at="1:2"):
            fleet = paddle.inference.ServingFleet(model, replicas=2, **KW)
            fids = [fleet.submit(p, max_new_tokens=6, seed=i)
                    for i, p in enumerate(prompts)]
            done = fleet.run()
        assert len(done) == len(fids)

        events = _read_log(run_log_dir)
        requeues = [e for e in events
                    if e.get("event") == "fleet" and e.get("kind") == "requeue"]
        assert requeues, "the chaos kill produced no requeue"
        tid = requeues[0]["trace"]
        assert tid
        path = [_label(e) for e in events if tid in _trace_ids(e)]

        # the full story, in order, under ONE trace id
        for a, b in [("fleet.submitted", "fleet.placed"),
                     ("fleet.placed", "serving.prefill_chunk"),
                     ("serving.prefill_chunk", "fleet.replica_dead"),
                     ("fleet.replica_dead", "fleet.requeue"),
                     ("fleet.requeue", "fleet.finished")]:
            assert path.index(a) < path.index(b), (a, b, path)
        assert path.count("fleet.placed") == 2  # killed replica + rescuer
        assert path[-1] == "fleet.finished"
        # no per-tick event names the requests a decode dispatch served: the
        # decode phase of a request is the tick spans between its first token
        # and its finish, which the span ring holds (slots = slots decoding)
        assert "serving.decode" not in {_label(e) for e in events}
        freq = next(f for f in done.values() if f.trace_id == tid)
        lo, hi = int(freq.first_token_ts * 1e9), int(freq.finished_ts * 1e9)
        ticks = [s for s in obs.spans.recent(since_ns=lo, until_ns=hi)
                 if s.name == "infer.sched.drain" and s.attrs["slots"] >= 1]
        steps = [s for s in obs.spans.recent(since_ns=lo, until_ns=hi)
                 if s.name == "infer.decode_step"]
        assert ticks and len(steps) == len(ticks)
        assert len(freq.tokens) - 1 <= KW["fuse"] * len(steps)
        # and the run log's span events now say when they started
        chunk = next(e for e in events if e.get("name") == "serving.prefill_chunk")
        assert chunk["start"] > 0 and chunk["seconds"] >= 0

        # every submission got its own trace id; all six delivered
        finished = [e for e in events
                    if e.get("event") == "fleet" and e.get("kind") == "finished"]
        assert len({e["trace"] for e in finished}) == len(fids)

        # the replica death dumped a flight record naming the lost traces
        frs = sorted(run_log_dir.glob("flightrec-*.json"))
        assert frs, "replica death produced no flight-recorder dump"
        doc = json.load(open(frs[0]))
        assert doc["format"] == 1 and doc["reason"] == "replica_death"
        assert tid in doc["context"]["traces"]
        assert doc["exception"]["type"] == "ChaosCrash"
        assert doc["events"] and doc["metrics"]["counters"]

    def test_merge_cli_renders_requeue_edges_and_paths(self, model,
                                                       run_log_dir, capsys):
        flightrec.reset()
        paddle.seed(0)
        rng = np.random.default_rng(42)
        prompts = [rng.integers(0, 512, (n,)).astype("int32")
                   for n in (5, 9, 3, 12)]
        with chaos.inject(FLAGS_chaos_replica_kill_at="1:2"):
            fleet = paddle.inference.ServingFleet(model, replicas=2, **KW)
            for i, p in enumerate(prompts):
                fleet.submit(p, max_new_tokens=6, seed=i)
            fleet.run()
        obs.monitor().flush()

        assert obs_main(["report", "--merge", str(run_log_dir), "--json"]) == 0
        m = json.loads(capsys.readouterr().out)
        assert m["requeue_edges"], "merge report lost the requeue edges"
        edge = m["requeue_edges"][0]
        assert edge["from"] != edge["to"] and edge["trace"]
        row = m["traces"]["paths"][edge["trace"]]
        assert "fleet.requeue" in row["path"]
        assert row["path"][-1] == "fleet.finished"
        assert m["lanes"], "merge report rendered no per-replica lanes"

        out = run_log_dir / "trace.json"
        assert obs_main(["trace", str(run_log_dir), "--out", str(out)]) == 0
        doc = json.load(open(out))
        cats = {e.get("cat") for e in doc["traceEvents"]}
        assert "fleet" in cats and "span" in cats
        assert any(e.get("ph") == "X" for e in doc["traceEvents"])


# ------------------------------------- merged timelines across 2 processes
_CHILD = textwrap.dedent("""
    import os, sys, time as _time
    rank = int(os.environ["OBS_RANK"])
    skew = float(os.environ["OBS_SKEW"])
    if skew:  # simulate a host whose wall clock runs ahead
        _real = _time.time
        _time.time = lambda: _real() + skew
    import paddle_tpu as paddle
    from paddle_tpu.distributed import TCPStore
    from paddle_tpu.observability import runlog, trace
    paddle.set_flags({"FLAGS_run_log_dir": os.environ["OBS_DIR"]})
    paddle.seed(0)
    store = TCPStore(port=int(os.environ["OBS_PORT"]), world_size=2,
                     timeout=30.0)
    store.barrier("obs_boot", timeout=30.0)
    trace.sync_clocks(store, rank, 2, timeout=30.0)
    tid = trace.new_trace_id("fleet")
    runlog.emit("fleet", kind="placed", component="fleet", id=rank,
                replica=rank, trace=tid)
    for s in (1, 2, 3):
        store.barrier("obs_step_%d" % s, timeout=30.0)
        runlog.emit("step", step=s, k=1, seconds=0.01)
    runlog.emit("fleet", kind="finished", component="fleet", id=rank,
                replica=rank, trace=tid, seconds=0.05, attempts=1)
    runlog.monitor().close()
""")


class TestMergedTimelines:
    def test_two_process_merge_aligns_clocks(self, tmp_path):
        """PR-14 acceptance: ``report --merge`` over a real 2-process run
        (rendezvous via a real TCPStore, rank 1's clock skewed +5s) renders
        per-replica lanes on a single aligned timeline."""
        from paddle_tpu.distributed import TCPStore

        skew = 5.0
        master = TCPStore(is_master=True, world_size=2, timeout=30.0)
        try:
            env_base = dict(os.environ, OBS_PORT=str(master.port),
                            OBS_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
                            PYTHONPATH=os.path.dirname(
                                os.path.dirname(os.path.abspath(__file__))))
            procs = []
            for rank in (0, 1):
                env = dict(env_base, OBS_RANK=str(rank),
                           PADDLE_TRAINER_ID=str(rank),
                           OBS_SKEW=str(skew if rank == 1 else 0.0))
                procs.append(subprocess.Popen([sys.executable, "-c", _CHILD],
                                              env=env))
            for p in procs:
                assert p.wait(timeout=120) == 0
        finally:
            master.close()

        m = analyze_merged(str(tmp_path))
        assert len(m["processes"]) == 2
        offs = {info["rank"]: info["offset_seconds"]
                for info in m["processes"].values()}
        assert abs(offs[0]) < 1.0
        assert abs(offs[1] - skew) < 2.0  # rank 1 published its skewed epoch

        # the same real-time steps land aligned: skew removed, residue tiny
        sk = m["step_skew"]
        assert sk["steps_compared"] == 3
        assert sk["max_seconds"] < 2.0  # would be ~5s without alignment
        assert sk["p50_seconds"] <= sk["p99_seconds"] <= sk["max_seconds"]

        # one lane per replica, each with its own trace id
        assert sorted(m["lanes"]) == [0, 1]
        tids = {lane[0]["trace"] for lane in m["lanes"].values()}
        assert len(tids) == 2  # rank-decorrelated id streams

        # the chrome trace carries one named track per process
        doc = chrome_trace_doc(str(tmp_path))
        tracks = [e for e in doc["traceEvents"]
                  if e.get("name") == "process_name"]
        assert len(tracks) == 2
        assert {t["args"]["name"].split(" ")[1] for t in tracks} == {"0", "1"}

    def test_sync_clocks_unit(self, run_log_dir):
        from paddle_tpu.distributed import TCPStore

        master = TCPStore(is_master=True, world_size=2, timeout=10.0)
        worker = TCPStore(port=master.port, world_size=2, timeout=10.0)
        try:
            # single-threaded: seed rank 0's epoch so neither call blocks
            master.set(f"{trace.EPOCH_KEY_PREFIX}/0/epoch", repr(1000.0))
            off1 = trace.sync_clocks(worker, 1, 2, timeout=5.0, epoch=1003.5)
            off0 = trace.sync_clocks(master, 0, 2, timeout=5.0, epoch=1000.0)
            assert off0 == 0.0
            assert abs(off1 - 3.5) < 1e-9
        finally:
            worker.close()
            master.close()
        syncs = [e for e in _read_log(run_log_dir)
                 if e.get("event") == "clock_sync"]
        assert {e["rank"] for e in syncs} == {0, 1}


# --------------------------------------------------------- live exporter
class TestExporter:
    def _get(self, port, path):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=5) as r:
            return r.status, r.read().decode()

    def test_endpoints(self):
        exp = exporter.MetricsExporter(port=0).start()
        try:
            metrics.counter_inc("trace.traces", 0)
            code, text = self._get(exp.port, "/metrics")
            assert code == 200
            assert "paddle_tpu_trace_traces_total" in text
            assert "paddle_tpu_fleet_requeues_total" in text
            code, text = self._get(exp.port, "/healthz")
            assert code == 200
            doc = json.loads(text)
            assert doc["ok"] is True and doc["pid"] == os.getpid()
            code, text = self._get(exp.port, "/snapshot")
            assert code == 200
            snap = json.loads(text)
            assert "counters" in snap and "histograms" in snap
            assert metrics.counters("exporter.")["exporter.requests"] >= 3
        finally:
            exp.stop()

    def test_failing_probe_degrades_healthz(self):
        exp = exporter.MetricsExporter(port=0).start()
        exporter.register_health("t_bad", lambda: {"ok": False, "why": "x"})
        try:
            code, text = None, None
            try:
                self._get(exp.port, "/healthz")
            except urllib.error.HTTPError as e:
                code, text = e.code, e.read().decode()
            assert code == 503
            doc = json.loads(text)
            assert doc["ok"] is False
            assert doc["components"]["t_bad"]["why"] == "x"
        finally:
            exporter.unregister_health("t_bad")
            exp.stop()

    def _get_with_headers(self, port, path):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=5) as r:
            return r.status, dict(r.headers), r.read().decode()

    def test_alerts_endpoint_schema_and_content_type(self):
        """/alerts is JSON with the pinned envelope; registered providers'
        docs merge in tagged with their source; a raising provider yields
        a warn doc instead of a 500."""
        exp = exporter.MetricsExporter(port=0).start()
        exporter.register_alerts(
            "t_prov", lambda: [{"slo": "t.x", "severity": "page",
                                "burn_fast": 20.0}])

        def boom():
            raise RuntimeError("provider died")

        exporter.register_alerts("t_boom", boom)
        try:
            code, headers, text = self._get_with_headers(exp.port, "/alerts")
            assert code == 200
            assert headers["Content-Type"] == "application/json"
            doc = json.loads(text)
            assert doc["pid"] == os.getpid() and doc["ts"] > 0
            assert doc["firing"] == len(doc["alerts"]) == 2
            assert doc["page"] == 1
            by_src = {a["source"]: a for a in doc["alerts"]}
            assert by_src["t_prov"]["slo"] == "t.x"
            assert by_src["t_prov"]["burn_fast"] == 20.0
            assert "RuntimeError" in by_src["t_boom"]["error"]
            assert by_src["t_boom"]["severity"] == "warn"
        finally:
            exporter.unregister_alerts("t_prov")
            exporter.unregister_alerts("t_boom")
            exp.stop()

    def test_healthz_ok_degraded_ok_cycle(self):
        """healthz flips 200/ok -> 503/degraded -> 200/ok as a probe's
        verdict changes — the load-balancer rotation contract."""
        exp = exporter.MetricsExporter(port=0).start()
        verdict = {"ok": True}
        exporter.register_health("t_cycle", lambda: dict(verdict))
        try:
            code, _, text = self._get_with_headers(exp.port, "/healthz")
            assert code == 200
            assert json.loads(text)["status"] == "ok"
            verdict["ok"] = False
            try:
                self._get(exp.port, "/healthz")
                code, text = None, None
            except urllib.error.HTTPError as e:
                code, text = e.code, e.read().decode()
            assert code == 503
            doc = json.loads(text)
            assert doc["status"] == "degraded" and doc["ok"] is False
            verdict["ok"] = True
            code, _, text = self._get_with_headers(exp.port, "/healthz")
            assert code == 200
            assert json.loads(text)["status"] == "ok"
        finally:
            exporter.unregister_health("t_cycle")
            exp.stop()

    def test_ensure_started_republishes_addr(self):
        """Repeat ensure_started calls re-publish the bound address — a
        restarted TCPStore (fresh kv) relearns the scrape target."""
        class FakeStore:
            def __init__(self):
                self.kv = {}

            def set(self, k, v):
                self.kv[k] = v

        import socket

        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        free_port = probe.getsockname()[1]
        probe.close()
        paddle.set_flags({"FLAGS_metrics_port": free_port})
        try:
            store = FakeStore()
            exp = exporter.ensure_started(store=store, rank=1)
            assert exp is not None
            key = f"{exporter.ADDR_KEY_PREFIX}/1/metrics_addr"
            assert store.kv[key] == exp.address
            store.kv.clear()  # simulate a store restart losing the key
            assert exporter.ensure_started(store=store, rank=1) is exp
            assert store.kv[key] == exp.address
        finally:
            paddle.set_flags({"FLAGS_metrics_port": 0})
            exporter.stop()

    def test_ensure_started_gated_by_flag_and_publishes_addr(self):
        import socket

        assert int(paddle.get_flags("FLAGS_metrics_port")["FLAGS_metrics_port"]) == 0
        assert exporter.ensure_started() is None  # default: off

        class FakeStore:
            def __init__(self):
                self.kv = {}

            def set(self, k, v):
                self.kv[k] = v

        store = FakeStore()
        busy = socket.socket()
        busy.bind(("127.0.0.1", 0))
        busy.listen(1)
        paddle.set_flags({"FLAGS_metrics_port": busy.getsockname()[1]})
        try:
            before = metrics.counters("exporter.").get(
                "exporter.bind_failures", 0)
            assert exporter.ensure_started(store=store, rank=3) is None
            assert metrics.counters("exporter.")["exporter.bind_failures"] \
                == before + 1
            busy.close()  # port freed: the same flag now binds
            exp = exporter.ensure_started(store=store, rank=3)
            assert exp is not None
            assert exporter.ensure_started() is exp  # idempotent
            assert store.kv[f"{exporter.ADDR_KEY_PREFIX}/3/metrics_addr"] \
                == exp.address
        finally:
            busy.close()
            paddle.set_flags({"FLAGS_metrics_port": 0})
            exporter.stop()


# ------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_dump_is_parseable_and_carries_context(self, run_log_dir):
        flightrec.reset()
        paddle.seed(0)
        tid = trace.new_trace_id("t")
        obs.emit("t_fr_event", detail=1)
        try:
            with trace.attach(tid):
                raise RuntimeError("induced crash")
        except RuntimeError as exc:
            with trace.attach(tid):
                path = flightrec.dump("test_crash", exc, widget=7,
                                      unjsonable=object())
        assert path and os.path.dirname(path) == str(run_log_dir)
        doc = json.load(open(path))
        assert doc["format"] == 1
        assert doc["reason"] == "test_crash"
        assert doc["trace"] == tid
        assert doc["context"]["widget"] == 7
        assert isinstance(doc["context"]["unjsonable"], str)
        assert doc["exception"]["type"] == "RuntimeError"
        assert "induced crash" in doc["exception"]["message"]
        assert any(e.get("event") == "t_fr_event" for e in doc["events"])
        # the dump itself is a run-log event too
        frs = [e for e in _read_log(run_log_dir)
               if e.get("event") == "flightrec"]
        assert frs and frs[0]["reason"] == "test_crash"

    def test_budget_bounds_dumps_per_process(self, run_log_dir):
        flightrec.reset()
        paths = [flightrec.dump(f"storm_{i}") for i in range(6)]
        assert all(p is not None for p in paths[:4])
        assert paths[4] is None and paths[5] is None  # budget spent
        assert len({os.path.basename(p) for p in paths[:4]}) == 4
        flightrec.reset()
        assert flightrec.dump("re_armed") is not None

    def test_disabled_by_flag(self):
        flightrec.reset()
        paddle.set_flags({"FLAGS_flightrec_events": 0})
        try:
            assert flightrec.dump("off") is None
        finally:
            paddle.set_flags({"FLAGS_flightrec_events": 256})

    def test_dispatch_exception_dumps(self, run_log_dir):
        """An unhandled exception inside a compiled dispatch leaves a
        flight record naming the component."""
        import paddle_tpu.nn as nn

        flightrec.reset()
        model = nn.Sequential(nn.Linear(4, 2))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = paddle.jit.TrainStep(model, opt, nn.CrossEntropyLoss())
        X = np.random.randn(8, 4).astype("float32")
        Y = np.random.randint(0, 2, (8,)).astype("int64")
        step(X, Y)

        def boom(*args):
            raise RuntimeError("poisoned dispatch")

        sig = next(iter(step._compiled))
        step._compiled[sig] = boom  # a dispatch entry that dies mid-flight
        with pytest.raises(RuntimeError, match="poisoned"):
            step(X, Y)
        dumps = sorted(run_log_dir.glob("flightrec-*.json"))
        assert dumps, "dispatch exception produced no flight record"
        doc = json.load(open(dumps[0]))
        assert doc["reason"] == "dispatch_exception"
        assert doc["context"]["component"] == "train_step"
        assert doc["exception"]["type"] == "RuntimeError"
