"""Fault-tolerant serving fleet: kill-safe drain/requeue (exactly-once,
bitwise), prefix-affinity routing, load shedding, deadlines/cancellation,
heartbeat health, AOT-warm scale-out, jittered retry backoff, and the
fleet observability surface."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import (
    ContinuousBatchingScheduler,
    DecodeEngine,
    FleetDrainedError,
    FleetOverloadError,
    Router,
    ServingFleet,
)
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.observability import runlog
from paddle_tpu.testing import chaos

# one engine spec for the whole module: identical fingerprints mean the
# shared FLAGS_compile_cache_dir AOT store compiles each program ONCE and
# every later engine/replica in the file boots from disk
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
    d = tmp_path_factory.mktemp("fleet_aot")
    paddle.set_flags({"FLAGS_compile_cache_dir": str(d)})
    yield str(d)
    paddle.set_flags({"FLAGS_compile_cache_dir": prev})


def _prompts(n, lens=(5, 9, 3, 12, 7, 11)):
    rng = np.random.default_rng(42)
    return [rng.integers(0, 512, (lens[i % len(lens)],)).astype("int32")
            for i in range(n)]


def _reference_tokens(model, prompts, max_new=6):
    """Unkilled single-engine run: the tokens every fleet run must match."""
    eng = DecodeEngine(model, **KW)
    sched = ContinuousBatchingScheduler(eng)
    rids = [sched.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(prompts)]
    done = sched.run()
    return [list(done[r].tokens) for r in rids]


# ------------------------------------------------------- kill + requeue
class TestKillRequeue:
    def test_mid_stream_kill_finishes_exactly_once_bitwise(self, model):
        """The acceptance pin: FLAGS_chaos_replica_kill_at fires mid-stream
        on a 2-replica fleet; every submitted request finishes exactly once
        with tokens bitwise-equal to the unkilled single-replica run."""
        prompts = _prompts(6)
        want = _reference_tokens(model, prompts)
        profiler.reset_counters("fleet.")
        with chaos.inject(FLAGS_chaos_replica_kill_at="1:2"):
            fleet = ServingFleet(model, replicas=2, **KW)
            fids = [fleet.submit(p, max_new_tokens=6, seed=i)
                    for i, p in enumerate(prompts)]
            done = fleet.run()
        st = fleet.stats()
        assert st["dead"] == [1] and st["alive"] == [0]
        assert st["requeues"] >= 1  # the kill really hit in-flight work
        # exactly once: every fid present, finished, no duplicates possible
        # (completion writes the ledger once, keyed by fid)
        assert sorted(done) == sorted(fids)
        for i, f in enumerate(fids):
            assert done[f].status == "finished"
            assert list(done[f].tokens) == want[i], f"request {i} diverged"
        c = profiler.counters("fleet.")
        assert c["fleet.replica_deaths"] == 1
        assert c["fleet.requeues"] == st["requeues"]
        assert c["fleet.requests_completed"] == len(prompts)

    def test_admin_kill_requeues_queued_and_running(self, model):
        """kill_replica (the direct form of the chaos kill) drains BOTH the
        dead replica's queue and its mid-decode slots onto the survivor."""
        prompts = _prompts(6)
        want = _reference_tokens(model, prompts)
        fleet = ServingFleet(model, replicas=2, **KW)
        fids = [fleet.submit(p, max_new_tokens=6, seed=i)
                for i, p in enumerate(prompts)]
        fleet.step()  # admit into slots; queues still hold the overflow
        victim = 1
        assert any(fleet.requests[f].replica == victim for f in fids)
        fleet.kill_replica(victim)
        done = fleet.run()
        assert sorted(done) == sorted(fids)
        for i, f in enumerate(fids):
            assert list(done[f].tokens) == want[i]
        assert all(r.replica == 0 for r in done.values()
                   if r.attempts > 1)

    def test_all_replicas_dead_is_loud(self, model):
        fleet = ServingFleet(model, replicas=1, **KW)
        fid = fleet.submit(_prompts(1)[0], max_new_tokens=6)
        fleet.step()
        with pytest.raises(FleetDrainedError) as ei:
            fleet.kill_replica(0)
        assert fid in ei.value.lost

    def test_cascade_death_during_requeue_keeps_full_lost_accounting(
            self, model, monkeypatch):
        """Regression: the survivor dies WHILE absorbing requeued work —
        _on_replica_death re-enters mid-drain. The single-pass requeue
        raised a FleetDrainedError accounting only the nested replica's
        in-flight set, silently dropping the first victim's remaining
        fids; the re-entrant drain must report every lost fid once."""
        fleet = ServingFleet(model, replicas=2, **KW)
        fids = [fleet.submit(p, max_new_tokens=4, seed=i, replica=i % 2)
                for i, p in enumerate(_prompts(4))]
        orig_place = fleet._place
        fired = []

        def cascade_place(freq, rid, reason, deadline_s="unset"):
            orig_place(freq, rid, reason, deadline_s=deadline_s)
            if not fired and reason.startswith("requeue"):
                fired.append(rid)
                fleet._on_replica_death(
                    fleet.replicas[rid],
                    RuntimeError("cascade: survivor died absorbing requeue"))

        monkeypatch.setattr(fleet, "_place", cascade_place)
        with pytest.raises(FleetDrainedError) as ei:
            fleet.kill_replica(0)
        # every in-flight fid is accounted lost, exactly once
        assert sorted(ei.value.lost) == sorted(fids)
        assert fleet.stats()["alive"] == []
        assert not fleet._draining and not fleet._requeue_backlog


# ------------------------------------------------------------- routing
class TestRouting:
    def test_prefix_affinity_lands_on_chain_holder(self, model):
        """A shared-prefix request routes to the replica already holding the
        chain — the satellite's affinity pin."""
        rng = np.random.default_rng(7)
        fleet = ServingFleet(model, replicas=3, **dict(KW, prefix_cache_mb=8.0))
        shared = rng.integers(0, 512, (17,)).astype("int32")  # 2 full chunks
        f0 = fleet.submit(shared, max_new_tokens=4)
        fleet.run()
        holder = fleet.requests[f0].replica
        tail = np.concatenate(
            [shared[:16], rng.integers(0, 512, (5,)).astype("int32")])
        profiler.reset_counters("fleet.routed_")
        f1 = fleet.submit(tail, max_new_tokens=4)
        assert fleet.requests[f1].replica == holder
        assert profiler.counters("fleet.")["fleet.routed_affinity"] == 1
        fleet.run()
        # and the engine really reused the chain: prefix cache hit on holder
        assert fleet.replicas[holder].engine.prefix_cache.hits >= 1

    def test_affinity_forgotten_on_death(self, model):
        rng = np.random.default_rng(8)
        fleet = ServingFleet(model, replicas=2, **KW)
        shared = rng.integers(0, 512, (17,)).astype("int32")
        f0 = fleet.submit(shared, max_new_tokens=4)
        fleet.run()
        holder = fleet.requests[f0].replica
        fleet.kill_replica(holder)
        f1 = fleet.submit(shared, max_new_tokens=4)
        assert fleet.requests[f1].replica != holder
        done = fleet.run()
        assert done[f1].status == "finished"

    def test_router_load_tiebreak_and_slack(self):
        r = Router(chunk=8, affinity_load_slack=1)
        prompt = np.arange(32, dtype=np.int32)
        r.register(prompt, 1)
        # holder within slack -> affinity; past slack -> least load
        assert r.place(prompt, {0: 0, 1: 1}) == (1, "affinity")
        assert r.place(prompt, {0: 0, 1: 5}) == (0, "load")
        assert r.place(prompt, {0: 2, 1: 7, 2: 2}) == (0, "load")  # id tiebreak
        r.forget_replica(1)
        assert r.place(prompt, {0: 3, 1: 0}) == (1, "load")


# -------------------------------------------------- graceful degradation
class TestDegradation:
    def test_overload_sheds_structured(self, model):
        fleet = ServingFleet(model, replicas=1, max_queue_depth=2, **KW)
        p = _prompts(1)[0]
        fleet.submit(p, max_new_tokens=4)
        fleet.submit(p, max_new_tokens=4)
        profiler.reset_counters("fleet.sheds")
        with pytest.raises(FleetOverloadError) as ei:
            fleet.submit(p, max_new_tokens=4)
        assert (ei.value.queued, ei.value.limit, ei.value.replicas_alive) == (2, 2, 1)
        assert profiler.counters("fleet.")["fleet.sheds"] == 1
        fleet.run()
        fleet.submit(p, max_new_tokens=4)  # drained: admission reopens

    def test_fleet_deadline_expires_and_counts(self, model):
        fleet = ServingFleet(model, replicas=1, **KW)
        p = _prompts(1)[0]
        profiler.reset_counters("fleet.deadline_hits")
        fid = fleet.submit(p, max_new_tokens=40, deadline_s=1e-4)
        time.sleep(0.002)
        fleet.run()
        assert fleet.requests[fid].status == "deadline_exceeded"
        assert fleet.requests[fid].tokens == []
        assert profiler.counters("fleet.")["fleet.deadline_hits"] == 1
        # the slot is free again: a normal request completes
        fid2 = fleet.submit(p, max_new_tokens=4)
        assert fleet.run()[fid2].status == "finished"


# ----------------------------------------------- scheduler cancel path
class TestSchedulerCancel:
    def test_cancel_mid_decode_frees_slot(self, model):
        eng = DecodeEngine(model, **KW)
        s = ContinuousBatchingScheduler(eng)
        p = _prompts(2)
        r1 = s.submit(p[0], max_new_tokens=30)
        while not s.running:  # drive through prefill into decode
            s.step()
        assert eng.free_slots() == [1]
        runlog.monitor().clear()
        assert s.cancel(r1) is True
        assert s.cancel(r1) is False  # already gone: idempotent no-op
        assert s.cancelled[r1].status == "cancelled"
        assert eng.free_slots() == [0, 1]
        evs = runlog.monitor().events("request")
        assert any(e.get("status") == "cancelled" and e.get("id") == r1
                   for e in evs)
        # the freed slot admits new work and the stream stays healthy
        r2 = s.submit(p[1], max_new_tokens=4)
        done = s.run()
        assert r2 in done and r1 not in done

    def test_deadline_exceeded_mid_stream(self, model):
        eng = DecodeEngine(model, **KW)
        s = ContinuousBatchingScheduler(eng)
        p = _prompts(2)
        rfast = s.submit(p[0], max_new_tokens=4)
        rdead = s.submit(p[1], max_new_tokens=40, deadline_s=1e-4)
        profiler.reset_counters("serving.deadline_exceeded")
        time.sleep(0.002)
        runlog.monitor().clear()
        done = s.run()
        assert rfast in done and rdead not in done
        assert s.cancelled[rdead].status == "deadline_exceeded"
        assert profiler.counters("serving.")["serving.deadline_exceeded"] == 1
        assert any(e.get("status") == "deadline_exceeded"
                   for e in runlog.monitor().events("request"))

    def test_deadline_validation(self, model):
        eng = DecodeEngine(model, **KW)
        s = ContinuousBatchingScheduler(eng)
        with pytest.raises(ValueError):
            s.submit(_prompts(1)[0], max_new_tokens=4, deadline_s=0)


# --------------------------------------------------- health + heartbeat
class TestHealth:
    def test_slow_replica_declared_dead_and_drained(self, model):
        """FLAGS_chaos_replica_slow_ms past the heartbeat window = zombie:
        same drain/requeue protocol as a crash."""
        p = _prompts(4, lens=(5,))
        with chaos.inject(FLAGS_chaos_replica_slow_ms="1:30"):
            fleet = ServingFleet(model, replicas=2, heartbeat_timeout=0.02, **KW)
            fids = [fleet.submit(q, max_new_tokens=4, seed=3) for q in p]
            done = fleet.run()
        st = fleet.stats()
        assert st["dead"] == [1]
        assert "heartbeat lost" in st["per_replica"][1]["death_reason"]
        assert sorted(done) == sorted(fids)

    def test_store_heartbeats_published(self, model):
        from paddle_tpu.distributed.store import TCPStore

        store = TCPStore(is_master=True, timeout=5.0)
        try:
            fleet = ServingFleet(model, replicas=2, store=store, **KW)
            fid = fleet.submit(_prompts(1)[0], max_new_tokens=4)
            fleet.run()
            ages = fleet.membership()
            assert set(ages) == {0, 1}
            assert all(a < 5.0 for a in ages.values())
            assert fleet.requests[fid].status == "finished"
        finally:
            store.close()


# ----------------------------------------------------- AOT warm scale-out
class TestScaleOut:
    def test_scale_out_serves_at_zero_compiles(self, model, aot_dir):
        """Cold scale-out replica boots from the AOT executable cache:
        first token at infer.compiles == 0 (the acceptance pin)."""
        p = _prompts(1)[0]
        # an executable loads only on the device it was built for, and
        # replica r lives on local device r: warm both devices' stores
        warm = ServingFleet(model, replicas=2, **KW)
        for r in (0, 1):
            warm.submit(p, max_new_tokens=4, seed=1, replica=r)
        warm.run()
        fleet = ServingFleet(model, replicas=1, **KW)
        f0 = fleet.submit(p, max_new_tokens=4, seed=1)
        fleet.run()  # ensures the family is compiled AND serialized
        profiler.reset_counters("infer.")
        new = fleet.scale_out(1)
        f1 = fleet.submit(p, max_new_tokens=4, seed=1, replica=new[0])
        done = fleet.run()
        c = profiler.counters("infer.")
        assert int(c.get("infer.compiles", 0)) == 0, c
        assert int(c.get("infer.aot_cache_hits", 0)) >= 1
        assert list(done[f1].tokens) == list(fleet.requests[f0].tokens)
        assert profiler.counters("fleet.")["fleet.scale_outs"] >= 1


# ------------------------------------------------------- retry jitter
class TestRetryJitter:
    def _sleeps(self, jitter, seed=42, attempts=4):
        from paddle_tpu.distributed.resilience import retry

        paddle.seed(seed)
        sleeps = []
        orig = time.sleep
        time.sleep = lambda s: sleeps.append(s)
        try:
            @retry(max_attempts=attempts, base_delay=0.01, max_delay=0.05,
                   jitter=jitter)
            def boom():
                raise OSError("injected")

            with pytest.raises(OSError):
                boom()
        finally:
            time.sleep = orig
        return sleeps

    def test_full_jitter_deterministic_and_capped(self):
        first = self._sleeps(jitter=True)
        again = self._sleeps(jitter=True)
        assert first == again  # framework.random seeding: bitwise replay
        caps = [0.01, 0.02, 0.04]
        assert all(0.0 <= s <= c for s, c in zip(first, caps))
        assert first != caps  # it actually jittered off the cap schedule

    def test_jitter_off_keeps_deterministic_caps(self):
        assert self._sleeps(jitter=False) == [0.01, 0.02, 0.04]

    def test_flag_knob_controls_default(self):
        prev = paddle.get_flags("FLAGS_store_retry_jitter")["FLAGS_store_retry_jitter"]
        try:
            paddle.set_flags({"FLAGS_store_retry_jitter": False})
            assert self._sleeps(jitter=None) == [0.01, 0.02, 0.04]
            paddle.set_flags({"FLAGS_store_retry_jitter": True})
            assert self._sleeps(jitter=None) != [0.01, 0.02, 0.04]
        finally:
            paddle.set_flags({"FLAGS_store_retry_jitter": prev})

    def test_distinct_seeds_decorrelate(self):
        assert self._sleeps(jitter=True, seed=1) != self._sleeps(jitter=True, seed=2)


# --------------------------------------------------------- chaos hooks
class TestChaosHooks:
    def test_kill_hook_fires_once_per_replica(self):
        with chaos.inject(FLAGS_chaos_replica_kill_at="2:3"):
            assert not chaos.replica_kill_due(2, 2)   # not yet at tick 3
            assert not chaos.replica_kill_due(1, 5)   # wrong replica
            assert chaos.replica_kill_due(2, 3)
            assert not chaos.replica_kill_due(2, 4)   # already fired
        assert not chaos.replica_kill_due(2, 3)       # chaos off: no-op

    def test_slow_hook_specs(self):
        assert chaos.replica_slow_ms(0) == 0.0  # chaos off
        with chaos.inject(FLAGS_chaos_replica_slow_ms="25"):
            assert chaos.replica_slow_ms(0) == 25.0
            assert chaos.replica_slow_ms(7) == 25.0
        with chaos.inject(FLAGS_chaos_replica_slow_ms="1:40"):
            assert chaos.replica_slow_ms(1) == 40.0
            assert chaos.replica_slow_ms(0) == 0.0


# ------------------------------------------------------- observability
class TestObservability:
    def test_fleet_counters_predeclared(self):
        from paddle_tpu.observability.metrics import FLEET_COUNTERS, counters

        snap = counters("fleet.")
        for name in FLEET_COUNTERS:
            assert name in snap, name
        assert "serving.requests_cancelled" in counters("serving.")
        assert "serving.deadline_exceeded" in counters("serving.")

    def test_report_fleet_section(self, model):
        from paddle_tpu.observability.__main__ import analyze

        runlog.monitor().clear()
        with chaos.inject(FLAGS_chaos_replica_kill_at="1:2"):
            fleet = ServingFleet(model, replicas=2, **KW)
            for i, p in enumerate(_prompts(4)):
                fleet.submit(p, max_new_tokens=4, seed=i)
            fleet.run()
        a = analyze(runlog.monitor().events())
        fl = a["fleet"]
        assert fl["replica_deaths"] == 1
        assert fl["requeues"] == fleet.stats()["requeues"]
        assert fl["replicas_alive"] == [0] and fl["replicas_dead"] == [1]
        assert fl["finished"] == 4
        assert fl["finished_after_requeue"] >= 1
        assert 0 in fl["per_replica_rps"]
        assert "1" in str(list(fl["death_reasons"]))
