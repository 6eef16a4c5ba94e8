"""Cross-process serving fleet: subprocess replicas killed with a real
SIGKILL mid-decode must deliver completions bitwise-identical to an
unkilled in-process run, exactly once — including per-token streaming
clients (no gaps, duplicates, or reordering across the requeue) — with
warm AOT boots pinned at ``infer.compiles == 0``, stale-beat detection of
hung-but-alive children, FleetDrainedError on total loss, the store-RPC
transport itself, and the launcher's ``--serve`` mode."""
import json
import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (
    ContinuousBatchingScheduler,
    DecodeEngine,
    FleetDrainedError,
    ProcServingFleet,
)
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.observability import flightrec, runlog
from paddle_tpu.testing import chaos

# the one engine spec for the whole module: identical fingerprints mean
# the shared FLAGS_compile_cache_dir AOT store compiles each program ONCE
# (in the in-process reference run) and every replica SUBPROCESS after it
# boots from disk at infer.compiles == 0
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
    d = tmp_path_factory.mktemp("procfleet_aot")
    paddle.set_flags({"FLAGS_compile_cache_dir": str(d)})
    yield str(d)
    paddle.set_flags({"FLAGS_compile_cache_dir": prev})


@pytest.fixture
def run_log_dir(tmp_path):
    prev = paddle.get_flags("FLAGS_run_log_dir")["FLAGS_run_log_dir"]
    paddle.set_flags({"FLAGS_run_log_dir": str(tmp_path)})
    runlog.monitor().clear()
    yield str(tmp_path)
    paddle.set_flags({"FLAGS_run_log_dir": prev})


def _prompts(n, lens=(5, 9, 3, 12, 7, 11)):
    rng = np.random.default_rng(42)
    return [rng.integers(0, 512, (lens[i % len(lens)],)).astype("int32")
            for i in range(n)]


def _reference_tokens(model, prompts, max_new=6):
    """Unkilled single-engine in-process run: the tokens every
    cross-process run — killed or not — must match bitwise."""
    eng = DecodeEngine(model, **KW)
    sched = ContinuousBatchingScheduler(eng)
    rids = [sched.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(prompts)]
    done = sched.run()
    return [list(done[r].tokens) for r in rids]


# ------------------------------------------------- the tier-1 acceptance pin
class TestSigkillExactlyOnce:
    def test_sigkill_mid_decode_bitwise_exactly_once_streaming(
            self, model, run_log_dir):
        """The acceptance pin, against a real kill -9: a 2-replica
        subprocess fleet with FLAGS_chaos_replica_sigkill_at armed (once both
        children have reported their first tick) loses replica 1 to SIGKILL
        mid-decode; every request — including the
        stream=True client — finishes exactly once, bitwise-equal to the
        unkilled in-process reference; the streamed chunk sequence has no
        gaps/dups/reordering across the requeue; children boot warm at
        infer.compiles == 0; the merged report sees all three processes
        with the requeue edge; the parent dumps a flight record naming
        the dead rid and its in-flight fids."""
        prompts = _prompts(5)
        want = _reference_tokens(model, prompts)  # also warms the AOT cache
        flightrec.reset()
        with ProcServingFleet(GPTConfig.tiny(), replicas=2,
                              heartbeat_timeout=60.0, **KW) as fleet:
            # A replica loads its programs in its first tick, and the parent
            # learns a child's counters from its heartbeat, every 50 ms: a
            # kill at replica 1's first tick outruns the beat that reports
            # the load. So each replica first serves one request, the parent
            # waits (bounded) for both beats, and the kill is armed for the
            # next tick it harvests from replica 1: mid-decode of what
            # follows.
            for p in prompts[:2]:
                fleet.submit(p[::-1].copy(), max_new_tokens=2)
            fleet.run(timeout_s=120)
            deadline = time.monotonic() + 30.0
            while (min(c["aot_cache_hits"] for c in fleet.child_counters().values()) < 1
                   and time.monotonic() < deadline):
                fleet.step()
                time.sleep(0.02)
            with chaos.inject(FLAGS_chaos_replica_sigkill_at=f"1:{fleet.replicas[1].ticks + 1}"):
                stream = fleet.submit(prompts[0], max_new_tokens=6, seed=0,
                                      stream=True)
                fids = [stream.fid]
                fids += [fleet.submit(p, max_new_tokens=6, seed=i)
                         for i, p in enumerate(prompts) if i > 0]
                chunks = list(stream)          # drives the fleet until done
                fleet.run(timeout_s=300)       # finish the non-stream fids
                st = fleet.stats()
                counters = fleet.child_counters()
                got = [list(fleet.requests[f].tokens) for f in fids]

        # the kill really was a SIGKILL of a live subprocess, mid-work
        assert st["dead"] == [1] and st["alive"] == [0]
        assert "rc=-9" in st["per_replica"][1]["death_reason"]
        assert st["requeues"] >= 1
        # exactly once + bitwise: every request finished with the
        # reference tokens (the ledger admits no duplicate completion)
        assert all(fleet.requests[f].status == "finished" for f in fids)
        assert got == want
        # the stream: in-order chunks, each non-empty, concatenating to
        # exactly the reference — no gap, duplicate, or reorder survives
        # the mid-stream requeue
        assert chunks and all(c for c in chunks)
        assert [t for c in chunks for t in c] == want[0]
        # warm boot pin: both subprocesses served from the shared AOT
        # cache without compiling anything themselves
        for rid, c in counters.items():
            assert c["compiles"] == 0, (rid, c)
            assert c["aot_cache_hits"] >= 1, (rid, c)
        # cross-process observability: parent + both replica lanes merge,
        # the requeue edge survives the process boundary
        from paddle_tpu.observability.__main__ import analyze_merged
        merged = analyze_merged(run_log_dir)
        assert len(merged["processes"]) >= 3
        edges = merged.get("requeue_edges") or []
        assert any(e["from"] == 1 for e in edges)
        assert merged.get("lanes")
        # the parent-side flight record names the dead rid + in-flight fids
        recs = [f for f in os.listdir(run_log_dir) if f.startswith("flightrec-")]
        assert recs
        docs = [json.load(open(os.path.join(run_log_dir, f))) for f in recs]
        dead = [d for d in docs if d.get("context", {}).get("replica") == 1
                or d.get("reason") == "replica_death"]
        assert dead and dead[0]["context"]["inflight"]


# ------------------------------------------------------- transport + hooks
class TestRpc:
    def test_channel_ordering_destructive_reads_and_heartbeat(self):
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.inference.rpc import Channel, Heartbeat

        store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1,
                         timeout=5.0)
        try:
            w = Channel(store, "t/0/out")
            r = Channel(store, "t/0/out")
            for i in range(5):
                w.send("tick", i=i)
            msgs = r.recv()
            assert [m["i"] for m in msgs] == list(range(5))
            assert [m["seq"] for m in msgs] == [1, 2, 3, 4, 5]
            assert r.recv() == []        # drained; reads were destructive
            w.send("tick", i=99)
            assert [m["i"] for m in r.recv()] == [99]  # resumes in order

            hb = Heartbeat(store, "t", 0)
            hbr = Heartbeat(store, "t", 0)
            assert hbr.read(timeout=0.05) is None      # no beat yet
            hb.beat(ready=True, compiles=0)
            doc = hbr.read()
            assert doc["n"] == 1 and doc["ready"] and doc["compiles"] == 0
            hb.beat(ready=True)
            assert hbr.read()["n"] == 2                # counter moves
        finally:
            store.close()


class TestChaosHooks:
    def test_sigkill_hook_gated_scoped_and_fire_once(self):
        assert not chaos.replica_sigkill_due(1, 99)    # FLAGS_chaos off
        with chaos.inject(FLAGS_chaos_replica_sigkill_at="1:2"):
            assert not chaos.replica_sigkill_due(0, 99)  # other replica
            assert not chaos.replica_sigkill_due(1, 1)   # before K
            assert chaos.replica_sigkill_due(1, 2)
            assert not chaos.replica_sigkill_due(1, 3)   # fired once
            evs = [e for e in runlog.monitor().events("chaos_inject")
                   if e.get("kind") == "replica_sigkill"]
            assert evs and evs[-1]["replica"] == 1 and evs[-1]["tick"] == 2

    def test_hang_hook_gated_scoped_and_fire_once(self):
        assert chaos.replica_hang_due_ms(0) == 0.0     # FLAGS_chaos off
        with chaos.inject(FLAGS_chaos_replica_hang_ms="250"):
            assert chaos.replica_hang_due_ms(0) == 250.0
            assert chaos.replica_hang_due_ms(0) == 0.0  # fired once
            assert chaos.replica_hang_due_ms(1) == 250.0  # per-replica
        with chaos.inject(FLAGS_chaos_replica_hang_ms="1:100"):
            assert chaos.replica_hang_due_ms(0) == 0.0  # scoped to R
            assert chaos.replica_hang_due_ms(1) == 100.0
            evs = [e for e in runlog.monitor().events("chaos_inject")
                   if e.get("kind") == "replica_hang"]
            assert evs and evs[-1]["hang_ms"] == 100.0


# ------------------------------------------------------------- slow faults
@pytest.mark.slow
class TestSlowFaults:
    def test_hang_without_exit_detected_by_stale_beat(self, model):
        """FLAGS_chaos_replica_hang_ms wedges replica 1 (alive, silent)
        after its first served tick; only the parent's stale-beat sweep
        can tell. Its work requeues; completions stay bitwise."""
        prompts = _prompts(4)
        want = _reference_tokens(model, prompts)
        with chaos.inject(FLAGS_chaos_replica_hang_ms="1:60000"):
            with ProcServingFleet(GPTConfig.tiny(), replicas=2,
                                  heartbeat_timeout=1.5, beat_interval=0.05,
                                  **KW) as fleet:
                fids = [fleet.submit(p, max_new_tokens=6, seed=i)
                        for i, p in enumerate(prompts)]
                fleet.run(timeout_s=300)
                st = fleet.stats()
                got = [list(fleet.requests[f].tokens) for f in fids]
        assert st["dead"] == [1]
        assert "heartbeat lost" in st["per_replica"][1]["death_reason"]
        assert all(fleet.requests[f].status == "finished" for f in fids)
        assert got == want

    def test_all_replicas_dead_raises_drained_with_lost_fids(self, model):
        """Both subprocesses SIGKILLed: the first detected death requeues
        onto the (already dead) survivor, the second strands everything —
        one FleetDrainedError lists every lost fid, and later submits
        refuse loudly."""
        prompts = _prompts(3)
        with ProcServingFleet(GPTConfig.tiny(), replicas=2,
                              heartbeat_timeout=60.0, **KW) as fleet:
            for rep in fleet.replicas.values():
                os.kill(rep.pid, signal.SIGKILL)
            for rep in fleet.replicas.values():
                rep.proc.wait(timeout=30)
            fids = [fleet.submit(p, max_new_tokens=6, seed=i)
                    for i, p in enumerate(prompts)]
            with pytest.raises(FleetDrainedError) as ei:
                for _ in range(100):
                    fleet.step()
                    time.sleep(0.01)
            assert sorted(ei.value.lost) == sorted(fids)
            with pytest.raises(FleetDrainedError):
                fleet.submit(prompts[0], max_new_tokens=4)

    def test_launch_serve_boots_adoptable_fleet(self, model, tmp_path):
        """launch --serve boots store-registered replicas from the
        launcher; ProcServingFleet.attach adopts them, serves bitwise
        completions, and shutdown() drains the launcher to rc 0."""
        from paddle_tpu.distributed.launch.main import launch

        prompts = _prompts(3)
        want = _reference_tokens(model, prompts)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        master = f"127.0.0.1:{port}"
        spec = {"ns": "serve-t", "beat_interval": 0.05,
                "model": {"seed": 0, "config": vars(GPTConfig.tiny())},
                "engine_kwargs": KW}
        spec_path = tmp_path / "serve.json"
        spec_path.write_text(json.dumps(spec))
        rc = []
        t = threading.Thread(target=lambda: rc.append(launch(
            ["--serve", "--nproc_per_node", "2", "--master", master,
             str(spec_path)])), daemon=True)
        t.start()
        fleet = ProcServingFleet.attach(master, ns="serve-t",
                                        heartbeat_timeout=60.0,
                                        boot_timeout=180.0)
        try:
            assert len(fleet.replicas) == 2
            fids = [fleet.submit(p, max_new_tokens=6, seed=i)
                    for i, p in enumerate(prompts)]
            fleet.run(timeout_s=300)
            got = [list(fleet.requests[f].tokens) for f in fids]
            assert got == want
        finally:
            fleet.shutdown()
        t.join(timeout=60)
        assert not t.is_alive() and rc == [0]
