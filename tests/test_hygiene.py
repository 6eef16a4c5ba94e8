"""Dispatch-hygiene tests (PTA3xx + FLAGS_sanitize).

Per-pass fixture matrices with clean twins for the five static passes, the
CLI ``--hygiene`` mode (file/dir/module targets, --json schema, --strict
exits, ``# noqa`` suppression), the PTA-code drift guard (every registered
code appears in the README tables and the CLI help), the runtime sanitizer
guards (recompile churn naming the diffing aval, transfer_guard on the
dispatch path, donated-state poisoning, ledger growth), the keep-last-k
ledger GC (500-request regression), and the package self-check + the tiny
train/serve smokes under ``FLAGS_sanitize=1``.
"""
import json
import os
import re
import textwrap
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.analysis import format_report, sanitizer
from paddle_tpu.analysis.hygiene import (
    HYGIENE_CODES,
    check_path,
    check_source,
)
from paddle_tpu.inference import ContinuousBatchingScheduler, ServingFleet
from paddle_tpu.inference.fleet import FleetRequest
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import (
    GPTConfig,
    GPTForPretraining,
    GPTPretrainingCriterion,
)
from paddle_tpu.observability import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
    d = tmp_path_factory.mktemp("hygiene_aot")
    paddle.set_flags({"FLAGS_compile_cache_dir": str(d)})
    yield str(d)
    paddle.set_flags({"FLAGS_compile_cache_dir": prev})


@pytest.fixture
def sanitize():
    names = ("FLAGS_sanitize", "FLAGS_sanitize_strict",
             "FLAGS_sanitize_max_recompiles")
    prev = {n: paddle.get_flags(n)[n] for n in names}
    sanitizer.reset()
    paddle.set_flags({"FLAGS_sanitize": True})
    yield
    paddle.set_flags(prev)
    sanitizer.reset()


def _codes(diags):
    return [d.code for d in diags]


def _check(src):
    return check_source(textwrap.dedent(src))


# ------------------------------------------------- static pass fixtures
class TestPTA301HostSync:
    def test_sync_calls_in_traced_fn(self):
        diags = _check("""
            import paddle

            @paddle.jit.to_static
            def f(x):
                if bool(x.mean() > 0):
                    print(x)
                return x.item()
            """)
        codes = _codes(diags)
        assert codes.count("PTA301") == 3  # bool(), print, .item()

    def test_scan_body_by_reference(self):
        diags = _check("""
            from jax import lax

            def body(carry, x):
                return carry + float(x), x

            def run(xs):
                return lax.scan(body, 0.0, xs)
            """)
        assert "PTA301" in _codes(diags)

    def test_clean_twin_static_attrs_and_host_funcs(self):
        diags = _check("""
            import paddle

            @paddle.jit.to_static
            def f(x):
                n = x.shape[0]
                m = int(n)            # shape access is static, not a sync
                k = len(x.shape)
                return x.reshape((m, k))
            """)
        assert "PTA301" not in _codes(diags)


class TestPTA302RecompileHazard:
    def test_readback_into_shape_and_slice(self):
        diags = _check("""
            import jax.numpy as jnp

            def pad(x, lengths):
                n = int(lengths.max().item())
                y = jnp.zeros((n, 4))
                return y, x[:n]
            """)
        assert _codes(diags).count("PTA302") == 2  # shape arg + slice bound

    def test_clean_twin_bucketed_readback(self):
        diags = _check("""
            import jax.numpy as jnp

            def pad(x, lengths):
                n = int(lengths.max().item())
                nb = ((n + 63) // 64) * 64   # bucketing breaks the hazard
                return jnp.zeros((nb, 4))
            """)
        assert "PTA302" not in _codes(diags)


class TestPTA303DonationAliasing:
    DIRTY = """
        class Trainer:
            def go(self, batch):
                w = self.state["params"]["w"]
                self.run_steps(batch)
                return w.sum()
        """

    def test_leaf_held_across_donated_dispatch(self):
        diags = _check(self.DIRTY)
        assert "PTA303" in _codes(diags)

    def test_clean_twin_refetch_after_dispatch(self):
        diags = _check("""
            class Trainer:
                def go(self, batch):
                    self.run_steps(batch)
                    w = self.state["params"]["w"]
                    return w.sum()
            """)
        assert "PTA303" not in _codes(diags)


class TestPTA304Nondeterminism:
    def test_entropy_in_seed_derivation(self):
        diags = _check("""
            import random
            import time

            def derive_seed(rank):
                base = int(time.time())
                jitter = random.randint(0, 3)
                for r in {1, 2, 3}:
                    base += r
                return base + jitter + rank
            """)
        assert _codes(diags).count("PTA304") == 3  # time, random, set-iter

    def test_clean_twin_seeded_rng(self):
        diags = _check("""
            import numpy as np

            def derive_seed(rank):
                rng = np.random.default_rng(1234 + rank)
                return int(rng.integers(0, 2**31))
            """)
        assert "PTA304" not in _codes(diags)


class TestPTA305LedgerGrowth:
    DIRTY = """
        class Server:
            def __init__(self):
                self.done = {}

            def step(self, req):
                self.done[req.rid] = req
        """

    def test_grow_without_shrink(self):
        diags = _check(self.DIRTY)
        assert "PTA305" in _codes(diags)
        assert "done" in diags[_codes(diags).index("PTA305")].message

    def test_clean_twin_with_gc(self):
        diags = _check("""
            class Server:
                def __init__(self):
                    self.done = {}

                def step(self, req):
                    self.done[req.rid] = req
                    for rid in list(self.done)[:-16]:
                        del self.done[rid]
            """)
        assert "PTA305" not in _codes(diags)


class TestNoqa:
    def test_exact_code_and_bare_noqa_suppress(self):
        src = """
            class Server:
                def __init__(self):
                    self.done = {}

                def step(self, req):
                    self.done[req.rid] = req__NOQA__
            """

        def variant(noqa):
            return _check(src.replace("__NOQA__", noqa))

        assert "PTA305" in _codes(variant(""))
        assert variant("  # noqa: PTA305 (test)") == []
        assert variant("  # noqa") == []
        # a noqa for a different code does NOT suppress
        assert "PTA305" in _codes(variant("  # noqa: PTA301"))


# ------------------------------------------------------------------ CLI
class TestHygieneCLI:
    DIRTY = textwrap.dedent(TestPTA305LedgerGrowth.DIRTY)

    def test_file_dir_module_targets(self, tmp_path, capsys):
        from paddle_tpu.analysis.__main__ import main

        p = tmp_path / "srv.py"
        p.write_text(self.DIRTY)
        assert main(["--hygiene", str(p)]) == 0        # warnings only
        assert "PTA305" in capsys.readouterr().out
        assert main(["--hygiene", str(tmp_path)]) == 0  # directory walk
        assert "PTA305" in capsys.readouterr().out
        assert main(["--hygiene", "paddle_tpu.models.lenet"]) == 0
        assert "clean" in capsys.readouterr().out

    def test_strict_exit_and_mutual_exclusion(self, tmp_path, capsys):
        from paddle_tpu.analysis.__main__ import main

        p = tmp_path / "srv.py"
        p.write_text(self.DIRTY)
        assert main(["--hygiene", "--strict", str(p)]) == 1
        capsys.readouterr()
        assert main(["--hygiene", "--hlo", str(p)]) == 2
        assert main(["--hygiene", str(tmp_path / "missing.py")]) == 2

    def test_json_schema(self, tmp_path, capsys):
        from paddle_tpu.analysis.__main__ import main

        p = tmp_path / "srv.py"
        p.write_text(self.DIRTY)
        assert main(["--hygiene", "--json", str(p)]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert rows and rows[0]["code"] == "PTA305"
        for key in ("code", "severity", "message", "hint", "file", "line"):
            assert key in rows[0]
        assert rows[0]["file"] == str(p)
        assert rows[0]["severity"] == "warning"

    def test_noqa_through_cli(self, tmp_path, capsys):
        from paddle_tpu.analysis.__main__ import main

        p = tmp_path / "srv.py"
        p.write_text(self.DIRTY.replace(
            "self.done[req.rid] = req",
            "self.done[req.rid] = req  # noqa: PTA305 (bounded elsewhere)"))
        assert main(["--hygiene", "--strict", str(p)]) == 0
        assert "clean" in capsys.readouterr().out


def test_pta_code_drift_guard(capsys):
    """Every PTA code registered in passes.py / spmd.py / hygiene.py (as a
    string literal) must appear in the README code tables AND the CLI help
    — the doc form of the PR-14 counter-declaration drift guard."""
    from paddle_tpu.analysis.__main__ import main

    src = ""
    for rel in ("paddle_tpu/analysis/passes.py",
                "paddle_tpu/analysis/spmd.py",
                "paddle_tpu/analysis/hygiene.py"):
        with open(os.path.join(REPO, rel), encoding="utf-8") as f:
            src += f.read()
    codes = sorted(set(re.findall(r'"(PTA\d{3})"', src)))
    assert len(codes) >= 18  # 7 IR + parse error + 6 SPMD + 5 hygiene
    assert set(HYGIENE_CODES) <= set(codes)
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    with pytest.raises(SystemExit):
        main(["--help"])
    help_text = capsys.readouterr().out
    missing_readme = [c for c in codes if c not in readme]
    missing_help = [c for c in codes if c not in help_text]
    assert not missing_readme, f"codes missing from README: {missing_readme}"
    assert not missing_help, f"codes missing from CLI help: {missing_help}"


# -------------------------------------------------- runtime sanitizer
def _tiny_step():
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    return net, TrainStep(net, paddle.optimizer.SGD(learning_rate=0.05),
                          nn.MSELoss())


def _batch(b):
    rng = np.random.default_rng(b)
    return (rng.standard_normal((b, 4)).astype("float32"),
            rng.standard_normal((b, 2)).astype("float32"))


class TestSanitizerGuards:
    def test_recompile_churn_warns_naming_diffing_aval(self, sanitize):
        paddle.set_flags({"FLAGS_sanitize_max_recompiles": 2})
        _, step = _tiny_step()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for b in (1, 2, 3):  # 3 distinct batch shapes > limit 2
                step(*_batch(b))
        msgs = [str(x.message) for x in w
                if issubclass(x.category, RuntimeWarning)
                and "recompile churn" in str(x.message)]
        assert msgs, "churn sentinel never warned"
        assert "diffing aval" in msgs[0] and "->" in msgs[0]
        assert "train_step" in msgs[0]
        assert metrics.counters("sanitizer.")["sanitizer.recompile_churn"] >= 1

    def test_recompile_churn_strict_raises(self, sanitize):
        paddle.set_flags({"FLAGS_sanitize_strict": True,
                          "FLAGS_sanitize_max_recompiles": 1})
        _, step = _tiny_step()
        step(*_batch(1))
        with pytest.raises(sanitizer.RecompileChurnError) as ei:
            step(*_batch(2))
        assert ei.value.count == 2 and ei.value.limit == 1
        assert "float32[1,4] -> float32[2,4]" in ei.value.diff

    def test_transfer_guard_raises_inside_scope(self, sanitize):
        import jax.numpy as jnp

        before = metrics.counters("sanitizer.")["sanitizer.host_transfers"]
        arr = jnp.arange(4.0)
        with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
            with sanitizer.transfer_scope("test.decode"):
                float(arr[0])  # implicit device->host readback
        with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
            with sanitizer.transfer_scope("test.decode"):
                jnp.asarray(np.arange(3)) + 1  # un-staged host upload
        after = metrics.counters("sanitizer.")["sanitizer.host_transfers"]
        assert after >= before + 2

    def test_intended_transfers_outside_scope_pass(self, sanitize):
        import jax.numpy as jnp

        dev = sanitizer.explicit_device({"x": np.arange(3, dtype=np.float32),
                                         "two": np.float32(2.0),
                                         "one": np.float32(1.0)})
        with sanitizer.transfer_scope("test.ok"):
            out = dev["x"] * dev["two"] + dev["one"]  # device-only: clean
        assert np.asarray(out).tolist() == [1.0, 3.0, 5.0]
        assert isinstance(dev["x"], jnp.ndarray)

    def test_donated_leaf_reuse_raises_structured(self, sanitize):
        net, step = _tiny_step()
        step(*_batch(2))
        # the dispatch donated the state tree; the model's eager mirrors
        # now reference deleted buffers and were poisoned by the sweep
        with pytest.raises(sanitizer.StaleStateError) as ei:
            np.asarray(net[0].weight._value)
        assert "0.weight" in str(ei.value) and "donated" in str(ei.value)
        step.sync_to_model()  # refresh: mirrors usable again
        assert np.asarray(net[0].weight._value).shape == (4, 8)
        assert metrics.counters("sanitizer.")["sanitizer.leaves_poisoned"] > 0

    def test_deleted_state_leaf_fails_preflight(self, sanitize):
        import jax

        _, step = _tiny_step()
        step(*_batch(2))
        jax.tree_util.tree_leaves(step.state)[0].delete()
        with pytest.raises(sanitizer.StaleStateError) as ei:
            step(*_batch(2))
        assert ei.value.component == "train_step"
        assert ei.value.leaf  # names the offending tree path

    def test_ledger_growth_warns_then_strict_raises(self, sanitize):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            sanitizer.note_ledger("fleet", "requests", size=900, bound=520)
        assert any("unbounded host-state growth" in str(x.message)
                   for x in w)
        paddle.set_flags({"FLAGS_sanitize_strict": True})
        with pytest.raises(sanitizer.LedgerGrowthError):
            sanitizer.note_ledger("fleet", "requests2", size=900, bound=520)


# ---------------------------------------------------------- ledger GC
class _FakeJob:
    """One-chunk prefill job: first token emitted at admission."""

    def __init__(self):
        self.reused_tokens = 0
        self.first = 7
        self.more = True
        self.pending = None


class _FakeEngine:
    """Minimal engine surface the scheduler drives — prefill completes in
    one chunk, decode emits one token per occupied slot per tick. Lets the
    ledger-GC regression push 500 requests through without model compute."""

    max_seq_len = 4096
    fuse = 1

    def __init__(self, slots=8):
        self.slots = slots
        self._free = list(range(slots))
        self._remaining = {}

    def bucket_for(self, n):
        return 64

    def free_slots(self):
        return sorted(self._free)

    def begin_prefill(self, prompt, slot, max_new_tokens=16,
                      eos_token_id=None, seed=0):
        self._free.remove(slot)
        self._remaining[slot] = int(max_new_tokens) - 1
        return _FakeJob()

    def prefill_step(self, job):
        return True

    def decode_step(self, ahead=False):
        toks = np.zeros((1, self.slots), np.int32)
        emitted = np.zeros((1, self.slots), bool)
        active = np.ones(self.slots, bool)
        for slot in list(self._remaining):
            toks[0, slot] = 11
            emitted[0, slot] = True
            self._remaining[slot] -= 1
            if self._remaining[slot] <= 0:
                active[slot] = False
        return toks, emitted, active

    def free_slot(self, slot):
        self._remaining.pop(slot, None)
        if slot not in self._free:
            self._free.append(slot)


class TestLedgerGC:
    def test_500_request_run_keeps_ledger_bounded(self):
        """Satellite regression: 500 requests through the scheduler with
        keep_finished=16 — every request delivered exactly once, the
        finished ledger never grows past k + the per-tick completion burst."""
        eng = _FakeEngine(slots=8)
        sched = ContinuousBatchingScheduler(eng, keep_finished=16)
        rids = [sched.submit(np.arange(5), max_new_tokens=3, seed=i)
                for i in range(500)]
        done, peak = {}, 0
        while sched.queue or sched.prefilling or sched.running:
            for r in sched.step():
                done[r.rid] = r
            peak = max(peak, len(sched.finished))
        assert sorted(done) == rids  # all 500, exactly once
        assert all(r.status == "finished" and len(r.tokens) == 3
                   for r in done.values())
        assert peak <= 16 + eng.slots, f"ledger peaked at {peak}"

    def test_run_returns_gc_evicted_completions(self):
        sched = ContinuousBatchingScheduler(_FakeEngine(slots=4),
                                            keep_finished=4)
        for i in range(60):
            sched.submit(np.arange(3), max_new_tokens=2, seed=i)
        done = sched.run()
        assert len(done) == 60  # run() accumulates across GC ticks
        assert len(sched.finished) <= 4 + 4

    def test_keep_finished_validation(self):
        with pytest.raises(ValueError):
            ContinuousBatchingScheduler(_FakeEngine(), keep_finished=0)

    def test_fleet_gc_evicts_terminal_only(self, model):
        fleet = ServingFleet(model, replicas=1, keep_finished=8, **KW)
        for i in range(500):
            r = FleetRequest(10_000 + i, np.arange(3), 2, None, 0, None)
            r.status = "finished" if i % 2 else "cancelled"
            fleet.requests[r.fid] = r
        live = FleetRequest(99_999, np.arange(3), 2, None, 0, None)
        live.status = "running"
        fleet.requests[live.fid] = live
        fleet._gc_ledger()
        terminal = [r for r in fleet.requests.values()
                    if r.status in fleet._TERMINAL]
        assert len(terminal) == 8  # oldest evicted, newest 8 kept
        assert fleet.requests[99_999] is live  # in-flight never evicted
        with pytest.raises(ValueError):
            ServingFleet(model, replicas=1, keep_finished=0, **KW)

    def test_fleet_run_with_gc_delivers_all(self, model):
        rng = np.random.default_rng(3)
        fleet = ServingFleet(model, replicas=1, keep_finished=4, **KW)
        fids = [fleet.submit(rng.integers(0, 512, (4,)).astype("int32"),
                             max_new_tokens=2, seed=i) for i in range(12)]
        done = fleet.run()
        assert sorted(done) == sorted(fids)
        assert all(done[f].status == "finished" for f in fids)
        assert fleet.stats()["finished_total"] == 12  # survives eviction
        terminal = [r for r in fleet.requests.values()
                    if r.status in fleet._TERMINAL]
        assert len(terminal) <= 4 + len(fids)  # bounded, protect-set slack


# ------------------------------------------- self-check + smoke (tier 1)
def test_self_check_package_and_examples_hygiene_clean():
    """The whole package + examples/ are PTA3xx-clean (fix-or-noqa, same
    discipline as the PTA1xx/PTA2xx self-checks)."""
    for rel in ("paddle_tpu", "examples"):
        diags = check_path(os.path.join(REPO, rel))
        assert diags == [], format_report(diags)


def test_tiny_gpt_train_loop_green_under_sanitize(sanitize):
    paddle.seed(11)
    cfg = GPTConfig.tiny()
    m = GPTForPretraining(cfg)
    step = TrainStep(m, paddle.optimizer.Adam(learning_rate=1e-3),
                     GPTPretrainingCriterion())
    rng = np.random.default_rng(11)
    losses = []
    for _ in range(2):
        ids = rng.integers(0, cfg.vocab_size, (2, 16)).astype("int32")
        out = step(ids, ids)
        losses.append(float(np.asarray(out["loss"])))
    assert all(np.isfinite(l) for l in losses)


def test_serving_smoke_green_under_sanitize(sanitize, model):
    from paddle_tpu.inference import DecodeEngine

    rng = np.random.default_rng(5)
    eng = DecodeEngine(model, **KW)
    sched = ContinuousBatchingScheduler(eng)
    rids = [sched.submit(rng.integers(0, 512, (l,)).astype("int32"),
                         max_new_tokens=3, seed=i)
            for i, l in enumerate((5, 9))]
    done = sched.run()
    assert sorted(done) == sorted(rids)
    assert all(len(done[r].tokens) == 3 for r in rids)
    # the sanitized decode loop really ran under the churn sentinel
    assert any(k.startswith("decode_engine") for k in sanitizer.stats())
