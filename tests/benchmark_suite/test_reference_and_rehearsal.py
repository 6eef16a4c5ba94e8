"""At a tiny width on the CPU: the program against the plain reference, every
driver end to end from Python (the four-chip one on the suite's virtual CPU
devices), and ``python -m benchmark.run`` itself refusing to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import tiny_root
from benchmark import run as bench_run
from benchmark.harness import device, manifest, peaks
from benchmark.harness.records import Records

REPO = tiny_root.REPO
CFG = tiny_root.TINY_CONFIG


@pytest.fixture(autouse=True)
def _no_leaked_fleet_mesh():
    """The model's forward reads the process-wide fleet mesh; a test file that
    ran earlier in this worker may have left one, and the mesh driver leaves its own."""
    from paddle_tpu.distributed import fleet

    prev, fleet._hcg = fleet._hcg, None
    yield
    fleet._hcg = prev


@pytest.fixture(scope="module")
def family():
    return manifest.load_module(REPO, "benchmark", "families", "gpt")


def test_reference_is_independent_of_the_program(family):
    text = open(os.path.join(REPO, "benchmark", "families", "gpt.py")).read()
    start, end = text.index("# ---------------------------------------------------------------- reference"), \
        text.index("# ---------------------------------------------------------------- correct")
    assert "paddle_tpu" not in text[start:end]


def test_forward_and_loss_agree_with_the_reference(family):
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

    model = family.build_model(CFG, 3, "float32")
    model.eval()
    ids = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 33)).astype("int32")
    got = model(paddle.to_tensor(ids[:, :-1])).numpy()
    weights = family.init_weights(CFG, 3, "float32")
    want = np.stack([np.asarray(family.reference_logits(CFG, weights, row[:-1])) for row in ids])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()       # float32 against float32
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, opt, GPTPretrainingCriterion(), amp_level="O2")
    first = float(step(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))["loss"])
    check = family.check_training(CFG, 3, ids[:, :-1], ids[:, 1:], first, [first])
    assert check["correct"] and check["rel_diff"] < family.TRAIN_LOSS_REL
    assert not family.check_training(CFG, 3, ids[:, :-1], ids[:, 1:], first * 1.001, [first])["correct"]
    assert not family.check_training(CFG, 3, ids[:, :-1], ids[:, 1:], first, [first, float("nan")])["correct"]
    assert not family.check_training(CFG, 4, ids[:, :-1], ids[:, 1:], first, [first])["correct"]  # other weights


def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(family):
    from paddle_tpu.inference import DecodeEngine

    model = family.build_model(CFG, 11, "float32")
    model.eval()
    kw = dict(max_batch_slots=4, max_seq_len=128, prefill_chunk=16)
    check = family.check_serving(DecodeEngine(model, **kw), CFG, 5, n_decode=8)
    assert check["correct"] and check["positions"] == 18
    assert check["logit_rel_rms"] < 1e-5 and check["token_below_best"] == 0.0
    # a cache held in int8 is seen: far outside what float32 rounding explains
    int8 = family.check_serving(DecodeEngine(model, kv_dtype="int8", **kw), CFG, 5, n_decode=8)
    assert int8["logit_rel_rms"] > 100 * check["logit_rel_rms"]
    # so is a part of the mathematics left out: here a bias the reference is not given
    real = family.weights_of_engine
    family.weights_of_engine = lambda e: dict(real(e), out_b=real(e)["out_b"] * 0.0)
    try:
        assert not family.check_serving(DecodeEngine(model, **kw), CFG, 5, n_decode=8)["correct"]
    finally:
        family.weights_of_engine = real


def test_flops_and_bytes_from_shapes(family):
    real = manifest.load_json(REPO, "benchmark/configs/gpt2-medium.json")
    assert family.param_count(real) == 24 * (12 * 1024 * 1024 + 13 * 1024) + 50304 * 1024 + 1024 * 1024 + 2 * 1024
    assert family.matmul_params(real) == 24 * 12 * 1024 * 1024 + 50257 * 1024
    assert family.train_flops_per_token(real, 1024) == 6 * family.matmul_params(real) + 3 * 24 * 2 * 1024 * 1024
    big = manifest.load_json(REPO, "benchmark/configs/cerebras-gpt-1.3b.json")
    assert 1.31e9 < family.param_count(big) < 1.32e9
    assert family.kv_row_bytes(big) == 2 * 24 * 2048 * 2
    assert family.decode_step_bytes(big, 1000) - family.decode_step_bytes(big, 0) == 1000 * family.kv_row_bytes(big)
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tiny_root.make(tmp_path_factory.mktemp("bench"))
    return root, manifest.load_manifest(root)


@pytest.mark.parametrize("name", ["tiny.train", "tiny.train-mesh", "tiny.open", "tiny.closed"])
def test_each_driver_runs_end_to_end(tiny, name, monkeypatch, tmp_path):
    root, m = tiny
    cell = manifest.resolve_cell(m, name, root)
    devices = jax.devices()[:cell.chips]
    records = Records(cell=cell, seed=3000000019, seconds=1.0, chips=cell.chips, peaks=peaks.PEAKS["TPU v5 lite"])
    cell.driver.run(records, devices, process_start=time.perf_counter(), trace_on=False, trace_dir=str(tmp_path))
    monkeypatch.setattr(device, "describe", lambda devs, trace=None: {
        "platform": "cpu", "kind": "cpu", "count": len(devs), "memory_peak_bytes": 1})
    line = bench_run.result_line(cell, records, devices, trace_on=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {e["name"] for e in cell.end_to_end}
    assert all(v["value"] > 0 and isinstance(v["unit"], str) for v in line["metrics"].values())
    assert records.compiles_in_window == 0 and records.window_close > records.window_open
    json.dumps(line), json.dumps(records.notes)
    layer = bench_run.compute_metrics(cell, cell.per_layer, records)   # what needs no trace is there
    assert layer and all(np.isfinite(v["value"]) for v in layer.values())
    if name == "tiny.closed":
        # the stream is driven by tick numbers: another run of the seed admits the same at the same ticks
        again = Records(cell=cell, seed=3000000019, seconds=0.3, chips=1, peaks=records.peaks)
        cell.driver.run(again, devices, process_start=time.perf_counter(), trace_on=False, trace_dir=str(tmp_path))
        n = min(len(again.tick_admitted), len(records.tick_admitted)) - 1
        assert again.tick_admitted[:n] == records.tick_admitted[:n]
        assert again.tick_tokens[:n] == records.tick_tokens[:n]
        assert again.tick_live_rows[:n] == records.tick_live_rows[:n]


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "gpt2-medium.train", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""                 # no result: a CPU time never stands under a device metric's name
    assert "no accelerator" in p.stderr
