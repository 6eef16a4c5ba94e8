"""``chip_smoke.py`` on the CPU: it must refuse to pass without a TPU, and its
control flow must complete at a tiny size when the device check is stubbed.

The sizes are swapped here, in the test — the program has no option for it —
and the Pallas kernels run in interpret mode. Results at these sizes say that
paths, arguments and checks are wired; the real sizes run on the chip."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    """No accelerator: a non-zero exit code and no result line. The same in a
    directory that holds the script and nothing else of the repo."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cwd, script in ((ROOT, SCRIPT), (str(tmp_path), str(tmp_path / "chip_smoke.py"))):
        if cwd != ROOT:
            with open(SCRIPT) as src, open(script, "w") as dst:
                dst.write(src.read())
            env.pop("PYTHONPATH", None)
        r = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode != 0, r.stdout
        assert '"ok"' not in r.stdout, r.stdout


@pytest.fixture
def tiny_smoke(monkeypatch, tmp_path):
    from paddle_tpu.ops import decode_attention as da
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import flash_attention_flat as ff
    from paddle_tpu.ops import moe_pallas

    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(smoke, "MODEL", dict(vocab_size=512, hidden_size=128, num_layers=2,
                                             num_heads=2, max_seq_len=256))
    monkeypatch.setattr(smoke, "TRAIN", dict(batch=4, seq=256, steps=3, fused_k=2))
    monkeypatch.setattr(smoke, "SERVE", dict(slots=2, prefill_chunk=16,
                                             prompt_lens=(5, 19, 40, 9), max_new_tokens=4))
    monkeypatch.setattr(smoke, "FLASH_SHAPES", ((1, 256, 2, 64),))
    monkeypatch.setattr(smoke, "GQA_SHAPES", ((1, 256, 4, 64, 2, False),))
    monkeypatch.setattr(smoke, "MOE", dict(tokens=64, d_model=128, d_hidden=256, experts=4,
                                           top_k=2, capacity_factor=1.25))
    monkeypatch.setattr(smoke, "MESH", dict(num_layers=2, steps=2, rtol=1e-5))
    monkeypatch.setattr(smoke, "require_tpu", lambda n: jax.devices()[:n])  # the stub
    # a cache of its own: counters below must not depend on earlier runs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    prev_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    prior = (fa.set_interpret(True), ff.set_interpret(True), moe_pallas.set_interpret(True),
             da.set_interpret(True))
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.distributed import fleet
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops import registry

    compilation_cache.reset_cache()
    prev_hcg, fleet._hcg = fleet._hcg, None  # no mesh left over from an earlier test
    registry.clear_cache()  # the smoke counts selections: none may be remembered
    for prefix in ("train_step.", "infer.", "kernels."):
        metrics.reset_counters(prefix)
    yield smoke
    fleet._hcg = prev_hcg
    registry.clear_cache()
    fa.set_interpret(prior[0]), ff.set_interpret(prior[1]), moe_pallas.set_interpret(prior[2])
    da.set_interpret(prior[3])
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    compilation_cache.reset_cache()
    jax.config.update("jax_default_matmul_precision", "highest")  # conftest's pin


def _lines(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return [json.loads(line) for line in out]


def test_one_chip_phases_complete_at_a_tiny_size(tiny_smoke, capsys):
    assert tiny_smoke.main([]) == 0
    lines = _lines(capsys)
    assert [d.get("phase") for d in lines[:-1]] == ["device", "train", "serve", "kernels", "done"]
    assert lines[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    train, serve, kernels = lines[1:4]
    assert train["losses"][-1] < train["losses"][0]
    assert train["kernels"] == {"kernels.attention_core.picked": 1}
    assert serve["tokens_decoded"] == 16 and all(serve["matches_generate"])
    assert serve["kernels"] == {"kernels.decode_attention.picked": 1}
    assert kernels["selected"] == {"sdpa": "flash", "attention_core": "flash",
                                   "moe": "pallas_sorted"}


def test_four_chip_phases_complete_at_a_tiny_size(tiny_smoke, capsys):
    assert tiny_smoke.main(["--chips", "4"]) == 0
    lines = _lines(capsys)
    assert [d.get("phase") for d in lines[:-1]] == [
        "device", "replicas", "mesh_reference", "mesh", "mesh", "done"]
    assert lines[-1]["device"]["count"] == 4
    replicas = lines[1]
    assert len(set(replicas["cache_devices"])) == 4 and all(replicas["completed"])
    for mesh in lines[3:5]:
        assert mesh["max_rel_diff"] <= 1e-5
        assert mesh["qkv_shard_shape"] != mesh["qkv_shape"]
