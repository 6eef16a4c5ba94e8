"""The examples/ scripts stay runnable (subprocess smoke, CPU mesh)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "examples", script), *args],
                       capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert r.returncode == 0, f"{script} failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    return r.stdout


@pytest.mark.slow
def test_train_gpt_example():
    out = _run("train_gpt.py")
    assert "checkpoint saved" in out


@pytest.mark.slow
def test_train_dlrm_example():
    out = _run("train_dlrm.py")
    assert "resharded dp4 -> dp2 bitwise: True" in out
    assert "examples/sec" in out
    assert "embedding spec: ['dp']" in out


@pytest.mark.slow
def test_finetune_classifier_example():
    out = _run("finetune_classifier.py")
    assert "served int8 logits" in out


@pytest.mark.slow
def test_serve_text_example():
    out = _run("serve_text.py")
    assert "->" in out


@pytest.mark.slow
def test_serve_gpt_example():
    out = _run("serve_gpt.py")
    assert "2 compiled programs" in out


@pytest.mark.slow
def test_serve_gpt_http_example():
    out = _run("serve_gpt.py", "--http")
    assert "idempotent retry replayed" in out and "True" in out
    assert "final status finished" in out
    assert "drained with exit code 0" in out


@pytest.mark.slow
def test_serve_gpt_fleet_example():
    out = _run("serve_gpt.py", "--fleet")
    assert "bitwise-equal to the unkilled run: True" in out
    assert "overload shed" in out
    assert "deadline_exceeded" in out
    assert "served 6 requests" in out
