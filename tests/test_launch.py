"""Launcher + elastic tests (reference launch/main.py:18,
fleet/elastic/manager.py:131).

These drive real subprocesses: a 2-process localhost DP job through
``python -m paddle_tpu.distributed.launch``, including a worker kill that
the elastic manager must survive.
"""
import os
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_launch(script, workdir, extra_args, timeout=180):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # drop conftest's 8-device virtual mesh: 1 device per worker
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch"] + extra_args + [script]
    return subprocess.run(cmd, env=env, cwd=workdir, capture_output=True, text=True, timeout=timeout)


DP_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ.pop("PYTHONPATH", None)
    sys.path.insert(0, "__REPO__")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.env import init_parallel_env, get_rank, get_world_size

    init_parallel_env()
    assert get_world_size() == 2, get_world_size()
    rank = get_rank()

    # data-parallel gradient agreement: per-process shard, psum over 'dp'
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    local = np.full((2, 4), rank + 1.0, np.float32)
    sh = NamedSharding(mesh, P("dp"))
    x = jax.make_array_from_process_local_data(sh, local)
    w = jnp.ones((4,), jnp.float32)

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    g = jax.jit(jax.grad(loss), in_shardings=(None, sh), out_shardings=None)(w, x)
    gl = np.asarray(jax.device_get(g))  # replicated grad, averaged over both shards
    # shards are rank+1-valued: mean over the GLOBAL batch mixes both processes
    expected = None
    open(f"done.{rank}", "w").write(repr(gl.tolist()))
""").replace("__REPO__", REPO)


@pytest.mark.slow
def test_launch_two_process_dp():
    port = _free_port()
    with tempfile.TemporaryDirectory() as d:
        script = os.path.join(d, "train.py")
        open(script, "w").write(DP_SCRIPT)
        r = _run_launch(script, d, ["--nnodes", "1", "--nproc_per_node", "2", "--master", f"127.0.0.1:{port}"])
        assert r.returncode == 0, r.stdout + r.stderr
        g0 = open(os.path.join(d, "done.0")).read()
        g1 = open(os.path.join(d, "done.1")).read()
        assert g0 == g1  # replicated grads agree across processes


ELASTIC_SCRIPT = textwrap.dedent("""
    import os, sys, time
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    marker = f"attempt.{rank}"
    n = int(open(marker).read()) if os.path.exists(marker) else 0
    open(marker, "w").write(str(n + 1))
    if rank == 1 and n == 0:
        time.sleep(0.3)
        os._exit(17)  # first attempt: worker 1 dies
    time.sleep(1.0)
    open(f"finished.{rank}", "w").write("ok")
""")


def test_launch_elastic_survives_worker_kill():
    with tempfile.TemporaryDirectory() as d:
        script = os.path.join(d, "train.py")
        open(script, "w").write(ELASTIC_SCRIPT)
        r = _run_launch(script, d, ["--nnodes", "1", "--nproc_per_node", "2", "--elastic_retries", "2"], timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "elastic restart 1/2" in r.stderr
        assert os.path.exists(os.path.join(d, "finished.0"))
        assert os.path.exists(os.path.join(d, "finished.1"))
        # both workers ran twice (restart tears down the survivor too)
        assert open(os.path.join(d, "attempt.0")).read() == "2"
        assert open(os.path.join(d, "attempt.1")).read() == "2"


def test_launch_failure_without_elastic_propagates():
    with tempfile.TemporaryDirectory() as d:
        script = os.path.join(d, "train.py")
        open(script, "w").write("import os, sys; sys.exit(3 if os.environ['PADDLE_TRAINER_ID'] == '1' else 0)\n")
        r = _run_launch(script, d, ["--nnodes", "1", "--nproc_per_node", "2"], timeout=60)
        assert r.returncode == 1
