"""Elastic membership: heartbeat registry, watch loop, rescaled relaunch,
checkpoint resume across a scale-in event.

Parity: fleet/elastic/manager.py:131 (ElasticManager), :577 (watch →
HOLD/RESTART with rank rescaling). The TCPStore replaces etcd.
"""
import os
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_elastic_node_membership_and_rescale():
    from paddle_tpu.distributed.elastic import ElasticNode, parse_np_range
    from paddle_tpu.distributed.store import TCPStore

    assert parse_np_range("2") == (2, 2)
    assert parse_np_range("1:4") == (1, 4)

    master = TCPStore(is_master=True, timeout=10.0)
    n0 = ElasticNode(master, heartbeat_interval=0.1, timeout=1.0)
    client = TCPStore(port=master.port, timeout=10.0)
    n1 = ElasticNode(client, heartbeat_interval=0.1, timeout=1.0)
    assert n0.node_id != n1.node_id
    assert n0.wait_for(2, settle=0.3, deadline=10.0) == sorted([n0.node_id, n1.node_id])
    # scale-in: node 1 leaves; node 0's view shrinks and its rank rescales
    n1.leave()
    t0 = time.time()
    while len(n0.alive_nodes()) != 1 and time.time() - t0 < 10:
        time.sleep(0.1)
    alive = n0.alive_nodes()
    assert alive == [n0.node_id]
    assert alive.index(n0.node_id) == 0
    # scale-out: a new node joins with a fresh ticket
    n2 = ElasticNode(client, heartbeat_interval=0.1, timeout=1.0)
    got = n0.wait_for(2, settle=0.3, deadline=10.0)
    assert got == sorted([n0.node_id, n2.node_id])
    n0.leave()
    n2.leave()
    client.close()
    master.close()


TRAIN = textwrap.dedent("""
    import os, sys, json
    sys.path.insert(0, "__REPO__")
    os.environ.pop("PYTHONPATH", None)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle

    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    ckpt = "state.pdparams"
    paddle.seed(0)
    m = paddle.nn.Linear(4, 1)
    opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=m.parameters())
    start = 0
    if os.path.exists(ckpt):
        st = paddle.load(ckpt)
        start = int(np.asarray(st.pop("step")))
        m.set_state_dict(st)
    x = paddle.to_tensor(np.ones((8, 4), "float32"))
    y = paddle.to_tensor(np.zeros((8, 1), "float32"))
    for step in range(start, start + 6):
        loss = ((m(x) - y) ** 2).mean()
        loss.backward()
        opt.step(); opt.clear_grad()
        if rank == 0:
            with open("loss.log", "a") as f:
                f.write(json.dumps({"step": step, "world": world, "loss": float(loss)}) + chr(10))
            st = m.state_dict(); st["step"] = paddle.to_tensor(step + 1)
            paddle.save(st, ckpt)
    # keep the job alive long enough for membership churn unless world==1
    import time
    if world > 1:
        time.sleep(30)
""").replace("__REPO__", REPO)

FAKE_NODE = textwrap.dedent("""
    import sys, time
    sys.path.insert(0, "__REPO__")
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.distributed.elastic import ElasticNode
    store = None
    # membership registry lives at master port + 2 (launch/main.py port map);
    # the launcher (rank 0) hosts it — retry until up
    for _ in range(100):
        try:
            store = TCPStore(port=int(sys.argv[1]) + 2, timeout=30.0)
            break
        except (ConnectionError, OSError):
            time.sleep(0.2)
    node = ElasticNode(store, heartbeat_interval=0.2, timeout=2.0)
    time.sleep(float(sys.argv[2]))
    node.leave()
    time.sleep(1.0)
""").replace("__REPO__", REPO)


def test_elastic_scale_in_relaunches_and_resumes():
    """Node 0 runs the membership launcher (np 1:2); a second (weightless)
    node joins, the job starts at world=2, the node dies, the launcher
    detects the leave, relaunches at world=1, and training resumes from the
    checkpoint — loss keeps descending across the restart."""
    import json

    port = _free_port()
    with tempfile.TemporaryDirectory() as d:
        open(os.path.join(d, "train.py"), "w").write(TRAIN)
        fake = os.path.join(d, "fake_node.py")
        open(fake, "w").write(FAKE_NODE)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ""
        # fake node starts first (retries until the launcher's store is up),
        # stays ~20s (generous under CI load), then leaves -> scale-in while the world=2 job is alive
        fake_popen = subprocess.Popen([sys.executable, fake, str(port), "20"],
                                      env=env, cwd=d, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True)
        launcher = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--nproc_per_node", "1", "--rank", "0",
             "--master", f"127.0.0.1:{port}", "--elastic_np", "1:2",
             "--elastic_timeout", "2.0", "train.py"],
            env=env, cwd=d, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            fout, _ = fake_popen.communicate(timeout=90)
            assert fake_popen.returncode == 0, fout
            out, _ = launcher.communicate(timeout=120)
            assert launcher.returncode == 0, out
        finally:
            for pr in (launcher, fake_popen):
                if pr.poll() is None:
                    pr.kill()
        log = [json.loads(l) for l in open(os.path.join(d, "loss.log"))]
        worlds = [e["world"] for e in log]
        assert 2 in worlds and 1 in worlds, worlds  # ran at both world sizes
        assert "membership=" in out
        # resume happened: steps strictly increase across the restart
        steps = [e["step"] for e in log]
        assert steps == sorted(steps) and len(set(steps)) == len(steps), steps
        # loss descends across the whole run including the restart boundary
        losses = [e["loss"] for e in log]
        assert losses[-1] < losses[0]
        w1 = [e for e in log if e["world"] == 1]
        w2 = [e for e in log if e["world"] == 2]
        assert w1[0]["step"] > w2[-1]["step"]
        assert w1[0]["loss"] <= w2[0]["loss"]
