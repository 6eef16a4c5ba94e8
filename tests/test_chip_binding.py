"""One process per chip: how child processes are bound to TPU chips, and the
refusal to start more of them than the host has chips. The host's chip count
is stubbed — the sandbox has none — and nothing here starts a process."""
import argparse

import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.launch.main import CollectiveController, LaunchContext


@pytest.fixture
def tpu_host(monkeypatch):
    """A four-chip TPU host whose JAX_PLATFORMS does not keep children off it."""
    monkeypatch.setattr(paddle.device, "local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)


def test_a_replica_is_a_one_chip_host_of_its_own():
    assert paddle.device.chip_env(2) == {
        "TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


def test_more_processes_than_chips_is_refused(tpu_host, monkeypatch):
    assert paddle.device.place_on_chips(4, "test") is True
    with pytest.raises(RuntimeError, match="5 processes on a host with 4 TPU chip"):
        paddle.device.place_on_chips(5, "test")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # children kept off the chips: nothing to place
    assert paddle.device.place_on_chips(5, "test") is False


def test_no_chips_nothing_to_bind(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert paddle.device.local_tpu_chips() == 0  # counted on the PCI bus, no backend initialised
    assert paddle.device.place_on_chips(8, "test") is False


def _worker_env(local_rank, **args):
    ns = argparse.Namespace(nnodes=1, rank=0, nproc_per_node=4, master="127.0.0.1:49200",
                            devices=None, **args)
    return CollectiveController(LaunchContext(ns, []))._env_for(local_rank)


def test_launch_binds_each_worker_to_the_chip_of_its_local_rank(tpu_host):
    env = _worker_env(3)
    assert env["TPU_VISIBLE_CHIPS"] == "3" and env["CLOUD_TPU_TASK_ID"] == "3"
    assert env["TPU_PROCESS_BOUNDS"] == "2,2,1" and env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_PORT"] == "49219"
    assert env["TPU_PROCESS_ADDRESSES"] == ",".join(f"localhost:{49216 + i}" for i in range(4))
    assert "CUDA_VISIBLE_DEVICES" not in env


def test_launch_leaves_cpu_workers_unbound(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert "TPU_VISIBLE_CHIPS" not in _worker_env(1)
