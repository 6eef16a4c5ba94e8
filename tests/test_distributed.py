"""Distributed tests on the virtual 8-device CPU mesh (parity: the
reference's localhost-subprocess cluster simulation, test_dist_base.py:786 —
single-process multi-device here, per SURVEY §4 takeaway)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn


def _rand(*shape):
    return np.random.randn(*shape).astype("float32")


@pytest.fixture(scope="module")
def fleet8():
    strat = dist.DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "sharding_degree": 2, "pp_degree": 1}
    strat.sharding = True
    strat.sharding_configs = {"sharding_stage": 2}
    dist.fleet.init(is_collective=True, strategy=strat)
    return dist.fleet


@pytest.fixture
def fleet4(request):
    """``fleet4(stage, amp)``: the singleton re-initialised as sdp 2 x mp 2 on
    four devices (the four-chip cell's layout); the module's ``fleet8`` is
    put back afterwards."""
    prior = (dist.fleet._hcg, dist.fleet._strategy, dist.fleet._is_initialized)

    def init(stage, amp):
        strat = dist.DistributedStrategy()
        strat.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "sharding_degree": 2, "pp_degree": 1}
        strat.sharding = True
        strat.sharding_configs = {"sharding_stage": stage}
        if amp:
            strat.amp = True
            strat.amp_configs = {"level": amp, "dtype": "bfloat16"}
        return dist.fleet.init(is_collective=True, strategy=strat, devices=jax.devices()[:4])

    yield init
    dist.fleet._hcg, dist.fleet._strategy, dist.fleet._is_initialized = prior


def _tiny_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(7)
    return GPTForPretraining(GPTConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4, max_seq_len=32))


_OPTIMIZERS = {
    "sgd": (0.1, lambda m: paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())),
    "adamw": (1e-3, lambda m: paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())),
}


class TestTopology:
    def test_mesh_axes(self, fleet8):
        assert dict(fleet8.mesh.shape) == {"dp": 2, "pp": 1, "sdp": 2, "mp": 2, "sep": 1}
        hcg = fleet8.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_sharding_parallel_world_size() == 2

    def test_too_many_devices_raises(self):
        from paddle_tpu.distributed.topology import HybridCommunicateGroup

        with pytest.raises(ValueError):
            HybridCommunicateGroup(dp_degree=100)


class TestCollectives:
    def test_psum_allgather_in_shard_map(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

        def f(x):
            return dist.all_reduce(x, group="dp")

        mapped = jax.shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
        x = np.arange(8, dtype="float32")
        out = mapped(x)
        # each shard of 2 elements is summed across 4 devices
        want = x.reshape(4, 2).sum(0)
        np.testing.assert_allclose(np.asarray(out).reshape(4, 2)[0], want)

    def test_ppermute_ring(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("pp",))

        def f(x):
            perm = [(i, (i + 1) % 4) for i in range(4)]
            return dist.ppermute(x, perm, group="pp")

        mapped = jax.shard_map(f, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"), check_vma=False)
        x = np.arange(4, dtype="float32")
        out = np.asarray(mapped(x))
        np.testing.assert_allclose(out, [3, 0, 1, 2])

    def test_reduce_scatter(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

        def f(x):
            return dist.reduce_scatter(None, x, group="dp")

        mapped = jax.shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P("dp"), check_vma=False)
        x = np.ones((8,), "float32")
        out = np.asarray(mapped(x))
        np.testing.assert_allclose(out, 4.0)  # summed over 4 devices, scattered


class TestDistributedTrainStep:
    def test_zero2_with_tp_converges(self, fleet8):
        paddle.seed(0)
        mlp = nn.Sequential(nn.Linear(128, 256), nn.GELU(), nn.Linear(256, 8))
        mlp[0].weight.dist_spec = P(None, "mp")
        mlp[2].weight.dist_spec = P("mp", None)
        step = fleet8.distributed_step(mlp, paddle.optimizer.AdamW(learning_rate=1e-2), nn.CrossEntropyLoss())
        x, y = _rand(16, 128), np.random.randint(0, 8, 16)
        losses = [float(step(x, y)["loss"]) for _ in range(15)]
        assert losses[-1] < losses[0] * 0.7
        # opt state is sharded over sdp
        spec = step.state["opt"]["m"]["0.weight"].sharding.spec
        assert "sdp" in str(spec)

    def test_dist_matches_single_device(self, fleet8):
        """Distributed compiled step == single-device compiled step."""
        from paddle_tpu.jit import TrainStep

        paddle.seed(3)
        net1 = nn.Linear(16, 4)
        w0, b0 = net1.weight.numpy().copy(), net1.bias.numpy().copy()
        step1 = TrainStep(net1, paddle.optimizer.SGD(learning_rate=0.1), nn.MSELoss())
        x, y = _rand(8, 16), _rand(8, 4)
        step1(x, y)

        net2 = nn.Linear(16, 4)
        net2.weight.set_value(w0)
        net2.bias.set_value(b0)
        step2 = fleet8.distributed_step(net2, paddle.optimizer.SGD(learning_rate=0.1), nn.MSELoss())
        step2(x, y)
        np.testing.assert_allclose(
            np.asarray(step1.state["params"]["weight"]),
            np.asarray(step2.state["params"]["weight"]),
            atol=1e-5,
        )

    # (stage, amp, optimizer, the losses' relative and the master's absolute tolerance). Without AMP the file's own
    # 1e-5 holds. With bf16 compute the partial sums differ by rounding: SGD keeps that at the update's size; Adam
    # divides by sqrt(v), so a gradient that rounds the other way moves its element by up to lr a step either way,
    # and 2 x lr x steps is what three steps can differ by (the same mesh at stage 1, the parent's arithmetic: 3.6e-3)
    @pytest.mark.parametrize("stage,amp,optimizer,loss_rtol,master_atol", [
        (2, None, "sgd", 1e-6, 1e-5), (2, None, "adamw", 1e-6, 1e-5),
        (2, "O2", "sgd", 5e-5, 5e-5), (2, "O2", "adamw", 5e-5, 6e-3), (3, "O2", "adamw", 5e-5, 6e-3)],
        ids=["stage2-f32-sgd", "stage2-f32-adamw", "stage2-O2-sgd", "stage2-O2-adamw", "stage3-O2-adamw"])
    def test_zero_steps_match_one_device(self, fleet4, stage, amp, optimizer, loss_rtol, master_atol):
        """Three steps of ``fleet.distributed_step`` on sdp 2 x mp 2 with the
        master sharded over 'sdp' (the compute copy gathered from the cast
        shard, the gradients handed back to their owners) give the losses and
        the final master of the one-device ``TrainStep`` from the same seed."""
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.gpt import GPTPretrainingCriterion

        ids = np.random.default_rng(0).integers(0, 512, (3, 8, 33)).astype(np.int32)
        lr, make = _OPTIMIZERS[optimizer]
        one = _tiny_gpt()
        step1 = TrainStep(one, make(one), GPTPretrainingCriterion(), amp_level=amp)
        want = [float(step1(b[:, :-1], b[:, 1:])["loss"]) for b in ids]

        fleet = fleet4(stage, amp)
        four = _tiny_gpt()
        step4 = fleet.distributed_step(four, make(four), GPTPretrainingCriterion())
        assert step4._param_placement                        # the mechanism is on: masters held under another spec
        got = [float(step4(b[:, :-1], b[:, 1:])["loss"]) for b in ids]
        np.testing.assert_allclose(got, want, rtol=loss_rtol)
        for name, master in step1.state["params"].items():
            mine = step4.state["params"][name]
            assert mine.dtype == jnp.float32                 # the master stays f32, whatever crossed the wire
            np.testing.assert_allclose(np.asarray(mine), np.asarray(master), atol=master_atol, err_msg=name)
        moved = float(np.max(np.abs(np.asarray(step4.state["params"]["gpt.layers.qkv_w"]) - four.gpt.layers.qkv_w.numpy())))
        worst = max(float(np.max(np.abs(np.asarray(step4.state["params"][n]) - np.asarray(m)))) for n, m in step1.state["params"].items())
        print(f"moved {moved:.3g}, worst master difference {worst:.3g}, limit {master_atol:.3g}")
        assert moved > (lr if optimizer == "adamw" else 3 * master_atol)     # the steps did move what is compared

    @pytest.mark.parametrize("stage", [0, 1, 2, 3])
    def test_state_params_carry_sdp_from_stage_2(self, fleet4, stage):
        """``state["params"]`` is split over 'sdp' at stages 2 and 3 and not
        at 0 and 1, in the placed state and in what the step hands back."""
        fleet = fleet4(stage, "O2")
        model = _tiny_gpt()
        from paddle_tpu.models.gpt import GPTPretrainingCriterion

        step = fleet.distributed_step(model, _OPTIMIZERS["adamw"][1](model), GPTPretrainingCriterion())
        ids = np.random.default_rng(1).integers(0, 512, (8, 33)).astype(np.int32)
        for when in ("placed", "after a step"):
            specs = {n: str(a.sharding.spec) for n, a in step.state["params"].items()}
            assert ("sdp" in specs["gpt.layers.qkv_w"]) == (stage >= 2), (when, specs["gpt.layers.qkv_w"])
            assert ("sdp" in str(step.state["opt"]["m"]["gpt.layers.qkv_w"].sharding.spec)) == (stage >= 1)
            assert bool(step._param_placement) == (stage >= 2)
            step(ids[:, :-1], ids[:, 1:])

    @pytest.mark.parametrize("reader", ["sync_to_model", "checkpoint"])
    def test_sharded_master_reads_whole(self, fleet8, reader, tmp_path):
        """Whoever reads ``step.state["params"]`` under stage 2 gets whole
        arrays equal to the master: the eager model after ``sync_to_model``,
        and a save / restore through ``distributed/checkpoint.py``."""
        from paddle_tpu.distributed import checkpoint as ckpt

        def build():
            paddle.seed(11)
            mlp = nn.Sequential(nn.Linear(128, 256), nn.GELU(), nn.Linear(256, 8))
            mlp[0].weight.dist_spec = P(None, "mp")
            mlp[2].weight.dist_spec = P("mp", None)
            return mlp, fleet8.distributed_step(mlp, paddle.optimizer.AdamW(learning_rate=1e-2), nn.CrossEntropyLoss())

        mlp, step = build()
        x, y = _rand(16, 128), np.random.randint(0, 8, 16)
        step(x, y)
        master = {k: np.asarray(v) for k, v in step.state["params"].items()}
        assert "sdp" in str(step.state["params"]["0.weight"].sharding.spec)
        if reader == "sync_to_model":
            step.sync_to_model()
            for name, p in mlp.named_parameters():
                assert tuple(p.shape) == master[name].shape
                np.testing.assert_array_equal(p.numpy(), master[name])
            out = mlp(paddle.to_tensor(x))                  # and the eager model computes with them
            assert out.shape == [16, 8] and np.isfinite(out.numpy()).all()
        else:
            path = str(tmp_path / "ck")
            ckpt.save_train_step(step, path)
            _, step2 = build()
            ckpt.load_train_step(step2, path, shardings=None)
            for name, a in step2.state["params"].items():
                np.testing.assert_array_equal(np.asarray(a), master[name])
            assert "sdp" in str(step2.state["params"]["0.weight"].sharding.spec)
            step2(x, y)                                      # resumes on the sharded master

    def test_shard_batch_placement(self, fleet8):
        x = _rand(16, 8)
        placed = fleet8.shard_batch(x)
        assert placed.sharding.spec == P(("dp", "sdp"))


class TestShardingPolicies:
    @pytest.mark.parametrize("stage", [0, 1, 2, 3])
    def test_stage_specs(self, stage):
        """Who holds what: the moments are split over 'sdp' from stage 1, the
        master with them from stage 2 (the rank that updates a shard keeps
        it), and nothing is at stage 0; small params stay replicated."""
        from paddle_tpu.distributed.sharding import build_state_specs
        from paddle_tpu.distributed.topology import HybridCommunicateGroup

        mesh = HybridCommunicateGroup(dp_degree=2, sharding_degree=2, mp_degree=2).mesh
        params = {"w": np.zeros((256, 128), "float32"), "tiny": np.zeros((4,), "float32")}
        p, o = build_state_specs(params, mesh, stage=stage)
        assert ("sdp" in str(o["w"])) == (stage >= 1)
        assert ("sdp" in str(p["w"])) == (stage >= 2)
        if stage >= 2:
            assert p["w"] == o["w"]        # the master lies where its moments lie
        else:
            assert p["w"] == P()
        assert p["tiny"] == P() and o["tiny"] == P()

    def test_mp_specs_respected(self):
        from paddle_tpu.distributed.sharding import build_state_specs
        from paddle_tpu.distributed.topology import HybridCommunicateGroup

        mesh = HybridCommunicateGroup(dp_degree=2, sharding_degree=2, mp_degree=2).mesh
        params = {"w": np.zeros((256, 128), "float32")}
        p3, _ = build_state_specs(params, mesh, stage=3, mp_specs={"w": P(None, "mp")})
        # sdp takes the most major dim that mp does not hold: a gradient
        # reduce-scatters over 'sdp' along a dim of its own (composed onto
        # the mp dim, the compiler all-reduced it and sliced: PERF.md §6, PR 35)
        assert p3["w"] == P("sdp", "mp")
        # params with no mp spec get sdp on the most major divisible dim
        p3b, _ = build_state_specs(params, mesh, stage=3, mp_specs={})
        assert p3b["w"] == P("sdp")
        # every dim held: sdp composes with the one whose shard still divides
        p3c, _ = build_state_specs({"v": np.zeros((32768,), "float32")}, mesh, stage=3, mp_specs={"v": P("mp")})
        assert p3c["v"] == P(("mp", "sdp"))
        # a stacking axis comes after the layer's own dims, and is cut when they are held or too small
        stacked = {"s": np.zeros((24, 64, 128), "float32"), "b": np.zeros((24, 4096), "float32")}
        p2, _ = build_state_specs(stacked, mesh, stage=2, mp_specs={"s": P("pp", None, "mp"), "b": P("pp", "mp")})
        assert p2["s"] == P("pp", "sdp", "mp") and p2["b"] == P(("pp", "sdp"), "mp")


class TestMPLayers:
    def test_mp_layers_single_device_numerics(self):
        col = dist.ColumnParallelLinear(8, 16, gather_output=True)
        row = dist.RowParallelLinear(16, 4)
        x = paddle.to_tensor(_rand(2, 8))
        out = row(col(x))
        assert out.shape == [2, 4]
        assert col.weight.dist_spec == P(None, "mp")
        assert row.weight.dist_spec == P("mp", None)

    def test_vocab_parallel_embedding(self):
        emb = dist.VocabParallelEmbedding(100, 16)
        out = emb(paddle.to_tensor(np.array([1, 50, 99])))
        assert out.shape == [3, 16]
        assert emb.weight.dist_spec == P("mp", None)

    def test_parallel_cross_entropy(self):
        pce = dist.ParallelCrossEntropy()
        logits = paddle.to_tensor(_rand(4, 10), stop_gradient=False)
        loss = pce(logits, paddle.to_tensor(np.random.randint(0, 10, 4))).mean()
        loss.backward()
        assert logits.grad is not None


class TestRecompute:
    def test_remat_matches(self):
        from paddle_tpu.distributed.recompute import remat

        f = lambda x: jnp.tanh(x) ** 2
        g1 = jax.grad(lambda x: f(x).sum())(jnp.ones((4,)))
        g2 = jax.grad(lambda x: remat(f)(x).sum())(jnp.ones((4,)))
        np.testing.assert_allclose(g1, g2, atol=1e-7)


def test_recompute_mixed_static_args_under_jit():
    """Public recompute() must accept non-tensor flag args under jit: only
    traced leaves cross the checkpoint boundary, flags ride the closure."""
    import jax

    from paddle_tpu.distributed import recompute

    def seg(x, double):
        if double:  # a traced bool here would raise TracerBoolConversionError
            return x * 2
        return x

    def loss(xv):
        t = paddle.to_tensor(xv)
        out = recompute(seg, t, True)
        return (out._value ** 2).sum()

    g = jax.grad(loss)(jnp.asarray([1.0, 2.0]))
    np.testing.assert_allclose(np.asarray(g), [8.0, 16.0], rtol=1e-6)
    # and the remat boundary is really there
    jaxpr = jax.make_jaxpr(loss)(jnp.asarray([1.0, 2.0]))
    assert any("remat" in e.primitive.name or "checkpoint" in e.primitive.name
               for e in jaxpr.jaxpr.eqns)
