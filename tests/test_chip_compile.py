"""The main path's kernels and programs, compiled for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a ``v5e:2x2``
host and the installed TPU compiler compiles for it, raising what the chip's
compiler would raise — a kernel over its VMEM budget, a slice the tiling
refuses, a Mosaic kernel inside a program GSPMD must partition. Interpret
mode, which is how the kernels are otherwise tested here, can see none of
these. Nothing runs, so these tests say nothing about results or times;
``chip_smoke.py`` does that on the chip.

The persistent compilation cache is off around them: an executable compiled
for a described device is written to it but cannot be read back without a
chip, and the next run would warn about every entry.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
# libtpu lets one process at a time load it, which is about chips; nothing here
# touches one, and parallel test workers each describe the topology
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining, GPTPretrainingCriterion
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import flash_attention_flat as ff
from paddle_tpu.ops import moe_pallas


def _topology():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        return exc


_TOPO = _topology()
pytestmark = pytest.mark.skipif(isinstance(_TOPO, Exception),
                                reason=f"cannot describe a v5e:2x2 topology: {_TOPO}")


@pytest.fixture(autouse=True)
def _no_persistent_cache_no_leaked_mesh():
    """...and no fleet mesh left initialised by an earlier test: the model's
    forward and the kernels' shard_map read that global."""
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.distributed import fleet

    prev, prev_hcg = jax.config.jax_enable_compilation_cache, fleet._hcg
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    fleet._hcg = None
    yield
    fleet._hcg = prev_hcg
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Code that asks "is this a TPU" sees the CPU here and would take its
    CPU branch; the one helper every such site asks is steered in the test,
    not through an option of the program."""
    from paddle_tpu.ops import registry

    monkeypatch.setattr(paddle.device, "is_tpu", lambda: True)
    for kernel in ("sdpa", "attention_core", "moe"):
        registry.clear_cache(kernel)
    yield
    for kernel in ("sdpa", "attention_core", "moe"):
        registry.clear_cache(kernel)


def _one_chip(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(_TOPO.devices[0]))


def _abstract(tree, sharding_of=lambda a: SingleDeviceSharding(_TOPO.devices[0])):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding_of(a)), tree)


def _compile(fn, *args):
    compiled = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*args).compile()
    return compiled.as_text().count("tpu_custom_call")


def _grad_of(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=argnums)


# ---------------------------------------------------------------- kernels
_QKV = (8, 1024, 16, 64)  # the flagship attention shape, bf16
_FLASH = {
    "classic": lambda q, k, v: fa._flash(q, k, v, True),
    "flat": lambda q, k, v: ff.flash_flat(q, k, v, True),
    "packed": lambda q, k, v: ff.flash_packed(jnp.stack([q, k, v], axis=2), True),
}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("family", sorted(_FLASH))
def test_flash_attention_compiles_for_v5e(family, direction):
    q = _one_chip(_QKV, jnp.bfloat16)
    fn = _FLASH[family] if direction == "fwd" else _grad_of(_FLASH[family], (0, 1, 2))
    assert _compile(fn, q, q, q) >= 1


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_moe_dispatch_combine_compiles_for_v5e_at_flagship_width(dtype, direction):
    """D=1024 / H=4096 / E=8 / top-2: the weight-gradient kernel's [D, tile]
    f32 blocks overran the 16 MiB scoped-VMEM limit at the forward's
    512-wide tile (16.32M bf16, 18.00M f32) until the tile was chosen from D
    and the dtype (``moe_pallas._dw_hidden_tile``). bf16 — the dtype the
    model trains in — is compiled at the flagship's T=8192, where the old
    tile was refused; float32 is refused already at T=1024, which keeps
    its compile short."""
    D, H, E, K = 1024, 4096, 8, 2
    T = 8192 if dtype == "bfloat16" else 1024
    capacity = int(1.25 * T * K / E)
    dt = jnp.dtype(dtype)
    args = (_one_chip((T, D), dt), _one_chip((T, K), dt), _one_chip((T, K), jnp.int32),
            _one_chip((E, D, H), dt), _one_chip((E, 1, H), dt),
            _one_chip((E, H, D), dt), _one_chip((E, 1, D), dt))

    def moe(tok, gv, gi, w1, b1, w2, b2):
        return moe_pallas.moe_dispatch_combine(tok, gv, gi, None, w1, b1, w2, b2,
                                               capacity=capacity, activation=jax.nn.gelu)

    fn = moe if direction == "fwd" else _grad_of(moe, (0, 1, 3, 4, 5, 6))
    assert _compile(fn, *args) >= (1 if direction == "fwd" else 2)


def test_layer_norm_fused_compiles_for_v5e():
    from paddle_tpu.ops.layer_norm import layer_norm_fused

    x, w = _one_chip((8, 1024, 1024), jnp.bfloat16), _one_chip((1024,), jnp.float32)
    _compile(_grad_of(lambda x, w, b: layer_norm_fused(x, w, b, 1e-5), (0, 1, 2)), x, w, w)


# --------------------------------------------------------------- programs
_WIDE = dict(vocab_size=50304, hidden_size=1024, num_heads=16, max_seq_len=1024)


def _wide_model(num_layers, dtype=None):
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig(num_layers=num_layers, **_WIDE))
    if dtype is not None:
        model.astype(dtype)
        model.eval()
    return model


def test_decode_step_compiles_for_v5e():
    """``DecodeEngine``'s decode program at h1024 / L16 / 8 slots / S=1024,
    bf16: the engine is built on the CPU and its own jitted step is lowered
    for the described chip with the shapes it is dispatched with."""
    from paddle_tpu.inference import DecodeEngine

    engine = DecodeEngine(_wide_model(16, "bfloat16"), max_batch_slots=8, max_seq_len=1024,
                          prefill_chunk=128)
    args = (engine._params, engine._ck, engine._cv, engine._pos, engine._tok, engine._active,
            engine._eos, engine._limit, engine._seed)
    _compile(engine._decode_jit, *_abstract(args))


def _train_step(model, make_step):
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    return make_step(model, opt, GPTPretrainingCriterion())


def test_train_step_picks_and_compiles_the_flash_kernels_for_v5e(as_tpu):
    """``TrainStep``'s whole AMP-O2 step at the flagship width (depth cut to
    one layer: the block is compiled once whatever the depth) with the
    Pallas attention kernels in it, forward and backward."""
    from paddle_tpu.jit import TrainStep

    step = _train_step(_wide_model(1), lambda m, o, c: TrainStep(m, o, c, amp_level="O2"))
    ids = _one_chip((8, 1024), jnp.int32)
    assert _compile(step._jit, _abstract(step.state), ((ids,), (ids,))) >= 3


@pytest.mark.parametrize("layout", [dict(dp=2, mp=2, sdp=1, stage=0),
                                    dict(dp=1, mp=2, sdp=2, stage=2)],
                         ids=["dp2xmp2", "sharding2xmp2"])
def test_distributed_step_compiles_for_a_v5e_2x2_mesh(as_tpu, layout):
    """``fleet.distributed_step`` at the flagship width on the described
    four-chip mesh. A Mosaic kernel cannot be partitioned automatically —
    the compiler's own words are "wrap the call in a shard_map" — so the
    attention kernels must run per shard (batch over dp x sdp, heads over
    mp) inside the GSPMD-partitioned step."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.sharding import state_shardings
    from paddle_tpu.distributed.strategy import DistributedStrategy
    from paddle_tpu.distributed.topology import AXES

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": layout["dp"], "mp_degree": layout["mp"],
                               "pp_degree": 1, "sharding_degree": layout["sdp"]}
    if layout["sdp"] > 1:
        strategy.sharding = True
        strategy.sharding_configs = {"sharding_stage": layout["stage"]}
    strategy.amp = True
    strategy.amp_configs = {"level": "O2", "dtype": "bfloat16"}
    try:
        fleet.init(is_collective=True, strategy=strategy, devices=jax.devices()[:4])
        model = _wide_model(1)
        step = _train_step(model, fleet.distributed_step)
        # the trace reads the fleet mesh for its sharding constraints and for
        # the kernels' shard_map: hand it the described chips
        mesh = Mesh(np.array(_TOPO.devices).reshape(fleet.mesh.devices.shape), AXES)
        fleet._hcg.mesh = mesh
        mp_specs = {n: p.dist_spec for n, p in model.named_parameters()
                    if getattr(p, "dist_spec", None) is not None}
        shardings = state_shardings(step.state, mesh, stage=layout["stage"], mp_specs=mp_specs)
        batch = NamedSharding(mesh, P(("dp", "sdp")))
        state = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), step.state, shardings)
        ids = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=batch)
        jitted = jax.jit(step._step, donate_argnums=0, in_shardings=(shardings, batch),
                         out_shardings=(shardings, None))
        assert _compile(jitted, state, ((ids,), (ids,))) >= 3
    finally:
        fleet._hcg = None
