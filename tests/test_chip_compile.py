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

import re

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


@pytest.fixture(scope="module")
def topo():
    """The described host. Described when the first test of this file runs,
    never while a module is imported: the process that describes it loads the
    TPU's library and keeps it, and every test worker imports every file."""
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """``one_chip(shape, dtype)``: an abstract array on the host's first chip."""
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


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
    registry.clear_cache()
    yield
    registry.clear_cache()


def _abstract(tree, one_chip):
    return jax.tree_util.tree_map(lambda a: one_chip(np.shape(a), a.dtype), tree)


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
def test_flash_attention_compiles_for_v5e(one_chip, family, direction):
    q = one_chip(_QKV, jnp.bfloat16)
    fn = _FLASH[family] if direction == "fwd" else _grad_of(_FLASH[family], (0, 1, 2))
    assert _compile(fn, q, q, q) >= 1


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_moe_dispatch_combine_compiles_for_v5e_at_flagship_width(one_chip, dtype, direction):
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
    args = (one_chip((T, D), dt), one_chip((T, K), dt), one_chip((T, K), jnp.int32),
            one_chip((E, D, H), dt), one_chip((E, 1, H), dt),
            one_chip((E, H, D), dt), one_chip((E, 1, D), dt))

    def moe(tok, gv, gi, w1, b1, w2, b2):
        return moe_pallas.moe_dispatch_combine(tok, gv, gi, None, w1, b1, w2, b2,
                                               capacity=capacity, activation=jax.nn.gelu)

    fn = moe if direction == "fwd" else _grad_of(moe, (0, 1, 3, 4, 5, 6))
    assert _compile(fn, *args) >= (1 if direction == "fwd" else 2)


def test_layer_norm_fused_compiles_for_v5e(one_chip):
    from paddle_tpu.ops.layer_norm import layer_norm_fused

    x, w = one_chip((8, 1024, 1024), jnp.bfloat16), one_chip((1024,), jnp.float32)
    _compile(_grad_of(lambda x, w, b: layer_norm_fused(x, w, b, 1e-5), (0, 1, 2)), x, w, w)


# --------------------------------------------------------------- programs
_WIDE = dict(vocab_size=50304, hidden_size=1024, num_heads=16, max_seq_len=1024)


def _wide_model(num_layers):
    paddle.seed(0)
    return GPTForPretraining(GPTConfig(num_layers=num_layers, **_WIDE))


# The serving cells' decode programs (BENCHMARK.json): slots, context, width.
_DECODE = {"cerebras-gpt-1.3b.serve-longgen": dict(L=24, B=8, H=16, S=2048, D=2048),
           "gpt2-medium.serve-chat": dict(L=24, B=64, H=16, S=1024, D=1024)}
_CACHE_MAY_PASS_THROUGH = {"custom-call", "parameter", "get-tuple-element", "bitcast", "tuple"}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")


def _cache_shaped_ops(hlo_text, L, B, H, S, dh):
    """``{opcode: count}`` of the instructions whose result, or a member of
    whose tuple result, has the shape of one layer of the cache or of the
    whole cache, in either stored order of the last two dimensions."""
    layer = {(B, H, S, dh), (B, H, dh, S)}
    shapes = layer | {(L,) + s for s in layer}
    found = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1)):
            dims = tuple(int(d) for d in dims.split(","))
            while len(dims) > 4 and dims[0] == 1:
                dims = dims[1:]
            if dims in shapes:
                found[m.group(2)] = found.get(m.group(2), 0) + 1
                break
    return found


@pytest.mark.parametrize("cell", sorted(_DECODE))
def test_decode_step_touches_the_cache_once_on_v5e(as_tpu, one_chip, cell):
    """The decode forward (``_slot_decode_forward``, the body of the engine's
    ``decode_fn``) at the two serving cells' shapes, bf16, cache donated,
    abstract arguments only. It compiles for the described chip with one
    ``decode_attn`` call a layer; its temporaries stay under a quarter of the
    cache (the lax program: 1.2x and 1.3x); and nothing in the optimized
    program but that call makes, copies, scatters into or re-lays-out a
    buffer the shape of a cache layer or of the cache — at head size 64, where
    the device stores S in the lanes, as at 128."""
    from paddle_tpu.models.gpt import _slot_decode_forward
    from paddle_tpu.observability import metrics

    L, B, H, S, D = (_DECODE[cell][k] for k in "LBHSD")
    dh, V, bf = D // H, 50304, jnp.bfloat16
    stack = tuple(one_chip((L,) + shape, bf) for shape in (
        (D,), (D,), (D, 3 * D), (3 * D,), (D, D), (D,), (D,), (D,), (D, 4 * D), (4 * D,), (4 * D, D), (D,)))
    cache = one_chip((L, B, H, S, dh), bf)

    def step(stack, idx, wte, wpe, fnw, fnb, tok, ck, cv, pos, active):
        logits, ck, cv = _slot_decode_forward((stack, idx), wte, wpe, fnw, fnb, tok, ck, cv, pos,
                                              num_heads=H, active=active)
        return jnp.argmax(logits.astype(jnp.float32), axis=-1), ck, cv

    metrics.reset_counters("kernels.decode_attention.")
    compiled = jax.jit(step, donate_argnums=(7, 8)).lower(
        stack, one_chip((L,), jnp.int32), one_chip((V, D), bf), one_chip((S, D), bf),
        one_chip((D,), bf), one_chip((D,), bf), one_chip((B,), jnp.int32), cache, cache,
        one_chip((B,), jnp.int32), one_chip((B,), jnp.bool_)).compile()
    counts = metrics.counters("kernels.decode_attention.")
    assert counts["kernels.decode_attention.picked"] == 1 and not counts.get("kernels.decode_attention.fallback")
    text = compiled.as_text()
    assert sum("custom-call(" in line and "decode_attn" in line for line in text.splitlines()) == L
    cache_bytes = 2 * L * B * H * S * dh * 2
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= 0.25 * cache_bytes, memory.temp_size_in_bytes
    assert memory.alias_size_in_bytes >= cache_bytes       # both caches updated in place
    touched = _cache_shaped_ops(text, L, B, H, S, dh)
    assert touched.get("custom-call") == L
    assert set(touched) <= _CACHE_MAY_PASS_THROUGH, touched


# What the engine's three GPT programs lower to at the two serving cells' shapes, as PR 29's engine (which imported
# ``models/gpt.py``'s private forwards by name) lowered them: sha256 of the StableHLO text, and its lines. Not in the
# hash (taken under this suite's conftest: its matmul-precision pin is in the text): the names of the results (``jax.result_info``: the cache is one argument of two leaves now) and the Mosaic
# kernel's serialized body, which carries the kernel file's line numbers. A PR that means to change a GPT serving
# program changes these with it.
_PARENT_PROGRAMS = {
    "cerebras-gpt-1.3b.serve-longgen": {"decode_fn": ("bc2c4b6c6f63602d", 3274), "chunk_core": ("a62eab5d420e6b87", 4747),
                                        "chunk_final_core": ("bda3c0fbf1929d7c", 4988)},
    "gpt2-medium.serve-chat": {"decode_fn": ("f76958b28793439a", 3418), "chunk_core": ("72c1ac6ef53f24dc", 4747),
                               "chunk_final_core": ("83884956b5b900e0", 4988)},
}


def _fingerprint(lowered_text):
    import hashlib

    text = re.sub(r'backend_config = "(?:\\.|[^"\\])*"', 'backend_config = ""', lowered_text)
    text = re.sub(r'jax\.result_info = "[^"]*"', "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], text.count("\n")


@pytest.mark.parametrize("cell", sorted(_DECODE))
def test_gpt_serving_programs_are_the_parents_through_the_decoder_interface(as_tpu, one_chip, cell):
    """``DecodeEngine`` takes GPT's forwards from ``model.decoder()`` and no longer by name from ``models/gpt.py``.
    Its decode, chunk and final-chunk programs, lowered for the described chip at the two cells' shapes (chunk 128,
    bf16, abstract arguments), are the programs the parent's engine lowered: same fingerprint, same line count.
    The engine is built from a one-layer model of the cell's width and its decoder told the cell's depth: the
    programs take every shape from their arguments and only the layer indices from the decoder."""
    from paddle_tpu.inference import DecodeEngine

    L, B, H, S, D = (_DECODE[cell][k] for k in "LBHSD")
    C, V, dh, bf = 128, 50304, D // H, jnp.bfloat16
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig(vocab_size=512, hidden_size=D, num_layers=1, num_heads=H, max_seq_len=S))
    model.astype("bfloat16")
    engine = DecodeEngine(model, max_batch_slots=2, max_seq_len=S, prefill_chunk=C)
    engine._dec.idx = jnp.arange(L, dtype=jnp.int32)
    engine._build()
    stack = tuple(one_chip((L,) + shape, bf) for shape in (
        (D,), (D,), (D, 3 * D), (3 * D,), (D, D), (D,), (D,), (D,), (D, 4 * D), (4 * D,), (4 * D, D), (D,)))
    p = {"stack": stack, "wte": one_chip((V, D), bf), "wpe": one_chip((S, D), bf),
         "fnw": one_chip((D,), bf), "fnb": one_chip((D,), bf)}
    cache = (one_chip((L, B, H, S, dh), bf),) * 2
    scalar, ids = one_chip((), jnp.int32), one_chip((1, C), jnp.int32)
    slots = lambda dt: one_chip((B,), dt)  # noqa: E731
    state = (slots(jnp.int32), slots(jnp.int32), slots(jnp.bool_))
    programs = {
        "decode_fn": (engine._decode_jit, (p, cache) + state + (slots(jnp.int32),) * 3),
        "chunk_core": (engine._chunk_jit, (p, cache, ids, scalar, scalar)),
        "chunk_final_core": (engine._chunk_final_jit, (p, cache) + state + (ids,) + (scalar,) * 7),
    }
    got = {name: _fingerprint(fn.lower(*args).as_text()) for name, (fn, args) in programs.items()}
    assert got == _PARENT_PROGRAMS[cell]


@pytest.mark.parametrize("program", ["decode_fn", "chunk_core"])
def test_solar_open2_decode_program_compiles_for_v5e_at_the_cells_shapes(as_tpu, one_chip, program):
    """The decode program of ``solar-open2-250b.serve-reasoning`` (128 slots x 16,384, one chip's share at the published
    widths, bf16, abstract arguments) compiles for the described chip: the GQA layer through ``decode_attn`` by group,
    two grouped matmuls an expert layer through ``ops/grouped_matmul.py`` (one selection for the program; its row tile
    32 for 1,024 pairs over a router of 320), every slot buffer updated in place, and next to no temporaries — which
    holds only while a layer's experts are an array of their own (a slice of a stack is copied out for the grouped
    matmul: 4.2 GB) and the recurrent state is one buffer a layer. The chunk program at 1,024 tokens the same way: its
    8,192 pairs in tiles of 128 rows."""
    import json

    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models.solar_open2 import F32_WEIGHTS, PER_LAYER_WEIGHTS, SolarOpen2Config, SolarOpen2ForCausalLM
    from paddle_tpu.observability import metrics

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs",
                           "solar-open2-250b.json")) as f:
        cfg = SolarOpen2Config.from_config_file(json.load(f))
    B, S, C, bf = 128, 16384, 1024, jnp.bfloat16
    weights = {k: one_chip(shape, jnp.float32 if k in F32_WEIGHTS else bf) for k, shape in cfg.weight_shapes().items()}
    for k in PER_LAYER_WEIGHTS:
        weights[k] = tuple(one_chip(weights[k].shape[1:], bf) for _ in range(weights[k].shape[0]))
    decoder = SolarOpen2ForCausalLM(cfg, weights=weights).decoder()
    engine = DecodeEngine.__new__(DecodeEngine)       # the programs only: nothing is allocated, nothing runs
    engine._dec, engine._ddec, engine._sample, engine.spec_k, engine._donate, engine._chunk = decoder, None, (False, 1.0, 0, 1.0), 0, True, C
    engine._build()
    cache = tuple(one_chip(spec.shape, spec.dtype) for spec in decoder.buffer_specs(B, S))
    slots = lambda dt: one_chip((B,), dt)  # noqa: E731
    scalar = one_chip((), jnp.int32)
    metrics.reset_counters("kernels.")
    if program == "decode_fn":
        compiled = engine._decode_jit.lower(weights, cache, slots(jnp.int32), slots(jnp.int32), slots(jnp.bool_),
                                            slots(jnp.int32), slots(jnp.int32), slots(jnp.int32)).compile()
        assert metrics.counters("kernels.decode_attention.")["kernels.decode_attention.picked"] == 1
    else:
        compiled = engine._chunk_jit.lower(weights, cache, one_chip((1, C), jnp.int32), scalar, scalar).compile()
    assert metrics.counters("kernels.grouped_matmul.") == {"kernels.grouped_matmul.picked": 1, "kernels.grouped_matmul.fallback": 0}
    calls = [line for line in compiled.as_text().splitlines() if "custom-call(" in line]
    # an intermediate chunk returns the buffers only, so the last layer's experts, which feed none, are not compiled
    tm, layers = (32, cfg.num_hidden_layers) if program == "decode_fn" else (128, cfg.num_hidden_layers - 1)
    assert sum(f"%moe_grouped_{tm}" in line for line in calls) == 2 * layers
    assert not any("ragged-dot" in line for line in calls)
    routed = [line for line in calls if "moe_grouped" in line]
    assert all("/moe_routed/" in line for line in routed), routed[0]      # the scope the by-part readers take it by
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize for spec in decoder.buffer_specs(B, S))
    if program == "decode_fn":
        assert 6.9e9 < memory.argument_size_in_bytes < 7.1e9
        assert sum("decode_attn" in line for line in calls) == len(cfg.gqa_layers)
        assert memory.alias_size_in_bytes >= held and memory.temp_size_in_bytes < 0.1e9, memory.temp_size_in_bytes
    else:
        assert memory.temp_size_in_bytes < 0.6e9, memory.temp_size_in_bytes      # 0.55 GB with XLA's grouped matmul


def test_engine_imports_no_private_function_of_a_model():
    import ast
    import inspect

    from paddle_tpu.inference import engine

    for node in ast.walk(ast.parse(inspect.getsource(engine))):
        if isinstance(node, ast.ImportFrom) and "models" in (node.module or ""):
            assert not [a.name for a in node.names if a.name.startswith("_")], (node.module, [a.name for a in node.names])


def _train_step(model, make_step):
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    return make_step(model, opt, GPTPretrainingCriterion())


def test_train_step_picks_and_compiles_the_flash_kernels_for_v5e(as_tpu, one_chip):
    """``TrainStep``'s whole AMP-O2 step at the flagship width (depth cut to
    one layer: the block is compiled once whatever the depth) with the
    Pallas attention kernels in it, forward and backward."""
    from paddle_tpu.jit import TrainStep

    step = _train_step(_wide_model(1), lambda m, o, c: TrainStep(m, o, c, amp_level="O2"))
    ids = one_chip((8, 1024), jnp.int32)
    assert _compile(step._jit, _abstract(step.state, one_chip), ((ids,), (ids,))) >= 3


@pytest.mark.parametrize("layout", [dict(dp=2, mp=2, sdp=1, stage=0),
                                    dict(dp=1, mp=2, sdp=2, stage=2)],
                         ids=["dp2xmp2", "sharding2xmp2"])
def test_distributed_step_compiles_for_a_v5e_2x2_mesh(as_tpu, topo, layout):
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
        mesh = Mesh(np.array(topo.devices).reshape(fleet.mesh.devices.shape), AXES)
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
