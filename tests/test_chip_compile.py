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


def _custom_calls(hlo_text, name=""):
    """The ``custom-call`` instructions whose own name starts with ``name`` (one that takes such a call's result is
    not one)."""
    return [line for line in hlo_text.splitlines()
            if "custom-call(" in line and line.split(" = ")[0].split()[-1].startswith("%" + name)]


def _ops_of_result(hlo_text, dtype, dims):
    """``{opcode: count}`` of the instructions one of whose results is ``dtype[dims]`` (fused computations'
    instructions included: a value inside a fusion is still a value the chip forms)."""
    want, found = f"{dtype}[{','.join(str(d) for d in dims)}]", {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is not None and want in m.group(1):
            found[m.group(2)] = found.get(m.group(2), 0) + 1
    return found


_DECODE_COMPILED = {}     # cell -> (the decode step compiled, the decode kernel's counters its lowering left)


def _decode_step_compiled(cell, one_chip):
    """The decode forward (``_slot_decode_forward``, the body of the engine's ``decode_fn``) and an argmax at a serving
    cell's shapes, bf16, cache donated, abstract arguments only, compiled for the described chip once for this file's
    tests."""
    if cell in _DECODE_COMPILED:
        return _DECODE_COMPILED[cell]
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
    _DECODE_COMPILED[cell] = compiled, metrics.counters("kernels.decode_attention.")
    return _DECODE_COMPILED[cell]


@pytest.mark.parametrize("cell", sorted(_DECODE))
def test_decode_step_touches_the_cache_once_on_v5e(as_tpu, one_chip, cell):
    """The decode step (``_decode_step_compiled``) compiles for the described
    chip with one ``decode_attn`` call a layer; its temporaries stay under a
    quarter of the cache (the lax program: 1.2x and 1.3x); and nothing in the
    optimized program but that call makes, copies, scatters into or
    re-lays-out a buffer the shape of a cache layer or of the cache — at head
    size 64, where the device stores S in the lanes, as at 128."""
    L, B, H, S, D = (_DECODE[cell][k] for k in "LBHSD")
    dh = D // H
    compiled, counts = _decode_step_compiled(cell, one_chip)
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


@pytest.mark.parametrize("cell", sorted(_DECODE))
def test_decode_step_reads_each_qkv_weight_where_it_lies_on_v5e(as_tpu, one_chip, cell):
    """The decode step's qkv matmuls read each layer's slice of the stacked ``[L, D, 3D]`` weight where it lies in HBM.
    Until the product was formed whole before its split into q, k, v and heads (``_serve_block``), XLA folded that split
    and the transpose after it into the matmul's output layout and re-laid the weight to match: every layer's qkv weight
    was sliced out into VMEM (``S(1)``) by multi-output fusions, slice-starts and ``ConcatBitcast`` calls and copied
    again into ``[3D, D]``, every step (longgen: 24 x 25.2 MB). Now the one value a layer of
    a qkv weight's shape is the stacked parameter's slice that the matmul's own fusion reads — a ``bitcast`` of the
    slice inside the ``fusion`` that wraps it — in HBM, in the stored order; and at longgen's shapes the program moves
    little more than its arguments (the parent: 1.65x)."""
    L, D = _DECODE[cell]["L"], _DECODE[cell]["D"]
    compiled, _ = _decode_step_compiled(cell, one_chip)
    text = compiled.as_text()
    assert _ops_of_result(text, "bf16", (3 * D, D)) == {}
    assert _ops_of_result(text, "bf16", (D, 3 * D)) == {"bitcast": L, "fusion": L}
    assert not re.search(rf"bf16\[{D},{3 * D}\]\{{[^}}]*S\(1\)", text)
    assert "ConcatBitcast" not in text and " slice-start(" not in text
    if cell == "cerebras-gpt-1.3b.serve-longgen":
        moved = compiled.cost_analysis()["bytes accessed"]
        assert moved <= 1.10 * compiled.memory_analysis().argument_size_in_bytes, moved


# What each cell's programs lower to for the described chip: sha256 of the StableHLO text, and its lines. The GPT
# serving cells' first three are as PR 29's engine (which imported ``models/gpt.py``'s private forwards by name)
# lowered them; ``prefill_core``, Solar's three, the train step and the four-chip step were taken at 094436e, before
# PR 32 folded the cache forwards of ``models/gpt.py`` onto one block body and one layer walk. Not in the hash (taken
# under this suite's conftest: its matmul-precision pin is in the text): the names of the results
# (``jax.result_info``) and the Mosaic kernels' serialized bodies, which carry the kernel files' line numbers.
# ``_PARENT_SCOPES``: the operations under each scope (``_scope_counts``), taken at the same commit. A PR that means to
# change a program changes its entries with it; a refactor that moves one reordered an operation or moved a scope.
# PR 33 re-pinned the three ``decode_fn``: only the result tuple changed. The step's report to the host — tokens, which
# slots emitted them, which stay active, and Solar's counters — leaves as one int32 array beside the carry (GPT gains
# the output: two converts and a concatenate, ``unscoped`` 621 -> 624 and 765 -> 768; Solar's widens by two operands,
# 608 -> 610), so that a step launched ahead can be pulled after the next launch has donated ``tok`` and ``active``.
# The body (``decode_body``) is untouched: every named scope's count, and every other program, is as it was.
# PR 35 re-pinned ``cerebras-gpt-1.3b.train-zero2mp2``'s ``_step`` and nothing else (819e0ecbdaf33112, 1319 lines
# before): at ZeRO stage 2 the f32 master enters and leaves the program split over 'sdp' like its moments, and the step
# gains, under ``amp_cast``, a sharding constraint on each of the six split parameters' bf16 cast (the gather) and one on
# each of their gradients (the scatter to the owner) — ``amp_cast`` 32 -> 44, twelve lines; their casts moved out of
# the differentiated function with them. No other scope's count moved; ``gpt2-medium.train`` has no mesh and is as it was.
# PR 37 re-pinned the nine programs of Solar's, GigaChat's and Granite's cells and nothing else: the routed experts' way
# back to token order (``ops/moe_dropless.py``) is one ``moe_combine`` call a layer (``ops/moe_combine.py``) where it was
# a scatter for the inverse order, a gather of the float32 ``[T k, D]`` result, a reshape to ``[T, k, D]`` and a masked
# weighted sum over ``k``. ``moe_routed`` falls by 13 to 15 operations an expert layer (Granite 1530 -> 1400, 1278 ->
# 1161, 1420 -> 1290; Solar 612 -> 560, 426 -> 387, 568 -> 516; GigaChat 624 -> 572, 435 -> 396, 580 -> 528) and
# ``unscoped`` by the bodies of the ``_take`` and ``_where`` helpers that ``jax.numpy`` outlines once a program, which
# carry no scope path and which nothing calls at those shapes any more (Granite 1168 -> 1097, 1706 -> 1639, 1808 ->
# 1737; Solar 610 -> 563, 896 -> 853, 1011 -> 964; GigaChat 752 -> 705, 1156 -> 1113, 1279 -> 1232). No other scope's
# count moved; the GPT serving programs and both training steps are byte for byte what they were.
# PR 39 re-pinned ``chunk_core`` and ``chunk_final_core`` of Solar's and GigaChat's cells and nothing else: the chunkwise
# delta rule (``ops/delta_rule.py``) inverts its unit lower-triangular matrix by halving, in straight-line batched
# products, where it ran 64 rows of forward substitution in a ``while``; it multiplies the inverse into ``w`` and ``u0``
# as two products where one solve took the two concatenated; and where a head has one decay (GigaChat) the running sum of
# its logarithm is taken with the rows as the minor axis. ``linear_core`` gains 131 operations a delta-rule layer in
# Solar (553 -> 946, 597 -> 990) and 134 in GigaChat (752 -> 1288, 796 -> 1332), ``unscoped`` loses the 31 of the loop's
# body, which carried no scope path (Solar 853 -> 760, 964 -> 871; GigaChat 1113 -> 989, 1232 -> 1108); the texts grow by
# 96 and 99 lines a layer (2284 -> 2572, 2668 -> 2956; 3308 -> 3704, 3780 -> 4176). No other scope's count moved; both
# cells' ``decode_fn`` (``delta_rule_step``), Granite's three programs, every GPT program and both training steps are
# byte for byte what they were. ``test_a_delta_rule_layer_of_a_chunk_program_loops_...`` keeps the 64-trip loop out.
# Last, the eight programs of the two GPT serving cells were re-pinned, and nothing else: ``_serve_block`` forms the qkv
# product whole behind an ``optimization_barrier`` before it is cut into q, k, v and heads, so that XLA no longer folds
# that split into the matmul's output layout and re-lays each layer's weight to match. ``attn_qkv`` gains the barrier,
# one operation a layer (432 -> 456; ``chunk_core`` 429 -> 453), and each text 24 lines. No other scope's count moved;
# every other program is byte for byte what it was.
_PARENT_PROGRAMS = {
    "cerebras-gpt-1.3b.serve-longgen": {
        "decode_fn": ("b02a995193741a98", 3301),
        "chunk_core": ("3c56facf54c91a1f", 4771),
        "chunk_final_core": ("e7943466e024bb55", 5012),
        "prefill_core": ("e80e0d81f8cd1f1d", 4599),
    },
    "gpt2-medium.serve-chat": {
        "decode_fn": ("89699f9b67988ebf", 3445),
        "chunk_core": ("a28049e1ee380dfb", 4771),
        "chunk_final_core": ("c17038f0d7f5e4bc", 5012),
        "prefill_core": ("f04111e91e471fed", 4599),
    },
    "solar-open2-250b.serve-reasoning": {
        "decode_fn": ("3aa7e33f2c334eb3", 1921),
        "chunk_core": ("3f17368804500c83", 2572),
        "chunk_final_core": ("666a74b2a9dad466", 2956),
    },
    # PR 34, the cell's first programs (PR 37: the experts' way back; PR 39: the chunk programs' delta-rule solve): what a
    # later refactor of ``models/gigachat3_5.py`` or of the ops it shares with Solar's (``ops/delta_rule.py``,
    # ``ops/moe_dropless.py``) has to leave as it is
    "gigachat3.5-432b-a28b.serve-longdoc": {
        "decode_fn": ("e5b34fb18caa72c6", 2788),
        "chunk_core": ("0b7248241d4da690", 3704),
        "chunk_final_core": ("b00d62003595727e", 4176),
    },
    # PR 36, the cell's first programs (PR 37: the experts' way back): what a later refactor of
    # ``models/granite_moe_hybrid.py``, of what it imports from ``models/solar_open2.py`` or of ``ops/ssd.py`` has to
    # leave as it is
    "granite-4.0-h-small.serve-rag": {
        "decode_fn": ("e2259e07a63ac9ae", 4238),
        "chunk_core": ("4ee46cefbf68329d", 5074),
        "chunk_final_core": ("3e199cca4ffe3ad1", 5441),
    },
    "gpt2-medium.train": {
        "_step": ("af707415b346d11f", 1303),
    },
    "cerebras-gpt-1.3b.train-zero2mp2": {
        "_step": ("71f99c73f25beaf1", 1331),
    },
}

_PARENT_SCOPES = {
    "cerebras-gpt-1.3b.serve-longgen": {
        "decode_fn": {"unscoped": 624, "embed": 3, "norm": 1221, "attn_qkv": 456, "attn_out": 216, "mlp": 720, "attn_core": 48, "head_loss": 1},
        "chunk_core": {"unscoped": 916, "embed": 7, "norm": 1175, "attn_qkv": 453, "attn_out": 207, "mlp": 690, "cache_read": 280, "cache_write": 390, "attn_core": 644},
        "chunk_final_core": {"unscoped": 1023, "embed": 7, "norm": 1223, "attn_qkv": 456, "attn_out": 216, "mlp": 720, "cache_read": 288, "cache_write": 390, "attn_core": 672, "head_loss": 3},
        "prefill_core": {"unscoped": 952, "embed": 7, "norm": 1221, "attn_qkv": 456, "attn_out": 216, "mlp": 720, "cache_read": 96, "cache_write": 246, "attn_core": 672, "head_loss": 1},
    },
    "gpt2-medium.serve-chat": {
        "decode_fn": {"unscoped": 768, "embed": 3, "norm": 1221, "attn_qkv": 456, "attn_out": 216, "mlp": 720, "attn_core": 48, "head_loss": 1},
        "chunk_core": {"unscoped": 916, "embed": 7, "norm": 1175, "attn_qkv": 453, "attn_out": 207, "mlp": 690, "cache_read": 280, "cache_write": 390, "attn_core": 644},
        "chunk_final_core": {"unscoped": 1023, "embed": 7, "norm": 1223, "attn_qkv": 456, "attn_out": 216, "mlp": 720, "cache_read": 288, "cache_write": 390, "attn_core": 672, "head_loss": 3},
        "prefill_core": {"unscoped": 952, "embed": 7, "norm": 1221, "attn_qkv": 456, "attn_out": 216, "mlp": 720, "cache_read": 96, "cache_write": 246, "attn_core": 672, "head_loss": 1},
    },
    "solar-open2-250b.serve-reasoning": {
        "decode_fn": {"unscoped": 563, "embed": 1, "norm": 169, "attn_qkv": 13, "attn_core": 2, "attn_out": 4, "moe_router": 68, "moe_routed": 560, "moe_shared": 48, "linear_proj": 102, "linear_core": 294, "linear_out": 57, "head_loss": 2},
        "chunk_core": {"unscoped": 760, "embed": 1, "norm": 133, "attn_qkv": 13, "cache_write": 18, "cache_read": 12, "attn_core": 28, "attn_out": 3, "moe_router": 51, "moe_routed": 387, "moe_shared": 36, "linear_proj": 92, "linear_core": 946, "linear_out": 38},
        "chunk_final_core": {"unscoped": 871, "embed": 1, "norm": 168, "attn_qkv": 13, "cache_write": 18, "cache_read": 12, "attn_core": 28, "attn_out": 3, "moe_router": 68, "moe_routed": 516, "moe_shared": 48, "linear_proj": 102, "linear_core": 990, "linear_out": 57, "head_loss": 2},
    },
    "gigachat3.5-432b-a28b.serve-longdoc": {
        "decode_fn": {"unscoped": 705, "embed": 1, "norm": 566, "linear_proj": 100, "linear_core": 404, "linear_out": 124, "mlp": 14, "mla_q": 35, "mla_kv": 27, "rope": 46, "mla_core": 3, "mla_out": 17, "moe_router": 68, "moe_routed": 572, "moe_shared": 60, "head_loss": 2},
        "chunk_core": {"unscoped": 989, "embed": 1, "norm": 459, "linear_proj": 99, "linear_core": 1288, "linear_out": 93, "mlp": 14, "mla_q": 32, "mla_kv": 27, "rope": 46, "cache_write": 8, "mla_core": 73, "mla_out": 13, "moe_router": 51, "moe_routed": 396, "moe_shared": 45},
        "chunk_final_core": {"unscoped": 1108, "embed": 1, "norm": 565, "linear_proj": 100, "linear_core": 1332, "linear_out": 124, "mlp": 14, "mla_q": 32, "mla_kv": 27, "rope": 46, "cache_write": 8, "mla_core": 73, "mla_out": 13, "moe_router": 68, "moe_routed": 528, "moe_shared": 60, "head_loss": 2},
    },
    "granite-4.0-h-small.serve-rag": {
        "decode_fn": {"unscoped": 1097, "embed": 4, "norm": 376, "ssm_proj": 54, "ssm_conv": 405, "ssm_core": 369, "ssm_norm": 171, "ssm_out": 9, "moe_router": 170, "moe_routed": 1400, "moe_shared": 120, "attn_qkv": 6, "attn_core": 2, "attn_out": 1, "head_loss": 4},
        "chunk_core": {"unscoped": 1639, "embed": 4, "norm": 342, "ssm_proj": 54, "ssm_conv": 412, "ssm_core": 826, "ssm_norm": 152, "ssm_out": 8, "moe_router": 153, "moe_routed": 1161, "moe_shared": 108, "attn_qkv": 4, "cache_write": 18, "attn_core": 99, "attn_out": 2},
        "chunk_final_core": {"unscoped": 1737, "embed": 4, "norm": 375, "ssm_proj": 54, "ssm_conv": 414, "ssm_core": 873, "ssm_norm": 171, "ssm_out": 9, "moe_router": 170, "moe_routed": 1290, "moe_shared": 120, "attn_qkv": 4, "cache_write": 18, "attn_core": 99, "attn_out": 2, "head_loss": 4},
    },
    "gpt2-medium.train": {
        "_step": {"unscoped": 370, "amp_cast": 32, "embed": 31, "norm": 210, "attn_qkv": 22, "attn_out": 21, "mlp": 73, "attn_core": 37, "head_loss": 47, "optimizer": 439},
    },
    "cerebras-gpt-1.3b.train-zero2mp2": {
        "_step": {"unscoped": 413, "amp_cast": 44, "embed": 31, "norm": 210, "attn_qkv": 22, "attn_out": 21, "mlp": 73, "attn_core": 4, "head_loss": 49, "optimizer": 439},
    },
}


def _fingerprint(lowered_text):
    import hashlib

    text = re.sub(r'backend_config = "(?:\\.|[^"\\])*"', 'backend_config = ""', lowered_text)
    text = re.sub(r'jax\.result_info = "[^"]*"', "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], text.count("\n")


# Every ``jax.named_scope`` a by-part metric reads (PERF.md §3; ``benchmark/families/*.py:PART_OF_SCOPE``).
_SCOPES = {"norm", "attn_qkv", "attn_core", "attn_out", "mlp", "embed", "head_loss", "cache_write", "cache_read",
           "optimizer", "amp_cast", "linear_proj", "linear_core", "linear_out", "moe_router", "moe_routed", "moe_shared",
           "mla_q", "mla_kv", "rope", "mla_core", "mla_out", "ssm_proj", "ssm_conv", "ssm_core", "ssm_norm", "ssm_out"}
_LOC_NAME = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)
_LOC_USE = re.compile(r" loc\((#loc\d*)\)$", re.M)


def _scope_counts(lowered):
    """``{scope: ops}`` of a lowered program: each operation of the StableHLO text under the first scope of
    ``_SCOPES`` in its name path (``jit(decode_fn)/norm/reduce_sum`` -> norm; file and line are not looked at),
    ``unscoped`` where the path has none. The fingerprint does not see these names and the by-part metrics do."""
    text = lowered.as_text(debug_info=True)
    path_of = dict(_LOC_NAME.findall(text))
    counts = {}
    for ref in _LOC_USE.findall(text):
        scope = next((w for w in re.findall(r"[A-Za-z_]\w*", path_of.get(ref, "")) if w in _SCOPES), "unscoped")
        counts[scope] = counts.get(scope, 0) + 1
    return counts


_LOWERED = {}      # cell -> {program: lowered}: each cell's programs are lowered once for this file's tests


def _gpt_serving_lowered(cell, one_chip):
    """The engine's four GPT programs lowered for the described chip at a serving cell's shapes (chunk 128, a
    one-shot prefill bucket of 512, bf16, abstract arguments). The engine is built from a one-layer model of the
    cell's width and its decoder told the cell's depth: the programs take every shape from their arguments and only
    the layer indices from the decoder."""
    if cell in _LOWERED:
        return _LOWERED[cell]
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
        "prefill_core": (engine._prefill_jit, (p, cache) + state + (one_chip((1, 512), jnp.int32),) + (scalar,) * 5),
    }
    _LOWERED[cell] = {name: fn.lower(*args) for name, (fn, args) in programs.items()}
    return _LOWERED[cell]


@pytest.mark.parametrize("cell", sorted(_DECODE))
def test_gpt_serving_programs_are_the_parents_through_the_decoder_interface(as_tpu, one_chip, cell):
    """``DecodeEngine`` takes GPT's forwards from ``model.decoder()`` and no longer by name from ``models/gpt.py``.
    Its decode, chunk, final-chunk and one-shot prefill programs, lowered for the described chip at the two cells'
    shapes, are the programs the parent's engine lowered: same fingerprint, same line count."""
    got = {name: _fingerprint(lowered.as_text()) for name, lowered in _gpt_serving_lowered(cell, one_chip).items()}
    assert got == _PARENT_PROGRAMS[cell]


_SOLAR = "solar-open2-250b.serve-reasoning"


def _share_lowered(cell, module, name, config_file, one_chip, B, S, C=1024):
    """``(cfg, decoder, {program: (lowered, the kernels its lowering picked)})`` of a cell that serves one chip's share
    of the model ``<name>ForCausalLM`` of ``paddle_tpu.models.<module>``: ``B`` slots x ``S``, chunk ``C``, at the published widths, bf16,
    abstract arguments. The engine holds the programs only: nothing is allocated."""
    if cell in _LOWERED:
        return _LOWERED[cell]
    import importlib
    import json

    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.observability import metrics

    model = importlib.import_module(f"paddle_tpu.models.{module}")
    config, causal_lm = getattr(model, name + "Config"), getattr(model, name + "ForCausalLM")
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs", config_file)) as f:
        cfg = config.from_config_file(json.load(f))
    bf = jnp.bfloat16
    weights = {k: one_chip(shape, jnp.float32 if k in model.F32_WEIGHTS else bf) for k, shape in cfg.weight_shapes().items()}
    for k in model.PER_LAYER_WEIGHTS:
        weights[k] = tuple(one_chip(weights[k].shape[1:], bf) for _ in range(weights[k].shape[0]))
    decoder = causal_lm(cfg, weights=weights).decoder()
    engine = DecodeEngine.__new__(DecodeEngine)
    engine._dec, engine._ddec, engine._sample, engine.spec_k, engine._donate, engine._chunk = decoder, None, (False, 1.0, 0, 1.0), 0, True, C
    engine._build()
    cache = tuple(one_chip(spec.shape, spec.dtype) for spec in decoder.buffer_specs(B, S))
    slots = lambda dt: one_chip((B,), dt)  # noqa: E731
    scalar, ids = one_chip((), jnp.int32), one_chip((1, C), jnp.int32)
    state = (slots(jnp.int32), slots(jnp.int32), slots(jnp.bool_))
    programs = {
        "decode_fn": (engine._decode_jit, (weights, cache) + state + (slots(jnp.int32),) * 3),
        "chunk_core": (engine._chunk_jit, (weights, cache, ids, scalar, scalar)),
        "chunk_final_core": (engine._chunk_final_jit, (weights, cache) + state + (ids,) + (scalar,) * 7),
    }
    lowered = {}
    for name, (fn, args) in programs.items():
        metrics.reset_counters("kernels.")
        lowered[name] = (fn.lower(*args), metrics.counters("kernels."))
    _LOWERED[cell] = cfg, decoder, lowered
    return _LOWERED[cell]


def _solar_lowered(one_chip):
    """The Solar cell's programs: 128 slots x 16,384, chunk 1,024."""
    return _share_lowered(_SOLAR, "solar_open2", "SolarOpen2", "solar-open2-250b.json", one_chip, 128, 16384)


@pytest.mark.parametrize("program", ["decode_fn", "chunk_core"])
def test_solar_open2_decode_program_compiles_for_v5e_at_the_cells_shapes(as_tpu, one_chip, program):
    """The decode program of ``solar-open2-250b.serve-reasoning`` (128 slots x 16,384, one chip's share at the published
    widths, bf16, abstract arguments) compiles for the described chip: the GQA layer through ``decode_attn`` by group,
    two grouped matmuls an expert layer through ``ops/grouped_matmul.py`` (one selection for the program; its row tile
    32 for 1,024 pairs over a router of 320), every slot buffer updated in place, and next to no temporaries — which
    holds only while a layer's experts are an array of their own (a slice of a stack is copied out for the grouped
    matmul: 4.2 GB) and the recurrent state is one buffer a layer. The chunk program at 1,024 tokens the same way: its
    8,192 pairs in tiles of 128 rows."""
    cfg, decoder, lowered = _solar_lowered(one_chip)
    B, S = 128, 16384
    lowered, picked = lowered[program]
    compiled = lowered.compile()
    if program == "decode_fn":
        assert picked["kernels.decode_attention.picked"] == 1
    assert {k: v for k, v in picked.items() if "grouped_matmul" in k or "moe_combine" in k} == {
        "kernels.grouped_matmul.picked": 1, "kernels.grouped_matmul.fallback": 0,
        "kernels.moe_combine.picked": 1, "kernels.moe_combine.fallback": 0}
    text = compiled.as_text()
    calls = _custom_calls(text)
    # an intermediate chunk returns the buffers only, so the last layer's experts, which feed none, are not compiled
    tm, layers = (32, cfg.num_hidden_layers) if program == "decode_fn" else (128, cfg.num_hidden_layers - 1)
    assert len(_custom_calls(text, f"moe_grouped_{tm}")) == 2 * layers
    assert len(_custom_calls(text, "moe_combine")) == layers        # the way back to token order: one call a layer (PR 37)
    assert not any("ragged-dot" in line for line in calls)
    routed = _custom_calls(text, "moe_")
    assert all("/moe_routed/" in line for line in routed), routed[0]      # the scope the by-part readers take it by
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize for spec in decoder.buffer_specs(B, S))
    if program == "decode_fn":
        assert 6.9e9 < memory.argument_size_in_bytes < 7.1e9
        assert sum("decode_attn" in line for line in calls) == len(cfg.gqa_layers)
        assert memory.alias_size_in_bytes >= held and memory.temp_size_in_bytes < 0.1e9, memory.temp_size_in_bytes
    else:
        assert memory.temp_size_in_bytes < 0.6e9, memory.temp_size_in_bytes      # 0.55 GB with XLA's grouped matmul


_GIGA = "gigachat3.5-432b-a28b.serve-longdoc"


def _giga_lowered(one_chip):
    """The GigaChat cell's programs: 48 slots x 32,768, chunk 1,024."""
    return _share_lowered(_GIGA, "gigachat3_5", "GigaChat35", "gigachat3.5-432b-a28b.json", one_chip, 48, 32768)


@pytest.mark.parametrize("program", ["decode_fn", "chunk_core", "chunk_final_core"])
def test_gigachat3_5_programs_compile_for_v5e_at_the_cells_shapes(as_tpu, one_chip, program):
    """The programs of ``gigachat3.5-432b-a28b.serve-longdoc`` (48 slots x 32,768, chunk 1,024, one chip's share at the
    published widths, bf16, abstract arguments) compile for the described chip: the latent layer's decode through the
    ``mla_decode`` kernel on the ``[B, S, 576]`` cache, aliased; two grouped matmuls an expert layer through
    ``ops/grouped_matmul.py`` at D 7,168 and width 2,048 (row tile 16 for 384 pairs over a router of 256, 128 for a
    chunk's 8,192), none through XLA's; every slot buffer updated in place; and the chunk programs' temporaries beside
    the 12.1 GB held inside the chip's 16 GB."""
    cfg, decoder, lowered = _giga_lowered(one_chip)
    B, S = 48, 32768
    lowered, picked = lowered[program]
    compiled = lowered.compile()
    # one selection a kernel a set of shapes: the final chunk's are the chunk's, selected when that was lowered
    once = 0 if program == "chunk_final_core" else 1
    new = "mla_decode" if program == "decode_fn" else "mla_prefill"
    assert {k: v for k, v in picked.items() if v} == {k: v for k, v in {
        "kernels.grouped_matmul.picked": once, "kernels.moe_combine.picked": once, f"kernels.{new}.picked": once,
        "kernels.rope.picked": 2 * once}.items() if v}                                       # the queries' shape and the key's
    text = compiled.as_text()
    calls = _custom_calls(text)
    experts = len(cfg.expert_layers) - (program == "chunk_core")     # an intermediate chunk's last expert layer feeds nothing
    tm = 16 if program == "decode_fn" else 128
    assert len(_custom_calls(text, f"moe_grouped_{tm}")) == 2 * experts and len(_custom_calls(text, "moe_combine")) == experts
    assert not any("ragged-dot" in line for line in calls)
    assert all("/moe_routed/" in line for line in _custom_calls(text, "moe_"))
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize for spec in decoder.buffer_specs(B, S))
    print(f"{_GIGA} {program}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB; {picked}")
    # 9.46 GB of weights + 2.84 GB of slots (a latent row padded to 640 lanes); an intermediate chunk takes neither
    # the head nor the last layer's experts
    assert (10.5e9 if program == "chunk_core" else 11.9e9) < memory.argument_size_in_bytes < 12.6e9
    assert memory.alias_size_in_bytes >= held
    if program == "decode_fn":
        mla = [line for line in calls if "mla_decode" in line]
        assert len(mla) == len(cfg.full_attention_layers) and all("/mla_core/" in line for line in mla)
        assert memory.temp_size_in_bytes < 0.3e9, memory.temp_size_in_bytes
    else:
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9, memory.temp_size_in_bytes


_GRANITE = "granite-4.0-h-small.serve-rag"


def _granite_lowered(one_chip):
    """The Granite cell's programs: 40 slots x 8,192, chunk 1,024."""
    return _share_lowered(_GRANITE, "granite_moe_hybrid", "GraniteMoeHybrid", "granite-4.0-h-small.json", one_chip, 40, 8192)


@pytest.mark.parametrize("program", ["decode_fn", "chunk_core", "chunk_final_core"])
def test_granite_moe_hybrid_programs_compile_for_v5e_at_the_cells_shapes(as_tpu, one_chip, program):
    """The programs of ``granite-4.0-h-small.serve-rag`` (40 slots x 8,192, chunk 1,024, one chip's share at the published
    widths, bf16, abstract arguments) compile for the described chip: the attention layer's decode through ``decode_attn``
    by group (8 key/value heads a slot, 4 query heads each); two grouped matmuls an expert layer through
    ``ops/grouped_matmul.py`` at D 4,096 and width 768 (400 pairs over a router of 72 in the decode program, 10,240 in a
    chunk: one selection a program's shapes, none through XLA's); the state-space scan and step through the registry
    (one implementation each, ``lax``: ``picked`` counts the shapes); every slot buffer updated in place; and the chunk
    programs' temporaries beside the 12.4 GB held inside the chip's 16 GB."""
    cfg, decoder, lowered = _granite_lowered(one_chip)
    B, S = 40, 8192
    lowered, picked = lowered[program]
    compiled = lowered.compile()
    # one selection a kernel a set of shapes: the final chunk's are the chunk's, selected when that was lowered
    once = 0 if program == "chunk_final_core" else 1
    new = "ssd_step" if program == "decode_fn" else "ssd_chunked"
    want = {"kernels.grouped_matmul.picked": once, "kernels.moe_combine.picked": once, f"kernels.{new}.picked": once}
    if program == "decode_fn":
        want["kernels.decode_attention.picked"] = 1
    assert {k: v for k, v in picked.items() if v} == {k: v for k, v in want.items() if v}
    text = compiled.as_text()
    calls = _custom_calls(text)
    layers = cfg.num_hidden_layers - (program == "chunk_core")      # an intermediate chunk's last expert layer feeds nothing
    grouped = _custom_calls(text, "moe_grouped_")
    assert len(grouped) == 2 * layers and not any("ragged-dot" in line for line in calls)
    assert len(_custom_calls(text, "moe_combine")) == layers
    assert all("/moe_routed/" in line for line in _custom_calls(text, "moe_"))
    if program != "decode_fn":
        # the way back to token order (PR 37): the second grouped matmul's float32 [T k, D] result is read by the one
        # ``moe_combine`` call and by nothing else, and [T, k, D] — ten sublanes padded to sixteen, a 268-MB relayout
        # and a reduction across sublanes — is never formed, inside a fusion or out
        assert _ops_of_result(text, "f32", (1024, 10, 4096)) == {}
        assert "tensor<1024x10x4096xf32>" not in lowered.as_text()
        assert _ops_of_result(text, "f32", (10240, 4096)) == {"custom-call": layers}
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize for spec in decoder.buffer_specs(B, S))
    print(f"{_GRANITE} {program}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB; {picked}; "
          f"tiles {sorted({line.split('%moe_grouped_')[1].split('.')[0].split(' ')[0] for line in grouped})}")
    assert held == 40 * 71_759_360
    # 9.51 GB of weights + 2.87 GB of slots; an intermediate chunk takes neither the last layer's experts nor the final norm
    assert (11.6e9 if program == "chunk_core" else 12.3e9) < memory.argument_size_in_bytes < 12.5e9
    assert memory.alias_size_in_bytes >= held
    if program == "decode_fn":
        attn = [line for line in calls if "decode_attn" in line]
        assert len(attn) == len(cfg.gqa_layers) and all("/attn_core/" in line for line in attn)
        assert memory.temp_size_in_bytes < 0.3e9, memory.temp_size_in_bytes
    else:
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9, memory.temp_size_in_bytes


_EVA = "evabyte-6.5b.serve-bytegen"


def _eva_lowered(one_chip):
    """The EvaByte cell's programs: 16 slots x 32,768 bytes, chunk 1,024."""
    return _share_lowered(_EVA, "evabyte", "EvaByte", "evabyte-6.5b.json", one_chip, 16, 32768)


@pytest.mark.parametrize("program", ["decode_fn", "chunk_core", "chunk_final_core"])
def test_evabyte_programs_compile_for_v5e_at_the_cells_shapes(as_tpu, one_chip, program):
    """The programs of ``evabyte-6.5b.serve-bytegen`` (16 slots x 32,768 bytes, chunk 1,024, pipeline stage 0 at the
    published widths, bf16, abstract arguments) compile for the described chip: the decode program's EVA core is one
    ``eva_decode`` call a layer, scoped ``eva_core``, and no other operation makes, copies or re-lays-out a buffer the
    shape of a ring or a table (a layer of one, or all of one: both are ``[16, 32, 2,048, 128]`` a layer) — so nothing
    but the kernel, which streams the live tiles alone, reads one whole; the chunk programs read the ring and the table
    a block at a time; every slot buffer is updated in place; and the temporaries sit beside the 11.83 GB held."""
    cfg, decoder, lowered = _eva_lowered(one_chip)
    B, S = 16, 32768
    L, H, d = cfg.num_hidden_layers, cfg.num_attention_heads, cfg.head_dim
    lowered, picked = lowered[program]
    compiled = lowered.compile()
    # one selection a kernel a set of shapes (the queries' and the keys' rotation share theirs); the final chunk's are
    # the chunk's, selected when that was lowered
    once = 0 if program == "chunk_final_core" else 1
    want = {"kernels.rope.picked": once}
    if program == "decode_fn":
        want["kernels.eva_decode.picked"] = 1
    assert {k: v for k, v in picked.items() if v} == {k: v for k, v in want.items() if v}
    text = compiled.as_text()
    held = sum(int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize for spec in decoder.buffer_specs(B, S))
    assert held == 16 * 536_870_912
    memory = compiled.memory_analysis()
    print(f"{_EVA} {program}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB; {picked}")
    # 3.24 GB of weights + 8.59 GB of rings and tables; an intermediate chunk takes neither the final norm nor the head
    assert 11.8e9 < memory.argument_size_in_bytes < 11.9e9
    assert memory.alias_size_in_bytes >= held
    touched = _cache_shaped_ops(text, L, B, H, 2048, d)
    if program == "decode_fn":
        eva = _custom_calls(text, "eva_decode")
        assert len(eva) == L and all("/eva_core/" in line for line in eva), eva[:1]
        assert touched.get("custom-call") == L and set(touched) <= _CACHE_MAY_PASS_THROUGH, touched
        assert memory.temp_size_in_bytes < 0.2e9, memory.temp_size_in_bytes
    else:
        # each layer's rows and summaries go into the four buffers in place, and the blocked attention's loops carry them
        assert touched.get("dynamic-update-slice") == 4 * L, touched
        assert set(touched) <= _CACHE_MAY_PASS_THROUGH | {"dynamic-update-slice", "while"}, touched
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9, memory.temp_size_in_bytes


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


def _train_lowered(one_chip):
    """``TrainStep``'s whole AMP-O2 step at ``gpt2-medium.train``'s shape (b8 s1024; depth cut to one layer: the
    block is compiled once whatever the depth), lowered for the described chip."""
    from paddle_tpu.jit import TrainStep

    step = _train_step(_wide_model(1), lambda m, o, c: TrainStep(m, o, c, amp_level="O2"))
    ids = one_chip((8, 1024), jnp.int32)
    return step._jit.lower(_abstract(step.state, one_chip), ((ids,), (ids,)))


def test_train_step_picks_and_compiles_the_flash_kernels_for_v5e(as_tpu, one_chip):
    """The step compiles with the Pallas attention kernels in it, forward and backward."""
    assert _train_lowered(one_chip).compile().as_text().count("tpu_custom_call") >= 3


_LAYOUTS = {"dp2xmp2": dict(dp=2, mp=2, sdp=1, stage=0), "sharding2xmp2": dict(dp=1, mp=2, sdp=2, stage=2)}


def _distributed_lowered(topo, layout):
    """``fleet.distributed_step`` at the flagship width, lowered for the described four-chip mesh."""
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
        return jitted.lower(state, ((ids,), (ids,)))
    finally:
        fleet._hcg = None


_COMPILED4 = {}    # layout -> the four-chip step's optimized HLO text, compiled once for this file's tests


def _distributed_compiled(topo, layout):
    if layout not in _COMPILED4:
        _COMPILED4[layout] = _distributed_lowered(topo, _LAYOUTS[layout]).compile().as_text()
    return _COMPILED4[layout]


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_distributed_step_compiles_for_a_v5e_2x2_mesh(as_tpu, topo, layout):
    """A Mosaic kernel cannot be partitioned automatically — the compiler's own words are "wrap the call in a
    shard_map" — so the attention kernels must run per shard (batch over dp x sdp, heads over mp) inside the
    GSPMD-partitioned step."""
    assert _distributed_compiled(topo, layout).count("tpu_custom_call") >= 3


# The four-chip step of 7835fc5 (PR 34), compiled as ``_distributed_lowered`` compiles it (one layer at width 1024,
# b8 s1024, AMP O2, sdp 2 x mp 2 at stage 2) and read by ``analysis/hlo.py`` as this PR leaves it: bytes a chip moves a
# step in the all-gathers over the 'sdp' groups — six f32 parameters gathered after the update (66.2 MB) and the
# embedding gradient's bf16 rows (8.4 MB) — and in all all-gathers (with the 25.2 MB of qkv activations over 'mp',
# which no ZeRO placement touches). Its weight gradients: one all-reduce of twelve bf16 tensors over 'sdp', 64.1 MB.
_PARENT_SDP_ALL_GATHER_BYTES, _PARENT_ALL_GATHER_BYTES = 74_596_352, 99_774_464
_SDP_GROUPS = ((0, 2), (1, 3))     # mesh (dp 1, pp 1, sdp 2, mp 2, sep 1): chip = 2 * sdp + mp


def test_zero2_step_gathers_the_cast_and_scatters_the_gradients_on_v5e(as_tpu, topo):
    """What crosses the wire in ``cerebras-gpt-1.3b.train-zero2mp2``'s step, read off the program compiled for the
    described ``v5e:2x2``: every parameter all-gather is of the bf16 cast (none of 1 MiB or more has an f32 result)
    and is named by the cast, none by the optimizer — nothing follows the update; over the 'sdp' groups the
    all-gathers move at most 0.55 of the parent's bytes (0.444; over all groups 0.584, the 'mp' gathers of
    activations being the parent's); and every weight matrix's gradient is reduce-scattered in bf16 to the rank that
    updates it — the chip's fused ``all-reduce-scatter`` — where the parent all-reduced it and sliced: what is still
    all-reduced over 'sdp' is vectors and the position table (2.1 MB of the parent's 64.1)."""
    from paddle_tpu.analysis import hlo

    collectives = hlo.parse_collectives(_distributed_compiled(topo, "sharding2xmp2"))
    gathers = [c for c in collectives if c.kind == "all-gather"]
    wide = [c for c in gathers if c.result_bytes >= 1 << 20 and any(dt == "f32" for dt, _ in c.result_shapes)]
    assert not wide, [c.describe() for c in wide]
    sdp = [c for c in collectives if c.groups == _SDP_GROUPS]
    # (two 6-KB gathers of bias moments over 'mp' sit under ``optimizer`` here as in the parent; they are not ZeRO's)
    assert not [c.describe() for c in gathers if "optimizer" in c.op_name.split("/") and (c in sdp or c.result_bytes >= 1 << 20)]
    params = [c for c in sdp if c.kind == "all-gather"]
    assert all("amp_cast" in c.op_name.split("/") for c in params), [c.op_name for c in params]
    assert sum(hlo.moved_bytes(c) for c in params) <= 0.55 * _PARENT_SDP_ALL_GATHER_BYTES
    assert sum(hlo.moved_bytes(c) for c in gathers) <= 0.6 * _PARENT_ALL_GATHER_BYTES
    # pinned as this PR leaves them: (ops, bytes moved a chip a step) by kind and element type over the 'sdp' groups.
    # reduce-scatter: the table's gradient (25.8 MB), ffn1's, ffn2's, and qkv's with the output projection's (2.1 MB
    # each); all-to-all: the embedding rows' gradient re-laid from batch- to column-sharded for a local scatter-add
    by_type, ops = hlo.moved_bytes_by_type(sdp), {}
    for c in sdp:
        ops[c.kind] = ops.get(c.kind, 0) + 1
    assert (ops, by_type) == (
        {"reduce-scatter": 4, "all-gather": 6, "all-reduce": 2, "all-to-all": 1},
        {"reduce-scatter bf16": 32_047_104, "all-gather bf16": 33_095_680, "all-reduce bf16": 2_120_708,
         "all-to-all bf16": 4_194_304})
    matrices = [dims for c in sdp if c.kind == "all-reduce" for _, dims in c.result_shapes if len(dims) >= 2 and min(dims) >= 512]
    assert matrices == [(1024, 1024)]     # the position table's gradient, a reduce_sum and no dot: the one weight still all-reduced


_TRAIN, _TRAIN4 = "gpt2-medium.train", "cerebras-gpt-1.3b.train-zero2mp2"
_PROGRAMS = ([(cell, name) for cell in sorted(_DECODE) for name in ("decode_fn", "chunk_core", "chunk_final_core", "prefill_core")]
             + [(cell, name) for cell in (_SOLAR, _GIGA, _GRANITE) for name in ("decode_fn", "chunk_core", "chunk_final_core")]
             + [(_TRAIN, "_step"), (_TRAIN4, "_step")])


@pytest.mark.parametrize("cell,program", _PROGRAMS, ids=[f"{c}-{n}" for c, n in _PROGRAMS])
def test_program_is_the_parents_by_fingerprint_and_by_scope(as_tpu, topo, one_chip, cell, program):
    """Each cell's program lowers to the text it lowered to at 094436e (the three hybrid cells': at PR 37, which
    changed the experts' way back to token order in all nine; Solar's and GigaChat's chunk programs: at PR 39, which
    changed the chunkwise delta rule's solve; the GPT serving cells': since ``_serve_block`` forms the qkv product whole
    before its split), and the operations under each
    ``jax.named_scope`` the by-part metrics read are as many as they were: the hash does not see a scope's name, the
    metrics see nothing else. The four-chip cell's step is ``test_distributed_step``'s ``sharding2xmp2`` layout, the
    one-chip train cell's ``test_train_step``'s."""
    if cell in _DECODE:
        lowered = _gpt_serving_lowered(cell, one_chip)[program]
    elif cell == _SOLAR:
        lowered = _solar_lowered(one_chip)[2][program][0]
    elif cell == _GIGA:
        lowered = _giga_lowered(one_chip)[2][program][0]
    elif cell == _GRANITE:
        lowered = _granite_lowered(one_chip)[2][program][0]
    elif cell == _TRAIN:
        lowered = _train_lowered(one_chip)
    else:
        lowered = _distributed_lowered(topo, _LAYOUTS["sharding2xmp2"])
    assert _fingerprint(lowered.as_text()) == _PARENT_PROGRAMS[cell][program]
    assert _scope_counts(lowered) == _PARENT_SCOPES[cell][program]


@pytest.mark.parametrize("cell,program", [(c, p) for c in (_SOLAR, _GIGA) for p in ("chunk_core", "chunk_final_core")],
                         ids=lambda v: v)
def test_a_delta_rule_layer_of_a_chunk_program_loops_over_its_inner_chunks_and_over_nothing_else(as_tpu, one_chip, cell, program):
    """PR 39: the chunkwise delta rule (``ops/delta_rule.py``) inverts its unit lower-triangular matrix by halving, in
    straight-line batched products. Until then every such layer of a chunk program held a second ``while``, of 64 trips
    — a row of forward substitution each — which was 4.9 ms a layer on the chip at GigaChat's 1,024 (head, inner chunk)
    pairs. What loops now is the scan over the 1,024 / 64 inner chunks, once a layer, and in GigaChat's programs the
    latent layer's walk over the blocks of its context, whose trip count is an argument."""
    cfg, _, lowered = (_solar_lowered if cell == _SOLAR else _giga_lowered)(one_chip)
    text = lowered[program][0].as_text()
    linear = cfg.num_hidden_layers - len(cfg.gqa_layers if cell == _SOLAR else cfg.full_attention_layers)
    fixed = [int(n) for n in re.findall(r"stablehlo\.while\(.*\n\s*cond \{\n\s*%\S+ = stablehlo\.constant dense<(\d+)> : tensor<i32>", text)]
    assert fixed == [1024 // 64] * linear, fixed
    assert text.count("stablehlo.while(") == linear + (cell == _GIGA)           # the parent: twice ``linear`` and the same one
