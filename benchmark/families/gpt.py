"""The GPT-2 family: learned positions, pre-LayerNorm blocks, GELU MLP, full
multi-head attention, output head tied to the token embedding.

Three things live here, for every configuration whose file says
``"family": "gpt"``:

1. the builder of the *program's* model (``build_model``) with weights made on
   the device from the seed in one jitted call (``init_weights``);
2. the **plain reference** (``reference_logits`` / ``reference_loss``): the
   forward pass and next-token loss in straight ``jax.numpy``, float32,
   ``jax.default_matmul_precision("highest")``, no kernels, no cache, one
   sequence at a time, layer by layer so that a 1.3B model's float32 weights
   never have to sit beside the live state. It imports nothing from
   ``paddle_tpu.models`` and takes weights as plain arrays in the layout of
   ``weight_shapes``;
3. the functions that give required FLOPs and bytes from shapes, and the
   comparisons that decide ``correct`` (``check_serving``, ``check_training``)
   with their tolerances.

Departures from the published models, also listed in each configuration's
``assumed``: the embedding table has ``padded_vocab_size`` rows (token ids are
drawn below ``vocab_size``; the reference is given the same table), and the
program's MLP always uses the tanh form of GELU, which is what ``gelu_new``
means but not what Cerebras-GPT's ``gelu`` means. The reference computes the
*published* activation, so the program's approximation counts against the
tolerance instead of being hidden by it.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import numpy as np

# Host spans of the program that the trace reduction attributes idle gaps to.
HOST_SPAN_PREFIXES = ("infer.", "train_step.", "bench.")
# Names (parts of them) of the compiled programs in the device trace.
DECODE_PROGRAM = "decode_fn"
PREFILL_PROGRAMS = ("chunk_core", "chunk_final_core", "prefill_core")
TRAIN_PROGRAM = "_step"

STACKED = ("norm1_w", "norm1_b", "qkv_w", "qkv_b", "out_w", "out_b",
           "norm2_w", "norm2_b", "ffn1_w", "ffn1_b", "ffn2_w", "ffn2_b")

# ---- tolerances ------------------------------------------------------------
# Serving, logits: the program computes in bfloat16 (8 significant bits: the
# residual stream is rounded to 2^-9 relative at each of its 48 additions),
# the reference in float32. Measured on the v5e (PR 24, PERF.md Findings):
# relative RMS error of the logits 1.04e-2 for gpt2-medium and 1.06e-2 for
# cerebras-gpt-1.3b, the same to 1% for every seed tried. The limit leaves
# half as much again; a layer left out or a wrong position moves the logits
# by tens of percent. With random weights attention is nearly uniform and
# averages an int8 cache's rounding away (it measured 1.20e-2, inside the
# limit), so the cache has a comparison of its own below.
SERVE_LOGIT_REL_RMS = 1.5e-2
# Serving, the cache itself: the first layer's keys and values as the engine's
# programs left them in the slot's rows, against the reference's. They are one
# LayerNorm and one matmul away from the embeddings, so bfloat16 leaves
# 3.24e-3 relative RMS (measured, both models), and a cache held in int8
# (1/254 of each row's largest magnitude) 6.78e-3 (measured, gpt2-medium).
# The limit sits between the two.
SERVE_CACHE_REL_RMS = 4.5e-3
# A served token must be one the reference cannot tell from its own best:
# within 2^-5 of the row's largest magnitude below the reference's maximum
# (8 bf16 ulps: the engine's rounding, the reference's own, and a tie).
# Measured: 33 of 34 served tokens are the reference's argmax, the other
# 5e-4 below it.
SERVE_TOKEN_TIE = 2.0 ** -5
# Training: the first step's loss under AMP O2 (bf16 matmuls, f32 master
# weights, f32 loss) against the float32 loss on the same weights and batch.
# The loss is a mean over 8,192 tokens of a log-softmax, so bf16 rounding
# mostly averages out: measured 1.6e-6 to 1.9e-5 relative over six seeds of
# gpt2-medium. The limit is ten times the largest of those; how far a dropped
# layer or a wrong mask moves the loss at initialisation was not measured.
TRAIN_LOSS_REL = 2e-4


# ---------------------------------------------------------------- shapes
def dims(config: dict) -> dict:
    d = int(config["n_embd"])
    inner = config.get("n_inner") or 4 * d
    return dict(L=int(config["n_layer"]), D=d, H=int(config["n_head"]), F=int(inner),
                S=int(config["n_positions"]), V=int(config["vocab_size"]),
                Vp=int(config["assumed"]["padded_vocab_size"]))


def weight_shapes(config: dict) -> Dict[str, tuple]:
    z = dims(config)
    L, D, F = z["L"], z["D"], z["F"]
    return {
        "wte": (z["Vp"], D), "wpe": (z["S"], D),
        "norm1_w": (L, D), "norm1_b": (L, D), "qkv_w": (L, D, 3 * D), "qkv_b": (L, 3 * D),
        "out_w": (L, D, D), "out_b": (L, D), "norm2_w": (L, D), "norm2_b": (L, D),
        "ffn1_w": (L, D, F), "ffn1_b": (L, F), "ffn2_w": (L, F, D), "ffn2_b": (L, D),
        "fnw": (D,), "fnb": (D,),
    }


def param_count(config: dict) -> int:
    return int(sum(math.prod(s) for s in weight_shapes(config).values()))


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    the blocks' four matrices and the output head (the tied embedding, at its
    published number of rows). The embedding lookups are not matmuls."""
    z = dims(config)
    return z["L"] * (4 * z["D"] * z["D"] + 2 * z["D"] * z["F"]) + z["V"] * z["D"]


def train_flops_per_token(config: dict, seq: int) -> float:
    """Operations the forward and backward passes *require* per token at
    sequence length ``seq``: 6 per matmul parameter, and causal attention's
    two products (QK^T and AV), each ``seq/2`` keys of width D on average,
    forward plus twice that backward. Nothing recomputed is counted."""
    z = dims(config)
    attention_fwd = z["L"] * 2 * (2 * (seq / 2.0) * z["D"])
    return 6.0 * matmul_params(config) + 3.0 * attention_fwd


def decode_step_bytes(config: dict, live_rows: float, bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read: every weight once (blocks, head and
    final norm; the position table is touched by a row per slot) and the live
    rows of the key and value cache, ``live_rows`` summed over the slots."""
    z = dims(config)
    weights = matmul_params(config) + z["L"] * (9 * z["D"] + z["F"]) + 2 * z["D"]
    cache = 2.0 * z["L"] * z["D"] * live_rows
    return bytes_per_value * (weights + cache)


def kv_row_bytes(config: dict, bytes_per_value: int = 2) -> int:
    z = dims(config)
    return 2 * z["L"] * z["D"] * bytes_per_value


# ---------------------------------------------------------------- weights
def init_weights(config: dict, seed: int, dtype: str, shardings: Optional[dict] = None):
    """Every weight of the model from ``seed``, on the device, in ``dtype``,
    in one jitted call. Matrices, tables and biases are N(0, 0.02); LayerNorm
    scales 1 + N(0, 0.02): biases and scales that are not exactly 0 and 1
    make the comparison with the reference see a dropped bias or scale."""
    import jax

    shardings = None if shardings is None else tuple(sorted(shardings.items()))
    make = _weight_maker(tuple(sorted(weight_shapes(config).items())), str(dtype), shardings)
    return make(jax.random.key(int(seed) % (2 ** 31 - 1)))


@functools.lru_cache(maxsize=None)
def _weight_maker(shapes: tuple, dtype: str, shardings: Optional[tuple]):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            w = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if name in ("norm1_w", "norm2_w", "fnw"):
                w = 1.0 + w
            out[name] = w.astype(dt)
        return out

    return jax.jit(make) if shardings is None else jax.jit(make, out_shardings=dict(shardings))


_PROGRAM_NAMES = {
    "wte": "gpt.embeddings.word_embeddings.weight",
    "wpe": "gpt.embeddings.position_embeddings.weight",
    "fnw": "gpt.final_norm.weight", "fnb": "gpt.final_norm.bias",
    **{n: f"gpt.layers.{n}" for n in STACKED},
}


def build_model(config: dict, seed: int, dtype: str, mesh=None):
    """The program's ``GPTForPretraining`` at the configuration's sizes, its
    parameters replaced by ``init_weights`` (as a checkpoint load would). The
    constructor's own initialisation is switched to constants so that it
    costs nothing. Under a ``mesh`` each weight is made where its parameter's
    ``dist_spec`` puts it, so no chip ever holds the whole model."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.nn import initializer as I

    z = dims(config)
    I.set_global_initializer(I.Constant(0.0), I.Constant(0.0))
    try:
        paddle.seed(int(seed) % (2 ** 31 - 1))
        model = GPTForPretraining(GPTConfig(
            vocab_size=z["Vp"], hidden_size=z["D"], num_layers=z["L"], num_heads=z["H"],
            ffn_hidden_size=z["F"], max_seq_len=z["S"]))
    finally:
        I.set_global_initializer(None, None)
    if dtype != "float32":
        model.astype(dtype)
    params = dict(model.named_parameters())
    shardings = None
    if mesh is not None:
        shardings = {ours: NamedSharding(mesh, getattr(params[theirs], "dist_spec", None) or PartitionSpec())
                     for ours, theirs in _PROGRAM_NAMES.items()}
    weights = init_weights(config, seed, dtype, shardings)
    for ours, theirs in _PROGRAM_NAMES.items():
        params[theirs]._value = weights[ours]
    del weights
    jax.block_until_ready([p._value for p in params.values()])
    return model


def drop_eager_weights(model):
    """Free the eager model's copy of the weights once a compiled step holds
    its own (``fleet.distributed_step`` places fresh buffers): on a sharded
    1.3B model it is 2.6 GB a chip that nothing reads again."""
    import jax.numpy as jnp

    for p in model.parameters():
        p._value = jnp.zeros((), p._value.dtype)


def weights_of_engine(engine) -> dict:
    """The served weights as plain arrays in this file's layout."""
    p = engine._params
    out = dict(zip(STACKED, p["stack"]))
    out.update(wte=p["wte"], wpe=p["wpe"], fnw=p["fnw"], fnb=p["fnb"])
    return out


# ---------------------------------------------------------------- reference
def _gelu(x, activation: str):
    import jax.numpy as jnp
    from jax.scipy.special import erf

    if activation == "gelu_new":   # Hendrycks & Gimpel's tanh form, GPT-2's
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if activation == "gelu":       # the exact form
        return 0.5 * x * (1.0 + erf(x / math.sqrt(2.0)))
    raise ValueError(f"activation {activation!r}")


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _reference_block(x, stacked, layer, *, n_head, eps, activation):
    """One pre-LN block on one sequence ``x`` [s, D], float32. ``stacked``
    holds the [L, ...] arrays; ``layer`` picks one."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    lp = {k: jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False).astype(f32)
          for k, v in stacked.items()}
    s, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, lp["norm1_w"], lp["norm1_b"], eps)
    qkv = (h @ lp["qkv_w"] + lp["qkv_b"]).reshape(s, 3, n_head, hd)   # the program's packing
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v).reshape(s, d)
    x = x + att @ lp["out_w"] + lp["out_b"]
    h = _layer_norm(x, lp["norm2_w"], lp["norm2_b"], eps)
    return x + _gelu(h @ lp["ffn1_w"] + lp["ffn1_b"], activation) @ lp["ffn2_w"] + lp["ffn2_b"]


def _reference_fns(config: dict):
    return _reference_fns_of(dims(config)["H"], float(config.get("layer_norm_epsilon", 1e-5)),
                             config["activation_function"])


@functools.lru_cache(maxsize=None)
def _reference_fns_of(n_head: int, eps: float, activation: str):
    import jax
    import jax.numpy as jnp

    block = jax.jit(functools.partial(_reference_block, n_head=n_head, eps=eps, activation=activation))

    @jax.jit
    def embed(wte, wpe, ids):
        return wte[ids].astype(jnp.float32) + wpe[:ids.shape[0]].astype(jnp.float32)

    @jax.jit
    def head(x, fnw, fnb, wte):
        x = _layer_norm(x, fnw.astype(jnp.float32), fnb.astype(jnp.float32), eps)
        return x @ wte.astype(jnp.float32).T

    return embed, block, head


def reference_first_layer_kv(config: dict, weights: dict, ids):
    """Keys and values [s, H, hd] of the first block for one sequence: what a
    cache has to hold for it, float32."""
    import jax
    import jax.numpy as jnp

    z = dims(config)
    eps = float(config.get("layer_norm_epsilon", 1e-5))
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = weights["wte"][ids].astype(f32) + weights["wpe"][:ids.shape[0]].astype(f32)
        h = _layer_norm(x, weights["norm1_w"][0].astype(f32), weights["norm1_b"][0].astype(f32), eps)
        qkv = h @ weights["qkv_w"][0].astype(f32) + weights["qkv_b"][0].astype(f32)
        qkv = qkv.reshape(ids.shape[0], 3, z["H"], z["D"] // z["H"])
        return qkv[:, 1], qkv[:, 2]


def reference_logits(config: dict, weights: dict, ids):
    """Logits [s, padded vocab] of one sequence of token ids, float32."""
    import jax
    import jax.numpy as jnp

    embed, block, head = _reference_fns(config)
    stacked = {k: weights[k] for k in STACKED}
    with jax.default_matmul_precision("highest"):
        x = embed(weights["wte"], weights["wpe"], jnp.asarray(ids, jnp.int32))
        for layer in range(dims(config)["L"]):
            x = block(x, stacked, jnp.int32(layer))
        return head(x, weights["fnw"], weights["fnb"], weights["wte"])


@functools.lru_cache(maxsize=None)
def _nll():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def nll(logits, lab):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1))

    return nll


def reference_loss(config: dict, weights: dict, inputs, labels) -> float:
    """Mean next-token cross entropy over the batch ``inputs`` [b, s] with
    ``labels`` [b, s] (already shifted), sequence by sequence."""
    import jax.numpy as jnp

    total, count = 0.0, 0
    for row, lab in zip(np.asarray(inputs), np.asarray(labels)):
        total += float(_nll()(reference_logits(config, weights, row), jnp.asarray(lab, jnp.int32)))
        count += len(lab)
    return total / count


# ---------------------------------------------------------------- correct
def check_serving(engine, config: dict, seed: int, n_decode: int = 16) -> dict:
    """Two seeded prompts through the engine's own prefill and ``n_decode``
    decode steps (its compiled programs, its cache), then the logits at each
    of those positions against the reference's full forward.

    The engine hands out tokens, not logits. So the logits are read back from
    what its programs left behind: the slot's rows of the key/value cache,
    attended by the program's own decode forward (``_slot_decode_forward``,
    one slot wide) at each probed position. An int8 cache, a wrong chunk
    boundary or a stale row shows in them. The served tokens themselves must
    each be one the reference rates within ``SERVE_TOKEN_TIE`` of its best."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import _kvc_read, _kvc_slice, _slot_decode_forward

    z = dims(config)
    chunk = engine._chunk or 64
    rng = np.random.default_rng([int(seed), 7])
    lengths = [chunk + chunk // 2 + 7, max(8, chunk // 4 + 3)]   # chunk + final chunk; final chunk alone
    engine.reset()
    prompts = [rng.integers(0, z["V"], (n,)).astype(np.int32) for n in lengths]
    served = []
    for slot, prompt in enumerate(prompts):
        first, _ = engine.prefill(prompt, slot, max_new_tokens=n_decode + 2)
        served.append([int(first)])
    for _ in range(n_decode):
        toks, emitted, _ = engine.decode_step(fuse=1)
        for slot in range(len(prompts)):
            if np.atleast_2d(emitted)[0, slot]:
                served[slot].append(int(np.atleast_2d(toks)[0, slot]))

    shape = engine._shape
    seg = (shape[0], 1, shape[2], shape[3], shape[4])
    dt = engine._params["wte"].dtype

    # every array is an argument: one closed over would be baked into the
    # executable (2.6 GB of it for the 1.3B model)
    @jax.jit
    def probe(params, idx, ck, cv, slot, tok, pos):
        k1 = _kvc_slice(ck, (0, slot, 0, 0, 0), seg)
        v1 = _kvc_slice(cv, (0, slot, 0, 0, 0), seg)
        logits, _, _ = _slot_decode_forward(
            (tuple(params["stack"]), idx), params["wte"], params["wpe"], params["fnw"], params["fnb"],
            tok[None], k1, v1, pos[None], num_heads=z["H"], active=jnp.ones((1,), bool))
        return logits[0].astype(jnp.float32)

    @jax.jit
    def first_layer_rows(ck, cv, slot):
        one = (1, 1, shape[2], shape[3], shape[4])
        k = _kvc_read(_kvc_slice(ck, (0, slot, 0, 0, 0), one), dt)
        v = _kvc_read(_kvc_slice(cv, (0, slot, 0, 0, 0), one), dt)
        return k[0, 0].astype(jnp.float32), v[0, 0].astype(jnp.float32)     # [H, S, hd]

    weights = weights_of_engine(engine)
    worst_rms, worst_max, worst_tie, worst_cache, agree, rows = 0.0, 0.0, 0.0, 0.0, 0, 0
    for slot, (prompt, toks) in enumerate(zip(prompts, served)):
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        n = len(prompt)
        want = np.asarray(reference_logits(config, weights, seq))[n - 1:]   # all rows of the table: both sides hold the same
        got = np.stack([np.asarray(probe(engine._params, engine._idx, engine._ck, engine._cv, jnp.int32(slot),
                                         jnp.int32(seq[j]), jnp.int32(j)))
                        for j in range(n - 1, len(seq))])
        worst_rms = max(worst_rms, float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))))
        worst_max = max(worst_max, float(np.abs(got - want).max() / np.abs(want).max()))
        for row, tok in zip(want, toks):
            worst_tie = max(worst_tie, float((row.max() - row[tok]) / np.abs(row).max()))
            agree += int(np.argmax(row) == tok)
            rows += 1
        # the first layer's keys and values as the engine's programs left them in the cache
        k_want, v_want = (np.asarray(a) for a in reference_first_layer_kv(config, weights, seq))   # [s, H, hd]
        k_got, v_got = (np.asarray(a)[:, :len(seq)].transpose(1, 0, 2) for a in
                        first_layer_rows(engine._ck, engine._cv, jnp.int32(slot)))
        for g, w in ((k_got, k_want), (v_got, v_want)):
            worst_cache = max(worst_cache, float(np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2))))
    engine.reset()
    finite = bool(np.isfinite(worst_rms) and np.isfinite(worst_max) and np.isfinite(worst_cache))
    return {"correct": bool(finite and worst_rms <= SERVE_LOGIT_REL_RMS and worst_tie <= SERVE_TOKEN_TIE
                            and worst_cache <= SERVE_CACHE_REL_RMS),
            "logit_rel_rms": worst_rms, "logit_rel_max": worst_max, "limit_rel_rms": SERVE_LOGIT_REL_RMS,
            "cache_rel_rms": worst_cache, "limit_cache_rel_rms": SERVE_CACHE_REL_RMS,
            "token_below_best": worst_tie, "limit_token": SERVE_TOKEN_TIE,
            "tokens_equal_reference_argmax": agree, "positions": rows, "prompt_lengths": lengths}


def check_training(config: dict, seed: int, inputs, labels, first_loss: float, losses) -> dict:
    """The first step's loss against the reference's loss on the same weights
    (made again from the seed: the step has updated its own in place) and
    batch; and every loss of the run finite."""
    weights = init_weights(config, seed, "float32")
    want = reference_loss(config, weights, inputs, labels)
    del weights
    rel = abs(first_loss - want) / abs(want)
    finite = bool(np.all(np.isfinite(np.asarray(losses, np.float64))))
    return {"correct": bool(finite and rel <= TRAIN_LOSS_REL), "first_loss": float(first_loss),
            "reference_loss": float(want), "rel_diff": float(rel), "limit_rel": TRAIN_LOSS_REL,
            "losses_finite": finite, "sequences": int(np.asarray(inputs).shape[0])}
