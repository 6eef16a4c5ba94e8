"""The Solar Open 2 family (``model_type: solar_open2``): pre-norm residual
layers, RMSNorm, no biases, no positions, an untied embedding and head. Layer
*i* mixes by gated softmax attention over grouped key/value heads when *i* is
in ``gqa_layers`` and otherwise by linear attention (the gated delta rule with
per-channel decay, Kimi Delta Attention, arXiv:2510.26692); every layer then
routes each token to ``num_experts_per_tok`` of ``n_routed_experts`` gated
experts, dropless, and adds one shared expert. Serving only.

For every configuration whose file says ``"family": "solar_open2"``:

1. ``build_model``: the program's model with weights made on the device from
   the seed;
2. the **plain reference** (``reference_forward`` / ``reference_logits`` and the
   three layer functions): straight ``jax.numpy``, float32,
   ``jax.default_matmul_precision("highest")``, one sequence, the linear layers
   token by token, a Python loop over the experts that were chosen, no cache,
   nothing imported from ``paddle_tpu.models`` or ``paddle_tpu.ops``. It takes
   weights as plain arrays in the layout of ``weight_shapes`` and is told what
   it holds by ``dims``: how many heads, which experts (``held``), how many
   rows of the vocabulary. Given the whole model's weights it is the whole
   model; given a share's (``share_dims`` / ``share_weights``) it is that
   chip's partial result, which is what the program computes;
3. required bytes of a decode step, and ``check_serving`` with its limits.

The layout the reference reads (the program's, ``SolarOpen2Config.weight_shapes``):
``attn_kv`` is keys then values, ``[D, 2, Hkv, d]`` flattened; ``lin_qkv`` and
``lin_conv`` are q, k, v, ``[.., 3, H, d]`` flattened; ``lin_conv [K, C]`` holds
tap ``K - 1`` for the current token and tap 0 for the oldest;
``experts_gate_up`` and ``shared_gate_up`` are gate then up. Query head ``h``
attends key/value head ``h // (Hq / Hkv)``.

Not in the source's config and assumed (each also in the configuration's
``assumed``): sigmoid scores with no groups and no selection bias; low-rank
width ``head_dim`` for the decay's and the output gate's projections; no
QK-norm in the GQA layers; no convolution bias; L2 normalisation as
``x / sqrt(sum x^2 + 1e-6)``; ``A_log = log U(1, 16)`` and ``dt_bias =
softplus^-1(U(1e-3, 0.1))`` from the seed; state and gates in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import numpy as np

HOST_SPAN_PREFIXES = ("infer.", "bench.")
DECODE_PROGRAM = "decode_fn"
CHUNK_PROGRAMS = ("chunk_core", "chunk_final_core")
PREFILL_PROGRAMS = CHUNK_PROGRAMS + ("prefill_core",)
SCOPES_OF_PROGRAM = {DECODE_PROGRAM: "infer/decode", "chunk_core": "infer/prefill_chunk",
                     "chunk_final_core": "infer/prefill_final"}
# ``jax.named_scope`` names of ``models/solar_open2.py`` -> the part a metric
# reports. The router belongs to the routed path; ``mlp`` is the shared
# expert, which every chip of the group computes alike; ``embed`` goes with
# the head; the cache scopes are the GQA layer's lax path.
PART_OF_SCOPE = {"attn_qkv": "attn", "attn_core": "attn", "attn_out": "attn", "cache_write": "attn", "cache_read": "attn",
                 "linear_proj": "linear", "linear_core": "linear", "linear_out": "linear",
                 "moe_router": "routed", "moe_routed": "routed", "moe_shared": "mlp",
                 "norm": "norm", "head_loss": "head_loss", "embed": "head_loss",
                 # XLA rewrites ``lax.ragged_dot`` into its own grouped-matmul kernel and that kernel's metadata op,
                 # and names both after itself (``op_name="ragged-dot-none"``, ``"ragged-dot-metadata"``): the scope
                 # path is gone, the first word is what is left to go by. Only the routed experts call it.
                 "ragged": "routed"}

# ---- limits of ``check_serving`` (readings: my chip runs, PR 30, eight seeds; PERF.md §6) ----
# Each reading is taken with the reference following the program's choice of
# experts (``check_serving`` says why), at the published widths, bfloat16
# weights and activations, float32 state and gates. The control is the same
# program with the state *held* in bfloat16 (``serving.state_dtype``).
#
# Logits of the program's decode forward against the float32 reference,
# relative RMS over 17 positions of a prompt: 1.70e-2 to 1.75e-2 (a dense
# bfloat16 model of 24 layers reads 1.0e-2 to 1.1e-2; here four layers of
# experts round a gated product to bfloat16 between their two projections).
# Half as much again; a dropped layer or a wrong gate moves them by tens of
# percent (the CPU suite plants two). The control reads 1.65e-2 to 1.83e-2:
# this limit cannot see it.
SERVE_LOGIT_REL_RMS = 2.6e-2
# The first GQA layer's cached keys and values against the reference's: one
# RMSNorm and one matmul from the embeddings: 2.35e-3 to 2.37e-3. Half as much
# again (the GPT family's int8 cache, a quarter of the bits, read 6.8e-3).
SERVE_CACHE_REL_RMS = 3.5e-3
# The first linear layer's state matrix after the last decode step against the
# reference's token-by-token recurrence (1,559 and 275 tokens): 1.52e-2 to
# 1.67e-2 — the layer's *inputs*, which have a whole bfloat16 layer behind
# them, not the state's own arithmetic. Half as much again. A state that
# admission did not zero, or beta without its factor 2, moves it by tens of
# percent. The control reads 1.56e-2 to 1.72e-2: at these decays (memory of
# tens of tokens) a bfloat16 state's rounding adds 0.4e-2 in quadrature, under
# the inputs' 1.5e-2, so no limit on this number can tell the two apart.
SERVE_STATE_REL_RMS = 2.5e-2
# Hence a reading of the precision the state is held in, on the state itself:
# the share of its elements that are exactly bfloat16 numbers (low 16 bits of
# the float32 zero). A float32 state reads 4e-5 to 6e-5 (2^-16 and the odd
# zero); the control reads 1.0 and fails here, and only here.
SERVE_STATE_ON_BF16_GRID = 0.5
# A served token must be one the reference rates within 2^-5 of the row's
# largest magnitude below its best (the GPT family's margin). Readings 0 to
# 1.10e-2 (32 to 34 of 34 served tokens are the reference's argmax).
SERVE_TOKEN_TIE = 2.0 ** -5
# The reference follows the program's choice of experts where its own eighth
# and ninth scores tie within the program's rounding: the lowest sigmoid score
# among the program's eight may lie this far under the reference's own eighth
# best. Readings 5.4e-3 to 8.6e-3 (the worst of 7,336 rows x 4 layers a run;
# a bfloat16 router input moves a score by about 2e-3). Twice that; an expert
# picked by a wrong rule lies 0.1 to 0.5 under.
SERVE_ROUTING_TIE = 2.0e-2


# ---------------------------------------------------------------- shapes
def dims(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file: head counts,
    ``V`` and ``held`` are what is held *here*; ``E`` is the router's width
    (the published count where ``n_routed_experts`` is reduced)."""
    lin = config["linear_attn_config"]
    E = int(config.get("published", {}).get("n_routed_experts", config["n_routed_experts"]))
    held = config.get("held_experts") or [0, int(config["n_routed_experts"])]
    return dict(D=int(config["hidden_size"]), L=int(config["num_hidden_layers"]),
                gqa=tuple(int(i) for i in config["gqa_layers"]),
                Hq=int(config["num_attention_heads"]), Hkv=int(config["num_key_value_heads"]), d=int(config["head_dim"]),
                Hl=int(lin["num_heads"]), dl=int(lin["head_dim"]), K=int(lin["short_conv_kernel_size"]),
                V=int(config["vocab_size"]), F=int(config["moe_intermediate_size"]), E=E,
                held=(int(held[0]), int(held[1])), shared=int(config["n_shared_experts"]),
                top_k=int(config["num_experts_per_tok"]), norm_topk=bool(config["norm_topk_prob"]),
                scale=float(config["routed_scaling_factor"]), eps=float(config["rms_norm_eps"]),
                R=int(config.get("assumed", {}).get("low_rank", lin["head_dim"])),
                neg_eigval=bool(config["kda_allow_neg_eigval"]))


def weight_shapes(config: dict) -> Dict[str, tuple]:
    z = dims(config)
    D, L, F, R, K = z["D"], z["L"], z["F"], z["R"], z["K"]
    Lg, Ll = len(z["gqa"]), z["L"] - len(z["gqa"])
    q, kv, lin = z["Hq"] * z["d"], z["Hkv"] * z["d"], z["Hl"] * z["dl"]
    return {
        "embed": (z["V"], D), "head": (z["V"], D), "final_norm": (D,),
        "norm1": (L, D), "norm2": (L, D), "router": (L, D, z["E"]),
        "experts_gate_up": (L, z["held"][1], D, 2 * F), "experts_down": (L, z["held"][1], F, D),
        "shared_gate_up": (L, D, 2 * F * z["shared"]), "shared_down": (L, F * z["shared"], D),
        "attn_q": (Lg, D, q), "attn_kv": (Lg, D, 2 * kv), "attn_gate": (Lg, D, q), "attn_out": (Lg, q, D),
        "lin_qkv": (Ll, D, 3 * lin), "lin_conv": (Ll, K, 3 * lin), "lin_f_down": (Ll, D, R), "lin_f_up": (Ll, R, lin),
        "lin_dt_bias": (Ll, lin), "lin_a_log": (Ll, z["Hl"]), "lin_beta": (Ll, D, z["Hl"]),
        "lin_g_down": (Ll, D, R), "lin_g_up": (Ll, R, lin), "lin_out_norm": (Ll, z["dl"]), "lin_out": (Ll, lin, D),
    }


def param_count(config: dict) -> int:
    return int(sum(math.prod(s) for s in weight_shapes(config).values()))


def share_dims(z: dict, share: int, shares: int) -> dict:
    """``dims`` of share ``share`` of ``shares`` equal shares of the model
    ``z``: its heads, its experts, its rows of the vocabulary."""
    first, count = z["held"]
    return dict(z, Hq=z["Hq"] // shares, Hkv=z["Hkv"] // shares, Hl=z["Hl"] // shares, V=z["V"] // shares,
                held=(first + share * (count // shares), count // shares))


def share_weights(z: dict, w: dict, share: int, shares: int) -> dict:
    """The weights share ``share`` holds of the whole model's ``w``: its heads'
    columns of the projections and rows of the output projections, its
    experts, its rows of the vocabulary; what every chip computes alike (the
    norms, the router, the shared expert, the low-rank down projections) whole."""
    def heads(a, axis, groups, n_heads):
        """Slice the heads of this share out of ``axis``, laid out ``[groups, heads, d]``."""
        a = np.asarray(a)
        shape = a.shape
        d = shape[axis] // (groups * n_heads)
        a = a.reshape(shape[:axis] + (groups, n_heads, d) + shape[axis + 1:])
        per = n_heads // shares
        a = np.take(a, range(share * per, (share + 1) * per), axis=axis + 1)
        return a.reshape(shape[:axis] + (groups * per * d,) + shape[axis + 1:])

    rows = z["V"] // shares
    per_e = z["held"][1] // shares
    out = {k: np.asarray(v) for k, v in w.items()}
    out["embed"], out["head"] = out["embed"][share * rows:(share + 1) * rows], out["head"][share * rows:(share + 1) * rows]
    for k in ("experts_gate_up", "experts_down"):
        out[k] = out[k][:, share * per_e:(share + 1) * per_e]
    out["attn_q"], out["attn_gate"] = heads(w["attn_q"], 2, 1, z["Hq"]), heads(w["attn_gate"], 2, 1, z["Hq"])
    out["attn_kv"], out["attn_out"] = heads(w["attn_kv"], 2, 2, z["Hkv"]), heads(w["attn_out"], 1, 1, z["Hq"])
    out["lin_qkv"], out["lin_conv"] = heads(w["lin_qkv"], 2, 3, z["Hl"]), heads(w["lin_conv"], 2, 3, z["Hl"])
    for k in ("lin_f_up", "lin_g_up"):
        out[k] = heads(w[k], 2, 1, z["Hl"])
    out["lin_dt_bias"], out["lin_out"] = heads(w["lin_dt_bias"], 1, 1, z["Hl"]), heads(w["lin_out"], 1, 1, z["Hl"])
    per_h = z["Hl"] // shares
    out["lin_a_log"] = out["lin_a_log"][:, share * per_h:(share + 1) * per_h]
    out["lin_beta"] = out["lin_beta"][:, :, share * per_h:(share + 1) * per_h]
    return out


# ---------------------------------------------------------------- the program's model
def build_model(config: dict, seed: int, dtype: str, mesh=None):
    """The program's model at the configuration's sizes with weights made on
    the device from the seed, in ``dtype`` (the decay's parameters float32).
    Where the file has ``serving.state_dtype`` (the check's control: a state
    held in bfloat16) the program's configuration reads it."""
    from paddle_tpu.models.solar_open2 import SolarOpen2Config, SolarOpen2ForCausalLM

    if mesh is not None:
        raise NotImplementedError("the solar_open2 family serves on one chip: no mesh")
    return SolarOpen2ForCausalLM(SolarOpen2Config.from_config_file(config), seed=seed, dtype=dtype)


def weights_of_engine(engine) -> dict:
    """The served weights as plain arrays in ``weight_shapes``' layout."""
    return dict(engine._params)


# ---------------------------------------------------------------- reference
def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def reference_gqa(z: dict, lw: dict, x):
    """The gated GQA mixer on one sequence ``x [s, D]`` (already normalised):
    ``(y [s, D], k [s, Hkv, d], v [s, Hkv, d])``."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    Hq, Hkv, d = z["Hq"], z["Hkv"], z["d"]
    q = (x @ lw["attn_q"]).reshape(s, Hq, d)
    kv = (x @ lw["attn_kv"]).reshape(s, 2, Hkv, d)
    k, v = kv[:, 0], kv[:, 1]
    group = Hq // Hkv
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, group, axis=1)) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), jnp.repeat(v, group, axis=1)).reshape(s, Hq * d)
    return (att * jax.nn.sigmoid(x @ lw["attn_gate"])) @ lw["attn_out"], k, v


def reference_linear(z: dict, lw: dict, x, state_after: Optional[int] = None):
    """The linear mixer on one sequence ``x [s, D]``, the recurrence token by
    token from an empty state: ``(y [s, D], S [H, d, d])`` with ``S`` the state
    after ``state_after`` tokens (default: all)."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    H, d, K = z["Hl"], z["dl"], z["K"]
    padded = jnp.concatenate([jnp.zeros((K - 1, 3 * H * d), jnp.float32), x @ lw["lin_qkv"]], axis=0)
    conv = sum(padded[j:j + s] * lw["lin_conv"][j] for j in range(K))
    q, k, v = (a[:, 0] for a in jnp.split(jax.nn.silu(conv).reshape(s, 3, H, d), 3, axis=1))
    l2 = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = l2(q) * d ** -0.5, l2(k)
    dt = jax.nn.softplus((x @ lw["lin_f_down"]) @ lw["lin_f_up"] + lw["lin_dt_bias"]).reshape(s, H, d)
    alpha = jnp.exp(-jnp.exp(lw["lin_a_log"])[None, :, None] * dt)                   # (0, 1)^d a head
    beta = (2.0 if z["neg_eigval"] else 1.0) * jax.nn.sigmoid(x @ lw["lin_beta"])    # [s, H]
    keep_at = s if state_after is None else int(state_after)

    def token(carry, xs):
        S, kept = carry
        t, q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, :, None] * S                                                      # Diag(alpha) S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return (S, jnp.where(t + 1 == keep_at, S, kept)), jnp.einsum("hkv,hk->hv", S, q_t)

    zero = jnp.zeros((H, d, d), jnp.float32)
    (_, kept), o = jax.lax.scan(token, (zero, zero), (jnp.arange(s), q, k, v, alpha, beta))
    o = _rms(o, lw["lin_out_norm"], z["eps"]).reshape(s, H * d)
    return (o * jax.nn.sigmoid((x @ lw["lin_g_down"]) @ lw["lin_g_up"])) @ lw["lin_out"], kept


def _gated(x, w_gate_up, w_down):
    import jax

    h = x @ w_gate_up
    f = w_down.shape[0]
    return (jax.nn.silu(h[:, :f]) * h[:, f:]) @ w_down


def reference_moe(z: dict, lw: dict, x, shared: bool = True, chosen=None):
    """The expert layer on rows ``x [s, D]``: every expert of the router is
    scored, the ``top_k`` largest chosen and their weights normalised; the
    experts held here (``z["held"]``) that some token chose add their part,
    one at a time; the shared expert is added once (``shared``). Returns
    ``(y [s, D], shortfall)``.

    ``chosen [s, k]`` is another's word on which experts each row takes (the
    program's, computed in bfloat16, where the eighth and ninth scores of 320
    lie within its rounding of each other for a few rows in a hundred): the
    reference then scores with its own router, takes *those* experts with its
    own scores as weights, and reports as ``shortfall`` how far the lowest of
    them lies under its own ``top_k``-th best score (0 where the choices
    agree). The caller holds that to a limit: a tie may fall either way, a
    wrong router may not."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    scores = jax.nn.sigmoid(x @ lw["router"])
    w, idx = jax.lax.top_k(scores, z["top_k"])
    shortfall = 0.0
    if chosen is not None:
        kth = w[:, -1:]
        idx = jnp.asarray(chosen, jnp.int32)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        shortfall = float(jnp.max(jnp.maximum(kth - w, 0.0)))
    if z["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * z["scale"]
    first, count = z["held"]
    out = jnp.zeros_like(x)
    for e in np.unique(np.asarray(idx)):
        if first <= e < first + count:
            w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            out = out + w_e[:, None] * _gated(x, lw["experts_gate_up"][e - first].astype(f32),
                                              lw["experts_down"][e - first].astype(f32))
    if shared:
        out = out + _gated(x, lw["shared_gate_up"].astype(f32), lw["shared_down"].astype(f32))
    return out, shortfall


def _layer_weights(weights: dict, prefix, i: int, cast=True) -> dict:
    import jax.numpy as jnp

    names = [k for k in weights if k.startswith(prefix)]
    return {k: (jnp.asarray(weights[k][i], jnp.float32) if cast else weights[k][i]) for k in names}


def reference_forward(config_or_dims, weights: dict, ids, rows_from: int = 0, state_after: Optional[int] = None,
                      routing=None) -> dict:
    """One sequence through the model: ``logits [s - rows_from, V]`` (float32)
    of the rows from ``rows_from``, the first GQA layer's keys and values
    ``[s, Hkv, d]`` and the first linear layer's state after ``state_after``
    tokens; with ``routing [L, s, k]`` (``reference_moe``'s ``chosen``, a
    layer) also ``routing_shortfall``, the worst over layers and rows. Layer by layer, a layer's weights cast to float32 only while it
    runs and the experts one at a time, so a share at the published widths
    fits beside the served model."""
    import jax
    import jax.numpy as jnp

    z = config_or_dims if "gqa" in config_or_dims else dims(config_or_dims)
    f32 = jnp.float32
    out = {"k": None, "v": None, "state": None, "routing_shortfall": 0.0}
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(weights["embed"])[jnp.asarray(ids, jnp.int32)].astype(f32)
        gi = li = 0
        for layer in range(z["L"]):
            x = _rms(h, jnp.asarray(weights["norm1"][layer], f32), z["eps"])
            if layer in z["gqa"]:
                y, k, v = reference_gqa(z, _layer_weights(weights, "attn_", gi), x)
                if gi == 0:
                    out["k"], out["v"] = k, v
                gi += 1
            else:
                y, state = reference_linear(z, _layer_weights(weights, "lin_", li), x, state_after)
                if li == 0:
                    out["state"] = state
                li += 1
            h = h + y
            x = _rms(h, jnp.asarray(weights["norm2"][layer], f32), z["eps"])
            lw = {"router": jnp.asarray(weights["router"][layer], f32),
                  **_layer_weights(weights, ("experts_", "shared_"), layer, cast=False)}
            y, shortfall = reference_moe(z, lw, x, chosen=None if routing is None else routing[layer])
            out["routing_shortfall"] = max(out["routing_shortfall"], shortfall)
            h = h + y
        h = _rms(h[rows_from:], jnp.asarray(weights["final_norm"], f32), z["eps"])
        out["logits"] = h @ jnp.asarray(weights["head"], f32).T
    return out


def reference_logits(config: dict, weights: dict, ids):
    """Logits ``[s, V]`` of one sequence of token ids, float32."""
    return reference_forward(config, weights, ids)["logits"]


# ---------------------------------------------------------------- required bytes
def _decode_spans(records, indices):
    """The program's ``infer.decode_step`` spans that carry the routed
    experts' counters and ended inside ticks ``indices`` (a contiguous run)."""
    if not indices:
        return []
    try:
        from paddle_tpu.observability import spans
    except ImportError:
        return []
    lo = records.tick_end[indices[0] - 1] if indices[0] > 0 else 0.0
    found = spans.recent(since_ns=int(lo * 1e9), until_ns=int(records.tick_end[indices[-1]] * 1e9))
    return [s for s in found if s.name == "infer.decode_step" and s.attrs and "experts_hit" in s.attrs]


def experts_hit_per_step(records, traced: bool = True):
    """Mean over the traced ticks' (or the window's) decode steps of the held
    experts that at least one token chose, summed over the layers; None where
    the program reported none."""
    ticks = records.in_trace(records.tick_end) if traced else records.inside(records.tick_end)
    hits = [s.attrs["experts_hit"] for s in _decode_spans(records, ticks)]
    return sum(hits) / len(hits) if hits else None


def expert_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """One expert's weights: gate, up and down."""
    z = dims(config)
    return 3 * z["D"] * z["F"] * bytes_per_value


def routed_step_bytes(config: dict, records, bytes_per_value: int = 2):
    """Bytes the routed path of one decode step has to read: every layer's
    router and the weights of the experts its tokens hit (counted by the
    program in the traced ticks). None where nothing was counted."""
    z = dims(config)
    hit = experts_hit_per_step(records)
    if hit is None:
        return None
    return hit * expert_bytes(config, bytes_per_value) + z["L"] * z["D"] * z["E"] * bytes_per_value


def decode_step_bytes(config: dict, live_rows: float, records=None, bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read (and, for the state, write): the
    weights outside the routed experts, the head, the live rows of the GQA
    layers' cache, the decoding slots' recurrent state read and written, and
    the weights of the experts hit in the traced ticks. Where the program
    counted nothing (no ``records``), every held expert counts."""
    z = dims(config)
    D, F = z["D"], z["F"]
    Lg, Ll = len(z["gqa"]), z["L"] - len(z["gqa"])
    q, kv, lin = z["Hq"] * z["d"], z["Hkv"] * z["d"], z["Hl"] * z["dl"]
    gqa = D * q * 3 + D * 2 * kv
    linear = D * 3 * lin + z["K"] * 3 * lin + 2 * D * z["R"] + 2 * z["R"] * lin + D * z["Hl"] + lin * D
    per_layer = 2 * D + 3 * D * F * z["shared"]
    weights = Lg * gqa + Ll * linear + z["L"] * per_layer + z["V"] * D + D
    rows = 2.0 * Lg * kv * live_rows
    decoding = None if records is None else \
        _mean([records.tick_decoding[i] for i in records.in_trace(records.tick_end) if records.tick_decoding[i]])
    if decoding is None:
        decoding = int(config["serving"]["slots"])
    state = 2.0 * decoding * Ll * (z["Hl"] * z["dl"] * z["dl"] * 4 + (z["K"] - 1) * 3 * lin * bytes_per_value)
    routed = None if records is None else routed_step_bytes(config, records, bytes_per_value)
    if routed is None:
        routed = z["L"] * (z["held"][1] * expert_bytes(config, bytes_per_value) + D * z["E"] * bytes_per_value)
    return bytes_per_value * (weights + rows) + state + routed


def _mean(values):
    return sum(values) / len(values) if values else None


# ---------------------------------------------------------------- correct
def check_serving(engine, config: dict, seed: int, n_decode: int = 16) -> dict:
    """Two seeded prompts (a chunk and a final chunk; a final chunk alone)
    through the engine's own prefill and ``n_decode`` decode steps, on slots
    the window's traffic has used (so a state that admission did not zero
    shows).

    A recurrent state cannot be probed after the fact, so before each decode
    step — and once after the last — the program's own decode forward runs one
    slot wide on a copy of that slot's buffers and gives the logits of the
    token about to be consumed: ``n_decode + 1`` positions a prompt, compared
    with the reference's full forward over the share (relative RMS). Each
    served token must be within ``SERVE_TOKEN_TIE`` of the reference's best.
    And the state itself is compared: the first GQA layer's rows of keys and
    values, and the first linear layer's matrix state after the last step,
    against the reference's.

    **Routing.** With seeded weights a router's eighth and ninth scores of 320
    lie within bfloat16 rounding of each other for several rows in a hundred,
    and a row that takes another expert than the reference's is off by that
    expert's whole contribution — in its logits, and through the state and
    the cached rows in every later row's (readings without what follows:
    logits 2 % to 8 %, state 1.5 % to 4 %, whatever the state's precision).
    So the reference is told which experts the program took — the prompt's
    rows from the program's chunk forward replayed on a scratch slot with the
    engine's own chunking (``chunk_routing``), the decoded rows from the probe
    (``decode_probe``) — scores them with its own router, weighs them with its
    own scores, and reports how far the lowest lies under its own eighth best
    (``routing_below_kth``, held to ``SERVE_ROUTING_TIE``): a tie may fall
    either way, a wrong router may not. What is left is precision."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import solar_open2 as program

    z = dims(config)
    chunk = engine._chunk or 64
    rng = np.random.default_rng([int(seed), 7])
    lengths = [chunk + chunk // 2 + 7, max(8, chunk // 4 + 3)]
    engine.reset()
    prompts = [rng.integers(0, z["V"], (n,)).astype(np.int32) for n in lengths]
    dec, specs, cfg = engine._dec, engine._specs, engine._dec.cfg

    # Both run at the engine's own shapes — every slot's buffers, the engine's batch — so that what they compute
    # is, op for op, what the engine's programs computed: a forward one slot wide rounds a matmul's sums in
    # another order, and one element of 4,096 a token lands on the other side of a bfloat16 rounding.
    @jax.jit
    def probe(params, cache, tok, pos, active):
        logits, experts = program.decode_probe(cfg, params, cache, tok, pos, active)
        return logits[:len(prompts)].astype(jnp.float32), experts[:, :len(prompts)]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def replay(params, cache, ids, slot, start, n_valid):
        return program.chunk_routing(cfg, params, cache, ids, slot, start, n_valid)

    def prompt_routing(cache, prompt, slot):
        """Which experts the program's chunk forward takes for each row of the prompt, ``[L, n, k]``: the engine's
        chunking replayed into ``slot`` of a copy of the engine's buffers."""
        n = len(prompt)
        padded = -(-n // chunk) * chunk
        ids = np.zeros((padded,), np.int32)
        ids[:n] = prompt
        parts = []
        for start in range(0, padded, chunk):
            cache, experts = replay(engine._params, cache, jnp.asarray(ids[start:start + chunk]), jnp.int32(slot),
                                    jnp.int32(start), jnp.int32(min(chunk, n - start)))
            parts.append(np.asarray(experts))
        return cache, np.concatenate(parts, axis=1)[:, :n]

    scratch = jax.tree_util.tree_map(jnp.copy, engine._cache)
    routing_of_prompt = []
    for slot, prompt in enumerate(prompts):
        scratch, experts = prompt_routing(scratch, prompt, slot)
        routing_of_prompt.append(experts)
    del scratch

    served, probed, probed_routing = [], [[] for _ in prompts], [[] for _ in prompts]
    for slot, prompt in enumerate(prompts):
        first, _ = engine.prefill(prompt, slot, max_new_tokens=n_decode + 4)
        served.append([int(first)])

    def probe_all():
        # the engine's own buffers and slot state, as its next decode program will take them
        logits, experts = probe(engine._params, engine._cache, engine._tok, engine._pos, engine._active)
        logits, experts = np.asarray(logits), np.asarray(experts)
        for slot in range(len(prompts)):
            probed[slot].append(logits[slot])
            probed_routing[slot].append(experts[:, slot])

    for _ in range(n_decode):
        probe_all()
        toks, emitted, _ = engine.decode_step(fuse=1)
        for slot in range(len(prompts)):
            if np.atleast_2d(emitted)[0, slot]:
                served[slot].append(int(np.atleast_2d(toks)[0, slot]))
    probe_all()

    weights = weights_of_engine(engine)
    # buffers 0, 1: the GQA layers' keys and values [Lg, B, ...]; buffer 2: the first linear layer's state [B, ...]
    k_cache, v_cache = (np.asarray(engine._cache[i][0, :len(prompts)].astype(jnp.float32)) for i in range(2))
    state_cache = np.asarray(engine._cache[2][:len(prompts)].astype(jnp.float32))
    # how much of the state is exactly a bfloat16 number: 2^-16 of a float32 state, all of one held in bfloat16
    on_bf16_grid = float(np.mean((state_cache.view(np.uint32) & 0xFFFF) == 0))
    worst_max = worst_tie = worst_cache = worst_routing = 0.0
    agree = rows = 0
    by_prompt, by_position, state_by_prompt = [], [], []      # logits of a prompt's positions together; each alone; the state
    rel = lambda g, w: float(np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2)))  # noqa: E731
    for slot, (prompt, toks) in enumerate(zip(prompts, served)):
        n = len(prompt)
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])           # the last token is probed, not consumed
        written = len(seq) - 1                                              # tokens the slot's state has taken in
        routing = np.concatenate([routing_of_prompt[slot], np.stack(probed_routing[slot], axis=1)], axis=1)   # [L, s, k]
        ref = reference_forward(z, weights, seq, rows_from=n - 1, state_after=written, routing=routing)
        worst_routing = max(worst_routing, ref["routing_shortfall"])
        want = np.asarray(ref["logits"])                                    # positions n-1 .. n+len(toks)-1
        got = np.stack(probed[slot])                                        # positions n .. n+len(toks)-1
        by_prompt.append(rel(got, want[1:]))
        by_position.extend(rel(g, w) for g, w in zip(got, want[1:]))
        worst_max = max(worst_max, float(np.abs(got - want[1:]).max() / np.abs(want[1:]).max()))
        for row, tok in zip(want, toks):
            worst_tie = max(worst_tie, float((row.max() - row[tok]) / np.abs(row).max()))
            agree += int(np.argmax(row) == tok)
            rows += 1
        k_want, v_want = (np.asarray(a)[:written].transpose(1, 0, 2) for a in (ref["k"], ref["v"]))   # [Hkv, s, d]
        worst_cache = max(worst_cache, rel(k_cache[slot][:, :written], k_want), rel(v_cache[slot][:, :written], v_want))
        state_by_prompt.append(rel(state_cache[slot], np.asarray(ref["state"])))
    engine.reset()
    worst_rms, worst_state = max(by_prompt), max(state_by_prompt)
    numbers = (worst_rms, worst_max, worst_cache, worst_state, worst_tie, worst_routing)
    finite = bool(all(np.isfinite(v) for v in numbers))
    return {"correct": bool(finite and worst_rms <= SERVE_LOGIT_REL_RMS and worst_tie <= SERVE_TOKEN_TIE
                            and worst_cache <= SERVE_CACHE_REL_RMS and worst_state <= SERVE_STATE_REL_RMS
                            and worst_routing <= SERVE_ROUTING_TIE and on_bf16_grid <= SERVE_STATE_ON_BF16_GRID),
            "logit_rel_rms": worst_rms, "logit_rel_max": worst_max, "cache_rel_rms": worst_cache,
            "state_rel_rms": worst_state, "token_below_best": worst_tie, "routing_below_kth": worst_routing, "state_on_bf16_grid": on_bf16_grid,
            "logit_rel_rms_by_position": by_position, "state_rel_rms_by_prompt": state_by_prompt,
            "tokens_equal_reference_argmax": agree, "positions": rows, "prompt_lengths": lengths,
            "compared": {"logit_rel_rms": [worst_rms, SERVE_LOGIT_REL_RMS],
                         "cache_rel_rms": [worst_cache, SERVE_CACHE_REL_RMS],
                         "state_rel_rms": [worst_state, SERVE_STATE_REL_RMS],
                         "token_below_best": [worst_tie, SERVE_TOKEN_TIE],
                         "routing_below_kth": [worst_routing, SERVE_ROUTING_TIE],
                         "state_on_bf16_grid": [on_bf16_grid, SERVE_STATE_ON_BF16_GRID]}}
