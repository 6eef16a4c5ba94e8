"""The Granite 4.0-H family (``model_type: granitemoehybrid``): pre-norm residual
layers, RMSNorm with weight, no biases but the convolution's, no positions, one
table for the embedding and the head, and four scalar multipliers:

    h      = embedding_multiplier * E[id]
    layer:   h = h + residual_multiplier * Mixer(RMSNorm(h))
             h = h + residual_multiplier * (Routed(u) + Shared(u)),  u = RMSNorm(h)
    logits = (RMSNorm(h) @ E^T) / logits_scaling

Layer *i* mixes by a Mamba-2 state-space layer where ``layer_types[i]`` is
``"mamba"`` (arXiv:2405.21060: one scalar decay a head, ``B`` and ``C`` shared by
a group's heads, a depthwise causal convolution with bias over ``x``, ``B`` and
``C`` together, the gate applied before one norm over all channels) and by
grouped-query softmax attention without positions where it is ``"attention"``
(scores times ``attention_multiplier``); every layer then sends each token to
the ``num_experts_per_tok`` experts with the largest router *logits*, weighs
them by a softmax over those logits, and adds one shared gated MLP. Serving
only.

For every configuration whose file says ``"family": "granite_moe_hybrid"``:

1. ``build_model``: the program's model with weights made on the device from
   the seed;
2. the **plain reference** (``reference_forward`` / ``reference_logits`` and the
   layer functions): straight ``jax.numpy``, float32,
   ``jax.default_matmul_precision("highest")``, one sequence, the Mamba-2
   recurrence token by token (never chunkwise: the chunk form is what is under
   test), the convolution as its sum over the taps, a Python loop over the
   experts that were chosen, no cache, nothing imported from
   ``paddle_tpu.models`` or ``paddle_tpu.ops``. It takes weights as plain
   arrays in the layout of ``weight_shapes`` and is told what it holds by
   ``dims``: which experts (``held``), how many rows of the table. Given the
   whole model's weights it is the whole model; given a share's (``share_dims``
   / ``share_weights``) it is that chip's partial result, which is what the
   program computes;
3. required bytes and operations of a decode step, of its state-space part and
   of a chunk's, and ``check_serving`` with its limits and controls.

The layout the reference reads (the program's,
``GraniteMoeHybridConfig.weight_shapes``): ``ssm_in`` gives ``[z (H P) | x (H P) |
B (G N) | C (G N) | dt (H)]``; ``ssm_conv [K, C]`` (tap ``K - 1`` for the current
token) and ``ssm_conv_bias [C]`` run over the middle three; ``attn_kv`` is keys
then values, ``[D, 2, Hkv, d]`` flattened; ``*_gate_up`` are gate then up. Head
``h`` of a Mamba layer takes group ``h // (H / G)``, query head ``h`` key/value
head ``h // (Hq / Hkv)``.

Departures from the published description, and sizes it does not give (each
also in the configuration's ``assumed``): (1) ``A_log = log U(1, 16)``, ``D = 1``,
``dt_bias = softplus^-1(exp(U(log 1e-3, log 0.1)))`` from the seed, so the decay
is near 1 and the state carries history; matrices N(0, 0.02), norm weights 1 +
N(0, 0.02), the convolution's taps and bias U(-K^-1/2, K^-1/2) — the lineage's
default for a depthwise convolution; at N(0, 0.02) the recurrence would be one
part in seventy of the mixer's output and no comparison downstream of it would
see the state; (2) ``time_step_limit`` (0, inf): ``dt`` is not clamped;
(3) precision in the program: state, ``dt``, decay, the convolution's sum, the
gated norm and the residual stream float32; weights, matmul operands, the
convolution's inputs (so the tails) and cached rows bfloat16 — the reference is
float32 throughout; (4) no vocabulary padding: the table has the rows of the
slice; (5) ``intermediate_size`` is the width of one expert (the source has no
key of its own for it).
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Dict, Optional

import numpy as np

# the routed experts' counters and span attributes are Solar's (PERF.md §3): so is their reading
from benchmark.families.solar_open2 import experts_hit_per_step  # noqa: F401  (also what ``routed_experts_hit_pct`` asks the family for)

HOST_SPAN_PREFIXES = ("infer.", "bench.")
DECODE_PROGRAM = "decode_fn"
CHUNK_PROGRAMS = ("chunk_core", "chunk_final_core")
PREFILL_PROGRAMS = CHUNK_PROGRAMS + ("prefill_core",)
SCOPES_OF_PROGRAM = {DECODE_PROGRAM: "infer/decode", "chunk_core": "infer/prefill_chunk",
                     "chunk_final_core": "infer/prefill_final"}
# ``jax.named_scope`` names of ``models/granite_moe_hybrid.py`` (and of what it takes from ``models/solar_open2.py``) ->
# the part a metric reports. ``ssm`` is the whole Mamba mixer: projections, convolution, recurrence, gated norm; the
# router belongs to the routed path; ``mlp`` is the shared MLP, which every chip of the group computes alike; ``embed``
# goes with the head (one table); the cache scopes are the attention layer's lax path.
PART_OF_SCOPE = {"ssm_proj": "ssm", "ssm_conv": "ssm", "ssm_core": "ssm", "ssm_norm": "ssm", "ssm_out": "ssm",
                 "attn_qkv": "attn", "attn_core": "attn", "attn_out": "attn", "cache_write": "attn", "cache_read": "attn",
                 "moe_router": "routed", "moe_routed": "routed", "moe_shared": "mlp",
                 "norm": "norm", "head_loss": "head_loss", "embed": "head_loss",
                 # where XLA's own grouped matmul runs (``lax.ragged_dot``) it names the kernel after itself and the
                 # scope path is gone (``families/solar_open2.py`` has the story); only the routed experts call it
                 "ragged": "routed"}

# ---- limits of ``check_serving`` (readings: my chip runs, PR 36; PERF.md §2 and §6) ----
# Each reading is taken with the reference following the program's choice of experts (``check_serving`` says why), at
# the published widths: bfloat16 weights, matmul operands, convolution inputs and cached rows; float32 residual stream,
# ``dt``, decay, state and gated norm. The change's readings are seventeen runs of the cell under the check as it stands
# (three prompts of 4,393, 1,543 and 259 tokens, all 40 slots decoding; every seed its own). The other side of each limit is a control
# (``planted``, at the end of this file): the same program with one thing wrong, one run of the cell each.
#
# Logits of the program's decode forward against the float32 reference, relative RMS over 17 positions of a prompt:
# 1.02e-2 to 1.24e-2, the three prompts alike (ten bfloat16 layers behind them, the stream float32: GigaChat's five
# post-normed layers read 2.2e-2, Solar's four 1.7e-2 — here a layer adds 0.22 of a sub-layer's output, and the
# sub-layer's own rounding with it). Half as much again. A state not zeroed at admission reads 6.1e-2 and 7.5e-2 (in the
# shortest prompt, whose 259 tokens have not outlived the last request's state), a tail held in float8 7.4e-2; the other
# two controls leave the logits where they were (1.10e-2 to 1.21e-2): seventeen positions hundreds of tokens behind a
# seam, or over a state coarser by 4e-3, do not see them — the rows and the state do.
SERVE_LOGIT_REL_RMS = 1.9e-2
# The attention layer's cached keys and values against the reference's (five Mamba layers and five expert layers of
# bfloat16 lie before them), relative RMS over all of a prompt's rows: 8.42e-3 to 8.62e-3. Half as much again. The
# convolution started cold at every seam reads 6.7e-2 (three rows behind each of a 4,393-token prompt's four seams are
# another convolution's), a state not zeroed 0.18 and 0.21, a tail held in float8 1.5e-2 ...
SERVE_CACHE_REL_RMS = 1.3e-2
# ... and the worst single row's, which is what a fault in a few rows of thousands stands out in: 1.05e-2 to 1.16e-2
# sound (the worst of 4,410 rows reads a quarter over their mean); 1.2 behind a cold seam and with a state not zeroed
# (the row is another row altogether). Twice the largest sound reading, a fiftieth of the controls'.
SERVE_CACHE_ROW_REL_RMS = 2.5e-2
# The first Mamba layer's state after the last decode step against the reference's token-by-token recurrence: 3.0e-3
# to 4.2e-3 by prompt, the worst of a run 3.44e-3 to 4.23e-3 — the layer is the model's first, so what reads here is
# the chunkwise scan's and the step's own arithmetic on bfloat16 inputs. The control, the state rounded to bfloat16 after
# every chunk and step (the nearest precision below its float32): 5.9e-3 to 7.8e-3 by prompt, 7.79e-3 and 9.84e-3 the
# run. The limit lies between: 30 % over the largest sound reading, 29 % under the control's lower. What it does not see: a state
# that admission did not zero reads 4.9e-3 in one run and 2.4e-2 in another — the decay has forgotten most of the last
# request by the end of a long prompt, not of a short one — which the rows hold in every run.
SERVE_STATE_REL_RMS = 5.5e-3
# Its convolution tail (the last three inputs of the convolution, held in bfloat16) against the reference's: 2.33e-3
# to 2.43e-3, a bfloat16 rounding of a bfloat16 matmul's result. Half as much again; the control, the tail rounded to
# float8 (e4m3, the nearest precision below) on its way into the slot, reads 2.68e-2, eleven times the sound reading.
SERVE_TAIL_REL_RMS = 3.6e-3
# The share of the state's elements that are exactly bfloat16 numbers (low 16 bits of the float32 zero): 1.6e-5 to
# 2.4e-5 for a float32 state, 1.0 in the bfloat16 control: the precision the state is *held* in, read on the state.
SERVE_STATE_ON_BF16_GRID = 0.5
# A served token must be one the reference rates within 2^-5 of the row's largest magnitude below its best (the other
# families' margin). Readings 0.0: all 51 served tokens are the reference's argmax in every run, the controls' too (a
# tied table rates the token before highest by a wide margin at seeded weights): this limit holds the sampling path —
# a wrong slice of the table, an argmax over the wrong axis — not precision.
SERVE_TOKEN_TIE = 2.0 ** -5
# The reference follows the program's choice of experts where its own tenth and eleventh logits tie within the
# program's rounding: the lowest logit among the program's ten may lie this far under the reference's own tenth best.
# Readings 5.2e-2 to 7.5e-2 of a logit (logits of standard deviation 1.3; the worst of 6.2 k rows x 10 layers a run:
# the router's input has up to ten bfloat16 layers behind it). Nearly twice the largest; a router fed another row's input
# (the cold seam, the state not zeroed) picks experts 4.2 to 4.9 under, one fed a float8 tail 0.24.
SERVE_ROUTING_TIE = 0.14

_COLUMNS = 4096     # columns of a weight the reference casts to float32 at a time
_HEADS = 8          # attention heads the reference scores at a time
_ROWS = 512         # query rows whose scores against every key it holds at a time


# ---------------------------------------------------------------- shapes
def dims(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file: ``V`` and
    ``held`` are what is held *here*; ``E`` is the router's width (the
    published count where ``num_local_experts`` is reduced)."""
    types = tuple(str(t) for t in config["layer_types"])
    E = int(config.get("router_experts", config["num_local_experts"]))
    held = config.get("held_experts") or [0, int(config["num_local_experts"])]
    D, Hq = int(config["hidden_size"]), int(config["num_attention_heads"])
    # ``L`` counts the layers that hold experts (all of them): what ``layer_metrics/routed_experts_hit_pct.py`` divides by
    return dict(D=D, layers=len(types), L=len(types), attn=tuple(i for i, t in enumerate(types) if t == "attention"),
                Hq=Hq, Hkv=int(config["num_key_value_heads"]), d=int(config.get("head_dim") or D // Hq),
                H=int(config["mamba_n_heads"]), P=int(config["mamba_d_head"]), N=int(config["mamba_d_state"]),
                G=int(config["mamba_n_groups"]), K=int(config["mamba_d_conv"]), V=int(config["vocab_size"]),
                F=int(config["intermediate_size"]), Fs=int(config["shared_intermediate_size"]), E=E,
                held=(int(held[0]), int(held[1])), top_k=int(config["num_experts_per_tok"]), eps=float(config["rms_norm_eps"]),
                embed_scale=float(config["embedding_multiplier"]), residual_scale=float(config["residual_multiplier"]),
                attn_scale=float(config["attention_multiplier"]), logit_scale=float(config["logits_scaling"]))


def weight_shapes(config_or_dims: dict) -> Dict[str, tuple]:
    z = config_or_dims if "attn" in config_or_dims else dims(config_or_dims)
    D, L, F, Fs = z["D"], z["layers"], z["F"], z["Fs"]
    La, Lm = len(z["attn"]), z["layers"] - len(z["attn"])
    q, kv, inner = z["Hq"] * z["d"], z["Hkv"] * z["d"], z["H"] * z["P"]
    conv = inner + 2 * z["G"] * z["N"]
    return {
        "embed": (z["V"], D), "final_norm": (D,),
        "norm1": (L, D), "norm2": (L, D), "router": (L, D, z["E"]),
        "experts_gate_up": (L, z["held"][1], D, 2 * F), "experts_down": (L, z["held"][1], F, D),
        "shared_gate_up": (L, D, 2 * Fs), "shared_down": (L, Fs, D),
        "attn_q": (La, D, q), "attn_kv": (La, D, 2 * kv), "attn_out": (La, q, D),
        "ssm_in": (Lm, D, inner + conv + z["H"]), "ssm_conv": (Lm, z["K"], conv), "ssm_conv_bias": (Lm, conv),
        "ssm_a_log": (Lm, z["H"]), "ssm_d": (Lm, z["H"]), "ssm_dt_bias": (Lm, z["H"]), "ssm_norm": (Lm, inner),
        "ssm_out": (Lm, inner, D),
    }


def param_count(config: dict) -> int:
    return int(sum(math.prod(s) for s in weight_shapes(config).values()))


def share_dims(z: dict, share: int, shares: int) -> dict:
    """``dims`` of share ``share`` of ``shares`` equal shares of the model
    ``z``: its experts and its rows of the table (the mixers, the routers and
    the shared MLP are whole on every chip)."""
    first, count = z["held"]
    return dict(z, V=z["V"] // shares, held=(first + share * (count // shares), count // shares))


def share_weights(z: dict, w: dict, share: int, shares: int) -> dict:
    """The weights share ``share`` holds of the whole model's ``w``: its
    experts and its rows of the table; everything else whole."""
    rows, per_e = z["V"] // shares, z["held"][1] // shares
    out = {k: (tuple(np.asarray(a) for a in v) if isinstance(v, tuple) else np.asarray(v)) for k, v in w.items()}
    out["embed"] = out["embed"][share * rows:(share + 1) * rows]
    for k in ("experts_gate_up", "experts_down"):
        out[k] = tuple(a[share * per_e:(share + 1) * per_e] for a in out[k])
    return out


# ---------------------------------------------------------------- the program's model
def build_model(config: dict, seed: int, dtype: str, mesh=None):
    """The program's model at the configuration's sizes with weights made on
    the device from the seed, in ``dtype`` (the recurrence's parameters float32)."""
    from paddle_tpu.models.granite_moe_hybrid import GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM

    if mesh is not None:
        raise NotImplementedError("the granite_moe_hybrid family serves on one chip: no mesh")
    return GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig.from_config_file(config), seed=seed, dtype=dtype)


def weights_of_engine(engine) -> dict:
    """The served weights as plain arrays in ``weight_shapes``' layout."""
    return dict(engine._params)


# ---------------------------------------------------------------- reference
class _Layer:
    """Entry ``i`` of a stack of weights (a layer, an expert), cut out only
    where it is indexed: ``stack[i][:, a:b]`` would copy the whole entry out
    first (0.14 GB of a Mamba layer's input projection)."""

    def __init__(self, stack, i: int):
        self.stack, self.i, self.shape = stack, int(i), tuple(stack.shape[1:])

    def __getitem__(self, index):
        return self.stack[(self.i,) + (index if isinstance(index, tuple) else (index,))]


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a[...] if isinstance(a, _Layer) else a, jnp.float32)


def _settled(x):
    """``x``, computed: eager dispatch runs ahead of the device, and every
    block that is queued holds its float32 copy of a weight until it has run."""
    import jax

    return jax.block_until_ready(x)


def _times(x, w):
    """``x @ w`` with ``w`` cast to float32 a block of columns at a time, so
    that a share at the published widths fits beside the served model."""
    import jax.numpy as jnp

    n = w.shape[-1]
    if n <= _COLUMNS:
        return _settled(x @ _f32(w))
    return jnp.concatenate([_settled(x @ _f32(w[:, i:i + _COLUMNS])) for i in range(0, n, _COLUMNS)], axis=-1)


def _norm(z, x, w):
    """``x / rms(x) * w``."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + z["eps"]) * _f32(w)


def reference_mamba(z: dict, lw: dict, x, state_after: Optional[int] = None):
    """The Mamba-2 mixer on one sequence ``x [s, D]`` (already normalised), the
    recurrence token by token from an empty state: ``(y [s, D], S [H, P, N],
    tail [K - 1, C])`` with ``S`` the state after ``state_after`` tokens
    (default: all) and ``tail`` the convolution's inputs of the ``K - 1``
    tokens before that point."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    H, P, N, G, K = z["H"], z["P"], z["N"], z["G"], z["K"]
    inner, conv_ch = H * P, H * P + 2 * G * N
    keep_at = s if state_after is None else int(state_after)
    tails = []

    def conv(first, last):
        """SiLU of the convolution over channels ``first`` to ``last`` of ``xBC``: the bias and one term a tap."""
        raw = _times(x, lw["ssm_in"][:, inner + first:inner + last])
        padded = jnp.concatenate([jnp.zeros((K - 1, last - first), jnp.float32), raw], axis=0)
        tails.append(padded[keep_at:keep_at + K - 1])                                  # the K - 1 inputs before ``keep_at``
        total = _f32(lw["ssm_conv_bias"][first:last])
        for j in range(K):                                                            # tap K - 1 is the current token
            total = total + padded[j:j + s] * _f32(lw["ssm_conv"][j, first:last])
        return _settled(jax.nn.silu(total))

    xs = jnp.concatenate([conv(i, min(i + _COLUMNS, inner)) for i in range(0, inner, _COLUMNS)], axis=-1).reshape(s, H, P)
    B, C = conv(inner, inner + G * N).reshape(s, G, N), conv(inner + G * N, conv_ch).reshape(s, G, N)
    dt = jax.nn.softplus(_times(x, lw["ssm_in"][:, inner + conv_ch:]) + _f32(lw["ssm_dt_bias"]))       # [s, H]
    decay = jnp.exp(dt * -jnp.exp(_f32(lw["ssm_a_log"])))                              # in (0, 1)
    skip = _f32(lw["ssm_d"])

    def token(carry, step):
        S, kept = carry
        t, x_t, b_t, c_t, dt_t, a_t = step
        b_t, c_t = jnp.repeat(b_t, H // G, axis=0), jnp.repeat(c_t, H // G, axis=0)   # a group's B and C, for each of its heads
        S = a_t[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", S, c_t) + skip[:, None] * x_t
        return (S, jnp.where(t + 1 == keep_at, S, kept)), y_t

    zero = jnp.zeros((H, P, N), jnp.float32)
    (_, kept), y = jax.lax.scan(token, (zero, zero), (jnp.arange(s), xs, B, C, dt, decay))
    del xs, B, C
    o = _settled(y.reshape(s, inner) * jax.nn.silu(_times(x, lw["ssm_in"][:, :inner])))            # the gate first
    o = _settled(_norm(z, o, lw["ssm_norm"]))                                          # then one norm over all channels
    return _times(o, lw["ssm_out"]), kept, jnp.concatenate(tails, axis=-1)


def reference_attention(z: dict, lw: dict, x):
    """Grouped-query softmax attention without positions on one sequence ``x
    [s, D]``: ``(y [s, D], k [s, Hkv, d], v [s, Hkv, d])``; the scores times
    ``attention_multiplier``. A few heads and a block of query rows at a time."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    Hq, Hkv, d = z["Hq"], z["Hkv"], z["d"]
    q = _times(x, lw["attn_q"]).reshape(s, Hq, d)
    kv = _times(x, lw["attn_kv"]).reshape(s, 2, Hkv, d)
    k, v = kv[:, 0], kv[:, 1]
    positions = np.arange(s)
    heads = []
    for h in range(0, Hq, _HEADS):
        of = np.arange(h, min(h + _HEADS, Hq)) // (Hq // Hkv)                         # each query head's key/value head
        k_h, v_h, blocks = k[:, of], v[:, of], []
        for r in range(0, s, _ROWS):
            rs = slice(r, r + _ROWS)
            scores = jnp.einsum("qhd,khd->hqk", q[rs, h:h + _HEADS], k_h) * z["attn_scale"]
            causal = jnp.asarray(positions[rs, None] >= positions[None, :])[None]
            prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            blocks.append(_settled(jnp.einsum("hqk,khd->qhd", prob, v_h)))
        heads.append(jnp.concatenate(blocks, axis=0))
    return _times(jnp.concatenate(heads, axis=1).reshape(s, Hq * d), lw["attn_out"]), k, v


def _gated(x, w_gate_up, w_down):
    """``W_down(silu(W_gate x) * W_up x)``, gate and up side by side in ``w_gate_up``."""
    import jax

    f = w_down.shape[0]
    return _settled((jax.nn.silu(x @ _f32(w_gate_up[:, :f])) * (x @ _f32(w_gate_up[:, f:]))) @ _f32(w_down))


def reference_moe(z: dict, lw: dict, x, shared: bool = True, chosen=None):
    """The expert layer on rows ``x [s, D]``: every expert of the router gets
    a logit, the ``top_k`` largest are chosen and weighed by a softmax over
    those ``top_k`` logits; the experts held here (``z["held"]``) that some
    token chose add their part, one at a time; the shared MLP is added once
    (``shared``). Returns ``(y [s, D], shortfall)``.

    ``chosen [s, k]`` is another's word on which experts each row takes (the
    program's, computed in bfloat16, where the tenth and eleventh logits of 72
    lie within its rounding of each other for a few rows in a hundred): the
    reference then takes *those* experts, weighs them by a softmax over its
    own logits of them, and reports as ``shortfall`` how far the lowest of
    them lies under its own ``top_k``-th best logit (0 where the choices
    agree). The caller holds that to a limit: a tie may fall either way, a
    wrong router may not."""
    import jax
    import jax.numpy as jnp

    logits = x @ _f32(lw["router"])
    top, idx = jax.lax.top_k(logits, z["top_k"])
    shortfall = 0.0
    if chosen is not None:
        kth = top[:, -1:]
        idx = jnp.asarray(chosen, jnp.int32)
        top = jnp.take_along_axis(logits, idx, axis=-1)
        shortfall = float(jnp.max(jnp.maximum(kth - top, 0.0)))
    w = jax.nn.softmax(top, axis=-1)
    first, count = z["held"]
    out = jnp.zeros_like(x)
    for e in np.unique(np.asarray(idx)):
        if first <= e < first + count:
            w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            out = out + w_e[:, None] * _gated(x, _Layer(lw["experts_gate_up"], e - first), _Layer(lw["experts_down"], e - first))
    if shared:
        out = out + _gated(x, lw["shared_gate_up"], lw["shared_down"])
    return out, shortfall


def reference_head(z: dict, weights: dict, h):
    """``RMSNorm(h) @ E^T / logits_scaling`` over the rows of the table held."""
    import jax.numpy as jnp

    return _times(_norm(z, h, weights["final_norm"]), jnp.asarray(weights["embed"]).T) / z["logit_scale"]


def _layer_weights(weights: dict, prefix, i: int) -> dict:
    """Layer ``i`` of every stack named ``prefix…`` (the experts: one array a layer, as the program holds them)."""
    return {k: (weights[k][i] if isinstance(weights[k], (tuple, list)) else _Layer(weights[k], i)) for k in weights if k.startswith(prefix)}


def reference_forward(config_or_dims, weights: dict, ids, rows_from: int = 0, state_after: Optional[int] = None,
                      routing=None) -> dict:
    """One sequence through the model: ``logits [s - rows_from, V]`` (float32)
    of the rows from ``rows_from``, the first attention layer's keys and
    values ``k``, ``v`` ``[s, Hkv, d]``, the first Mamba layer's ``state``
    after ``state_after`` tokens and its convolution's ``tail`` there; with
    ``routing [L, s, k]`` (``reference_moe``'s ``chosen``, a layer) also
    ``routing_shortfall``, the worst over layers and rows. Layer by layer, a
    weight cast to float32 a block of columns at a time and the experts one
    at a time, so a share at the published widths fits beside the served model."""
    import jax
    import jax.numpy as jnp

    z = config_or_dims if "attn" in config_or_dims else dims(config_or_dims)
    out = {"k": None, "v": None, "state": None, "tail": None, "routing_shortfall": 0.0}
    with jax.default_matmul_precision("highest"):
        h = z["embed_scale"] * _f32(jnp.asarray(weights["embed"])[jnp.asarray(ids, jnp.int32)])
        ai = mi = 0
        for layer in range(z["layers"]):
            x = _norm(z, h, weights["norm1"][layer])
            if layer in z["attn"]:
                y, k, v = reference_attention(z, _layer_weights(weights, "attn_", ai), x)
                if ai == 0:
                    out["k"], out["v"] = k, v
                ai += 1
            else:
                y, state, tail = reference_mamba(z, _layer_weights(weights, "ssm_", mi), x, state_after)
                if mi == 0:
                    out["state"], out["tail"] = state, tail
                mi += 1
            h = h + z["residual_scale"] * y
            x = _norm(z, h, weights["norm2"][layer])
            y, shortfall = reference_moe(z, _layer_weights(weights, ("router", "experts_", "shared_"), layer), x,
                                         chosen=None if routing is None else routing[layer])
            out["routing_shortfall"] = max(out["routing_shortfall"], shortfall)
            h = _settled(h + z["residual_scale"] * y)
        out["logits"] = reference_head(z, weights, h[rows_from:])
    return out


def reference_logits(config: dict, weights: dict, ids):
    """Logits ``[s, V]`` of one sequence of token ids, float32."""
    return reference_forward(config, weights, ids)["logits"]


# ---------------------------------------------------------------- required bytes and operations
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


def ssm_weight_count(config: dict) -> int:
    """The parameters of one Mamba mixer."""
    return sum(math.prod(shape[1:]) for name, shape in weight_shapes(config).items() if name.startswith("ssm_"))


def slot_bytes(config: dict, bytes_per_value: int = 2) -> dict:
    """What one slot holds, by kind: ``state`` (a float32 matrix a head a
    Mamba layer), ``tail`` (the convolution's last inputs) and ``kv`` (the
    attention layers' rows at the configuration's context)."""
    z = dims(config)
    Lm, conv = z["layers"] - len(z["attn"]), z["H"] * z["P"] + 2 * z["G"] * z["N"]
    return {"state": Lm * z["H"] * z["P"] * z["N"] * 4, "tail": Lm * (z["K"] - 1) * conv * bytes_per_value,
            "kv": len(z["attn"]) * 2 * z["Hkv"] * z["d"] * int(config["serving"]["context"]) * bytes_per_value}


def ssm_step_floor_s(config: dict, decoding: float, peaks: dict, bytes_per_value: int = 2) -> float:
    """The least time the chip could take over the Mamba mixers of one decode
    step with ``decoding`` slots live: every mixer's weights read once, and
    each live slot's state and tail read and written once, at the peak
    bandwidth. The algorithm's count, the same whatever implements the step."""
    z = dims(config)
    per_slot = slot_bytes(config, bytes_per_value)
    weights = (z["layers"] - len(z["attn"])) * ssm_weight_count(config) * bytes_per_value
    return (weights + decoding * 2 * (per_slot["state"] + per_slot["tail"])) / peaks["hbm_bytes_per_s"]


def ssm_chunk_floor_s(config: dict, rows: int, peaks: dict) -> float:
    """The least time the chip could take over the Mamba mixers of a chunk of
    ``rows`` tokens: the two projections' operations and the recurrence's
    least — a decay-and-write and a read-out of ``P x N`` a head a token, two
    operations a product, which is what an inner chunk of one row does and
    less than any longer one — at the peak rate. So no implementation reads
    over 100 %."""
    z = dims(config)
    inner = z["H"] * z["P"]
    conv = inner + 2 * z["G"] * z["N"]
    per_token = 2 * z["D"] * (inner + conv + z["H"]) + 2 * inner * z["D"] + 4 * z["P"] * z["N"] * z["H"]
    return rows * (z["layers"] - len(z["attn"])) * per_token / peaks["bf16_flops_per_s"]


def decode_step_bytes(config: dict, live_rows: float, records=None, bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read (and, for the state, write): the
    weights outside the routed experts (the table once: it is the head), the
    live rows of the attention layers' cache, the decoding slots' recurrent
    state and tails read and written, and the weights of the experts hit in
    the traced ticks. Where the program counted nothing (no ``records``),
    every held expert counts."""
    z = dims(config)
    D = z["D"]
    held_experts = z["L"] * z["held"][1] * 3 * D * z["F"]
    weights = param_count(config) - held_experts - z["L"] * D * z["E"]        # the routers are counted with the routed path
    rows = len(z["attn"]) * 2 * z["Hkv"] * z["d"] * live_rows
    decoding = None if records is None else \
        _mean([records.tick_decoding[i] for i in records.in_trace(records.tick_end) if records.tick_decoding[i]])
    if decoding is None:
        decoding = int(config["serving"]["slots"])
    per_slot = slot_bytes(config, bytes_per_value)
    state = 2.0 * decoding * (per_slot["state"] + per_slot["tail"])
    routed = None if records is None else routed_step_bytes(config, records, bytes_per_value)
    if routed is None:
        routed = z["L"] * (z["held"][1] * expert_bytes(config, bytes_per_value) + D * z["E"] * bytes_per_value)
    return bytes_per_value * (weights + rows) + state + routed


def _mean(values):
    return sum(values) / len(values) if values else None


# ---------------------------------------------------------------- correct
def check_serving(engine, config: dict, seed: int, n_decode: int = 16) -> dict:
    """Three seeded prompts — four chunks and a final chunk whose rows are no
    multiple of the scan's inner chunk (4,393 tokens at the cell's chunk of
    1,024: 297 rows in the last), a chunk and a final chunk, a final chunk
    alone — through the engine's own chunked prefill, every other slot filled
    with a short seeded prompt so that each decode step runs with the whole
    batch live, and ``n_decode`` decode steps, on slots the window's traffic
    has used (so a state that admission did not zero shows).

    A recurrent state cannot be probed after the fact, so before each decode
    step — and once after the last — the program's own decode forward runs on
    the engine's buffers at the engine's batch width and gives the logits of
    the token about to be consumed: ``n_decode + 1`` positions a prompt,
    compared with the reference's full forward over the share (relative RMS).
    The probe hands the buffers back with the recurrent state and the tails
    as they were and the key/value cache with the probed token's row written
    (the step proper writes the same row again): a copy of the buffers would
    not fit beside them. The engine hands out tokens, not logits: what its own
    decode program computed is held to the reference through each served
    token, which must be within ``SERVE_TOKEN_TIE`` of the reference's best.
    And what the slots hold is compared: the attention layer's cached keys and
    values (over all rows, and the worst single row), the first Mamba layer's
    state after the last step and its convolution tail, against the
    reference's.

    **Routing.** With seeded weights a router's tenth and eleventh logits of
    72 lie within bfloat16 rounding of each other for several rows in a
    hundred, and a row that takes another expert than the reference's is off
    by that expert's whole contribution (``families/solar_open2.py`` has the
    readings). So the reference is told which experts the program took — the
    prompt's rows from the program's chunk forward replayed on the slot with
    the engine's own chunking (``chunk_routing``), the decoded rows from the
    probe (``decode_probe``) — weighs them by a softmax over its own logits of
    them, and reports how far the lowest lies under its own tenth best
    (``routing_below_kth``, held to ``SERVE_ROUTING_TIE``): a tie may fall
    either way, a wrong router may not. What is left is precision."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import granite_moe_hybrid as program

    z = dims(config)
    chunk = engine._chunk or 64
    rng = np.random.default_rng([int(seed), 7])
    deep = min(4 * chunk + chunk // 4 + chunk // 32 + 9, int(config["serving"]["context"]) - n_decode - 8)
    lengths = [deep, chunk + chunk // 2 + 7, max(8, chunk // 4 + 3)]
    engine.reset()
    prompts = [rng.integers(0, z["V"], (n,)).astype(np.int32) for n in lengths]
    fillers = {slot: rng.integers(0, z["V"], (max(4, chunk // 8 + slot % 5),)).astype(np.int32)
               for slot in range(len(prompts), engine.max_batch_slots)}
    cfg = engine._dec.cfg
    names = [spec.name for spec in engine._specs]
    i_state, i_tail = names.index("ssm_state0"), names.index("conv_tail0")

    # Both run at the engine's own shapes — every slot's buffers, the engine's batch — so that what they compute
    # is, op for op, what the engine's programs computed: a forward one slot wide rounds a matmul's sums in
    # another order, and one element of 4,096 a token lands on the other side of a bfloat16 rounding.
    @functools.partial(jax.jit, donate_argnums=(1,))
    def probe(params, cache, tok, pos, active):
        logits, experts, after = program.decode_probe(cfg, params, cache, tok, pos, active)
        kept = tuple(after[:2]) + tuple(cache[2:])                        # the state and the tails as they were
        return logits[:len(prompts)].astype(jnp.float32), experts[:, :len(prompts)], kept

    @functools.partial(jax.jit, donate_argnums=(1,))
    def replay(params, cache, ids, slot, start, n_valid):
        return program.chunk_routing(cfg, params, cache, ids, slot, start, n_valid)

    def prompt_routing(prompt, slot):
        """Which experts the program's chunk forward takes for each row of the prompt, ``[L, n, k]``: the engine's
        chunking replayed into ``slot`` of the engine's own buffers (every slot is free, and the prefill that follows
        starts the slot afresh)."""
        n = len(prompt)
        padded = -(-n // chunk) * chunk
        ids = np.zeros((padded,), np.int32)
        ids[:n] = prompt
        parts = []
        for start in range(0, padded, chunk):
            engine._cache, experts = replay(engine._params, engine._cache, jnp.asarray(ids[start:start + chunk]),
                                            jnp.int32(slot), jnp.int32(start), jnp.int32(min(chunk, n - start)))
            parts.append(np.asarray(experts))
        return np.concatenate(parts, axis=1)[:, :n]

    routing_of_prompt = [prompt_routing(prompt, slot) for slot, prompt in enumerate(prompts)]

    served, probed, probed_routing = [], [[] for _ in prompts], [[] for _ in prompts]
    for slot, prompt in enumerate(prompts):
        first, _ = engine.prefill(prompt, slot, max_new_tokens=n_decode + 4)
        served.append([int(first)])
    for slot, prompt in fillers.items():                                    # decoding beside them through every step
        engine.prefill(prompt, slot, max_new_tokens=n_decode + 4)

    def probe_all():
        # the engine's own buffers and slot state, as its next decode program will take them
        logits, experts, engine._cache = probe(engine._params, engine._cache, engine._tok, engine._pos, engine._active)
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
    cached = max(lengths) + n_decode + 1
    # buffers 0 and 1: the attention layers' keys and values [La, B, Hkv, S, d]
    k_cache, v_cache = (np.asarray(engine._cache[i][0, :len(prompts), :, :cached].astype(jnp.float32)) for i in (0, 1))
    state_cache = np.asarray(engine._cache[i_state][:len(prompts)].astype(jnp.float32))
    tail_cache = np.asarray(engine._cache[i_tail][:len(prompts)].astype(jnp.float32))
    # how much of the state is exactly a bfloat16 number: 2^-16 of a float32 state, all of one held in bfloat16
    on_bf16_grid = float(np.mean((state_cache.view(np.uint32) & 0xFFFF) == 0))
    worst_max = worst_tie = worst_routing = 0.0
    agree = rows = 0
    by_prompt, by_position, state_by_prompt, tail_by_prompt, cache_by_prompt, row_by_prompt = [], [], [], [], [], []
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
        # the probe after the last step wrote the last token's row too: all of ``seq`` is cached. [Hkv, s, d] -> [s, Hkv, d]
        kv_got = np.concatenate([np.swapaxes(k_cache[slot][:, :len(seq)], 0, 1), np.swapaxes(v_cache[slot][:, :len(seq)], 0, 1)], axis=-1)
        kv_want = np.concatenate([np.asarray(ref["k"]), np.asarray(ref["v"])], axis=-1)
        cache_by_prompt.append(rel(kv_got, kv_want))
        per_row = np.sqrt(np.mean((kv_got - kv_want) ** 2, axis=(1, 2)) / np.mean(kv_want ** 2, axis=(1, 2)))
        row_by_prompt.append(float(per_row.max()))
        state_by_prompt.append(rel(state_cache[slot], np.asarray(ref["state"])))
        tail_by_prompt.append(rel(tail_cache[slot], np.asarray(ref["tail"])))
    engine.reset()
    worst_rms, worst_cache, worst_row = max(by_prompt), max(cache_by_prompt), max(row_by_prompt)
    worst_state, worst_tail = max(state_by_prompt), max(tail_by_prompt)
    compared = {"logit_rel_rms": [worst_rms, SERVE_LOGIT_REL_RMS],
                "cache_rel_rms": [worst_cache, SERVE_CACHE_REL_RMS],
                "cache_row_rel_rms": [worst_row, SERVE_CACHE_ROW_REL_RMS],
                "state_rel_rms": [worst_state, SERVE_STATE_REL_RMS],
                "tail_rel_rms": [worst_tail, SERVE_TAIL_REL_RMS],
                "token_below_best": [worst_tie, SERVE_TOKEN_TIE],
                "routing_below_kth": [worst_routing, SERVE_ROUTING_TIE],
                "state_on_bf16_grid": [on_bf16_grid, SERVE_STATE_ON_BF16_GRID]}
    return {"correct": bool(np.isfinite(worst_max) and all(np.isfinite(v) and v <= limit for v, limit in compared.values())),
            "logit_rel_rms": worst_rms, "logit_rel_max": worst_max, "cache_rel_rms": worst_cache,
            "cache_row_rel_rms": worst_row, "state_rel_rms": worst_state, "tail_rel_rms": worst_tail,
            "token_below_best": worst_tie, "routing_below_kth": worst_routing, "state_on_bf16_grid": on_bf16_grid,
            "logit_rel_rms_by_position": by_position, "logit_rel_rms_by_prompt": by_prompt,
            "cache_rel_rms_by_prompt": cache_by_prompt, "cache_row_rel_rms_by_prompt": row_by_prompt,
            "state_rel_rms_by_prompt": state_by_prompt, "tail_rel_rms_by_prompt": tail_by_prompt,
            "tokens_equal_reference_argmax": agree, "positions": rows,
            "prompt_lengths": lengths, "slots_decoding": len(prompts) + len(fillers), "compared": compared}


# ---------------------------------------------------------------- the check's controls
# Each is the same program with one thing wrong, planted from outside it (the program has no switch for any of them),
# and ``check_serving`` has to say not correct. ``python3 -m benchmark.families.granite_moe_hybrid <control> --workload
# granite-4.0-h-small.serve-rag --seed <n> --seconds <s> --trace 0`` is one run of the cell with one planted, on the
# chip through ``chiprun``; ``tests/benchmark_suite/test_granite_cell.py`` plants each at tiny widths.
CONTROLS = ("state_held_in_bfloat16", "state_not_reset_at_admission", "conv_tail_not_handed_over", "conv_tail_held_in_float8")


@contextlib.contextmanager
def planted(control: str):
    """The program with ``control`` wrong until the block ends: the Mamba
    layers' state rounded to bfloat16 (the nearest precision below its
    float32) after every chunk and step; a slot's recurrent state and tail
    left as the last request left them; a chunk's convolution started from
    zeros instead of the tail the chunk before it left (a decode step still
    takes the final chunk's); the tail rounded to float8 (e4m3, the nearest
    precision below its bfloat16) on its way into the slot, after every chunk
    and step. The engine's store of executables is keyed by
    configuration and shapes, not by program text, so it is off meanwhile: a
    planted program neither loads the sound one nor leaves itself under its
    key."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference import aot_cache
    from paddle_tpu.models import granite_moe_hybrid as program

    step, chunked, mixer, mixer_step = program.ssd_step, program.ssd_chunked, program._ssm_chunk, program._ssm_decode

    def coarse_state(scan):
        def wrong(*args, **kwargs):
            y, state = scan(*args, **kwargs)
            return y, jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)   # a cast there and back is simplified away
        return wrong

    def cold_seam(cfg, lp, x, state, tail, n_valid):
        return mixer(cfg, lp, x, state, jnp.zeros_like(tail), n_valid)

    def coarse_tail(mix):
        def wrong(*args, **kwargs):
            y, state, tail = mix(*args, **kwargs)
            return y, state, jax.lax.reduce_precision(tail.astype(jnp.float32), exponent_bits=4, mantissa_bits=3).astype(tail.dtype)
        return wrong

    wrong = {"state_held_in_bfloat16": [(program, "ssd_step", coarse_state(step)), (program, "ssd_chunked", coarse_state(chunked))],
             "state_not_reset_at_admission": [(program, "_admitting", lambda start: False)],
             "conv_tail_not_handed_over": [(program, "_ssm_chunk", cold_seam)],
             "conv_tail_held_in_float8": [(program, "_ssm_chunk", coarse_tail(mixer)), (program, "_ssm_decode", coarse_tail(mixer_step))]}[control]
    sound = [(where, name, getattr(where, name)) for where, name, _ in wrong] + [(aot_cache, "cache_dir", aot_cache.cache_dir)]
    for where, name, fn in wrong + [(aot_cache, "cache_dir", lambda scope="serving": None)]:
        setattr(where, name, fn)
    try:
        yield
    finally:
        for where, name, fn in sound:
            setattr(where, name, fn)


if __name__ == "__main__":
    import sys

    from benchmark import run                       # first: its clock is the run's ``setup_s``

    with planted(sys.argv[1]):
        sys.exit(run.main(sys.argv[2:]))
