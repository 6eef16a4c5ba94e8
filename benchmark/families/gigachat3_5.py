"""The GigaChat 3.5 family (``model_type: gigachat3_5``): blocks normalised
before and after each sub-layer (``h += N(Mixer(N(h))); h += N(FFN(N(h)))``),
no biases, an untied embedding and head, a final norm. Layer *i* mixes by
latent attention (MLA, DeepSeek-V3's equations, rotary positions with YaRN
frequencies on interleaved pairs) when *i* is in ``full_attention_layers`` and
otherwise by the gated delta net (arXiv:2412.06464: one scalar decay a value
head, two value heads a key head); its feed-forward is one dense gated FFN
below ``first_k_dense_replace`` and above it ``num_experts_per_tok`` of
``n_routed_experts`` gated experts, dropless, plus one shared expert. Serving
only; the source's two multi-token-prediction layers are not run.

For every configuration whose file says ``"family": "gigachat3_5"``:

1. ``build_model``: the program's model with weights made on the device from
   the seed;
2. the **plain reference** (``reference_forward`` / ``reference_logits`` and the
   layer functions): straight ``jax.numpy``, float32,
   ``jax.default_matmul_precision("highest")``, one sequence, latent attention
   un-absorbed with the rotation written out, the delta net token by token, a
   Python loop over the experts that were chosen, no cache, nothing imported
   from ``paddle_tpu.models`` or ``paddle_tpu.ops``. It takes weights as plain
   arrays in the layout of ``weight_shapes`` and is told what it holds by
   ``dims``: which experts (``held``), how many rows of the vocabulary. Given
   the whole model's weights it is the whole model; given a share's
   (``share_dims`` / ``share_weights``) it is that chip's partial result, which
   is what the program computes;
3. required bytes and operations of a decode step and of its latent attention,
   and ``check_serving`` with its limits.

The layout the reference reads (the program's, ``GigaChat35Config.weight_shapes``):
``mla_q_up`` is ``[q_rank, H, dn + rope]`` flattened, a head's ``q_nope`` then
its ``q_rope``; ``mla_kv_down`` gives ``[c_kv | k_r]``; ``mla_k_up`` / ``mla_v_up``
are ``[rank, H, d]`` flattened; ``gdn_in`` gives ``[q (Hk dk) | k (Hk dk) | v
(Hv dv) | z (Hv dv)]`` and ``gdn_conv [K, C]`` (tap ``K - 1`` for the current
token) runs over the first three; ``gdn_ab`` gives ``[a (Hv) | b (Hv)]``;
``*_gate_up`` are gate then up. Value head ``h`` of the delta net takes key
head ``h // (Hv / Hk)``.

Named by the source's config and defined in modelling code that is not here —
assumed (each also in the configuration's ``assumed``): (1) the attention gate
is elementwise over the ``H * dv`` outputs from a full ``W_g``; (2)
``ZeroCenteredGatedNorm`` with ``layernorm_gating_weight`` *g* is ``x / rms(x) *
g sigmoid(w)`` (scale 1 at ``w = 0``) for the block norms, both norms inside
MLA and the final norm; (3) ``gated_rmsnorm_sigmoid_zero_centered`` with
``linear_sigmoid_gate_scale`` *g* is ``o / rms(o) * (1 + w) * g sigmoid(W_z x)``
per value head; (4) sigmoid router scores, no selection bias; (5) ``A_log = log
U(1, 16)``, ``dt_bias = softplus^-1(U(1e-3, 0.1))`` from the seed; state, decay,
beta and gates float32; L2 normalisation as ``x / sqrt(sum x^2 + 1e-6)``; no
convolution bias; (6) ``swiglu_limit`` *L*: ``silu(min(gate, L)) * clip(up, -L,
L)`` in every gated FFN.
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
# ``jax.named_scope`` names of ``models/gigachat3_5.py`` -> the part a metric reports. ``attn`` is the latent layer's
# projections, rotation and gate; ``latent`` its attention over the cached rows with the row's write (one kernel on the
# TPU); the router belongs to the routed path; ``mlp`` is the shared expert and the dense FFN, which every chip of the
# group computes alike; ``embed`` goes with the head.
PART_OF_SCOPE = {"mla_q": "attn", "mla_kv": "attn", "rope": "attn", "mla_out": "attn",
                 "mla_core": "latent", "cache_write": "latent",
                 "linear_proj": "linear", "linear_core": "linear", "linear_out": "linear",
                 "moe_router": "routed", "moe_routed": "routed", "moe_shared": "mlp", "mlp": "mlp",
                 "norm": "norm", "head_loss": "head_loss", "embed": "head_loss",
                 # where XLA's own grouped matmul runs (``lax.ragged_dot``) it names the kernel after itself and the
                 # scope path is gone (``families/solar_open2.py`` has the story); only the routed experts call it
                 "ragged": "routed"}

# ---- limits of ``check_serving`` (readings: my chip runs, PR 34; PERF.md §6) ----
# Each reading is taken with the reference following the program's choice of experts (``check_serving`` says why), at
# the published widths: bfloat16 weights, matmul operands and cached rows; float32 residual stream, norms, gates and
# state. The change's readings are ten seeds of the cell under the check as it stands (three prompts of 8,711, 1,543
# and 259 tokens, all 48 slots decoding; runs r1, r2 and r3), and agree with the eight of the first round's two-prompt
# check to the second figure. The other side of each limit is a control (``planted``, at the end of this file): the
# same program with one thing wrong, one run of the cell each.
#
# Logits of the program's decode forward against the float32 reference, relative RMS over 17 positions of a prompt:
# 2.18e-2 to 2.28e-2, the deep prompt the lowest (1.95e-2 to 2.04e-2: its last rows attend 8.7 k others and average
# their errors). Solar's cell reads 1.7e-2 with four layers behind the logits; here five, each adding two post-normed
# terms of unit size whatever their own accuracy; with the residual stream held in bfloat16 the same program read
# 2.57e-2 and 2.70e-2. Half as much again; a dropped layer or a norm's scale off by two moves them by tens of percent
# (the CPU suite plants the second). Latent rows rounded to float8 read 6.0e-2 (every later token attends the coarse
# rows), a state not zeroed at admission 0.37; the shifted rotation reads 2.24e-2, as if nothing were wrong.
SERVE_LOGIT_REL_RMS = 3.4e-2
# The latent layer's cached rows against the reference's, the worse of the normalised latent ``c`` and the rotated key
# ``k_r``, each its own relative RMS: two norms and two matmuls from the embeddings, one bfloat16 layer behind them.
# 6.57e-3 to 6.65e-3 (both parts and all three prompts read alike: float32 angles at position 8,727 cost nothing that
# shows beside a bfloat16 row). Half as much again. Rows rounded to float8 (three bits of mantissa) read 2.73e-2; a
# rotation one position on turns the fast pairs by up to a radian and reads 0.24, on the key alone, with the latent
# and the logits where they were (rotary scores see differences of positions only); a state not zeroed at admission
# reads 0.21 (the first rows of the prompt come out of a dirty first layer). All three fail here, the second here alone.
SERVE_CACHE_REL_RMS = 1.0e-2
# The first delta-net layer's state matrix after the last decode step against the reference's token-by-token
# recurrence: 3.50e-3 to 3.64e-3 over 30 readings (ten seeds, three prompts) — the layer is the model's first, its
# inputs have the embedding and one norm behind them, so what reads here is the recurrence's own arithmetic on bfloat16
# q, k, v. The control, the state rounded to bfloat16 after every chunk and step (the nearest precision below its
# float32): 4.50e-3 to 4.57e-3. The limit lies between, 13 % over the largest reading and 9 % under the control's
# lowest, where seeds move either by 1 to 2 %. What it does not see: a state that admission did not zero reads 3.57e-3,
# because the heads' decay has forgotten the slot's last request within the shortest prompt's 259 tokens — that fault
# shows in the rows and the logits (above).
SERVE_STATE_REL_RMS = 4.1e-3
# The share of the state's elements that are exactly bfloat16 numbers (low 16 bits of the float32 zero): 4e-5 to 5e-5
# for a float32 state, 1.0 in the bfloat16 control: the precision the state is *held* in, read on the state itself.
SERVE_STATE_ON_BF16_GRID = 0.5
# A served token must be one the reference rates within 2^-5 of the row's largest magnitude below its best (the GPT
# and Solar families' margin). Readings 3.0e-3 to 1.67e-2 (46 to 50 of 51 served tokens are the reference's argmax):
# 1.9 times the largest. The logits' error is 0.56 % of a row's largest magnitude, so the margin is four standard
# deviations of the difference of two logits' errors. The float8 control reads 2.5e-2 and 3.7e-2 (two runs), a state
# not zeroed 0.19.
SERVE_TOKEN_TIE = 2.0 ** -5
# The reference follows the program's choice of experts where its own eighth and ninth scores tie within the
# program's rounding: the lowest sigmoid score among the program's eight may lie this far under the reference's own
# eighth best. Readings 5.0e-3 to 1.06e-2 (the worst of 10.5 k rows x 4 layers a run). Twice that; the float8 control
# reads 2.6e-2, a state not zeroed 0.96 (an expert picked from wrong rows lies anywhere under).
SERVE_ROUTING_TIE = 2.0e-2

_COLUMNS = 4096     # columns of a weight the reference casts to float32 at a time
_HEADS = 8          # attention heads the reference projects and scores at a time
_ROWS = 512         # query rows whose scores against every key it holds at a time


# ---------------------------------------------------------------- shapes
def dims(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file: ``V`` and
    ``held`` are what is held *here*; ``E`` is the router's width (the
    published count where ``n_routed_experts`` is reduced)."""
    E = int(config.get("published", {}).get("n_routed_experts", config["n_routed_experts"]))
    held = config.get("held_experts") or [0, int(config["n_routed_experts"])]
    rope = dict(config.get("rope_scaling") or {})
    layers, dense = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    # ``L`` counts the layers that hold experts: what ``layer_metrics/routed_experts_hit_pct.py`` divides the hits by
    return dict(D=int(config["hidden_size"]), layers=layers, L=layers - dense,
                full=tuple(int(i) for i in config["full_attention_layers"]), dense=dense,
                H=int(config["num_attention_heads"]), q_rank=int(config["q_lora_rank"]), rank=int(config["kv_lora_rank"]),
                dn=int(config["qk_nope_head_dim"]), dr=int(config["qk_rope_head_dim"]), dv=int(config["v_head_dim"]),
                Hk=int(config["linear_num_key_heads"]), Hv=int(config["linear_num_value_heads"]),
                dk=int(config["linear_key_head_dim"]), dl=int(config["linear_value_head_dim"]),
                K=int(config["linear_conv_kernel_dim"]), V=int(config["vocab_size"]), Fd=int(config["intermediate_size"]),
                F=int(config["moe_intermediate_size"]), E=E, held=(int(held[0]), int(held[1])),
                shared=int(config["n_shared_experts"]), top_k=int(config["num_experts_per_tok"]),
                norm_topk=bool(config["norm_topk_prob"]), scale=float(config["routed_scaling_factor"]),
                limit=config.get("swiglu_limit"), eps=float(config["rms_norm_eps"]),
                o_eps=float(config["linear_attn_o_norm_eps"]), norm_gate=float(config["layernorm_gating_weight"]),
                out_gate=float(config["linear_sigmoid_gate_scale"]), theta=float(config["rope_theta"]),
                rope=tuple(sorted(rope.items())), mla_scaling=bool(config.get("use_mla_scaling_factor", True)))


def weight_shapes(config: dict) -> Dict[str, tuple]:
    z = dims(config)
    D, L, H = z["D"], z["layers"], z["H"]
    Lm, Lg, Le, Ld = len(z["full"]), L - len(z["full"]), z["L"], z["dense"]
    conv, zed = 2 * z["Hk"] * z["dk"] + z["Hv"] * z["dl"], z["Hv"] * z["dl"]
    return {
        "embed": (z["V"], D), "head": (z["V"], D), "final_norm": (D,),
        "norm_pre1": (L, D), "norm_post1": (L, D), "norm_pre2": (L, D), "norm_post2": (L, D),
        "mla_q_down": (Lm, D, z["q_rank"]), "mla_q_norm": (Lm, z["q_rank"]),
        "mla_q_up": (Lm, z["q_rank"], H * (z["dn"] + z["dr"])), "mla_kv_down": (Lm, D, z["rank"] + z["dr"]),
        "mla_kv_norm": (Lm, z["rank"]), "mla_k_up": (Lm, z["rank"], H * z["dn"]), "mla_v_up": (Lm, z["rank"], H * z["dv"]),
        "mla_gate": (Lm, D, H * z["dv"]), "mla_out": (Lm, H * z["dv"], D),
        "gdn_in": (Lg, D, conv + zed), "gdn_ab": (Lg, D, 2 * z["Hv"]), "gdn_conv": (Lg, z["K"], conv),
        "gdn_dt_bias": (Lg, z["Hv"]), "gdn_a_log": (Lg, z["Hv"]), "gdn_out_norm": (Lg, z["dl"]), "gdn_out": (Lg, zed, D),
        "dense_gate_up": (Ld, D, 2 * z["Fd"]), "dense_down": (Ld, z["Fd"], D),
        "router": (Le, D, z["E"]),
        "experts_gate_up": (Le, z["held"][1], D, 2 * z["F"]), "experts_down": (Le, z["held"][1], z["F"], D),
        "shared_gate_up": (Le, D, 2 * z["F"] * z["shared"]), "shared_down": (Le, z["F"] * z["shared"], D),
    }


def param_count(config: dict) -> int:
    return int(sum(math.prod(s) for s in weight_shapes(config).values()))


def share_dims(z: dict, share: int, shares: int) -> dict:
    """``dims`` of share ``share`` of ``shares`` equal shares of the model
    ``z``: its experts and its rows of the vocabulary (the mixers, the dense
    FFN and the shared expert are whole on every chip)."""
    first, count = z["held"]
    return dict(z, V=z["V"] // shares, held=(first + share * (count // shares), count // shares))


def share_weights(z: dict, w: dict, share: int, shares: int) -> dict:
    """The weights share ``share`` holds of the whole model's ``w``: its
    experts and its rows of the vocabulary; everything else whole."""
    rows, per_e = z["V"] // shares, z["held"][1] // shares
    out = {k: (tuple(np.asarray(a) for a in v) if isinstance(v, tuple) else np.asarray(v)) for k, v in w.items()}
    out["embed"], out["head"] = out["embed"][share * rows:(share + 1) * rows], out["head"][share * rows:(share + 1) * rows]
    for k in ("experts_gate_up", "experts_down"):
        out[k] = tuple(a[share * per_e:(share + 1) * per_e] for a in out[k])
    return out


# ---------------------------------------------------------------- the program's model
def build_model(config: dict, seed: int, dtype: str, mesh=None):
    """The program's model at the configuration's sizes with weights made on
    the device from the seed, in ``dtype`` (the decay's parameters float32)."""
    from paddle_tpu.models.gigachat3_5 import GigaChat35Config, GigaChat35ForCausalLM

    if mesh is not None:
        raise NotImplementedError("the gigachat3_5 family serves on one chip: no mesh")
    return GigaChat35ForCausalLM(GigaChat35Config.from_config_file(config), seed=seed, dtype=dtype)


def weights_of_engine(engine) -> dict:
    """The served weights as plain arrays in ``weight_shapes``' layout."""
    return dict(engine._params)


# ---------------------------------------------------------------- reference
class _Layer:
    """Entry ``i`` of a stack of weights (a layer, an expert), cut out only
    where it is indexed: ``stack[i][:, a:b]`` would copy the whole entry out
    first (0.8 GB of the dense FFN, 0.35 GB of a delta-net input projection)."""

    def __init__(self, stack, i: int):
        self.stack, self.i, self.shape = stack, int(i), tuple(stack.shape[1:])

    def __getitem__(self, index):
        return self.stack[(self.i,) + (index if isinstance(index, tuple) else (index,))]


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a[...] if isinstance(a, _Layer) else a, jnp.float32)


def _settled(x):
    """``x``, computed: eager dispatch runs ahead of the device, and every
    block that is queued holds its float32 copy of a weight until it has run
    (2.7 GB at once without this, my chip runs, PR 34)."""
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
    """``x / rms(x) * g sigmoid(w)`` (``ZeroCenteredGatedNorm``, assumed (2))."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + z["eps"]) * (z["norm_gate"] * jax.nn.sigmoid(_f32(w)))


def rope_inv_freq(z: dict) -> np.ndarray:
    """The rotation's frequencies ``[dr / 2]`` (float64): ``theta^(-2i/dr)``,
    under YaRN divided by ``factor`` for the pairs that turn less than
    ``beta_slow`` times over the original context, kept for those that turn
    more than ``beta_fast`` times, a linear ramp between."""
    dr, theta, s = z["dr"], z["theta"], dict(z["rope"])
    freq = theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    if s.get("type", s.get("rope_type")) != "yarn":
        return freq
    turns_at = lambda n: dr * math.log(s["original_max_position_embeddings"] / (n * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(turns_at(s["beta_fast"])), 0), min(math.ceil(turns_at(s["beta_slow"])), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq / s["factor"] * ramp + freq * (1.0 - ramp)


def softmax_scale(z: dict) -> float:
    s = dict(z["rope"])
    scale = (z["dn"] + z["dr"]) ** -0.5
    if z["mla_scaling"] and s.get("type", s.get("rope_type")) == "yarn" and s["factor"] > 1:
        scale *= (0.1 * s.get("mscale_all_dim", 0) * math.log(s["factor"]) + 1.0) ** 2
    return scale


def _rotate(z, x, positions):
    """``x [s, ..., dr]`` with pair ``(2i, 2i + 1)`` of row ``t`` turned by
    ``positions[t] * inv_freq[i]`` (``rope_interleave``)."""
    import jax.numpy as jnp

    angle = np.asarray(positions, np.float64)[:, None] * rope_inv_freq(z)[None]           # [s, dr/2], float64 on the host
    shape = (len(positions),) + (1,) * (x.ndim - 2) + (z["dr"] // 2,)
    cos, sin = _f32(np.cos(angle).reshape(shape)), _f32(np.sin(angle).reshape(shape))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def reference_mla(z: dict, lw: dict, x):
    """The latent-attention mixer on one sequence ``x [s, D]`` (already
    normalised), un-absorbed: ``(y [s, D], rows [s, rank + dr])`` with ``rows``
    what a slot caches of each token, ``[c | rotated k_r]``. A few heads at a
    time from their up-projections on, and their scores a block of query rows
    at a time against every key, so that a sequence of 8,711 tokens fits beside
    the served model."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    H, dn, dr, dv, rank = z["H"], z["dn"], z["dr"], z["dv"], z["rank"]
    positions = np.arange(s)
    c_q = _norm(z, _times(x, lw["mla_q_down"]), lw["mla_q_norm"])
    ckv = _times(x, lw["mla_kv_down"])
    c, k_r = _norm(z, ckv[:, :rank], lw["mla_kv_norm"]), _rotate(z, ckv[:, rank:], positions)
    q_up, k_up, v_up = lw["mla_q_up"][...].reshape(-1, H, dn + dr), lw["mla_k_up"][...].reshape(rank, H, dn), lw["mla_v_up"][...].reshape(rank, H, dv)
    heads = []
    for h in range(0, H, _HEADS):
        hs, blocks = slice(h, h + _HEADS), []
        q = jnp.einsum("sc,chd->shd", c_q, _f32(q_up[:, hs]))
        q_nope, q_rope = q[..., :dn], _rotate(z, q[..., dn:], positions)
        k_nope, v = jnp.einsum("sc,chd->shd", c, _f32(k_up[:, hs])), jnp.einsum("sc,chd->shd", c, _f32(v_up[:, hs]))
        for r in range(0, s, _ROWS):
            rs = slice(r, r + _ROWS)
            scores = (jnp.einsum("qhd,khd->hqk", q_nope[rs], k_nope) + jnp.einsum("qhr,kr->hqk", q_rope[rs], k_r)) * softmax_scale(z)
            causal = jnp.asarray(positions[rs, None] >= positions[None, :])[None]
            prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            blocks.append(_settled(jnp.einsum("hqk,khd->qhd", prob, v)))
        heads.append(jnp.concatenate(blocks, axis=0))
    o = jnp.concatenate(heads, axis=1).reshape(s, H * dv)
    return _times(o * jax.nn.sigmoid(_times(x, lw["mla_gate"])), lw["mla_out"]), jnp.concatenate([c, k_r], axis=-1)


def reference_gdn(z: dict, lw: dict, x, state_after: Optional[int] = None):
    """The gated-delta-net mixer on one sequence ``x [s, D]``, the recurrence
    token by token from an empty state: ``(y [s, D], S [Hv, dk, dl])`` with
    ``S`` the state after ``state_after`` tokens (default: all)."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    Hk, Hv, dk, dl, K = z["Hk"], z["Hv"], z["dk"], z["dl"], z["K"]
    conv_ch = 2 * Hk * dk + Hv * dl

    def conv(first, last):
        """SiLU of the depthwise causal convolution over channels ``first`` to ``last`` of the input projection."""
        h = _times(x, lw["gdn_in"][:, first:last])
        padded = jnp.concatenate([jnp.zeros((K - 1, last - first), jnp.float32), h], axis=0)
        return _settled(jax.nn.silu(sum(padded[j:j + s] * _f32(lw["gdn_conv"][j, first:last]) for j in range(K))))

    l2 = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = l2(conv(0, Hk * dk).reshape(s, Hk, dk)) * dk ** -0.5, l2(conv(Hk * dk, 2 * Hk * dk).reshape(s, Hk, dk))
    v = jnp.concatenate([conv(i, min(i + _COLUMNS, conv_ch)) for i in range(2 * Hk * dk, conv_ch, _COLUMNS)], axis=-1).reshape(s, Hv, dl)
    ab = _times(x, lw["gdn_ab"])
    alpha = jnp.exp(-jnp.exp(_f32(lw["gdn_a_log"])) * jax.nn.softplus(ab[:, :Hv] + _f32(lw["gdn_dt_bias"])))   # [s, Hv] in (0, 1)
    beta = jax.nn.sigmoid(ab[:, Hv:])
    keep_at = s if state_after is None else int(state_after)

    def token(carry, xs):
        S, kept = carry
        t, q_t, k_t, v_t, a_t, b_t = xs
        q_t, k_t = jnp.repeat(q_t, Hv // Hk, axis=0), jnp.repeat(k_t, Hv // Hk, axis=0)   # a key head, its value heads
        S = a_t[:, None, None] * S                                                   # alpha S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return (S, jnp.where(t + 1 == keep_at, S, kept)), jnp.einsum("hkv,hk->hv", S, q_t)

    zero = jnp.zeros((Hv, dk, dl), jnp.float32)
    (_, kept), o = jax.lax.scan(token, (zero, zero), (jnp.arange(s), q, k, v, alpha, beta))
    del q, k, v                                                                      # 0.6 GB of a sequence of 8,711 tokens
    o = _settled(o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + z["o_eps"]) * (1.0 + _f32(lw["gdn_out_norm"])))
    o = _settled(o.reshape(s, Hv * dl) * (z["out_gate"] * jax.nn.sigmoid(_times(x, lw["gdn_in"][:, conv_ch:]))))
    return _times(o, lw["gdn_out"]), kept


def _gated(z, x, w_gate_up, w_down):
    """``W_down(silu(min(g, L)) * clip(u, -L, L))``, the intermediate width a
    block at a time (the dense FFN's is 18,432)."""
    import jax
    import jax.numpy as jnp

    f, L = w_down.shape[0], z["limit"]
    out = jnp.zeros((x.shape[0], w_down.shape[1]), jnp.float32)
    for i in range(0, f, _COLUMNS):
        j = min(i + _COLUMNS, f)
        g, u = x @ _f32(w_gate_up[:, i:j]), x @ _f32(w_gate_up[:, f + i:f + j])
        if L is not None:
            g, u = jnp.minimum(g, L), jnp.clip(u, -L, L)
        out = _settled(out + (jax.nn.silu(g) * u) @ _f32(w_down[i:j]))
    return out


def reference_moe(z: dict, lw: dict, x, shared: bool = True, chosen=None):
    """The expert layer on rows ``x [s, D]``: every expert of the router is
    scored, the ``top_k`` largest chosen and their weights normalised and
    scaled; the experts held here (``z["held"]``) that some token chose add
    their part, one at a time; the shared expert is added once (``shared``).
    Returns ``(y [s, D], shortfall)``.

    ``chosen [s, k]`` is another's word on which experts each row takes (the
    program's, computed in bfloat16, where the eighth and ninth scores of 256
    lie within its rounding of each other for a few rows in a hundred): the
    reference then scores with its own router, takes *those* experts with its
    own scores as weights, and reports as ``shortfall`` how far the lowest of
    them lies under its own ``top_k``-th best score (0 where the choices
    agree). The caller holds that to a limit: a tie may fall either way, a
    wrong router may not."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(x @ _f32(lw["router"]))
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
            out = out + w_e[:, None] * _gated(z, x, _Layer(lw["experts_gate_up"], e - first), _Layer(lw["experts_down"], e - first))
    if shared:
        out = out + _gated(z, x, lw["shared_gate_up"], lw["shared_down"])
    return out, shortfall


def _layer_weights(weights: dict, prefix, i: int) -> dict:
    """Layer ``i`` of every stack named ``prefix…`` (the experts: one array a layer, as the program holds them)."""
    return {k: (weights[k][i] if isinstance(weights[k], (tuple, list)) else _Layer(weights[k], i)) for k in weights if k.startswith(prefix)}


def reference_forward(config_or_dims, weights: dict, ids, rows_from: int = 0, state_after: Optional[int] = None,
                      routing=None) -> dict:
    """One sequence through the model: ``logits [s - rows_from, V]`` (float32)
    of the rows from ``rows_from``, the first latent layer's cache rows
    ``latent [s, rank + dr]`` and the first delta-net layer's state after
    ``state_after`` tokens; with ``routing [Le, s, k]`` (``reference_moe``'s
    ``chosen``, an expert layer) also ``routing_shortfall``, the worst over
    layers and rows. Layer by layer, a weight cast to float32 a block of
    columns at a time and the experts one at a time, so a share at the
    published widths fits beside the served model."""
    import jax
    import jax.numpy as jnp

    z = config_or_dims if "full" in config_or_dims else dims(config_or_dims)
    out = {"latent": None, "state": None, "routing_shortfall": 0.0}
    with jax.default_matmul_precision("highest"):
        h = _f32(jnp.asarray(weights["embed"])[jnp.asarray(ids, jnp.int32)])
        mi = gi = 0
        for layer in range(z["layers"]):
            x = _norm(z, h, weights["norm_pre1"][layer])
            if layer in z["full"]:
                y, rows = reference_mla(z, _layer_weights(weights, "mla_", mi), x)
                if mi == 0:
                    out["latent"] = rows
                mi += 1
            else:
                y, state = reference_gdn(z, _layer_weights(weights, "gdn_", gi), x, state_after)
                if gi == 0:
                    out["state"] = state
                gi += 1
            h = h + _norm(z, y, weights["norm_post1"][layer])
            x = _norm(z, h, weights["norm_pre2"][layer])
            if layer < z["dense"]:
                y = _gated(z, x, _Layer(weights["dense_gate_up"], layer), _Layer(weights["dense_down"], layer))
            else:
                ei = layer - z["dense"]
                y, shortfall = reference_moe(z, _layer_weights(weights, ("router", "experts_", "shared_"), ei), x,
                                             chosen=None if routing is None else routing[ei])
                out["routing_shortfall"] = max(out["routing_shortfall"], shortfall)
            h = h + _norm(z, y, weights["norm_post2"][layer])
        h = _norm(z, h[rows_from:], weights["final_norm"])
        out["logits"] = _times(h, jnp.asarray(weights["head"]).T)
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
    """Bytes the routed path of one decode step has to read: every expert
    layer's router and the weights of the experts its tokens hit (counted by
    the program in the traced ticks). None where nothing was counted."""
    z = dims(config)
    hit = experts_hit_per_step(records)
    if hit is None:
        return None
    return hit * expert_bytes(config, bytes_per_value) + z["L"] * z["D"] * z["E"] * bytes_per_value


def latent_row_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """What a slot caches of one token in one latent layer: ``[c | k_r]``."""
    z = dims(config)
    return (z["rank"] + z["dr"]) * bytes_per_value


def latent_row_flops(config: dict) -> int:
    """Operations absorbed decode attention needs for one cached row of one
    slot: every head's score over the row (``rank + dr`` products) and its
    weighted sum of the row's latent (``rank``), two operations a product."""
    z = dims(config)
    return 2 * (z["rank"] + z["dr"] + z["rank"]) * z["H"]


def latent_step_floor_s(config: dict, live_rows: float, peaks: dict) -> float:
    """The least time the chip could take over the latent layers' attention of
    one decode step with ``live_rows`` cached tokens in all: the larger of the
    rows' bytes at the peak bandwidth and their operations at the peak rate
    (the bytes, at 121 FLOP a byte against a ridge of 240). The same count
    whatever implements the core."""
    n = len(dims(config)["full"]) * live_rows
    return max(n * latent_row_bytes(config) / peaks["hbm_bytes_per_s"], n * latent_row_flops(config) / peaks["bf16_flops_per_s"])


def decode_step_bytes(config: dict, live_rows: float, records=None, bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read (and, for the state, write): the
    weights outside the routed experts, the head, the live rows of the latent
    layers' cache, the decoding slots' recurrent state read and written, and
    the weights of the experts hit in the traced ticks. Where the program
    counted nothing (no ``records``), every held expert counts."""
    z = dims(config)
    D, H = z["D"], z["H"]
    Lm, Lg, Le = len(z["full"]), z["layers"] - len(z["full"]), z["L"]
    conv, zed = 2 * z["Hk"] * z["dk"] + z["Hv"] * z["dl"], z["Hv"] * z["dl"]
    mla = (D * z["q_rank"] + z["q_rank"] + z["q_rank"] * H * (z["dn"] + z["dr"]) + D * (z["rank"] + z["dr"]) + z["rank"]
           + z["rank"] * H * (z["dn"] + z["dv"]) + 2 * D * H * z["dv"])
    gdn = D * (conv + zed) + D * 2 * z["Hv"] + z["K"] * conv + 2 * z["Hv"] + z["dl"] + zed * D
    weights = (Lm * mla + Lg * gdn + z["dense"] * 3 * D * z["Fd"] + Le * 3 * D * z["F"] * z["shared"]
               + z["layers"] * 4 * D + z["V"] * D + D)
    rows = Lm * (z["rank"] + z["dr"]) * live_rows
    decoding = None if records is None else \
        _mean([records.tick_decoding[i] for i in records.in_trace(records.tick_end) if records.tick_decoding[i]])
    if decoding is None:
        decoding = int(config["serving"]["slots"])
    state = 2.0 * decoding * Lg * (z["Hv"] * z["dk"] * z["dl"] * 4 + (z["K"] - 1) * conv * bytes_per_value)
    routed = None if records is None else routed_step_bytes(config, records, bytes_per_value)
    if routed is None:
        routed = Le * (z["held"][1] * expert_bytes(config, bytes_per_value) + D * z["E"] * bytes_per_value)
    return bytes_per_value * (weights + rows) + state + routed


def _mean(values):
    return sum(values) / len(values) if values else None


# ---------------------------------------------------------------- correct
def check_serving(engine, config: dict, seed: int, n_decode: int = 16) -> dict:
    """Three seeded prompts — eight chunks and a final chunk (8,711 tokens at
    the cell's chunk of 1,024: seventeen blocks of the decode kernel, positions
    where the rotation's float32 angles are coarsest), a chunk and a final
    chunk, a final chunk alone — through the engine's own prefill, every other
    slot filled with a short seeded prompt so that each decode step runs with
    the whole batch live, and ``n_decode`` decode steps, on slots the window's
    traffic has used (so a state that admission did not zero shows).

    A recurrent state cannot be probed after the fact, so before each decode
    step — and once after the last — the program's own decode forward runs on
    the engine's buffers and gives the logits of the token about to be
    consumed: ``n_decode + 1`` positions a prompt, compared with the
    reference's full forward over the share (relative RMS). The probe hands
    the buffers back with the recurrent state and the tails as they were and
    the latent caches with the probed token's row written (the step proper
    writes the same row again): a copy of the buffers would not fit beside
    them. The engine hands out tokens, not logits: what its own decode program
    computed is held to the reference through each served token, which must be
    within ``SERVE_TOKEN_TIE`` of the reference's best. And what the slots hold
    is compared: the first latent layer's rows ``[c | rotated k_r]``, and the
    first delta-net layer's matrix state after the last step, against the
    reference's.

    **Routing.** With seeded weights a router's eighth and ninth scores of 256
    lie within bfloat16 rounding of each other for several rows in a hundred,
    and a row that takes another expert than the reference's is off by that
    expert's whole contribution (``families/solar_open2.py`` has the
    readings). So the reference is told which experts the program took — the
    prompt's rows from the program's chunk forward replayed on the slot with
    the engine's own chunking (``chunk_routing``), the decoded rows from the
    probe (``decode_probe``) — scores them with its own router, weighs them
    with its own scores, and reports how far the lowest lies under its own
    eighth best (``routing_below_kth``, held to ``SERVE_ROUTING_TIE``): a tie
    may fall either way, a wrong router may not. What is left is precision."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import gigachat3_5 as program

    z = dims(config)
    chunk = engine._chunk or 64
    rng = np.random.default_rng([int(seed), 7])
    deep = min(8 * chunk + chunk // 2 + 7, int(config["serving"]["context"]) - n_decode - 8)
    lengths = [deep, chunk + chunk // 2 + 7, max(8, chunk // 4 + 3)]
    engine.reset()
    prompts = [rng.integers(0, z["V"], (n,)).astype(np.int32) for n in lengths]
    fillers = {slot: rng.integers(0, z["V"], (max(4, chunk // 8 + slot % 5),)).astype(np.int32)
               for slot in range(len(prompts), engine.max_batch_slots)}
    cfg = engine._dec.cfg
    n_lat = len(z["full"])

    # Both run at the engine's own shapes — every slot's buffers, the engine's batch — so that what they compute
    # is, op for op, what the engine's programs computed: a forward one slot wide rounds a matmul's sums in
    # another order, and one element of 7,168 a token lands on the other side of a bfloat16 rounding.
    @functools.partial(jax.jit, donate_argnums=(1,))
    def probe(params, cache, tok, pos, active):
        logits, experts, after = program.decode_probe(cfg, params, cache, tok, pos, active)
        kept = tuple(after[:n_lat]) + tuple(cache[n_lat:])                 # the state and the tails as they were
        return logits[:len(prompts)].astype(jnp.float32), experts[:, :len(prompts)], kept

    @functools.partial(jax.jit, donate_argnums=(1,))
    def replay(params, cache, ids, slot, start, n_valid):
        return program.chunk_routing(cfg, params, cache, ids, slot, start, n_valid)

    def prompt_routing(prompt, slot):
        """Which experts the program's chunk forward takes for each row of the prompt, ``[Le, n, k]``: the engine's
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
    # buffer 0: the first latent layer's rows [B, S, rank + dr padded to lanes]; buffer n_lat: the first delta-net layer's state
    rows_cache = np.asarray(engine._cache[0][:len(prompts), :max(lengths) + n_decode + 1].astype(jnp.float32))
    state_cache = np.asarray(engine._cache[n_lat][:len(prompts)].astype(jnp.float32))
    # how much of the state is exactly a bfloat16 number: 2^-16 of a float32 state, all of one held in bfloat16
    on_bf16_grid = float(np.mean((state_cache.view(np.uint32) & 0xFFFF) == 0))
    worst_max = worst_tie = worst_cache = worst_routing = 0.0
    agree = rows = 0
    by_prompt, by_position, state_by_prompt, cache_parts = [], [], [], []
    rel = lambda g, w: float(np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2)))  # noqa: E731
    for slot, (prompt, toks) in enumerate(zip(prompts, served)):
        n = len(prompt)
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])           # the last token is probed, not consumed
        written = len(seq) - 1                                              # tokens the slot's state has taken in
        routing = np.concatenate([routing_of_prompt[slot], np.stack(probed_routing[slot], axis=1)], axis=1)   # [Le, s, k]
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
        # the probe after the last step wrote the last token's row too: all of ``seq`` is cached
        rows_want, rows_got = np.asarray(ref["latent"]), rows_cache[slot][:len(seq)]
        width = z["rank"] + z["dr"]                                         # the cache pads its rows to whole lanes beyond
        parts = [rel(rows_got[:, :z["rank"]], rows_want[:, :z["rank"]]), rel(rows_got[:, z["rank"]:width], rows_want[:, z["rank"]:])]
        cache_parts.append(parts)
        worst_cache = max(worst_cache, *parts)
        state_by_prompt.append(rel(state_cache[slot], np.asarray(ref["state"])))
    engine.reset()
    worst_rms, worst_state = max(by_prompt), max(state_by_prompt)
    numbers = (worst_rms, worst_max, worst_cache, worst_state, worst_tie, worst_routing)
    finite = bool(all(np.isfinite(v) for v in numbers))
    return {"correct": bool(finite and worst_rms <= SERVE_LOGIT_REL_RMS and worst_tie <= SERVE_TOKEN_TIE
                            and worst_cache <= SERVE_CACHE_REL_RMS and worst_state <= SERVE_STATE_REL_RMS
                            and worst_routing <= SERVE_ROUTING_TIE and on_bf16_grid <= SERVE_STATE_ON_BF16_GRID),
            "logit_rel_rms": worst_rms, "logit_rel_max": worst_max, "cache_rel_rms": worst_cache,
            "state_rel_rms": worst_state, "token_below_best": worst_tie, "routing_below_kth": worst_routing,
            "state_on_bf16_grid": on_bf16_grid, "cache_rel_rms_latent_and_key_by_prompt": cache_parts,
            "logit_rel_rms_by_position": by_position, "state_rel_rms_by_prompt": state_by_prompt,
            "logit_rel_rms_by_prompt": by_prompt, "tokens_equal_reference_argmax": agree, "positions": rows,
            "prompt_lengths": lengths, "slots_decoding": len(prompts) + len(fillers),
            "compared": {"logit_rel_rms": [worst_rms, SERVE_LOGIT_REL_RMS],
                         "cache_rel_rms": [worst_cache, SERVE_CACHE_REL_RMS],
                         "state_rel_rms": [worst_state, SERVE_STATE_REL_RMS],
                         "token_below_best": [worst_tie, SERVE_TOKEN_TIE],
                         "routing_below_kth": [worst_routing, SERVE_ROUTING_TIE],
                         "state_on_bf16_grid": [on_bf16_grid, SERVE_STATE_ON_BF16_GRID]}}


# ---------------------------------------------------------------- the check's controls
# Each is the same program with one thing wrong, planted from outside it (the program has no switch for any of them),
# and ``check_serving`` has to say not correct. ``python3 -m benchmark.families.gigachat3_5 <control> --workload
# gigachat3.5-432b-a28b.serve-longdoc --seed <n> --seconds <s> --trace 0`` is one run of the cell with one planted, on
# the chip through ``chiprun``; ``tests/benchmark_suite/test_gigachat3_5_cell.py`` plants each at tiny widths.
CONTROLS = ("latent_rows_held_in_float8", "rotation_off_by_one_position", "state_held_in_bfloat16", "state_not_reset_at_admission")


@contextlib.contextmanager
def planted(control: str):
    """The program with ``control`` wrong until the block ends: the latent
    rows rounded to float8 (e4m3, the nearest precision below bfloat16) on
    their way into the cache; every rotation one position on; the delta
    net's state rounded to bfloat16 (the nearest precision below its float32)
    after every chunk and step; a slot's recurrent state and tail left as the
    last request left them. The engine's store of executables is keyed by
    configuration and shapes, not by program text, so it is off meanwhile: a
    planted program neither loads the sound one nor leaves itself under its
    key."""
    import jax

    from paddle_tpu.inference import aot_cache
    from paddle_tpu.models import gigachat3_5 as program
    from paddle_tpu.ops import rope

    project, angles = program._mla_project, rope.rope_angles
    step, chunked = program.delta_rule_step, program.delta_rule_chunked

    def coarse_state(rule):
        def wrong(*args, **kwargs):
            o, state = rule(*args, **kwargs)
            return o, jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)   # a cast there and back is simplified away
        return wrong

    def coarse_rows(cfg, lp, x, positions):
        q_nope, q_rope, row = project(cfg, lp, x, positions)
        return q_nope, q_rope, row.astype("float8_e4m3fn").astype(row.dtype)

    wrong = {"latent_rows_held_in_float8": [(program, "_mla_project", coarse_rows)],
             "rotation_off_by_one_position": [(rope, "rope_angles", lambda t, inv_freq: angles(t + 1, inv_freq))],
             "state_held_in_bfloat16": [(program, "delta_rule_step", coarse_state(step)),
                                        (program, "delta_rule_chunked", coarse_state(chunked))],
             "state_not_reset_at_admission": [(program, "_admitting", lambda start: False)]}[control]
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
