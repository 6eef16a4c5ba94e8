"""Granite 4.0-H (``model_type: granitemoehybrid``): a pre-norm decoder whose
layers mix by a Mamba-2 state-space layer nine times in ten and by one
grouped-query softmax-attention layer with no positions the tenth, each
followed by a sparse expert layer — served, whole or as *one chip's share* of
an expert-parallel group, through the serving engine.

    h      = embedding_multiplier * E[id]
    layer:   h = h + residual_multiplier * Mixer(RMSNorm(h))
             h = h + residual_multiplier * (Routed(u) + Shared(u)),  u = RMSNorm(h)
    logits = (RMSNorm(h) @ E^T) / logits_scaling                     (E tied)

- **Mamba-2 mixer** (``layer_types[i] == "mamba"``; ``ops/ssd.py`` has the
  recurrence): ``[z | xBC | dt] = W_in x``; ``xBC`` through a depthwise causal
  convolution over time (kernel ``mamba_d_conv``, with bias) and SiLU, then
  split ``[x (H x P) | B (G x N) | C (G x N)]``; ``dt = softplus(dt +
  dt_bias)`` and ``a = exp(dt * A)``, ``A = -exp(A_log)``, one scalar a head;
  ``S_t = a_t S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; ``out =
  W_out RMSNorm(y * silu(z))`` — the gate first, then one norm over all ``H x
  P`` channels. A slot's state is one float32 matrix ``[P, N]`` a head and the
  last ``mamba_d_conv - 1`` inputs of the convolution.
- **Attention layer**: ``Hq`` query heads on ``Hkv`` key/value heads of
  ``head_dim``, no bias, no positions, causal; the scores are scaled by
  ``attention_multiplier``, not by ``head_dim^-1/2``; no output gate.
- **Experts**: the ``num_experts_per_tok`` largest of the router's *logits*,
  weights a softmax over those; the experts *held here* (``held_experts =
  (first, count)``) compute their part, dropless (``ops/moe_dropless.py``), and
  one shared gated MLP of ``shared_intermediate_size`` is added.

One chip's share holds some of the experts and rows ``[0, vocab_size)`` of the
tied table (the embedding's and the head's alike); mixers, norms, routers and
shared MLPs are whole (the deployment runs them data-parallel). What the
absent experts would have added is left out and the partial result goes on to
the next layer (the model-configs guide, section 4). Nothing here stands in
for absent chips.

The walk over the layers, the admission reset, the attention layer's
projections and its decode accessor are ``models/solar_open2.py``'s, taken with
this family's factors; a chunk's attention is this family's own
(:func:`_attn_chunk`: blocked over the context).

Serving: :class:`GraniteMoeHybridDecoder` is the model's face to
``DecodeEngine`` (``models/decoder.py``): per slot, key/value rows for the
attention layers (no reset at admission) and, a Mamba layer, the matrix state
and the convolution's tail (zeroed inside the slot's first prefill program).
Weights, matmul operands, the convolution's inputs (so the tails) and the
cached rows take the model's dtype; the residual stream, ``dt``, the decay,
the state, the convolution's sum and the gated norm are float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..ops.ssd import causal_conv, ssd_chunked, ssd_step
from .decoder import BufferSpec, Decoder
from .solar_open2 import _admitting, _gqa_decode, _gqa_out, _gqa_project, _layer, _layers, _moe, _rms_norm

__all__ = ["GraniteMoeHybridConfig", "GraniteMoeHybridForCausalLM", "GraniteMoeHybridDecoder"]

_ATTN_BLOCK = 1024    # rows of the context a chunk's attention scores at a time


class GraniteMoeHybridConfig:
    """Sizes of the model, or of the share of it held here: ``vocab_size`` and
    ``held_experts`` are what *this* holder has; ``router_experts`` is the
    router's full width."""

    def __init__(self, *, hidden_size: int, layer_types: Sequence[str], num_attention_heads: int,
                 num_key_value_heads: int, head_dim: Optional[int] = None, mamba_n_heads: int, mamba_d_head: int,
                 mamba_d_state: int, mamba_n_groups: int = 1, mamba_d_conv: int = 4, mamba_chunk_size: int = 256,
                 vocab_size: int, intermediate_size: int, shared_intermediate_size: int, router_experts: int,
                 held_experts: Optional[Tuple[int, int]] = None, num_experts_per_tok: int = 10,
                 embedding_multiplier: float = 1.0, residual_multiplier: float = 1.0,
                 attention_multiplier: Optional[float] = None, logits_scaling: float = 1.0, rms_norm_eps: float = 1e-5,
                 max_position_embeddings: int = 1 << 17):
        self.hidden_size = int(hidden_size)
        self.layer_types = tuple(str(t) for t in layer_types)
        self.num_hidden_layers = len(self.layer_types)
        self.num_attention_heads, self.num_key_value_heads = int(num_attention_heads), int(num_key_value_heads)
        self.head_dim = int(head_dim) if head_dim else self.hidden_size // self.num_attention_heads
        self.mamba_n_heads, self.mamba_d_head = int(mamba_n_heads), int(mamba_d_head)
        self.mamba_d_state, self.mamba_n_groups = int(mamba_d_state), int(mamba_n_groups)
        self.mamba_d_conv, self.mamba_chunk_size = int(mamba_d_conv), int(mamba_chunk_size)
        self.vocab_size = int(vocab_size)
        self.intermediate_size, self.shared_intermediate_size = int(intermediate_size), int(shared_intermediate_size)
        self.router_experts = int(router_experts)
        self.held_experts = (0, self.router_experts) if held_experts is None else (int(held_experts[0]), int(held_experts[1]))
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.embedding_multiplier, self.residual_multiplier = float(embedding_multiplier), float(residual_multiplier)
        self.attention_multiplier = self.head_dim ** -0.5 if attention_multiplier is None else float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_position_embeddings = int(max_position_embeddings)
        # what the shared expert layer (``solar_open2._moe``) asks of a configuration: a softmax over the chosen sums to one
        self.norm_topk_prob, self.routed_scaling_factor = False, 1.0
        if any(t not in ("mamba", "attention") for t in self.layer_types):
            raise ValueError(f"layer_types {sorted(set(self.layer_types))}: 'mamba' or 'attention'")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of the key/value heads")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("Mamba heads are no multiple of the groups")
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.router_experts):
            raise ValueError(f"held_experts {self.held_experts} outside the router's {self.router_experts}")

    @classmethod
    def from_config_file(cls, cfg: dict) -> "GraniteMoeHybridConfig":
        """From a configuration file of the benchmark: the source's keys at
        the top level; ``num_local_experts`` is what is held here, the router's
        width is ``router_experts`` (the published count)."""
        return cls(
            hidden_size=cfg["hidden_size"], layer_types=cfg["layer_types"], num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg.get("head_dim"),
            mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"], mamba_d_state=cfg["mamba_d_state"],
            mamba_n_groups=cfg["mamba_n_groups"], mamba_d_conv=cfg["mamba_d_conv"], mamba_chunk_size=cfg["mamba_chunk_size"],
            vocab_size=cfg["vocab_size"], intermediate_size=cfg["intermediate_size"],
            shared_intermediate_size=cfg["shared_intermediate_size"],
            router_experts=cfg.get("router_experts", cfg["num_local_experts"]), held_experts=cfg.get("held_experts"),
            num_experts_per_tok=cfg["num_experts_per_tok"], embedding_multiplier=cfg["embedding_multiplier"],
            residual_multiplier=cfg["residual_multiplier"], attention_multiplier=cfg["attention_multiplier"],
            logits_scaling=cfg["logits_scaling"], rms_norm_eps=cfg["rms_norm_eps"],
            max_position_embeddings=cfg["max_position_embeddings"])

    # ------------------------------------------------------------- layout
    @property
    def gqa_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == "attention")

    @property
    def linear_layers(self) -> Tuple[int, ...]:
        """The Mamba layers, under the name the shared walk knows them by."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "mamba")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        """What the convolution runs over: ``x``, ``B`` and ``C`` together."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def fingerprint(self) -> tuple:
        return ("granitemoehybrid",) + tuple(v for _, v in sorted(vars(self).items()))

    def weight_shapes(self) -> Dict[str, tuple]:
        """Every weight by name. Per-layer weights are stacked: ``[L, ...]``
        over all layers for the norms, the router, the experts and the shared
        MLP, over the attention layers for ``attn_*`` and over the Mamba layers
        for ``ssm_*``. ``embed`` is the head too."""
        D, L, F, Fs = self.hidden_size, self.num_hidden_layers, self.intermediate_size, self.shared_intermediate_size
        La, Lm = len(self.gqa_layers), len(self.linear_layers)
        q, kv = self.num_attention_heads * self.head_dim, self.num_key_value_heads * self.head_dim
        H, inner, conv = self.mamba_n_heads, self.mamba_inner, self.conv_channels
        return {
            "embed": (self.vocab_size, D), "final_norm": (D,),
            "norm1": (L, D), "norm2": (L, D), "router": (L, D, self.router_experts),
            "experts_gate_up": (L, self.held_experts[1], D, 2 * F), "experts_down": (L, self.held_experts[1], F, D),
            "shared_gate_up": (L, D, 2 * Fs), "shared_down": (L, Fs, D),
            "attn_q": (La, D, q), "attn_kv": (La, D, 2 * kv), "attn_out": (La, q, D),
            "ssm_in": (Lm, D, inner + conv + H), "ssm_conv": (Lm, self.mamba_d_conv, conv), "ssm_conv_bias": (Lm, conv),
            "ssm_a_log": (Lm, H), "ssm_d": (Lm, H), "ssm_dt_bias": (Lm, H), "ssm_norm": (Lm, inner),
            "ssm_out": (Lm, inner, D),
        }


# kept float32 whatever the model's dtype: the recurrence's own parameters
F32_WEIGHTS = ("ssm_a_log", "ssm_d", "ssm_dt_bias")
# held as one array a layer (a tuple over the layers, indexed like a stack): the grouped matmul takes a layer's experts
# whole, and a slice of a stack would be copied out for it
PER_LAYER_WEIGHTS = ("experts_gate_up", "experts_down")
_NORMS = ("final_norm", "norm1", "norm2", "ssm_norm")


def init_weights(cfg: GraniteMoeHybridConfig, seed: int, dtype: str = "bfloat16"):
    """Every weight from ``seed``, on the device, in ``dtype``, in one jitted
    call. Matrices N(0, 0.02); norm scales 1 + N(0, 0.02) (so that a dropped
    scale shows); the convolution's taps and bias U(-K^-1/2, K^-1/2), the
    lineage's default for a depthwise convolution of kernel ``K`` (at N(0,
    0.02) its output, so ``x``, ``B`` and ``C``, would be a fortieth of its
    input and the recurrence one part in seventy of the mixer's output beside
    the skip ``D x``: nothing downstream would see the state); ``D = 1``;
    ``A_log = log U(1, 16)`` and ``dt_bias = softplus^-1(exp(U(log 1e-3, log
    0.1)))``: the decay starts near 1 and the state carries history."""
    make = _weight_maker(tuple(sorted(cfg.weight_shapes().items())), str(dtype))
    return make(jax.random.key(int(seed) % (2 ** 31 - 1)))


@functools.lru_cache(maxsize=None)
def _weight_maker(shapes: tuple, dtype: str):
    dt = jnp.dtype(dtype)
    taps = dict(shapes)["ssm_conv"][1]

    def one(name, shape, k):
        if name == "ssm_a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))  # noqa: PTA304 (jax.random, a key folded from the seed)
        if name == "ssm_d":
            return jnp.ones(shape, jnp.float32)
        if name == "ssm_dt_bias":
            dt0 = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(1e-3), jnp.log(0.1)))  # noqa: PTA304 (jax.random, a key folded from the seed)
            return dt0 + jnp.log(-jnp.expm1(-dt0))                      # softplus^-1
        if name in ("ssm_conv", "ssm_conv_bias"):
            bound = taps ** -0.5
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound).astype(dt)  # noqa: PTA304 (jax.random, a key folded from the seed)
        if name in PER_LAYER_WEIGHTS:
            return tuple(one("", shape[1:], jax.random.fold_in(k, layer)) for layer in range(shape[0]))
        w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        if name in _NORMS:
            w = 1.0 + w
        return w.astype(dt)

    def make(key):
        return {shapes[i][0]: one(shapes[i][0], shapes[i][1], jax.random.fold_in(key, i)) for i in range(len(shapes))}

    return jax.jit(make)


# ------------------------------------------------------------------ pieces
def _ssm_project(cfg, lp, x):
    """The Mamba layer's input projection of rows ``x [T, D]``: the gate's
    argument ``z [T, H P]`` (float32), the convolution's input ``xBC [T,
    conv_channels]`` (``x``'s dtype) and ``dt [T, H]`` after its softplus
    (float32)."""
    inner, conv = cfg.mamba_inner, cfg.conv_channels
    with jax.named_scope("ssm_proj"):
        h = jnp.matmul(x, lp["ssm_in"], preferred_element_type=jnp.float32)
        dt = jax.nn.softplus(h[:, inner + conv:] + lp["ssm_dt_bias"])
    return h[:, :inner], h[:, inner:inner + conv].astype(x.dtype), dt


def _ssm_conv(cfg, lp, window):
    """The convolution over ``window [..., K - 1 + T, conv_channels]`` and the
    split of what it gives: ``x [..., T, H, P]``, ``B``, ``C`` ``[..., T, G,
    N]``, float32."""
    inner, G, N = cfg.mamba_inner, cfg.mamba_n_groups, cfg.mamba_d_state
    with jax.named_scope("ssm_conv"):
        y = causal_conv(window, lp["ssm_conv"], lp["ssm_conv_bias"])
        lead = y.shape[:-1]
        return (y[..., :inner].reshape(lead + (cfg.mamba_n_heads, cfg.mamba_d_head)),
                y[..., inner:inner + G * N].reshape(lead + (G, N)), y[..., inner + G * N:].reshape(lead + (G, N)))


def _ssm_out(cfg, lp, y, z, dtype):
    """``W_out RMSNorm(y * silu(z))`` for ``y [T, H, P]`` float32: the gate
    first, then one norm over all ``H P`` channels."""
    with jax.named_scope("ssm_norm"):
        o = y.reshape(y.shape[0], -1) * jax.nn.silu(z)
        o = _rms_norm(o, lp["ssm_norm"], cfg.rms_norm_eps).astype(dtype)
    with jax.named_scope("ssm_out"):
        return jnp.matmul(o, lp["ssm_out"])


def _ssm_chunk(cfg, lp, x, state, tail, n_valid):
    """The Mamba mixer over ``C`` tokens ``x [C, D]`` of one sequence, from
    ``state [H, P, N]`` (float32) and the convolution's ``tail [K - 1,
    conv_channels]``. Rows at ``n_valid`` and after are padding: they decay
    nothing, write nothing and leave the tail alone. Returns ``(y [C, D],
    state, tail)``."""
    K = cfg.mamba_d_conv
    z, xbc, dt = _ssm_project(cfg, lp, x)
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=0)                  # [K-1+C, channels]
    xs, B, C = _ssm_conv(cfg, lp, window)
    with jax.named_scope("ssm_core"):
        y, state = ssd_chunked(xs, dt, -jnp.exp(lp["ssm_a_log"]), B, C, lp["ssm_d"], state, n_valid,
                               chunk=cfg.mamba_chunk_size)
    with jax.named_scope("ssm_conv"):
        tail = jax.lax.dynamic_slice_in_dim(window, n_valid, K - 1, axis=0).astype(tail.dtype)
    return _ssm_out(cfg, lp, y, z, x.dtype), state, tail


def _ssm_decode(cfg, lp, x, state, tail, active):
    """The Mamba mixer for one token of every slot: ``x [B, D]``, ``state [B,
    H, P, N]``, ``tail [B, K - 1, conv_channels]``. A slot that is not active
    keeps its state and tail bitwise."""
    z, xbc, dt = _ssm_project(cfg, lp, x)
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc[:, None]], axis=1)         # [B, K, channels]
    xs, B, C = _ssm_conv(cfg, lp, window)
    with jax.named_scope("ssm_core"):
        y, state = ssd_step(xs[:, 0], dt, -jnp.exp(lp["ssm_a_log"]), B[:, 0], C[:, 0], lp["ssm_d"], state, active)
    with jax.named_scope("ssm_conv"):
        tail = jnp.where(active[:, None, None], window[:, 1:].astype(tail.dtype), tail)
    return _ssm_out(cfg, lp, y, z, x.dtype), state, tail


def _attn_chunk(cfg, lp, x, ck, cv, li, slot, start):
    """The attention mixer over ``C`` tokens ``x [C, D]`` of one slot at
    ``start`` against the stacked cache ``[La, B, Hkv, S, d]``: the chunk's keys
    and values are written in place, then every row attends the slot's rows up
    to its own, a block of the context at a time with an online softmax —
    blocks past ``start + C`` are never read, and the scores of the whole
    context (32 heads x 1,024 x 8,192 float32: 1.07 GB) are never held. A
    key/value head's ``G`` query heads ride one matmul as ``G C`` rows (a
    batched ``[Hkv, G C, d] x [Hkv, block, d]``: as ``chgd,hsd->hgcs`` at 8
    key/value heads XLA ran the contraction on the vector unit, 100 ms a
    chunk). Returns ``(y [C, D], ck, cv)``."""
    C = x.shape[0]
    Hkv, d, S = cfg.num_key_value_heads, cfg.head_dim, ck.shape[3]
    G, bk = cfg.num_attention_heads // Hkv, min(_ATTN_BLOCK, S)
    q, k, v, gate = _gqa_project(cfg, lp, x)
    with jax.named_scope("cache_write"):
        ck = jax.lax.dynamic_update_slice(ck, jnp.swapaxes(k, 0, 1)[None, None], (li, slot, 0, start, 0))
        cv = jax.lax.dynamic_update_slice(cv, jnp.swapaxes(v, 0, 1)[None, None], (li, slot, 0, start, 0))
    with jax.named_scope("attn_core"):
        q = (q * jnp.asarray(cfg.attention_multiplier, q.dtype)).transpose(1, 2, 0, 3).reshape(Hkv, G * C, d)
        q_pos = start + jnp.tile(jnp.arange(C, dtype=jnp.int32), G)                 # row g C + c is token c

        def block(i, carry):
            m, l, acc = carry
            with jax.named_scope("cache_read"):
                kb = jax.lax.dynamic_slice(ck, (li, slot, 0, i * bk, 0), (1, 1, Hkv, bk, d))[0, 0]
                vb = jax.lax.dynamic_slice(cv, (li, slot, 0, i * bk, 0), (1, 1, Hkv, bk, d))[0, 0]
            scores = jnp.einsum("hmd,hsd->hms", q, kb, preferred_element_type=jnp.float32)
            k_pos = i * bk + jnp.arange(bk, dtype=jnp.int32)
            scores = jnp.where(k_pos[None, None, :] <= q_pos[None, :, None], scores, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))         # finite from block 0 on: row 0 is visible
            prob, shrink = jnp.exp(scores - m_new), jnp.exp(m - m_new)
            acc = acc * shrink + jnp.einsum("hms,hsd->hmd", prob.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
            return m_new, l * shrink + jnp.sum(prob, axis=-1, keepdims=True), acc

        init = (jnp.full((Hkv, G * C, 1), -jnp.inf, jnp.float32), jnp.zeros((Hkv, G * C, 1), jnp.float32),
                jnp.zeros((Hkv, G * C, d), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (start + C + bk - 1) // bk, block, init)
        att = (acc / l).reshape(Hkv, G, C, d).transpose(2, 0, 1, 3).reshape(C, -1)
    return _gqa_out(lp, att, gate, x.dtype), ck, cv


def _head(cfg, p, h):
    """``RMSNorm(h) @ E^T / logits_scaling`` over the rows of the table held."""
    with jax.named_scope("norm"):
        h = _rms_norm(h, p["final_norm"], cfg.rms_norm_eps).astype(p["embed"].dtype)
    with jax.named_scope("head_loss"):
        return jnp.matmul(h, p["embed"].T, preferred_element_type=jnp.float32) / cfg.logits_scaling


def _walk(cfg, p, cache, ids, gqa, linear, routed):
    """``solar_open2._layers`` with this family's expert layer and factors;
    the residual stream float32."""
    return _layers(cfg, p, cache, ids, gqa, linear, routed, moe=functools.partial(_moe, scoring="softmax_topk"),
                   embed_scale=cfg.embedding_multiplier, residual_scale=cfg.residual_multiplier, stream_dtype=jnp.float32)


def _chunk_forward(cfg: GraniteMoeHybridConfig, p: dict, cache, ids, slot, start, n_valid, want_rows, routed=None):
    """``C`` tokens ``ids [C]`` of slot ``slot`` at ``start`` through every
    layer. At ``start == 0`` the slot is being admitted: its state and tail
    start from zero, whatever an earlier request left there. ``want_rows``:
    ``None`` (no logits), a traced row index (that row's logits ``[1, V]``) or
    ``"all"`` (``[C, V]``). Returns ``(logits | None, cache)``."""
    fresh = _admitting(start)

    def gqa(gi, x, ck, cv):
        return _attn_chunk(cfg, _layer(p, "attn_", gi), x, ck, cv, gi, slot, start)

    def linear(mi, x, states, tails):
        st = jax.lax.dynamic_slice_in_dim(states, slot, 1, axis=0)[0]
        tl = jax.lax.dynamic_slice_in_dim(tails, slot, 1, axis=0)[0]
        st, tl = jnp.where(fresh, 0.0, st), jnp.where(fresh, jnp.zeros_like(tl), tl)
        y, st, tl = _ssm_chunk(cfg, _layer(p, "ssm_", mi), x, st, tl, n_valid)
        return (y, jax.lax.dynamic_update_slice(states, st[None], (slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(tails, tl[None], (slot, 0, 0)))

    h, cache, _ = _walk(cfg, p, cache, ids, gqa, linear, routed)
    if want_rows is None:
        return None, cache
    if not isinstance(want_rows, str):
        h = jax.lax.dynamic_slice_in_dim(h, want_rows, 1, axis=0)
    return _head(cfg, p, h), cache


def _decode_forward(cfg: GraniteMoeHybridConfig, p: dict, cache, tok, pos, active, routed=None):
    """One token of every slot: ``tok``, ``pos`` ``[B]``; writes gated by
    ``active``. Returns ``(logits [B, V], cache, stats int32[2])`` with the
    routed experts' load summed over the layers."""
    def gqa(gi, x, ck, cv):
        return _gqa_decode(cfg, _layer(p, "attn_", gi), x, ck, cv, gi, pos, active, scale=cfg.attention_multiplier)

    def linear(mi, x, state, tail):
        return _ssm_decode(cfg, _layer(p, "ssm_", mi), x, state, tail, active)

    h, cache, stats = _walk(cfg, p, cache, tok, gqa, linear, routed)
    return _head(cfg, p, h), cache, stats


def chunk_routing(cfg: GraniteMoeHybridConfig, p: dict, cache, ids, slot, start, n_valid):
    """The chunk forward of the engine's prefill programs, also saying which
    experts every row chose in every layer: ``(cache, experts [L, C, k])``.
    For a comparison that has to follow the program's routing where two
    logits tie within rounding (``benchmark/families/granite_moe_hybrid.py``)."""
    routed = []
    _, cache = _chunk_forward(cfg, p, cache, ids, slot, start, n_valid, None, routed)
    return cache, jnp.stack(routed)


def decode_probe(cfg: GraniteMoeHybridConfig, p: dict, cache, tok, pos, active):
    """The decode forward of the engine's decode program with its routing:
    ``(logits [B, V], experts [L, B, k], cache)``."""
    routed = []
    logits, cache, _ = _decode_forward(cfg, p, cache, tok, pos, active, routed)
    return logits, jnp.stack(routed), cache


# ------------------------------------------------------------------ decoder
class GraniteMoeHybridDecoder(Decoder):
    """The model through the serving engine's interface."""

    recurrent = True
    n_stats = 2
    stat_counters = ("infer.moe.assignments_local", "infer.moe.experts_hit")

    def __init__(self, model: "GraniteMoeHybridForCausalLM"):
        self.cfg = model.cfg
        self._weights = model.weights
        self.vocab_size = model.cfg.vocab_size
        self.max_positions = model.cfg.max_position_embeddings
        self.dtype = model.weights["embed"].dtype

    def params(self, int8: bool = False):
        if int8:
            raise NotImplementedError("GraniteMoeHybrid has no int8 weights")
        return dict(self._weights)

    def fingerprint(self) -> tuple:
        return self.cfg.fingerprint()

    def buffer_specs(self, slots: int, rows: int, kv_dtype=None):
        c = self.cfg
        B, La, Lm = int(slots), len(c.gqa_layers), len(c.linear_layers)
        kv = (La, B, c.num_key_value_heads, int(rows), c.head_dim)
        dt = str(self.dtype)
        # the state and the tail are one buffer a Mamba layer: a decode step rewrites each whole, and a stack of them
        # would be copied to be rebuilt
        return (BufferSpec("k", kv, dt, 1, False), BufferSpec("v", kv, dt, 1, False),
                *(BufferSpec(f"ssm_state{i}", (B, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state), "float32", 0, True)
                  for i in range(Lm)),
                *(BufferSpec(f"conv_tail{i}", (B, c.mamba_d_conv - 1, c.conv_channels), dt, 0, True) for i in range(Lm)))

    def prefill(self, p, cache, ids, length, slot):
        # a whole padded prompt: one chunk at start 0, into the fresh slot
        return _chunk_forward(self.cfg, p, cache, ids[0], slot, jnp.int32(0), length, length - 1)

    def chunk(self, p, cache, ids, slot, start, last_row=None):
        C = ids.shape[1]
        n_valid = jnp.int32(C) if last_row is None else last_row + 1
        return _chunk_forward(self.cfg, p, cache, ids[0], slot, start, n_valid, last_row)

    def decode(self, p, cache, tok, pos, active):
        return _decode_forward(self.cfg, p, cache, tok, pos, active)


# -------------------------------------------------------------------- model
class GraniteMoeHybridForCausalLM(nn.Layer):
    """The model (or one chip's share of it) with its weights as plain device
    arrays under ``weights`` (``GraniteMoeHybridConfig.weight_shapes`` names
    them); made from ``seed`` unless given."""

    def __init__(self, cfg: GraniteMoeHybridConfig, seed: int = 0, dtype: str = "bfloat16", weights: Optional[dict] = None):
        super().__init__()
        self.cfg = cfg
        self.weights = init_weights(cfg, seed, dtype) if weights is None else dict(weights)

    def decoder(self) -> GraniteMoeHybridDecoder:
        """What the serving engine runs this model through."""
        return GraniteMoeHybridDecoder(self)

    def forward(self, input_ids):
        """Logits ``[b, s, V]`` (float32) of whole sequences, each from an
        empty state: the chunk forward over a scratch cache."""
        from ..framework.core import unwrap
        from ..tensor._helpers import _wrap_value

        ids = jnp.asarray(unwrap(input_ids), jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        scratch = self.decoder().alloc(1, ids.shape[1])
        one = lambda row: _chunk_forward(self.cfg, self.weights, scratch, row, jnp.int32(0), jnp.int32(0),  # noqa: E731
                                         jnp.int32(ids.shape[1]), "all")[0]
        return _wrap_value(jnp.stack([one(row) for row in ids]))
