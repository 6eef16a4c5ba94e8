"""EvaByte (``model_type: evabyte``): a byte-level pre-norm decoder whose every
layer mixes by EVA attention — exact inside blocks of ``window_size`` rows,
one softmax-pooled key/value row for each chunk of ``chunk_size`` rows of the
windows before — served, whole or as one pipeline stage, through the serving
engine.

    h      = E[id]                                               (float32 stream)
    layer:   u = RMSNorm1(h);  q, k, v = u W_q, u W_k, u W_v      (q, k rotated at their position)
             h = h + EVA(q, k, v) W_o
             h = h + W_down(silu(u' W_gate) * u' W_up),  u' = RMSNorm2(h)
    logits = RMSNorm_f(h) W_head0                                (float32; untied)

RMSNorm carries a unit offset: ``x / rms(x) * (1 + w)``. Rotary positions are
half-split pairs (``ops/rope.py``, ``rotate_half``). Full multi-head: as many
key/value heads as query heads.

**EVA** (``ops/eva_attention.py`` has the decode step): for head ``h`` and
chunk ``c`` (rows ``c C .. c C + C - 1``), ``alpha = softmax_m(phi_h . k_m /
sqrt(d))``, ``k~_c = sum alpha k_m``, ``v~_c = sum alpha v_m`` over the *rotated*
keys as the cache holds them. The query at ``t`` in window ``w = t // W``
attends, with one softmax of ``q . k / sqrt(d)``, its window's rows up to
itself and the summaries of every chunk of the windows before. A window is a
block (a multiple of ``W`` starts an empty one), not a sliding window.

Serving: :class:`EvaByteDecoder` is the model's face to ``DecodeEngine``
(``models/decoder.py``). Per slot and layer a *window ring* of ``W`` exact
rows (row ``t mod W``) and a *summary table* of one row a chunk of the context
(row ``t // C``), keys and values each. Neither is reset at admission: a ring
row is written before the window attends it, and a summary row before any
later window does. A chunk's summary is formed once, when its last row is
written, from the rows in the ring — by a prefill chunk for the chunks it
completes, by the decode step for a chunk a decode token closes — and a final
chunk's padding forms none. The table cannot be rebuilt from what the ring
later holds, so the decoder is ``recurrent`` for the engine's refusals (prefix
cache, draft, int8 cache). Weights, matmul operands and cached rows take the
model's dtype; the residual stream, the norms, the pooling softmax and its
sums, the attention softmax and its running sums, and the logits are float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..ops import rope as _rope
from ..ops.eva_attention import eva_decode
from .decoder import BufferSpec, Decoder

__all__ = ["EvaByteConfig", "EvaByteForCausalLM", "EvaByteDecoder"]

_ATTN_BLOCK = 1024   # rows of the ring or of the table a chunk's attention scores at a time
PHI_STD = 2.0        # the pooling vectors' seeded scale (the configuration's ``assumed``): a chunk's weights uneven


class EvaByteConfig:
    """Sizes of the model, or of the pipeline stage held here
    (``num_hidden_layers``)."""

    def __init__(self, *, hidden_size: int, num_hidden_layers: int, num_attention_heads: int, intermediate_size: int,
                 vocab_size: int, window_size: int, chunk_size: int, num_key_value_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, rope_theta: float = 100000.0, rms_norm_eps: float = 1e-5,
                 max_position_embeddings: int = 32768, init_std: float = 0.01275):
        self.hidden_size, self.num_hidden_layers = int(hidden_size), int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads or num_attention_heads)
        self.head_dim = int(head_dim) if head_dim else self.hidden_size // self.num_attention_heads
        self.intermediate_size, self.vocab_size = int(intermediate_size), int(vocab_size)
        self.window_size, self.chunk_size = int(window_size), int(chunk_size)
        self.rope_theta, self.rms_norm_eps = float(rope_theta), float(rms_norm_eps)
        self.max_position_embeddings, self.init_std = int(max_position_embeddings), float(init_std)
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("EVA attention here is full multi-head: num_key_value_heads == num_attention_heads")
        if self.window_size % self.chunk_size or self.max_position_embeddings % self.window_size:
            raise ValueError(f"chunk_size {self.chunk_size} must divide window_size {self.window_size}, and that "
                             f"max_position_embeddings {self.max_position_embeddings}")

    @classmethod
    def from_config_file(cls, cfg: dict) -> "EvaByteConfig":
        """From a configuration file of the benchmark: the source's keys at the
        top level."""
        return cls(hidden_size=cfg["hidden_size"], num_hidden_layers=cfg["num_hidden_layers"],
                   num_attention_heads=cfg["num_attention_heads"], num_key_value_heads=cfg.get("num_key_value_heads"),
                   head_dim=cfg.get("head_dim"), intermediate_size=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
                   window_size=cfg["window_size"], chunk_size=cfg["chunk_size"], rope_theta=cfg["rope_theta"],
                   rms_norm_eps=cfg["rms_norm_eps"], max_position_embeddings=cfg["max_position_embeddings"],
                   init_std=cfg.get("init_std", 0.01275))

    def fingerprint(self) -> tuple:
        return ("evabyte",) + tuple(v for _, v in sorted(vars(self).items()))

    def weight_shapes(self) -> Dict[str, tuple]:
        """Every weight by name; per-layer weights stacked ``[L, ...]``.
        ``attn_qkv`` is ``[D, 3, H, d]`` flattened (queries, keys, values);
        ``mlp_gate_up`` gate then up; ``head`` is head 0 (the next byte)."""
        D, L, F, V = self.hidden_size, self.num_hidden_layers, self.intermediate_size, self.vocab_size
        H, d = self.num_attention_heads, self.head_dim
        return {"embed": (V, D), "head": (D, V), "final_norm": (D,), "norm1": (L, D), "norm2": (L, D),
                "attn_qkv": (L, D, 3 * H * d), "attn_out": (L, H * d, D), "eva_phi": (L, H, d),
                "mlp_gate_up": (L, D, 2 * F), "mlp_down": (L, F, D)}


_NORMS = ("final_norm", "norm1", "norm2")
# as the other families name theirs: no weight is kept float32 whatever the dtype, and none is held as one array a layer
F32_WEIGHTS = ()
PER_LAYER_WEIGHTS = ()


def init_weights(cfg: EvaByteConfig, seed: int, dtype: str = "bfloat16"):
    """Every weight from ``seed``, on the device, in ``dtype``, in one jitted
    call: matrices N(0, ``init_std``); the norms' offsets ``w`` N(0, 0.02) (so
    that the unit offset and the weight both show); the pooling vectors
    N(0, ``PHI_STD``), so that a chunk's sixteen weights are uneven."""
    make = _weight_maker(tuple(sorted(cfg.weight_shapes().items())), str(dtype), cfg.init_std)
    return make(jax.random.key(int(seed) % (2 ** 31 - 1)))


@functools.lru_cache(maxsize=None)
def _weight_maker(shapes: tuple, dtype: str, std: float):
    dt = jnp.dtype(dtype)

    def one(name, shape, k):
        scale = 0.02 if name in _NORMS else PHI_STD if name == "eva_phi" else std
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    def make(key):
        return {shapes[i][0]: one(shapes[i][0], shapes[i][1], jax.random.fold_in(key, i)) for i in range(len(shapes))}

    return jax.jit(make)


# ------------------------------------------------------------------ pieces
def _norm(cfg, h, w):
    """``h / rms(h) * (1 + w)`` in float32."""
    h = h.astype(jnp.float32)
    with jax.named_scope("norm"):
        return h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + cfg.rms_norm_eps) * (1.0 + w.astype(jnp.float32))


def _layer(p: dict, i: int) -> dict:
    return {k: v[i] for k, v in p.items() if v.ndim >= 2 and k not in ("embed", "head")}


def _angles(cfg, positions):
    """``(cos, sin)`` ``[..., d / 2]`` of integer ``positions``."""
    return _rope.rope_angles(positions, _rope.yarn_inv_freq(cfg.head_dim, cfg.rope_theta))


def _qkv(cfg, lp, x, cos, sin):
    """Rows ``x [T, D]`` (float32, normalised) -> ``q, k, v [T, H, d]`` in the
    weights' dtype, ``q`` and ``k`` rotated."""
    H, d = cfg.num_attention_heads, cfg.head_dim
    with jax.named_scope("eva_qkv"):
        qkv = jnp.matmul(x.astype(lp["attn_qkv"].dtype), lp["attn_qkv"], preferred_element_type=jnp.float32)
        # the product is formed whole before it is cut into q, k, v and halves: XLA otherwise moves those slices into
        # the weight's columns, and a layer's slice of the stacked weight is then copied out and re-laid every step
        # (two 100-MB copies a layer at the cell's widths)
        qkv = jax.lax.optimization_barrier(qkv).reshape(x.shape[0], 3, H, d)
        q = _rope.rotate(qkv[:, 0], cos[:, None], sin[:, None], pairs="half")
        k = _rope.rotate(qkv[:, 1], cos[:, None], sin[:, None], pairs="half")
        dt = lp["attn_qkv"].dtype
        return q.astype(dt), k.astype(dt), qkv[:, 2].astype(dt)


def _out(lp, att):
    """``att [T, H d]`` through ``W_o``, float32."""
    with jax.named_scope("eva_out"):
        return jnp.matmul(att.astype(lp["attn_out"].dtype), lp["attn_out"], preferred_element_type=jnp.float32)


def _mlp(lp, u):
    """``W_down(silu(u W_gate) * u W_up)`` of normalised rows ``u`` (float32)."""
    with jax.named_scope("mlp"):
        f = lp["mlp_down"].shape[0]
        gu = jnp.matmul(u.astype(lp["mlp_gate_up"].dtype), lp["mlp_gate_up"], preferred_element_type=jnp.float32)
        a = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(lp["mlp_down"].dtype)
        return jnp.matmul(a, lp["mlp_down"], preferred_element_type=jnp.float32)


def _pool(cfg, lp):
    """The pooling vectors of a layer, ``phi / sqrt(d)`` ``[H, d]`` float32."""
    return lp["eva_phi"].astype(jnp.float32) / math.sqrt(cfg.head_dim)


def _summaries_attended(cfg, positions):
    """How many summary rows a query at each of ``positions`` attends: one for
    every chunk of the windows before its own."""
    return (positions // cfg.window_size) * (cfg.window_size // cfg.chunk_size)


def _head(cfg, p, h):
    """``RMSNorm_f(h) W_head0``, float32."""
    u = _norm(cfg, h, p["final_norm"]).astype(p["head"].dtype)
    with jax.named_scope("head_loss"):
        return jnp.matmul(u, p["head"], preferred_element_type=jnp.float32)


def _embed(p, ids):
    with jax.named_scope("embed"):
        return jnp.take(p["embed"], ids, axis=0).astype(jnp.float32)


# ------------------------------------------------------------------ a chunk of one slot
def _ring_write(rk, rv, k, v, li, slot, first, n_valid):
    """Rows ``k``, ``v`` ``[C, H, d]`` into ring rows ``first ..`` of layer
    ``li`` of ``slot``; rows at ``n_valid`` and after (padding) keep what the
    ring held."""
    C = k.shape[0]
    keep = (jnp.arange(C) < n_valid)[None, None, None, :, None]

    def put(buf, new):
        old = jax.lax.dynamic_slice(buf, (li, slot, 0, first, 0), (1, 1) + buf.shape[2:3] + (C,) + buf.shape[4:])
        new = jnp.swapaxes(new, 0, 1)[None, None].astype(buf.dtype)
        return jax.lax.dynamic_update_slice(buf, jnp.where(keep, new, old), (li, slot, 0, first, 0))

    return [put(rk, k), put(rv, v)]


def _summarise(cfg, lp, rk, rv, sk, sv, li, slot, start, first, C, n_valid):
    """The summaries of the chunks that the ``C`` rows from ``start`` (ring
    rows ``first ..``) complete among their first ``n_valid``: each from its
    rows as the ring holds them, pooled in float32, into the table; a chunk
    the rows leave open keeps its table row (a decode step closes it)."""
    ch, H, d = cfg.chunk_size, rk.shape[2], rk.shape[4]
    n, hi = C // ch, jax.lax.Precision.HIGHEST
    with jax.named_scope("eva_summarise"):
        kc, vc = (jax.lax.dynamic_slice(buf, (li, slot, 0, first, 0), (1, 1, H, C, d))[0, 0].astype(jnp.float32).reshape(H, n, ch, d)
                  for buf in (rk, rv))
        alpha = jax.nn.softmax(jnp.einsum("hncd,hd->hnc", kc, _pool(cfg, lp), precision=hi), axis=-1)
        complete = ((jnp.arange(n) + 1) * ch <= n_valid)[None, :, None]

        def put(buf, rows):
            summary = jnp.einsum("hnc,hncd->hnd", alpha, rows, precision=hi).astype(buf.dtype)
            old = jax.lax.dynamic_slice(buf, (li, slot, 0, start // ch, 0), (1, 1, H, n, d))[0, 0]
            return jax.lax.dynamic_update_slice(buf, jnp.where(complete, summary, old)[None, None], (li, slot, 0, start // ch, 0))

        return [put(sk, kc), put(sv, vc)]


def _chunk_attend(cfg, q, rk, rv, sk, sv, li, slot, first, n_sum):
    """Queries ``q [C, H, d]`` at ring rows ``first ..`` against the slot's
    first ``n_sum`` summaries and its ring rows up to each query's own, a
    block of ``_ATTN_BLOCK`` rows at a time with one online softmax: no block
    past either count is read, and no score of the whole context is held.
    Returns ``[C, H d]`` float32."""
    C, H, d = q.shape
    qh, q_row, scale = jnp.swapaxes(q, 0, 1), first + jnp.arange(C), 1.0 / math.sqrt(d)

    def attend(bk_, bv_, size, n_blocks, visible, carry):
        bk = min(_ATTN_BLOCK, size)

        def block(i, carry):
            m, l, acc = carry
            s0 = jnp.minimum(i * bk, size - bk)                          # a last block moved back inside the buffer
            kb = jax.lax.dynamic_slice(bk_, (li, slot, 0, s0, 0), (1, 1, H, bk, d))[0, 0]
            vb = jax.lax.dynamic_slice(bv_, (li, slot, 0, s0, 0), (1, 1, H, bk, d))[0, 0]
            scores = jnp.einsum("hmd,hsd->hms", qh, kb, preferred_element_type=jnp.float32) * scale
            idx = s0 + jnp.arange(bk)
            scores = jnp.where(visible(idx) & (idx >= i * bk)[None], scores, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            prob, shrink = jnp.exp(scores - m_new), jnp.exp(m - m_new)
            acc = acc * shrink + jnp.einsum("hms,hsd->hmd", prob.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
            return m_new, l * shrink + jnp.sum(prob, axis=-1, keepdims=True), acc

        return jax.lax.fori_loop(0, n_blocks(bk), block, carry)

    R, W = sk.shape[3], rk.shape[3]
    init = (jnp.full((H, C, 1), -jnp.inf, jnp.float32), jnp.zeros((H, C, 1), jnp.float32), jnp.zeros((H, C, d), jnp.float32))
    carry = attend(sk, sv, R, lambda bk: (n_sum + bk - 1) // bk, lambda idx: (idx < n_sum)[None], init)
    _, l, acc = attend(rk, rv, W, lambda bk: (first + C + bk - 1) // bk, lambda idx: idx[None] <= q_row[:, None], carry)
    return jnp.swapaxes(acc / l, 0, 1).reshape(C, H * d)


def _eva_chunk(cfg, lp, x, cache, li, slot, start, n_valid, cos, sin):
    """The EVA mixer over ``C`` rows ``x [C, D]`` of one slot at ``start``
    (``C`` divides the window, so the rows lie in one): their rows into the
    ring, the summaries they complete into the table, then their attention.
    Returns ``(y [C, D], cache)``."""
    rk, rv, sk, sv = cache
    C, first = x.shape[0], start % rk.shape[3]
    q, k, v = _qkv(cfg, lp, x, cos, sin)
    with jax.named_scope("eva_core"):
        rk, rv = _ring_write(rk, rv, k, v, li, slot, first, n_valid)
    sk, sv = _summarise(cfg, lp, rk, rv, sk, sv, li, slot, start, first, C, n_valid)
    with jax.named_scope("eva_core"):
        att = _chunk_attend(cfg, q, rk, rv, sk, sv, li, slot, first, _summaries_attended(cfg, start))
    return _out(lp, att), (rk, rv, sk, sv)


def _chunk_hidden(cfg: EvaByteConfig, p: dict, cache, ids, slot, start, n_valid):
    """``C`` tokens ``ids [C]`` of slot ``slot`` at ``start`` (a multiple of
    ``C``) through every layer: ``(h [C, D] float32, cache)``. Rows at
    ``n_valid`` and after are padding: they write no ring row and complete no
    chunk. Rows that ``C`` does not fit into one window go in pieces of
    ``gcd(C, W)``, each inside one."""
    C = ids.shape[0]
    if cfg.window_size % C:
        piece, hs = math.gcd(C, cfg.window_size), []
        for i in range(0, C, piece):  # noqa: PTA104 (static unroll, host loop bound)
            h, cache = _chunk_hidden(cfg, p, cache, ids[i:i + piece], slot, start + i, jnp.clip(n_valid - i, 0, piece))
            hs.append(h)  # noqa: PTA104 (static unroll, host loop bound)
        return jnp.concatenate(hs, axis=0), cache
    h = _embed(p, ids)
    cos, sin = _angles(cfg, start + jnp.arange(C))
    for i in range(cfg.num_hidden_layers):  # noqa: PTA104 (static unroll, host loop bound)
        lp = _layer(p, i)
        y, cache = _eva_chunk(cfg, lp, _norm(cfg, h, lp["norm1"]), cache, i, slot, start, n_valid, cos, sin)
        h = h + y
        h = h + _mlp(lp, _norm(cfg, h, lp["norm2"]))
    return h, cache


def _chunk_forward(cfg: EvaByteConfig, p: dict, cache, ids, slot, start, n_valid, want_rows):
    """:func:`_chunk_hidden` and the head: ``want_rows`` ``None`` (no logits),
    a traced row index (that row's logits ``[1, V]``) or ``"all"``."""
    h, cache = _chunk_hidden(cfg, p, cache, ids, slot, start, n_valid)
    if want_rows is None:
        return None, cache
    if not isinstance(want_rows, str):
        h = jax.lax.dynamic_slice_in_dim(h, want_rows, 1, axis=0)
    return _head(cfg, p, h), cache


def decode_probe(cfg: EvaByteConfig, p: dict, cache, tok, pos, active):
    """The decode forward of the engine's decode program, one token of every
    slot: ``tok``, ``pos`` ``[B]``; writes gated by ``active``. Returns
    ``(logits [B, V], cache)``. What it writes — the tokens' ring rows and the
    summaries they close — the step that consumes the same tokens writes
    again, alike."""
    rk, rv, sk, sv = cache
    B = tok.shape[0]
    h = _embed(p, tok)
    cos, sin = _angles(cfg, pos)
    n_sum, n_ring = _summaries_attended(cfg, pos), pos % cfg.window_size + 1
    for i in range(cfg.num_hidden_layers):  # noqa: PTA104 (static unroll, host loop bound)
        lp = _layer(p, i)
        q, k, v = _qkv(cfg, lp, _norm(cfg, h, lp["norm1"]), cos, sin)
        att, rk, rv, sk, sv = eva_decode(q, k, v, rk, rv, sk, sv, pos, active, jnp.int32(i), _pool(cfg, lp), n_sum, n_ring,
                                         chunk=cfg.chunk_size)
        h = h + _out(lp, att.reshape(B, -1))
        h = h + _mlp(lp, _norm(cfg, h, lp["norm2"]))
    return _head(cfg, p, h), (rk, rv, sk, sv)


# ------------------------------------------------------------------ decoder
class EvaByteDecoder(Decoder):
    """The model through the serving engine's interface."""

    recurrent = True

    def __init__(self, model: "EvaByteForCausalLM"):
        self.cfg = model.cfg
        self._weights = model.weights
        self.vocab_size = model.cfg.vocab_size
        self.max_positions = model.cfg.max_position_embeddings
        self.dtype = model.weights["embed"].dtype

    def params(self, int8: bool = False):
        if int8:
            raise NotImplementedError("EvaByte has no int8 weights")
        return dict(self._weights)

    def fingerprint(self) -> tuple:
        return self.cfg.fingerprint()

    def buffer_specs(self, slots: int, rows: int, kv_dtype=None):
        c = self.cfg
        lead = (c.num_hidden_layers, int(slots), c.num_attention_heads)
        ring = lead + (min(c.window_size, int(rows)), c.head_dim)
        table = lead + (-(-int(rows) // c.chunk_size), c.head_dim)
        dt = str(self.dtype)
        return (BufferSpec("ring_k", ring, dt, 1, False), BufferSpec("ring_v", ring, dt, 1, False),
                BufferSpec("summary_k", table, dt, 1, False), BufferSpec("summary_v", table, dt, 1, False))

    def prefill(self, p, cache, ids, length, slot):
        # a whole padded prompt into the fresh slot
        h, cache = _chunk_hidden(self.cfg, p, cache, ids[0], slot, jnp.int32(0), length)
        return _head(self.cfg, p, jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=0)), cache

    def chunk(self, p, cache, ids, slot, start, last_row=None):
        C = ids.shape[1]
        n_valid = jnp.int32(C) if last_row is None else last_row + 1
        return _chunk_forward(self.cfg, p, cache, ids[0], slot, start, n_valid, last_row)

    def decode(self, p, cache, tok, pos, active):
        logits, cache = decode_probe(self.cfg, p, cache, tok, pos, active)
        return logits, cache, None

    def step_notes(self, positions, active) -> dict:
        """Rows one layer of the step attends, summed over the decoding slots
        (``eva_ring_rows``, ``eva_summary_rows``), and what it writes: a ring
        row a slot (``eva_rows_written``) and the summaries the slots' tokens
        close (``eva_summaries_written``)."""
        c = self.cfg
        pos = np.asarray(positions)[np.asarray(active, bool)].astype(np.int64)
        return {"eva_ring_rows": int(np.sum(pos % c.window_size + 1)),
                "eva_summary_rows": int(np.sum((pos // c.window_size) * (c.window_size // c.chunk_size))),
                "eva_rows_written": int(pos.size), "eva_summaries_written": int(np.sum(pos % c.chunk_size == c.chunk_size - 1))}

    def chunk_notes(self, start: int, rows: int) -> dict:
        """The summaries a prefill program of ``rows`` prompt rows from
        ``start`` writes: the chunks it completes."""
        ch = self.cfg.chunk_size
        return {"eva_summaries_written": (int(start) + int(rows)) // ch - int(start) // ch}


# -------------------------------------------------------------------- model
class EvaByteForCausalLM(nn.Layer):
    """The model (or the pipeline stage held here) with its weights as plain
    device arrays under ``weights`` (``EvaByteConfig.weight_shapes`` names
    them); made from ``seed`` unless given."""

    def __init__(self, cfg: EvaByteConfig, seed: int = 0, dtype: str = "bfloat16", weights: Optional[dict] = None):
        super().__init__()
        self.cfg = cfg
        self.weights = init_weights(cfg, seed, dtype) if weights is None else dict(weights)

    def decoder(self) -> EvaByteDecoder:
        """What the serving engine runs this model through."""
        return EvaByteDecoder(self)

    def forward(self, input_ids):
        """Logits ``[b, s, V]`` (float32) of whole sequences, each from an empty
        slot: padded to whole windows, over a scratch cache."""
        from ..framework.core import unwrap
        from ..tensor._helpers import _wrap_value

        ids = jnp.asarray(unwrap(input_ids), jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        s = ids.shape[1]
        padded = -(-s // self.cfg.window_size) * self.cfg.window_size
        dec = self.decoder()

        def one(row):
            h, _ = _chunk_hidden(self.cfg, self.weights, dec.alloc(1, padded), jnp.pad(row, (0, padded - s)),
                                 jnp.int32(0), jnp.int32(0), jnp.int32(s))
            return _head(self.cfg, self.weights, h[:s])

        return _wrap_value(jnp.stack([one(row) for row in ids]))
