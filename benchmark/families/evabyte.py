"""The EvaByte family (``model_type: evabyte``): a byte-level pre-norm decoder,
RMSNorm with a unit offset (``x / rms(x) * (1 + w)``), no biases, rotary
positions on half-split pairs, full multi-head attention, a SiLU-gated MLP, an
untied embedding and head, and EVA attention in every layer:

    u = RMSNorm1(x);  q, k, v = u W_q, u W_k, u W_v              (q, k rotated at their position)
    for head h and chunk c (rows c C .. c C + C - 1):
        alpha_{c,m} = softmax_m(phi_h . k_m / sqrt(d)),  k~_c = sum_m alpha k_m,  v~_c = sum_m alpha v_m
    the query at t, in window w = t // W, attends with one softmax of q . k / sqrt(d):
        its window's rows m (m // W == w, m <= t) and the summaries of every chunk c < w W / C
    h = x + o W_o;  y = h + W_down(silu(u' W_gate) * u' W_up),  u' = RMSNorm2(h)
    logits = RMSNorm_f(x_L) W_head0

(EVA: Zheng, Wang and Kong, arXiv:2302.04542, as the EvaByte release uses it.)
Serving only.

For every configuration whose file says ``"family": "evabyte"``:

1. ``build_model``: the program's model with weights made on the device from
   the seed;
2. the **plain reference** (``reference_forward`` / ``reference_logits``):
   straight ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
   one sequence, the equations above written out — every chunk's summary from
   its keys, every query's exact set and summary set by their definition, no
   ring and no running softmax (those are what is under test), the rotation's
   angles in float64 on the host — a few heads and a block of query rows at a
   time, weights cast to float32 a block of columns at a time so that the
   stage at the published widths fits beside the served model; nothing
   imported from ``paddle_tpu.models`` or ``paddle_tpu.ops``. It reads the
   weights in the layout of ``weight_shapes`` (the program's):
   ``attn_qkv`` is ``[D, 3, H, d]`` flattened (queries, keys, values),
   ``mlp_gate_up`` gate then up, ``head`` is head 0;
3. the bytes of a decode step and of its EVA core, and ``check_serving`` with
   its limits and planted controls.

Left open by the source's config and its description, and computed alike by
the program and the reference (each also in the configuration's ``assumed``):
(a) the exact rows are a *block* of ``window_size`` (a multiple of it starts an
empty window), not a sliding window; (b) a chunk's summary is the softmax
pooling above under one vector ``phi`` a head and layer, of the rotated keys;
(c) rotary pairs are half-split (``rotate_half``); (d) head *j* of the
``num_pred_heads`` predicts byte *t + 1 + j*, so head 0 is the next byte's;
(e) ``phi`` is seeded N(0, 2), so a chunk's weights are uneven at random
weights, and the norms' ``w`` N(0, 0.02); every matrix N(0, ``init_std``).
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Dict

import numpy as np

HOST_SPAN_PREFIXES = ("infer.", "bench.")
DECODE_PROGRAM = "decode_fn"
CHUNK_PROGRAMS = ("chunk_core", "chunk_final_core")
PREFILL_PROGRAMS = CHUNK_PROGRAMS + ("prefill_core",)
SCOPES_OF_PROGRAM = {DECODE_PROGRAM: "infer/decode", "chunk_core": "infer/prefill_chunk",
                     "chunk_final_core": "infer/prefill_final"}
# ``jax.named_scope`` names of ``models/evabyte.py`` -> the part a metric reports. ``eva`` is the EVA core: the ring
# write, the chunk summaries and the attention over the ring and the table (the ``eva_decode`` kernel on the TPU);
# ``attn`` the projections, the rotation and the output projection, which ``decode_attn_ms`` reads in every family;
# ``embed`` goes with the head.
PART_OF_SCOPE = {"eva_core": "eva", "eva_summarise": "eva", "eva_qkv": "attn", "eva_out": "attn",
                 "mlp": "mlp", "norm": "norm", "head_loss": "head_loss", "embed": "head_loss"}

# ---- limits of ``check_serving`` (readings on a TPU v5e; PERF.md §2 and §6 have them by run) ----
# Each reading is taken at the published widths: bfloat16 weights, matmul operands and cached rows; float32 residual
# stream, norms, pooling and attention softmaxes and their sums. The sound side: thirteen runs of the cell under the check
# as it stands (three prompts of 8,711, 6,137 and 259 bytes, all 16 slots decoding; every seed its own). The other side of
# each limit: the controls (``planted``, at the end of this file), one run of the cell each; the nearest is
# ``rows_held_in_float8``, the rows one precision below the one the configuration states.
#
# Logits of the program's decode forward against the float32 reference, relative RMS over 17 positions of a prompt:
# sound 8.08e-3 to 9.20e-3; rows in float8 7.46e-2 (a mean pool 0.60, stale summaries 0.67, no summaries 1.15).
SERVE_LOGIT_REL_RMS = 2.0e-2
# The first layer's ring rows of the current window against the reference's rotated keys and values: sound 2.34e-3 to
# 2.38e-3; rows in float8 2.68e-2 (the other three controls leave the ring alone and read as the sound program).
SERVE_CACHE_REL_RMS = 6.0e-3
# The first layer's summary rows (every chunk the prompt and its decode steps closed) against the reference's k~, v~:
# sound 3.81e-3 to 3.94e-3; rows in float8 4.77e-2 (a mean pool 0.86, stale summaries 1.41) ...
SERVE_SUMMARY_REL_RMS = 6.0e-3
# ... and the worst single summary row's, where a fault in one chunk of hundreds stands out and drowns in the mean:
# sound 4.40e-3 to 4.75e-3; rows in float8 5.48e-2 (a mean pool 0.91, stale summaries 2.20).
SERVE_SUMMARY_ROW_REL_RMS = 1.5e-2
# A served token must be one the reference rates within 2^-5 of the row's largest magnitude below its best (the other
# families' margin): this holds the sampling path — a wrong head, an argmax over the wrong axis — not precision. Sound 0
# to 4.4e-3; rows in float8 1.66e-2, which passes here and fails by the four limits above.
SERVE_TOKEN_TIE = 2.0 ** -5

_COLUMNS = 4096     # columns of a weight the reference casts to float32 at a time
_HEADS = 8          # attention heads the reference scores at a time
_ROWS = 512         # query rows whose scores it holds at a time


# ---------------------------------------------------------------- shapes
def dims(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    return dict(D=D, L=int(config["num_hidden_layers"]), H=H, d=int(config.get("head_dim") or D // H),
                F=int(config["intermediate_size"]), V=int(config["vocab_size"]), W=int(config["window_size"]),
                C=int(config["chunk_size"]), theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]))


def weight_shapes(config_or_dims: dict) -> Dict[str, tuple]:
    z = config_or_dims if "W" in config_or_dims else dims(config_or_dims)
    D, L, F, V, H, d = z["D"], z["L"], z["F"], z["V"], z["H"], z["d"]
    return {"embed": (V, D), "head": (D, V), "final_norm": (D,), "norm1": (L, D), "norm2": (L, D),
            "attn_qkv": (L, D, 3 * H * d), "attn_out": (L, H * d, D), "eva_phi": (L, H, d),
            "mlp_gate_up": (L, D, 2 * F), "mlp_down": (L, F, D)}


def param_count(config: dict) -> int:
    return int(sum(math.prod(s) for s in weight_shapes(config).values()))


def row_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """One cached row of one layer: a key and a value of every head."""
    z = dims(config)
    return 2 * z["H"] * z["d"] * bytes_per_value


def slot_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """What one slot holds: in every layer a ring of ``W`` rows and a table of
    one row a chunk of the context."""
    z = dims(config)
    context = int(config["serving"]["context"])
    return z["L"] * (min(z["W"], context) + -(-context // z["C"])) * row_bytes(config, bytes_per_value)


# ---------------------------------------------------------------- the program's model
def build_model(config: dict, seed: int, dtype: str, mesh=None):
    """The program's model at the configuration's sizes with weights made on
    the device from the seed, in ``dtype``."""
    from paddle_tpu.models.evabyte import EvaByteConfig, EvaByteForCausalLM

    if mesh is not None:
        raise NotImplementedError("the evabyte family serves on one chip: no mesh")
    return EvaByteForCausalLM(EvaByteConfig.from_config_file(config), seed=seed, dtype=dtype)


def weights_of_engine(engine) -> dict:
    """The served weights as plain arrays in ``weight_shapes``' layout."""
    return dict(engine._params)


# ---------------------------------------------------------------- reference
def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def _settled(x):
    """``x``, computed: eager dispatch runs ahead of the device, and every
    block that is queued holds its float32 copy of a weight until it has run."""
    import jax

    return jax.block_until_ready(x)


def _times(x, w):
    """``x @ w`` with ``w`` cast to float32 a block of columns at a time."""
    import jax.numpy as jnp

    n = w.shape[-1]
    return jnp.concatenate([_settled(x @ _f32(w[:, i:i + _COLUMNS])) for i in range(0, n, _COLUMNS)], axis=-1)


def _norm(z, x, w):
    """``x / rms(x) * (1 + w)``."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + z["eps"]) * (1.0 + _f32(w))


def _rotate(z, x, positions):
    """``x [s, H, d]`` with the half-split pair ``(i, i + d / 2)`` of row ``t``
    turned by ``positions[t] * theta^(-2i / d)``, the angles in float64."""
    import jax.numpy as jnp

    half = z["d"] // 2
    angle = np.asarray(positions, np.float64)[:, None] * z["theta"] ** (-np.arange(0, z["d"], 2, dtype=np.float64) / z["d"])
    cos, sin = _f32(np.cos(angle)[:, None]), _f32(np.sin(angle)[:, None])
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def reference_summaries(z: dict, phi, k, v):
    """Every complete chunk's summary of one layer: ``(k~, v~)`` ``[s // C, H,
    d]``, chunk ``c``'s from its ``C`` keys and values ``[s, H, d]`` by a softmax
    of ``phi_h . k_m / sqrt(d)`` over its rows."""
    import jax
    import jax.numpy as jnp

    C, n = z["C"], k.shape[0] // z["C"]
    kc, vc = k[:n * C].reshape(n, C, z["H"], z["d"]), v[:n * C].reshape(n, C, z["H"], z["d"])
    alpha = jax.nn.softmax(jnp.einsum("nchd,hd->nhc", kc, _f32(phi)) / math.sqrt(z["d"]), axis=-1)
    return jnp.einsum("nhc,nchd->nhd", alpha, kc), jnp.einsum("nhc,nchd->nhd", alpha, vc)


def reference_attention(z: dict, lw: dict, x, positions):
    """The EVA mixer on one sequence ``x [s, D]`` (normalised): ``(y [s, D], k,
    v [s, H, d] rotated as cached, k~, v~ [s // C, H, d])``. A block of up to
    ``_ROWS`` query rows lies in one window ``w``; its keys are the summaries of the
    chunks before ``w W`` and the window's rows, each query masked to its own
    exact set ``m <= t``. A few heads at a time."""
    import jax
    import jax.numpy as jnp

    s, H, d, W, C = x.shape[0], z["H"], z["d"], z["W"], z["C"]
    qkv = _times(x, lw["attn_qkv"]).reshape(s, 3, H, d)
    q, k, v = _rotate(z, qkv[:, 0], positions), _rotate(z, qkv[:, 1], positions), qkv[:, 2]
    ks, vs = reference_summaries(z, lw["eva_phi"], k, v)
    heads, step = [], math.gcd(_ROWS, W)                                   # a block of query rows inside one window
    for h in range(0, H, _HEADS):
        hs, blocks = slice(h, h + _HEADS), []
        for r in range(0, s, step):
            rows = np.arange(r, min(r + step, s))
            w = r // W
            exact = np.arange(w * W, min(w * W + W, s))
            n_sum = w * (W // C)
            keys = jnp.concatenate([ks[:n_sum, hs], k[exact[0]:exact[-1] + 1, hs]], axis=0)
            vals = jnp.concatenate([vs[:n_sum, hs], v[exact[0]:exact[-1] + 1, hs]], axis=0)
            scores = jnp.einsum("qhd,khd->hqk", q[r:r + len(rows), hs], keys) / math.sqrt(d)
            seen = np.concatenate([np.ones((len(rows), n_sum), bool), exact[None, :] <= rows[:, None]], axis=1)
            prob = jax.nn.softmax(jnp.where(jnp.asarray(seen)[None], scores, -jnp.inf), axis=-1)
            blocks.append(_settled(jnp.einsum("hqk,khd->qhd", prob, vals)))
        heads.append(jnp.concatenate(blocks, axis=0))
    return _times(jnp.concatenate(heads, axis=1).reshape(s, H * d), lw["attn_out"]), k, v, ks, vs


def _gated(x, w_gate_up, w_down):
    """``W_down(silu(x W_gate) * x W_up)``, gate and up side by side."""
    import jax

    f = w_down.shape[0]
    return _settled(_times(jax.nn.silu(_times(x, w_gate_up[:, :f])) * _times(x, w_gate_up[:, f:]), w_down))


def reference_forward(config_or_dims, weights: dict, ids, rows_from: int = 0) -> dict:
    """One sequence through the model: ``logits [s - rows_from, V]`` (float32)
    of the rows from ``rows_from``, and the first layer's rotated keys and
    values ``k``, ``v`` ``[s, H, d]`` and summaries ``ks``, ``vs`` ``[s // C,
    H, d]``."""
    import jax
    import jax.numpy as jnp

    z = config_or_dims if "W" in config_or_dims else dims(config_or_dims)
    out = {}
    positions = np.arange(len(ids))
    with jax.default_matmul_precision("highest"):
        h = _f32(jnp.asarray(weights["embed"])[jnp.asarray(ids, jnp.int32)])
        for layer in range(z["L"]):
            lw = {name: weights[name][layer] for name in ("attn_qkv", "attn_out", "eva_phi", "mlp_gate_up", "mlp_down")}
            y, k, v, ks, vs = reference_attention(z, lw, _norm(z, h, weights["norm1"][layer]), positions)
            if layer == 0:
                out.update(k=k, v=v, ks=ks, vs=vs)
            h = _settled(h + y)
            h = _settled(h + _gated(_norm(z, h, weights["norm2"][layer]), lw["mlp_gate_up"], lw["mlp_down"]))
        out["logits"] = _times(_norm(z, h[rows_from:], weights["final_norm"]), weights["head"])
    return out


def reference_logits(config: dict, weights: dict, ids):
    """Logits ``[s, V]`` of one sequence of byte ids, float32."""
    return reference_forward(config, weights, ids)["logits"]


# ---------------------------------------------------------------- required bytes
def _decode_step_spans(records):
    """The ``infer.decode_step`` records of the traced ticks (of the window,
    where nothing was traced) that carry the EVA counts, or []."""
    from benchmark.layer_metrics import _program

    spans = _program.window_spans(records) or []
    ticks = records.in_trace(records.tick_end) or records.inside(records.tick_end)
    if not ticks:
        return []
    lo = records.tick_end[ticks[0] - 1] if ticks[0] > 0 else records.window_open
    hi = records.tick_end[ticks[-1]]
    return [s for s in spans if s.name == "infer.decode_step" and s.attrs and "eva_ring_rows" in s.attrs
            and lo * 1e9 < s.end_ns <= hi * 1e9]


def eva_rows_per_step(records):
    """``(ring rows, summary rows, rows written, summaries written)`` one layer
    of a decode step attends and writes, summed over the decoding slots, the
    mean over the traced steps that decoded (the program's count on the
    ``infer.decode_step`` records); None where it counted nothing."""
    steps = [s.attrs for s in _decode_step_spans(records) if s.attrs["eva_rows_written"]]
    if not steps:
        return None
    return tuple(sum(a[key] for a in steps) / len(steps)
                 for key in ("eva_ring_rows", "eva_summary_rows", "eva_rows_written", "eva_summaries_written"))


def eva_step_bytes(config: dict, records, bytes_per_value: int = 2):
    """Bytes the EVA cores of one decode step have to move: every layer reads
    the live ring and summary rows it attends and writes each decoding slot's
    row and the summaries closed. None where the program counted nothing."""
    counted = eva_rows_per_step(records)
    if counted is None:
        return None
    return dims(config)["L"] * sum(counted) * row_bytes(config, bytes_per_value)


def eva_step_floor_s(config: dict, records, peaks: dict, bytes_per_value: int = 2):
    """The least time the chip could take over the EVA cores of one decode
    step: ``eva_step_bytes`` at the peak bandwidth. The algorithm's count, the
    same whatever implements the core."""
    moved = eva_step_bytes(config, records, bytes_per_value)
    return None if moved is None else moved / peaks["hbm_bytes_per_s"]


def decode_step_bytes(config: dict, live_rows: float, records=None, bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read: every weight once and the live ring
    and summary rows the program counted in the traced ticks (none where it
    counted nothing: the weights alone). ``live_rows`` — every token a slot has
    seen — is not what EVA reads."""
    eva = None if records is None else eva_step_bytes(config, records, bytes_per_value)
    return bytes_per_value * param_count(config) + (eva or 0.0)


# ---------------------------------------------------------------- correct
def _rel(got, want, axis=None):
    return np.sqrt(np.mean((got - want) ** 2, axis=axis) / np.mean(want ** 2, axis=axis))


def check_serving(engine, config: dict, seed: int, n_decode: int = 16) -> dict:
    """Three seeded prompts through the engine's own chunked prefill on slots
    the window's traffic has used — ``4 W + C / 2 + 7`` bytes (8,711 at the
    cell's window of 2,048 and chunk of 1,024: eight chunks and a final chunk,
    four windows and a partial one), ``3 W - 7`` (6,137: the sixteen decode
    steps close a chunk and a window, make a window's summaries attendable and
    start a fresh ring) and ``C / 4 + 3`` (259: a final chunk alone, no summary
    attended) — every other slot filled with a short seeded prompt so that each
    decode step runs with the whole batch live, and ``n_decode`` decode steps.

    Before each step, and once after the last, the program's own decode
    forward runs on the engine's buffers at the engine's batch width and gives
    the logits of the token about to be consumed (what it writes the step
    writes again, alike): ``n_decode + 1`` positions a prompt against the
    reference's full forward (relative RMS). Every served token must be within
    ``SERVE_TOKEN_TIE`` of the reference's best. And what the slots hold: the
    first layer's ring rows of the current window against the reference's
    rotated keys and values, and its summary rows — every chunk the prompt and
    its decode steps closed — against the reference's, over all and the worst
    single row."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import evabyte as program

    z = dims(config)
    W, Cs = z["W"], z["C"]
    chunk = engine._chunk or 64
    rng = np.random.default_rng([int(seed), 7])
    room = int(config["serving"]["context"]) - n_decode - 8
    lengths = [min(4 * W + chunk // 2 + 7, room), min(3 * W - 7, room), max(8, chunk // 4 + 3)]
    engine.reset()
    prompts = [rng.integers(0, z["V"], (n,)).astype(np.int32) for n in lengths]
    fillers = {slot: rng.integers(0, z["V"], (max(4, chunk // 8 + slot % 5),)).astype(np.int32)
               for slot in range(len(prompts), engine.max_batch_slots)}
    cfg = engine._dec.cfg

    # at the engine's own shapes — every slot's buffers, the engine's batch — so that what it computes is, op for op,
    # what the engine's decode program computed
    @functools.partial(jax.jit, donate_argnums=(1,))
    def probe(params, cache, tok, pos, active):
        logits, cache = program.decode_probe(cfg, params, cache, tok, pos, active)
        return logits[:len(prompts)].astype(jnp.float32), cache

    served, probed = [], [[] for _ in prompts]
    for slot, prompt in enumerate(prompts):
        first, _ = engine.prefill(prompt, slot, max_new_tokens=n_decode + 4)
        served.append([int(first)])
    for slot, prompt in fillers.items():                                    # decoding beside them through every step
        engine.prefill(prompt, slot, max_new_tokens=n_decode + 4)

    def probe_all():
        logits, engine._cache = probe(engine._params, engine._cache, engine._tok, engine._pos, engine._active)
        logits = np.asarray(logits)
        for slot in range(len(prompts)):
            probed[slot].append(logits[slot])

    for _ in range(n_decode):
        probe_all()
        toks, emitted, _ = engine.decode_step(fuse=1)
        for slot in range(len(prompts)):
            if np.atleast_2d(emitted)[0, slot]:
                served[slot].append(int(np.atleast_2d(toks)[0, slot]))
    probe_all()

    weights = weights_of_engine(engine)
    # buffers: ring_k, ring_v [L, B, H, W, d]; summary_k, summary_v [L, B, H, S / C, d]; the first layer of each slot
    ring = [np.asarray(engine._cache[i][0, :len(prompts)].astype(jnp.float32)) for i in (0, 1)]
    table = [np.asarray(engine._cache[i][0, :len(prompts)].astype(jnp.float32)) for i in (2, 3)]
    worst_max = worst_tie = 0.0
    agree = rows = 0
    by_prompt, by_position, cache_by_prompt, summary_by_prompt, summary_row_by_prompt = [], [], [], [], []
    for slot, (prompt, toks) in enumerate(zip(prompts, served)):
        n = len(prompt)
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])           # the last token is probed, not consumed
        ref = reference_forward(z, weights, seq, rows_from=n - 1)
        want = np.asarray(ref["logits"])                                    # positions n-1 .. n+len(toks)-1
        got = np.stack(probed[slot])                                        # positions n .. n+len(toks)-1
        by_prompt.append(float(_rel(got, want[1:])))
        by_position.extend(float(_rel(g, w)) for g, w in zip(got, want[1:]))
        worst_max = max(worst_max, float(np.abs(got - want[1:]).max() / np.abs(want[1:]).max()))
        for row, tok in zip(want, toks):
            worst_tie = max(worst_tie, float((row.max() - row[tok]) / np.abs(row).max()))
            agree += int(np.argmax(row) == tok)
            rows += 1
        # the probe after the last step wrote the last token's row too: all of ``seq`` is in the slot
        last = len(seq) - 1
        lo = (last // W) * W                                                # the current window's first position
        kv_got = np.concatenate([np.swapaxes(ring[i][slot][:, :last - lo + 1], 0, 1) for i in (0, 1)], axis=-1)
        kv_want = np.concatenate([np.asarray(ref["k"])[lo:], np.asarray(ref["v"])[lo:]], axis=-1)
        cache_by_prompt.append(float(_rel(kv_got, kv_want)))
        closed = len(seq) // Cs                                             # chunks whose last row the slot has written
        sum_got = np.concatenate([np.swapaxes(table[i][slot][:, :closed], 0, 1) for i in (0, 1)], axis=-1)
        sum_want = np.concatenate([np.asarray(ref["ks"]), np.asarray(ref["vs"])], axis=-1)[:closed]
        summary_by_prompt.append(float(_rel(sum_got, sum_want)) if closed else 0.0)
        summary_row_by_prompt.append(float(_rel(sum_got, sum_want, axis=(1, 2)).max()) if closed else 0.0)
    engine.reset()
    worst_rms, worst_cache = max(by_prompt), max(cache_by_prompt)
    worst_summary, worst_row = max(summary_by_prompt), max(summary_row_by_prompt)
    compared = {"logit_rel_rms": [worst_rms, SERVE_LOGIT_REL_RMS],
                "cache_rel_rms": [worst_cache, SERVE_CACHE_REL_RMS],
                "summary_rel_rms": [worst_summary, SERVE_SUMMARY_REL_RMS],
                "summary_row_rel_rms": [worst_row, SERVE_SUMMARY_ROW_REL_RMS],
                "token_below_best": [worst_tie, SERVE_TOKEN_TIE]}
    return {"correct": bool(np.isfinite(worst_max) and all(np.isfinite(v) and v <= limit for v, limit in compared.values())),
            "logit_rel_rms": worst_rms, "logit_rel_max": worst_max, "cache_rel_rms": worst_cache,
            "summary_rel_rms": worst_summary, "summary_row_rel_rms": worst_row, "token_below_best": worst_tie,
            "logit_rel_rms_by_position": by_position, "logit_rel_rms_by_prompt": by_prompt,
            "cache_rel_rms_by_prompt": cache_by_prompt, "summary_rel_rms_by_prompt": summary_by_prompt,
            "summary_row_rel_rms_by_prompt": summary_row_by_prompt,
            "tokens_equal_reference_argmax": agree, "positions": rows,
            "prompt_lengths": lengths, "slots_decoding": len(prompts) + len(fillers), "compared": compared}


# ---------------------------------------------------------------- the check's controls
# Each is the same program with one thing wrong, planted from outside it (the program has no switch for any of them),
# and ``check_serving`` has to say not correct. ``python3 -m benchmark.families.evabyte <control> --workload
# evabyte-6.5b.serve-bytegen --seed <n> --seconds <s> --trace 0`` is one run of the cell with one planted, on the
# chip; ``tests/benchmark_suite/test_evabyte_cell.py`` plants each at tiny widths.
CONTROLS = ("summaries_dropped", "summaries_mean_pooled", "summary_of_stale_rows", "rows_held_in_float8")


def _float8(x):
    """``x`` rounded to float8 (e4m3: the nearest precision below bfloat16), in its own dtype."""
    import jax
    import jax.numpy as jnp

    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=4, mantissa_bits=3).astype(x.dtype)


@contextlib.contextmanager
def planted(control: str):
    """The program with ``control`` wrong until the block ends: a query sees
    its own window alone (no summary attended, in the chunk programs and the
    decode step alike); every chunk's summary a plain mean of its rows (the
    pooling vectors zero); a chunk's summary formed from the ring as it was
    before the rows that close the chunk were written (a prefill chunk's from
    the rows it overwrites, a decode step's with the token's row stale); the
    ring rows and the summary rows rounded to float8 on their way into the
    slot. The engine's store of executables is keyed by configuration and
    shapes, not by program text, so it is off meanwhile: a planted program
    neither loads the sound one nor leaves itself under its key."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference import aot_cache
    from paddle_tpu.models import evabyte as program

    write, summarise, decode = program._ring_write, program._summarise, program.eva_decode
    before = {}

    def remember(rk, rv, k, v, li, slot, first, n_valid):
        before["ring"] = (rk, rv)
        return write(rk, rv, k, v, li, slot, first, n_valid)

    def from_stale(cfg, lp, rk, rv, sk, sv, *args):
        return summarise(cfg, lp, *before["ring"], sk, sv, *args)

    def stale_step(q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring, *, chunk):
        # the step as it is, then each closing chunk's summary formed again from the ring as the step found it
        att, rk, rv, sk, sv = decode(q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring, chunk=chunk)
        W, R = ring_k.shape[3], sum_k.shape[3]
        first = (pos % W) // chunk * chunk
        rows = [jax.vmap(lambda x, f: jax.lax.dynamic_slice_in_dim(x, f, chunk, axis=1))(buf[layer], first).astype(jnp.float32)
                for buf in (ring_k, ring_v)]
        alpha = jax.nn.softmax(jnp.einsum("bhcd,hd->bhc", rows[0], pool), axis=-1)
        closing = (active & (pos % chunk == chunk - 1))[:, None, None]
        slots, c = jnp.arange(pos.shape[0]), (pos // chunk) % R
        out = []
        for table, r in ((sk, rows[0]), (sv, rows[1])):
            lay = table[layer]
            stale = jnp.einsum("bhc,bhcd->bhd", alpha, r).astype(lay.dtype)
            out.append(table.at[layer].set(lay.at[slots, :, c].set(jnp.where(closing, stale, lay[slots, :, c]))))
        return (att, rk, rv) + tuple(out)

    def coarse_step(q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring, *, chunk):
        att, rk, rv, sk, sv = decode(q, _float8(k), _float8(v), ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool,
                                     n_sum, n_ring, chunk=chunk)
        slots, c = jnp.arange(pos.shape[0]), (pos // chunk) % sum_k.shape[3]
        return (att, rk, rv) + tuple(table.at[layer, slots, :, c].set(_float8(table[layer, slots, :, c])) for table in (sk, sv))

    def coarse_write(rk, rv, k, v, *args):
        return write(rk, rv, _float8(k), _float8(v), *args)

    def coarse_summaries(*args):
        sk, sv = summarise(*args)
        return _float8(sk), _float8(sv)

    wrong = {"summaries_dropped": [(program, "_summaries_attended", lambda cfg, positions: positions * 0)],
             "summaries_mean_pooled": [(program, "_pool", lambda cfg, lp: jnp.zeros(lp["eva_phi"].shape, jnp.float32))],
             "summary_of_stale_rows": [(program, "_ring_write", remember), (program, "_summarise", from_stale),
                                       (program, "eva_decode", stale_step)],
             "rows_held_in_float8": [(program, "_ring_write", coarse_write), (program, "_summarise", coarse_summaries),
                                     (program, "eva_decode", coarse_step)]}[control]
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
