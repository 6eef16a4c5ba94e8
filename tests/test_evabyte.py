"""EvaByte through the serving engine, against the plain reference of
``benchmark/families/evabyte.py``: tiny widths (hidden 256, 4 heads), a window
of 32 bytes and chunks of 4 so that a short prompt crosses several windows,
seeded weights, float32, the CPU."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.models import evabyte as eb
from paddle_tpu.observability import spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"family": "evabyte", "source": "test", "model_type": "evabyte", "hidden_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 320, "window_size": 32,
        "chunk_size": 4, "rope_theta": 100000, "rms_norm_eps": 1e-5, "max_position_embeddings": 256, "init_std": 0.01275}
W, CH = TINY["window_size"], TINY["chunk_size"]


@pytest.fixture(scope="module")
def family():
    return manifest.load_module(REPO, "benchmark", "families", "evabyte")


@pytest.fixture(scope="module")
def model():
    return eb.EvaByteForCausalLM(eb.EvaByteConfig.from_config_file(TINY), seed=3, dtype="float32")


@pytest.fixture(scope="module")
def engine(model):
    """One engine for the tests that serve through it (its programs compile once): 3 slots x 256, chunks of 16."""
    return DecodeEngine(model, max_batch_slots=3, max_seq_len=256, prefill_chunk=16)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)


def test_reference_is_independent_of_the_program(family, model):
    text = open(family.__file__).read()
    body = text[text.index("# ---------------------------------------------------------------- reference"):
                text.index("# ---------------------------------------------------------------- required bytes")]
    assert "paddle_tpu" not in body and '"highest"' in body and "np.float64" in body
    assert model.cfg.weight_shapes() == family.weight_shapes(TINY)                 # one layout, written twice
    assert family.param_count(TINY) == sum(int(np.prod(w.shape)) for w in model.weights.values())
    # the pooling vectors are far from zero (a mean pool must read far off), the norms' offsets small and nonzero
    assert np.asarray(model.weights["eva_phi"]).std() > 1.0 and 0 < np.abs(np.asarray(model.weights["norm1"])).max() < 0.2


# ------------------------------------------------ the system against the reference, in logits
@pytest.mark.parametrize("block", [None, 8], ids=["whole", "attending_in_blocks_of_8"])
def test_the_forward_agrees_with_the_reference(family, model, block, monkeypatch):
    """77 bytes: two whole windows and a partial one, so the third window's rows attend 16 summaries."""
    if block:
        monkeypatch.setattr(eb, "_ATTN_BLOCK", block)
    ids = _ids(77, 1)
    want = np.asarray(family.reference_logits(TINY, model.weights, ids))
    assert _rel(np.asarray(model(ids[None])._value)[0], want) < 2e-5


def test_chunked_prefill_gives_the_references_logits_at_every_row(family, model):
    """75 bytes in chunks of 16 (four, the last a final chunk of 11 with 5 rows of padding): every row's logits."""
    ids = _ids(75, 2)
    want = np.asarray(family.reference_logits(TINY, model.weights, ids))
    dec = model.decoder()
    p, cache, got = dec.params(), dec.alloc(2, 128), []
    forward = jax.jit(lambda p, cache, rows, start, n_valid: eb._chunk_forward(model.cfg, p, cache, rows, jnp.int32(1), start,
                                                                                n_valid, "all"))
    for start in range(0, 75, 16):
        rows = np.zeros((16,), np.int32)
        rows[:min(16, 75 - start)] = ids[start:start + 16]
        logits, cache = forward(p, cache, jnp.asarray(rows), jnp.int32(start), jnp.int32(min(16, 75 - start)))
        got.append(np.asarray(logits)[:75 - start])
    assert _rel(np.concatenate(got), want) < 2e-5


def _serve(model, engine, prompt, slot, steps):
    """``prompt`` into ``slot`` by the engine's own prefill, then ``steps`` decode steps: the tokens served, and the
    logits behind each but the first — the program's decode forward on the engine's buffers *before* the step that
    consumes the token, as the cell's check reads them."""
    first, _ = engine.prefill(prompt, slot, max_new_tokens=steps + 4)
    served, probed = [int(first)], []
    for _ in range(steps):
        logits, engine._cache = eb.decode_probe(model.cfg, engine._params, engine._cache, engine._tok, engine._pos,
                                                engine._active)
        probed.append(np.asarray(logits[slot]))
        toks, _, _ = engine.decode_step()
        served.append(int(toks[slot]))
    return served, probed


def _agrees(family, model, prompt, served, probed):
    n = len(prompt)
    want = np.asarray(family.reference_logits(TINY, model.weights, np.concatenate([prompt, np.asarray(served, np.int32)])))
    assert [int(np.argmax(r)) for r in want[n - 1:-1]] == served
    assert max(_rel(g, w) for g, w in zip(probed, want[n:])) < 2e-5


@pytest.mark.parametrize("n, chunk, steps", [(38, 16, 4), (44, 16, 4), (63, 16, 4), (61, 16, 8), (75, 16, 4), (100, 64, 6),
                                            (45, None, 4)],
                         ids=["ends_mid_chunk", "ends_on_a_chunk_boundary", "ends_a_row_before_a_window_boundary",
                              "decode_closes_a_chunk_and_a_window_and_opens_the_next", "several_prefill_chunks",
                              "a_prefill_chunk_of_two_windows", "a_whole_padded_prompt"])
def test_prefill_by_chunks_then_decode_through_the_engine_agrees_in_logits(family, model, engine, n, chunk, steps):
    prompt = _ids(n, n)
    if chunk == 16:
        engine.reset()
    else:                       # chunks of two windows each; or no chunks: the prompt padded to its bucket, in one program
        engine = DecodeEngine(model, max_batch_slots=3, max_seq_len=128, prefill_chunk=chunk)
    served, probed = _serve(model, engine, prompt, 1, steps)
    _agrees(family, model, prompt, served, probed)


def test_a_readmitted_slot_reads_nothing_of_its_last_request(family, model, engine):
    """A 150-byte request fills slot 0's ring and four windows of summaries; the next, 45 bytes, decodes across its
    window's end: its ring's later rows and the table's rows past its own are the last request's, and never read."""
    engine.reset()
    _serve(model, engine, _ids(150, 7), 0, 3)
    engine.free_slot(0)
    prompt = _ids(45, 8)
    served, probed = _serve(model, engine, prompt, 0, 22)                     # positions 45 .. 66
    _agrees(family, model, prompt, served, probed)


@pytest.mark.parametrize("kwargs, what", [(dict(prefill_chunk=16, prefix_cache_mb=1), "prefix_cache_mb"),
                                          (dict(kv_dtype="int8"), "int8")])
def test_the_engine_refuses_what_rests_on_rows_it_could_rebuild(model, kwargs, what):
    with pytest.raises(NotImplementedError, match=what):
        DecodeEngine(model, max_batch_slots=2, max_seq_len=128, **kwargs)


def test_the_engine_notes_the_rows_each_step_attends_and_the_summaries_written(engine):
    """Counted on the host from the slots' positions: a decode step's record has, summed over the decoding slots,
    the ring rows and the summary rows one layer attends and what it writes; a prefill program's, the chunks it closed."""
    engine.reset()
    t0 = time.perf_counter_ns()
    for slot, n in ((0, 70), (2, 30)):
        engine.prefill(_ids(n, slot), slot, max_new_tokens=8)
    for _ in range(3):
        engine.decode_step()
    records = spans.recent(since_ns=t0)
    chunks = [s.attrs["eva_summaries_written"] for s in records if s.name == "infer.prefill_chunk"]
    assert chunks == [4, 4, 4, 4, 1, 4, 3]                   # 70 = 16 x 4 + 6; 30 = 16 + 14 (a chunk of 4 left open)
    steps = [s.attrs for s in records if s.name == "infer.decode_step"]
    want = []
    for k in range(3):
        pos = np.asarray([70 + k, 30 + k])
        want.append({"eva_ring_rows": int(np.sum(pos % W + 1)), "eva_summary_rows": int(np.sum(pos // W * (W // CH))),
                     "eva_rows_written": 2, "eva_summaries_written": int(np.sum(pos % CH == CH - 1))})
    assert steps == want
    assert want[1] == {"eva_ring_rows": 8 + 32, "eva_summary_rows": 16, "eva_rows_written": 2, "eva_summaries_written": 2}
