"""Run-ahead of one decode step (PR 33): the scheduler's tick launches step
*k* before it pulls step *k - 1*. Every request must still get the token list
a loop of synchronous ``decode_step(fuse=1)`` calls gives it — over staggered
admissions with chunked prefill between decode ticks, a finish by eos, a finish
by limit, a cancel and a deadline expiry while a step is in flight, and a
re-admission to the freed slot in the same tick (the late token must not
appear) — for a tiny GPT and a tiny Solar Open 2 decoder; an engine with a
draft model and one at ``fuse=4`` keep the synchronous step; and the order
itself: the launch of step *k* precedes the pull of step *k - 1*."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import ContinuousBatchingScheduler, DecodeEngine
from paddle_tpu.models import solar_open2 as so2
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

KW = dict(max_seq_len=64, prefill_chunk=8)
SOLAR = {
    "family": "solar_open2", "source": "test", "model_type": "solar_open2",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
    "vocab_size": 128, "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "max_position_embeddings": 512,
    "gqa_layers": [0], "kda_allow_neg_eigval": True, "n_routed_experts": 16, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 4, "reduced": [],
    "assumed": {"low_rank": 8},
}
VOCAB = {"gpt": 512, "solar": 128}


@pytest.fixture(scope="module")
def models():
    paddle.seed(0)
    gpt = GPTForPretraining(GPTConfig.tiny())
    gpt.eval()
    solar = so2.SolarOpen2ForCausalLM(so2.SolarOpen2Config.from_config_file(SOLAR), seed=11, dtype="float32")
    return {"gpt": gpt, "solar": solar}


@pytest.fixture(scope="module")
def synchronous(models):
    """``tokens(which, prompt, max_new, eos)``: one request alone through a loop of synchronous
    ``decode_step(fuse=1)`` calls on an engine of its own — what every order of ticks has to serve it."""
    engines = {which: DecodeEngine(model, max_batch_slots=1, **KW) for which, model in models.items()}

    def tokens(which, prompt, max_new, eos=None):
        engine = engines[which]
        engine.reset()
        first, more = engine.prefill(prompt, 0, max_new, eos_token_id=eos)
        out = [first]
        while more:
            toks, emitted, active = engine.decode_step(fuse=1)
            assert emitted[0]
            out.append(int(toks[0]))
            more = bool(active[0])
        return out

    return tokens


def _prompt(which, n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB[which], (n,)).astype("int32")


def _ahead():
    return profiler.counters("infer.").get("infer.decode_ahead", 0)


# --------------------------------------------------------------- the same tokens
@pytest.mark.parametrize("which", ["gpt", "solar"])
def test_run_ahead_serves_every_request_the_synchronous_token_list(models, synchronous, which):
    """Staggered admissions, prompts of one to four chunks prefilled between decode ticks, a request that waits for a
    slot, one finish by eos, the rest by limit — one of them at its first token, pulled a tick after its chunk."""
    engine = DecodeEngine(models[which], max_batch_slots=3, **KW)
    sched = ContinuousBatchingScheduler(engine)
    plan = [(5, 12), (19, 9), (11, 20), (9, 6), (26, 7), (7, 1)]      # (prompt tokens, max_new_tokens)
    prompts = [_prompt(which, n, 100 + i) for i, (n, _) in enumerate(plan)]
    free_run = synchronous(which, prompts[2], plan[2][1])
    eos = next(t for i, t in enumerate(free_run) if i >= 3 and t not in free_run[:i])     # request 2 stops at it
    want = [synchronous(which, p, m, eos if i == 2 else None) for i, (p, (_, m)) in enumerate(zip(prompts, plan))]
    assert len(want[2]) < plan[2][1] and want[2][-1] == eos and all(len(want[i]) == plan[i][1] for i in (0, 1, 3, 4, 5))

    before, rids = _ahead(), []
    submit = lambda i: rids.append(sched.submit(prompts[i], max_new_tokens=plan[i][1],      # noqa: E731
                                                eos_token_id=eos if i == 2 else None))
    for i in (0, 1, 2):
        submit(i)
    for _ in range(3):
        sched.step()
    submit(3)                     # waits: three slots are taken
    for _ in range(2):
        sched.step()
    submit(4)
    submit(5)
    done = sched.run()
    assert [done[r].tokens for r in rids] == want
    assert all(done[r].status == "finished" for r in rids)
    assert engine._inflight is None                                  # run() ends with nothing in flight
    assert _ahead() > before
    assert not engine._occupied.any() and not engine._active_np.any()


# ------------------------------------------------------ a late token and its slot
@pytest.mark.parametrize("how", ["cancel", "deadline"])
@pytest.mark.parametrize("which", ["gpt", "solar"])
def test_a_token_in_flight_never_lands_in_the_slots_next_request(models, synchronous, which, how):
    """A and B decode, C waits for a slot. A is cancelled (or expires) while a step that computed its next token is
    in flight; the same tick admits C to A's slot and finishes its one-chunk prefill. The late token is dropped: C's
    list is its own, B's is untouched, A keeps a prefix of its own."""
    engine = DecodeEngine(models[which], max_batch_slots=2, **KW)
    sched = ContinuousBatchingScheduler(engine)
    prompts = [_prompt(which, n, 200 + n) for n in (7, 6, 5)]
    budgets = (30, 14, 8)
    want = [synchronous(which, p, m) for p, m in zip(prompts, budgets)]
    a, b, c = (sched.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets))
    for _ in range(4):
        sched.step()
    req_a, req_c = sched.find(a), sched.find(c)
    assert req_a.status == "running" and req_c.status == "queued" and engine._inflight is not None
    slot, had = req_a.slot, len(req_a.tokens)
    if how == "cancel":
        assert sched.cancel(a)
    else:
        req_a.deadline_s = 1e-9                                       # the next tick's sweep reclaims it
    sched.step()
    assert req_a.status == ("cancelled" if how == "cancel" else "deadline_exceeded")
    # admitted in the same tick: the slot claimed and the one chunk launched, behind the step that served A
    assert req_c.slot == slot and req_c.status == "prefilling" and sched._jobs[slot].pending is not None
    assert len(req_a.tokens) == had                                   # the token that was in flight is not delivered
    sched.step()
    assert req_c.status == "running" and req_c.tokens == want[2][:2]  # its first token, and its own second
    done = sched.run()
    assert done[c].tokens == want[2]
    assert done[b].tokens == want[1]
    assert req_a.tokens == want[0][:had] and a not in done
    assert engine._inflight is None


@pytest.mark.parametrize("which", ["gpt", "solar"])
def test_engine_masks_a_freed_slot_and_keeps_the_hosts_word_on_it(models, synchronous, which):
    """The engine alone: a slot freed and admitted into between a step's launch and its pull reads not emitted at
    that pull, and active for its new request; the neighbour's token of the same step is delivered; the next pull
    brings the new request's second token."""
    engine = DecodeEngine(models[which], max_batch_slots=2, **KW)
    old, new, other = _prompt(which, 6, 31), _prompt(which, 4, 32), _prompt(which, 7, 33)
    want, want_other = synchronous(which, new, 5), synchronous(which, other, 12)
    engine.prefill(old, 0, max_new_tokens=9)
    got_other = [engine.prefill(other, 1, max_new_tokens=12)[0]]
    toks, emitted, active = engine.decode_step(ahead=True)           # fills the pipe: nothing to pull
    assert not emitted.any() and toks.shape == emitted.shape == active.shape == (2,) and active.all()
    engine.free_slot(0)
    assert engine._inflight is not None                              # slot 1's token is still to be delivered
    first, more = engine.prefill(new, 0, max_new_tokens=5)
    assert (first, more) == (want[0], True)
    toks, emitted, active = engine.decode_step(ahead=True)           # pulls the step launched for ``old``
    assert emitted.tolist() == [False, True] and active.all()
    got, got_other = [first], got_other + [int(toks[1])]
    while engine._inflight is not None:
        toks, emitted, active = engine.decode_step(ahead=True)
        got += [int(toks[0])] if emitted[0] else []
        got_other += [int(toks[1])] if emitted[1] else []
    assert got == want and got_other == want_other and not active.any()


@pytest.mark.parametrize("which", ["gpt", "solar"])
def test_a_first_token_is_left_on_the_device_while_a_step_is_in_flight(models, synchronous, which):
    """With nothing in flight the last chunk's call pulls the first token, as a synchronous caller expects. With a
    step in flight the pull would wait for it and for every chunk queued behind it: the call returns False, the host
    takes the slot for active, the next decode step already serves it, and the next call pulls the token."""
    engine = DecodeEngine(models[which], max_batch_slots=2, **KW)
    new, other = _prompt(which, 4, 32), _prompt(which, 7, 33)
    want = synchronous(which, new, 5)
    job = engine.begin_prefill(other, 1, max_new_tokens=12)
    assert engine.prefill_step(job) is True and job.done and job.pending is None      # nothing in flight: at once
    engine.decode_step(ahead=True)
    before = profiler.counters("infer.").get("infer.prefill_first_deferred", 0)
    job = engine.begin_prefill(new, 0, max_new_tokens=5)
    assert engine.prefill_step(job) is False and job.pending is not None and not job.done and job.first is None
    assert profiler.counters("infer.")["infer.prefill_first_deferred"] == before + 1
    assert job.chunks_left(engine._chunk) == 0 and engine._active_np[0]
    _, emitted, active = engine.decode_step(ahead=True)               # this launch serves slot 0; the pull is the step before's
    assert emitted.tolist() == [False, True] and active.all()
    assert engine.prefill_step(job) is True and (job.first, job.more, job.pending) == (want[0], True, None)
    toks, emitted, _ = engine.decode_step(ahead=True)
    assert emitted.all() and int(toks[0]) == want[1]                  # the second token, from the step launched meanwhile


def test_a_step_launched_on_slots_all_freed_since_is_forgotten(models):
    engine = DecodeEngine(models["gpt"], max_batch_slots=2, **KW)
    engine.prefill(_prompt("gpt", 6, 31), 0, max_new_tokens=9)
    engine.decode_step(ahead=True)
    assert engine._inflight is not None
    engine.free_slot(0)
    assert engine._inflight is None                                  # no slot left active: nothing to deliver


def test_slot_constants_live_on_the_device_and_follow_admission_and_reset(models):
    engine = DecodeEngine(models["gpt"], max_batch_slots=2, **KW)
    engine.begin_prefill(_prompt("gpt", 5, 1), 1, max_new_tokens=4, eos_token_id=17, seed=23)
    eos, limit, seed = (np.asarray(a) for a in engine._slot_consts)
    assert eos.tolist() == [-1, 17] and limit.tolist() == [0, 9] and seed.tolist() == [0, 23]
    held = engine._slot_consts
    engine._eos[0] = 99                                               # the host arrays are written in place ...
    assert np.asarray(held[0]).tolist() == [-1, 17]                   # ... and the device's copies are copies
    engine.reset()
    assert [np.asarray(a).tolist() for a in engine._slot_consts] == [[-1, -1], [0, 0], [0, 0]]


def test_everything_cancelled_in_flight_leaves_nothing_in_flight(models):
    engine = DecodeEngine(models["gpt"], max_batch_slots=2, **KW)
    sched = ContinuousBatchingScheduler(engine)
    rids = [sched.submit(_prompt("gpt", 5, i), max_new_tokens=20) for i in range(2)]
    for _ in range(3):
        sched.step()
    assert engine._inflight is not None
    for rid in rids:
        sched.cancel(rid)
    assert engine._inflight is None and not sched.running
    assert sched.run() == {}


# ------------------------------------------------------ the synchronous contract
def test_a_direct_caller_keeps_the_synchronous_step(models, synchronous):
    """``decode_step(fuse=1)`` returns the tokens of the step it launched; with a step in flight it raises rather
    than lose that step's tokens; ``reset()`` drops the step, and ``generate`` (which resets) serves as before."""
    engine = DecodeEngine(models["gpt"], max_batch_slots=2, **KW)
    sched = ContinuousBatchingScheduler(engine)
    prompt = _prompt("gpt", 9, 77)
    want = synchronous("gpt", prompt, 6)
    sched.submit(prompt, max_new_tokens=12)
    for _ in range(4):
        sched.step()
    assert engine._inflight is not None
    with pytest.raises(RuntimeError, match="in flight"):
        engine.decode_step(fuse=1)
    engine.reset()
    assert engine._inflight is None
    first, _ = engine.prefill(prompt, 1, max_new_tokens=6)
    toks, emitted, active = engine.decode_step(fuse=1)
    assert emitted.tolist() == [False, True] and [first, int(toks[1])] == want[:2] and active[1]
    assert engine._inflight is None
    out = engine.generate(prompt[None], max_new_tokens=6)
    assert out[0, len(prompt):].tolist() == want


@pytest.mark.parametrize("kind", ["draft", "fuse4"])
def test_an_engine_that_pulls_a_stack_keeps_the_synchronous_step(models, synchronous, kind):
    kw = dict(draft=GPTConfig(vocab_size=512, hidden_size=32, num_layers=1, num_heads=2, max_seq_len=128), spec_k=3) \
        if kind == "draft" else dict(fuse=4)
    engine = DecodeEngine(models["gpt"], max_batch_slots=2, **KW, **kw)
    sched = ContinuousBatchingScheduler(engine)
    prompts = [_prompt("gpt", n, 300 + n) for n in (5, 12, 7)]
    want = [synchronous("gpt", p, 10) for p in prompts]
    profiler.reset_counters("infer.decode_ahead")
    profiler.reset_counters("infer.prefill_first")
    rids = [sched.submit(p, max_new_tokens=10) for p in prompts]
    seen_in_flight = False
    while sched.queue or sched.prefilling or sched.running:
        sched.step()
        seen_in_flight |= engine._inflight is not None
    assert [sched.finished[r].tokens for r in rids] == want
    assert not seen_in_flight and _ahead() == 0
    assert profiler.counters("infer.").get("infer.prefill_first_deferred", 0) == 0


# ------------------------------------------------------------------ the order
@pytest.mark.parametrize("which", ["gpt", "solar"])
def test_the_launch_of_a_step_precedes_the_pull_of_the_step_before(models, which):
    """The dispatch and the pull instrumented: in a scheduler tick the decode program of step k is dispatched before
    the report of step k - 1 is pulled, and ``infer.decode_ahead`` counts the decode dispatches less the pipe's
    fills (a launch with nothing in flight)."""
    engine = DecodeEngine(models[which], max_batch_slots=2, **KW)
    sched = ContinuousBatchingScheduler(engine)
    log, launched = [], []
    dispatch, collect = engine._dispatch, engine._collect_decode

    def logged_dispatch(kind, *a, **k):
        out = dispatch(kind, *a, **k)
        if kind == "decode":
            launched.append(out[4])
            log.append(("launch", len(launched) - 1))
        return out

    def logged_collect(step):
        if step is not None:
            log.append(("pull", next(i for i, r in enumerate(launched) if r is step.report)))
        return collect(step)

    engine._dispatch, engine._collect_decode = logged_dispatch, logged_collect
    profiler.reset_counters("infer.decode")
    profiler.reset_counters("infer.prefill_first")
    sched.submit(_prompt(which, 5, 1), max_new_tokens=6)
    sched.submit(_prompt(which, 13, 2), max_new_tokens=9)
    for _ in range(6):
        sched.step()
    sched.submit(_prompt(which, 6, 3), max_new_tokens=4)              # its last chunk is launched with a step in flight
    sched.run()
    sched.submit(_prompt(which, 4, 4), max_new_tokens=3)              # after an idle spell: the pipe fills again
    sched.run()

    # each pull is of the step before the one launched just before it; a step launched with nothing in flight fills
    # the pipe (the first of each busy spell), and the last step of a spell, launched for nobody, is never pulled
    pulls = [i for i, (what, _) in enumerate(log) if what == "pull"]
    assert len(pulls) >= 10
    assert all(log[i - 1] == ("launch", log[i][1] + 1) for i in pulls)
    fills = len(launched) - len(pulls)
    counts = profiler.counters("infer.")
    assert counts["infer.decode_dispatches"] == len(launched)
    assert counts["infer.decode_ahead"] == len(launched) - fills and fills == 2
    assert counts["infer.prefill_first_deferred"] == 2                # the second and the third request's last chunks
    assert engine._inflight is None
