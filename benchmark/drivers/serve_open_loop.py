"""Open loop: requests are due on a schedule fixed before the window opens,
whatever the server does, and each is timed from when it was *due*.

Traffic parameters: ``rate_per_s``, ``stream_seed``, ``prompt_tokens``,
``output_tokens``, ``max_total_tokens``, ``drain_seconds``,
``warmup_requests``. The schedule is a pure function of (traffic file,
``--seconds``): exponential gaps at the rate and log-normal lengths are drawn
from ``stream_seed`` until the gaps fill the window. ``--seed`` draws the token
ids (and the weights) and nothing else: every seed offers the same requests at
the same offsets. It did rotate the sequence to another starting point at
first; the order alone moved the median first-token time by +-6% between seeds
on the chip (PERF.md Findings, PR 24), so the seed was changing the work.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.drivers import _serving
from benchmark.harness import trace as _trace
from benchmark.drivers._serving import clock


def schedule(traffic: dict, seed: int, seconds: float, vocab: int):
    """(due offsets [n], prompts (list of int32 arrays), output lengths [n])."""
    base = np.random.default_rng([int(traffic["stream_seed"]), 0])
    n_max = int(seconds * traffic["rate_per_s"] * 2) + 16
    gaps = base.exponential(1.0 / traffic["rate_per_s"], n_max)
    n = int(np.searchsorted(np.cumsum(gaps), seconds))          # arrivals inside the window
    prompt = _serving.draw_lengths(base, traffic["prompt_tokens"], n_max)[:n]
    output = _serving.draw_lengths(base, traffic["output_tokens"], n_max)[:n]
    prompt, output = _serving.fit_total(prompt, output, int(traffic["max_total_tokens"]))
    due = np.cumsum(gaps[:n])
    rng = np.random.default_rng([int(seed), 1])
    prompts = [rng.integers(0, vocab, (int(p),)).astype(np.int32) for p in prompt]
    return due, prompts, output


def run(records, devices, *, process_start, trace_on, trace_dir):
    cell = records.cell
    traffic, vocab = cell.traffic, int(cell.config["vocab_size"])
    served = _serving.Served(records, devices)
    due, prompts, outputs = schedule(traffic, records.seed, records.seconds, vocab)

    # warm-up: the cell's own programs (prefill chunk, final chunk, decode)
    rng = np.random.default_rng([records.seed, 2])
    for w in traffic["warmup_requests"]:
        served.submit(rng.integers(0, vocab, (int(w["prompt"]),)).astype(np.int32), int(w["output"]),
                      due=clock(), in_window=False)
    while served.busy():
        served.tick()
    gc.collect()
    gc.freeze()   # what set-up built is not walked again by the collector

    builds = _serving.program_builds()
    t_open = clock()
    records.window_open, records.window_close = t_open, t_open + records.seconds
    records.setup_s = t_open - process_start
    served.last_end = t_open
    traced = _trace.TracedPart(trace_on, trace_dir, records, records.seconds)
    limit = records.window_close + float(traffic["drain_seconds"])
    i, n, pending = 0, len(due), []
    while True:
        now = clock()
        while i < n and t_open + due[i] <= now:
            entry = served.submit(prompts[i], int(outputs[i]), due=t_open + due[i], seed=i)
            if entry is not None:
                pending.append(entry)
            i += 1
        if now >= records.window_close and i >= n:
            pending = [e for e in pending if e[0].first_token is None and e[1].status in ("queued", "prefilling", "running")]
            if not pending or now >= limit:
                break
        if not served.busy():
            wake = t_open + due[i] if i < n else records.window_close
            time.sleep(max(0.0, min(wake - now, 0.002)))
            continue
        served.trace_on = traced.running
        end = served.tick()
        traced.after_unit(len(records.tick_end) - 1, end)
    traced.finish(len(records.tick_end) - 1)
    window = [r for r in records.requests if r.in_window]
    records.attempted = len(window)
    records.failed = sum(1 for r in window if r.first_token is None)   # refused, or not answered by the drain's end
    records.notes["offered_rate_per_s"] = n / records.seconds
    records.notes["queue_at_middle_and_close"] = _queue_depths(records, t_open)
    _serving.finish(served, records, builds)


def _queue_depths(records, t_open):
    """Requests sent and not yet answered at the window's middle and close:
    the sweep for the knee reads these."""
    out = []
    for at in (t_open + records.seconds / 2.0, t_open + records.seconds):
        out.append(sum(1 for r in records.requests if r.in_window and r.submitted <= at
                       and (r.first_token is None or r.first_token > at)))
    return out
