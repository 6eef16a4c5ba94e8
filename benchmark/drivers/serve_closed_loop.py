"""Closed loop: as many clients as the traffic file says, each sending its
next request when its last one finishes. The stream is a pure function of the
seed and is driven by tick numbers, never by the clock: request *k* of client
*c* has lengths fixed by (``stream_seed``, *c*, *k*); ``--seed`` deals the
clients' lists to the slots in another order and draws the token ids; a
finished client's next request is sent before the next tick; warm-up is a
fixed number of ticks. So every run admits the same lengths at the same tick
numbers and only time can differ between runs.

The window lies on tick boundaries: it opens at the end of the last warm-up
tick and closes at the last tick end at or before open + ``--seconds``.
"""
from __future__ import annotations

import gc

import numpy as np

from benchmark.drivers import _serving
from benchmark.harness import trace as _trace
from benchmark.harness import stats

REQUESTS_PER_CLIENT = 64   # more than any window can finish


def client_lists(traffic: dict, clients: int):
    """Per client, the (prompt, output) lengths of its requests."""
    lists = []
    for c in range(clients):
        rng = np.random.default_rng([int(traffic["stream_seed"]), c])
        prompt = _serving.draw_lengths(rng, traffic["prompt_tokens"], REQUESTS_PER_CLIENT)
        output = _serving.draw_lengths(rng, traffic["output_tokens"], REQUESTS_PER_CLIENT)
        prompt, output = _serving.fit_total(prompt, output, int(traffic["max_total_tokens"]))
        first = traffic["first_request"]
        prompt[0] = int(first["prompt_base"]) + int(first["prompt_step"]) * c
        output[0] = max(1, int(output[0] * (c + 1) / clients))     # the (c+1)/clients share
        output[0] = min(output[0], int(traffic["max_total_tokens"]) - prompt[0])
        lists.append(list(zip(prompt.tolist(), output.tolist())))
    return lists


def run(records, devices, *, process_start, trace_on, trace_dir):
    cell = records.cell
    traffic, vocab = cell.traffic, int(cell.config["vocab_size"])
    served = _serving.Served(records, devices)
    clients = records.slots if traffic["clients"] == "slots" else int(traffic["clients"])
    lists = client_lists(traffic, clients)
    rng = np.random.default_rng([records.seed, 1])
    deal = rng.permutation(clients)            # which list each client gets
    nxt = [0] * clients
    current = [None] * clients

    def send(c, in_window):
        p, o = lists[deal[c]][nxt[c]]
        nxt[c] += 1
        prompt = rng.integers(0, vocab, (p,)).astype(np.int32)
        current[c] = served.submit(prompt, o, due=_serving.clock(), in_window=in_window, seed=nxt[c])
        if current[c] is None:
            raise RuntimeError("the fleet refused a closed-loop request: max_queue_depth is below the client count")

    def refill(in_window):
        for c in range(clients):
            if current[c] is None or current[c][1].status not in ("queued", "prefilling", "running"):
                send(c, in_window)

    refill(False)
    warmup = int(traffic["warmup_ticks"])
    for k in range(warmup):
        if k == warmup - 1:   # before the last warm-up tick, whose end opens the window
            gc.collect()
            gc.freeze()       # what set-up built is not walked again by the collector
        served.tick()
        refill(False)
    builds = _serving.program_builds()
    t_open = records.tick_end[-1]
    records.window_open = t_open
    records.setup_s = t_open - process_start
    for entry in current:                      # in flight at the opening: part of the window's work
        entry[0].in_window = True
    traced = _trace.TracedPart(trace_on, trace_dir, records, records.seconds)
    while True:
        served.trace_on = traced.running
        end = served.tick()
        if end > t_open + records.seconds:
            break
        refill(True)
        traced.after_unit(len(records.tick_end) - 1, end)
    traced.finish(len(records.tick_end) - 2)
    i_open, i_close = stats.tick_window(records.tick_end, t_open, records.seconds)
    records.window_close = records.tick_end[i_close]
    window = [r for r in records.requests if r.in_window]
    records.attempted = len(window)
    records.failed = served.refused
    records.notes["requests_finished_in_window"] = sum(1 for r in window if r.finished)
    _serving.finish(served, records, builds)
