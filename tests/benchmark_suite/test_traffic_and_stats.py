"""The generators are pure functions of their seed, and the arithmetic of the
metrics on hand-made logs — above all ``out_tok_s`` on tick boundaries."""
import numpy as np
import pytest

import tiny_root
from benchmark.harness import manifest, stats
from benchmark.harness.records import Records, RequestRecord

REPO = tiny_root.REPO
MANIFEST = manifest.load_manifest(REPO)


def _module(kind, name):
    return manifest.load_module(REPO, "benchmark", kind, name)


def _traffic(name):
    return manifest.load_json(REPO, f"benchmark/traffic/{name}.json")


# ------------------------------------------------------------ generators
@pytest.mark.parametrize("seed", [0, 7, 3000000019])
def test_open_loop_schedule_is_a_pure_function_of_the_seed(seed):
    drv, t = _module("drivers", "serve_open_loop"), _traffic("chat-steady")
    due, prompts, out = drv.schedule(t, seed, 51.0, 50257)
    due2, prompts2, out2 = drv.schedule(t, seed, 51.0, 50257)
    assert np.array_equal(due, due2) and np.array_equal(out, out2)
    assert all(np.array_equal(a, b) for a, b in zip(prompts, prompts2))
    assert np.all(np.diff(due) > 0) and 0 < due[0] and due[-1] < 51.0
    assert abs(len(due) / 51.0 - t["rate_per_s"]) < 0.35 * t["rate_per_s"]
    p = np.array([len(x) for x in prompts])
    assert p.min() >= t["prompt_tokens"]["min"] and p.max() <= t["prompt_tokens"]["max"]
    assert out.min() >= 1 and out.max() <= t["output_tokens"]["max"]
    assert np.all(p + out <= t["max_total_tokens"])
    assert all(x.max() < 50257 and x.min() >= 0 for x in prompts)


def test_open_loop_seeds_offer_the_same_requests_with_other_tokens():
    drv, t = _module("drivers", "serve_open_loop"), _traffic("chat-steady")
    a = drv.schedule(t, 1, 51.0, 50257)
    b = drv.schedule(t, 2, 51.0, 50257)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[2], b[2])        # same offsets, same outputs
    assert [len(x) for x in a[1]] == [len(x) for x in b[1]]                  # same prompt lengths
    assert not all(np.array_equal(x, y) for x, y in zip(a[1], b[1]))         # other token ids


def test_closed_loop_lists_are_fixed_and_inside_the_context():
    drv, t = _module("drivers", "serve_closed_loop"), _traffic("longgen-saturated")
    lists, again = drv.client_lists(t, 8), drv.client_lists(t, 8)
    assert lists == again and len(lists) == 8
    assert lists[0] != lists[1]
    for c, reqs in enumerate(lists):
        assert reqs[0][0] == 128 + 96 * c                      # staggered depth by prefill
        for k, (p, o) in enumerate(reqs):
            assert p + o <= t["max_total_tokens"] and o >= 1
            if k:
                assert t["prompt_tokens"]["min"] <= p <= t["prompt_tokens"]["max"]
                assert t["output_tokens"]["min"] <= o <= t["output_tokens"]["max"]
    firsts = [reqs[0][1] for reqs in lists]
    assert firsts[0] < 1536 / 8 + 1 and len(set(firsts)) > 4   # first refills do not come together


# ------------------------------------------------------------ arithmetic
def test_percentile_median_spread():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile(list(range(101)), 95) == 95.0
    assert stats.percentile([], 95) is None and stats.mean([]) is None
    assert stats.spread([10, 10, 10, 10, 11, 9]) == pytest.approx((10.25 - 9.75) / 10)


def _records(**kw):
    r = Records(cell=None, seed=0, seconds=kw.pop("seconds", 10.0), peaks={}, chips=1)
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def test_itl_and_ttft_on_a_hand_made_token_log():
    reqs = [RequestRecord(due=1.0, submitted=1.1, prompt_tokens=5, output_tokens=4, first_token=1.5,
                          token_times=[1.5, 1.7, 2.0, 2.4]),
            RequestRecord(due=2.0, submitted=2.0, prompt_tokens=5, output_tokens=3, first_token=2.25,
                          token_times=[2.25, 2.35, 12.0]),       # its last gap ends after the window
            RequestRecord(due=3.0, submitted=3.0, prompt_tokens=5, output_tokens=3)]  # never answered
    r = _records(requests=reqs, window_open=1.0, window_close=11.0)
    itl, ttft = _module("end_to_end", "itl_p95_ms"), _module("layer_metrics", "ttft_p50_ms")
    assert sorted(round(x, 6) for x in r.itl_samples()) == [0.1, 0.2, 0.3, 0.4]
    assert itl.read(r) == pytest.approx(1e3 * np.percentile([0.1, 0.2, 0.3, 0.4], 95))
    assert r.ttft_samples() == [0.5, 0.25] and ttft.read(r) == pytest.approx(375.0)
    assert _module("layer_metrics", "ttft_mean_ms").read(r) == pytest.approx(375.0)
    assert _module("layer_metrics", "gen_late_ms").read(r) == pytest.approx(1e3 * np.percentile([0.1, 0, 0], 95))


def _ticks(gaps, start=100.0):
    return list(start + np.cumsum(gaps))


def test_out_tok_s_is_counted_between_tick_boundaries():
    read = _module("end_to_end", "out_tok_s").read
    ends = _ticks([0.05] * 250)                                  # 50-ms ticks, 8 tokens each
    r = _records(tick_end=ends, tick_tokens=[8] * 250, window_open=ends[9], seconds=10.01)
    assert read(r) == pytest.approx(160.0, rel=1e-9)
    # a partial last tick must not change the number
    r2 = _records(tick_end=ends, tick_tokens=[8] * 250, window_open=ends[9], seconds=10.04)
    assert read(r2) == pytest.approx(160.0, rel=1e-9)
    assert stats.tick_window(ends, ends[9], 10.01) == stats.tick_window(ends, ends[9], 10.04) == (9, 209)
    # a 2-s lump between two ticks must, by exactly the lump
    gaps = [0.05] * 250
    gaps[100] += 2.0
    lumpy = _ticks(gaps)
    r3 = _records(tick_end=lumpy, tick_tokens=[8] * 250, window_open=lumpy[9], seconds=10.01)
    i_open, i_close = stats.tick_window(lumpy, lumpy[9], 10.01)
    n = i_close - i_open
    assert n == 160                                              # 40 ticks fewer fit
    assert read(r3) == pytest.approx(8 * n / (0.05 * n + 2.0), rel=1e-9)
    assert read(r3) == pytest.approx(128.0, rel=1e-9)
    # the window opens at the first boundary at or after warm-up's end
    r4 = _records(tick_end=ends, tick_tokens=[8] * 250, window_open=ends[9] - 0.01, seconds=10.01)
    assert read(r4) == pytest.approx(160.0, rel=1e-9)


def test_train_rate_is_per_chip_between_step_boundaries():
    read = _module("end_to_end", "train_tok_s_chip").read
    ends = _ticks([0.2] * 100)
    r = _records(step_end=ends, tokens_per_step=8192, window_open=ends[2], seconds=10.1, chips=4)
    assert read(r) == pytest.approx(8192 / 0.2 / 4, rel=1e-9)


def test_longest_gaps_say_what_the_host_did():
    gaps = [0.05] * 20
    gaps[7] = 0.5
    parts = [{"decode_s": 0.04, "prefill_s": 0.0} for _ in gaps]
    parts[7] = {"decode_s": 0.04, "prefill_s": 0.45}
    top = stats.longest_gaps(_ticks(gaps), parts, k=3)
    assert top[0]["tick"] == 7 and top[0]["gap_s"] == pytest.approx(0.5) and top[0]["prefill_s"] == 0.45


def test_occupancy_and_live_rows():
    r = _records(tick_end=[1.0, 2.0, 3.0], tick_decoding=[8, 6, 8], tick_live_rows=[100, 200, 300],
                 tick_parts=[{"decode_s": 0.5}, {"decode_s": 0.5}, {"decode_s": 0.9}],
                 tick_prefill_dispatches=[0, 0, 1], slots=8, context=100, window_open=1.0, window_close=3.0)
    assert _module("layer_metrics", "batch_occupancy_pct").read(r) == pytest.approx(100 * 14 / 16)
    assert _module("layer_metrics", "kv_live_pct").read(r) == pytest.approx(100 * 500 / (2 * 800))
    assert _module("layer_metrics", "prefill_share_pct").read(r) is None        # needs the device trace
    assert _module("layer_metrics", "decode_step_ms").read(r) == pytest.approx(500.0)
