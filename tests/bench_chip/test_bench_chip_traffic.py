"""The benchmark's traffic generators and latency arithmetic (CPU)."""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import gen  # noqa: E402

CHAT = json.loads((CHIP / "traffic" / "chat.json").read_text())
SEED = 2 ** 33 + 5          # larger than 32 signed bits hold


def test_chat_schedule_is_a_function_of_the_seed():
    a = gen.chat_schedule(CHAT, seed=SEED, seconds=45, vocab=1000)
    b = gen.chat_schedule(CHAT, seed=SEED, seconds=45, vocab=1000)
    assert [(r.due_s, r.max_new_tokens, r.tenant) for r in a] == \
        [(r.due_s, r.max_new_tokens, r.tenant) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_offers_the_same_work_in_another_order():
    a = gen.chat_schedule(CHAT, seed=1, seconds=45, vocab=1000)
    b = gen.chat_schedule(CHAT, seed=2, seconds=45, vocab=1000)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert sorted(r.tenant for r in a) == sorted(r.tenant for r in b)
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_chat_schedule_rate_and_window():
    s = gen.chat_schedule(CHAT, seed=SEED, seconds=45, vocab=1000)
    assert len(s) == round(CHAT["rate"] * 45)
    due = np.array([r.due_s for r in s])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 45
    assert [r.index for r in s] == list(range(len(s)))


def test_chat_schedule_lengths_follow_the_stated_distributions():
    s = gen.chat_schedule(CHAT, seed=SEED, seconds=400, vocab=1000)
    p = np.array([len(r.prompt) for r in s])
    o = np.array([r.max_new_tokens for r in s])
    assert np.median(p) == pytest.approx(CHAT["prompt"]["median"], abs=2)
    assert np.median(o) == pytest.approx(CHAT["output"]["median"], abs=2)
    assert p.min() >= CHAT["prompt"]["min"] and p.max() <= CHAT["prompt"]["max"]
    assert o.min() >= CHAT["output"]["min"] and o.max() <= CHAT["output"]["max"]
    # lognormal: the log lengths' spread is sigma (clipping trims the tails)
    q1, q3 = np.percentile(np.log(p), [25, 75])
    assert (q3 - q1) / 1.349 == pytest.approx(CHAT["prompt"]["sigma"],
                                              rel=0.1)
    # Zipf tenants: the most popular first, every tenant present
    counts = np.bincount([r.tenant for r in s], minlength=CHAT["tenants"])
    assert list(counts) == sorted(counts, reverse=True) and counts.min() > 0
    # Poisson: gaps' coefficient of variation near 1
    gaps = np.diff([r.due_s for r in sorted(s, key=lambda r: r.due_s)])
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)


def test_train_stream_is_deterministic_and_differs_by_step():
    a = gen.TrainStream(vocab=500, seq=64, batch=2, seed=SEED)
    b = gen.TrainStream(vocab=500, seq=64, batch=2, seed=SEED)
    x, y = a.batch_np(3), b.batch_np(3)
    assert np.array_equal(x["tokens"], y["tokens"])
    assert np.array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert not np.array_equal(a.batch_np(4)["tokens"], x["tokens"])
    assert x["tokens"].dtype == np.int32 and x["tokens"].max() < 500


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert gen.percentile(v, 90) == 90
    assert gen.percentile(v, 99) == 99
    assert gen.percentile([5.0], 50) == 5.0
    assert gen.percentile([1, np.inf, 2], 90) == np.inf


def test_time_to_first_token_counts_from_when_the_request_was_due():
    import harness
    chat = harness.load_module("drivers", "chat")
    sched = [gen.Request(0, 0.0, np.zeros(4, np.int32), 3, 0),
             gen.Request(1, 1.0, np.zeros(4, np.int32), 3, 0),
             gen.Request(2, 2.0, np.zeros(4, np.int32), 3, 0)]

    class R:
        def __init__(self, reason):
            self.finish_reason = reason
    t0 = 100.0
    # request 1 was submitted late (a stall) and got its first token at
    # 101.5: its TTFT is 500 ms, not the time since it was submitted.
    times = {0: [100.2, 100.25, 100.3], 1: [101.5, 101.6, 101.7],
             2: [102.4]}
    results = {0: R("length"), 1: R("length"), 2: R("error")}
    ttft, itl = chat.latencies(sched, times, results, t0)
    assert ttft[0] == pytest.approx(200.0)
    assert ttft[1] == pytest.approx(500.0)
    assert ttft[2] == np.inf                     # failed: misses every limit
    assert sorted(itl) == pytest.approx([50.0, 50.0, 100.0, 100.0])


def test_the_check_sample_holds_the_longest_output_and_the_longest_prompt():
    import harness
    chat = harness.load_module("drivers", "chat")
    sched = [gen.Request(i, float(i), np.zeros(p, np.int32), 4, 0)
             for i, p in enumerate([10, 3000, 20, 30, 40, 50])]

    class R:
        def __init__(self, n, reason="length"):
            self.tokens, self.finish_reason = [0] * n, reason
    results = {0: R(9), 1: R(2), 2: R(50), 3: R(3), 4: R(4, "error"),
               5: R(5)}
    for seed in (1, 2, SEED):
        picked = chat.pick_sample(sched, results, {"check_requests": 3},
                                  seed)
        assert picked[:2] == [2, 1] and len(set(picked)) == 3
        assert 4 not in picked
    # the longest output and the longest prompt are one request
    results[1] = R(99)
    assert chat.pick_sample(sched, results, {"check_requests": 2}, 1)[0] == 1
