"""The seed may choose ids and order, never the amount of work."""
import json
import os

import pytest

import traffic_gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [0, 1, 2, 3, 5, 8, 13, 21, 34, 2**31 + 7, 2**31 + 99, 123456789]


def traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# the mix of the put-off cell gpt3-1.3b.chat (PERF.md, Open questions)
CHAT_OPEN = {"kind": "open-serve", "rate_rps": 8.0,
             "prompt_len": {"dist": "lognormal", "median": 200, "sigma": 0.9,
                            "lo": 32, "hi": 896},
             "output_len": {"dist": "lognormal", "median": 48, "sigma": 0.7,
                            "lo": 16, "hi": 160}}


def test_open_loop_offers_the_same_work_for_every_seed():
    t = CHAT_OPEN
    want = traffic_gen.summary(traffic_gen.open_schedule(t, 30, 50304, SEEDS[0]))
    assert want["requests"] == round(t["rate_rps"] * 30)
    assert 150 <= want["prompt_lens"][len(want["prompt_lens"]) // 2] <= 250
    assert want["prompt_lens"][0] >= 32 and want["prompt_lens"][-1] <= 896
    assert want["output_lens"][0] >= 16 and want["output_lens"][-1] <= 160
    orders = set()
    for seed in SEEDS[1:]:
        sched = traffic_gen.open_schedule(t, 30, 50304, seed)
        assert traffic_gen.summary(sched) == want
        assert all(a.due_s <= b.due_s for a, b in zip(sched, sched[1:]))
        orders.add(tuple(len(r.prompt) for r in sched[:20]))
    assert len(orders) > 1                       # the seed does rotate


def test_closed_loop_offers_the_same_work_for_every_seed():
    t = traffic("decode-long")

    def shape(clients):      # per client, what it will send, in order
        return sorted(tuple((len(r.prompt), r.max_tokens) for r in c)
                      for c in clients)

    want = shape(traffic_gen.closed_clients(t, 50304, SEEDS[0]))
    firsts = sorted(c[0][1] for c in want)
    assert firsts == [16 * (i + 1) for i in range(32)]      # the stagger
    assert sorted(ln for c in want for ln, _ in c[:1]) == list(
        traffic_gen.lengths(t["prompt_len"], 32))
    ids = set()
    for seed in SEEDS[1:]:
        clients = traffic_gen.closed_clients(t, 50304, seed)
        assert shape(clients) == want
        ids.add(clients[0][0].prompt[:8])
    assert len(ids) > 1


@pytest.mark.parametrize("spec,n,lo,hi", [
    ({"dist": "uniform", "lo": 256, "hi": 1024}, 32, 256, 1024),
    ({"dist": "lognormal", "median": 200, "sigma": 0.9, "lo": 32, "hi": 896},
     240, 32, 896)])
def test_lengths_are_a_function_of_spec_and_count(spec, n, lo, hi):
    a, b = traffic_gen.lengths(spec, n), traffic_gen.lengths(spec, n)
    assert list(a) == list(b) and len(a) == n
    assert a.min() >= lo and a.max() <= hi and list(a) == sorted(a)


def test_gaps_sum_to_the_window():
    g = traffic_gen.gaps(8.0, 240)
    assert abs(g.sum() - 30.0) < 0.5
