"""The generators and the client's arithmetic: the same multiset under
every seed, tokens counted by when they arrive, time from when a request
was due."""
from collections import Counter

import numpy as np
import pytest

from benchmark import clientstats, manifest, traffic

MAN = manifest.Manifest()


def _lens(plan, key, only_window=True):
    return Counter(r[key] for r in plan["requests"]
                   if r.get("in_window", True) or not only_window)


@pytest.mark.parametrize("name", ["chat-steady", "batch-offline"])
def test_same_requests_every_seed_other_tokens(name):
    """The seed may not change the work: lengths, their order and the
    arrival times are the traffic file's; the seed gives the token ids."""
    spec = MAN.traffic(name)
    plans = [traffic.make_plan(spec, s, 45) for s in (1, 2, 2**31 + 9)]
    assert plans[0] == plans[1] == plans[2]
    for key in ("prompt_len", "max_new"):
        assert len(_lens(plans[0], key)) > 20      # a spread of lengths
    r = plans[0]["requests"][3]
    ids = [traffic.prompt_tokens(s, r["tag"], r["prompt_len"], 32768)
           for s in (1, 2)]
    assert ids[0] != ids[1]
    other = traffic.make_plan(dict(spec, order_seed=1), 1, 45)
    assert other != plans[0]
    for key in ("prompt_len", "max_new"):
        assert _lens(other, key, False) == _lens(plans[0], key, False)


def test_open_loop_arrivals_lie_inside_the_window_and_scale_with_it():
    spec = MAN.traffic("chat-steady")
    plan = traffic.make_plan(spec, 5, 45)
    due = [r["due"] for r in plan["requests"] if r["in_window"]]
    assert len(due) == round(spec["rate_per_s"] * 45)
    assert all(0 < t < 45 for t in due) and due == sorted(due)
    lead = [r["due"] for r in plan["requests"] if not r["in_window"]]
    assert all(-spec["lead_in_s"] < t < 0 for t in lead)
    # the gaps are the exponential's quantile grid, scaled to the window
    gaps = sorted(np.diff([0.0] + due))
    grid = sorted(traffic.exponential_gaps(len(due) + 1, 45.0))
    assert len(set(np.round(gaps, 9)) & set(np.round(grid, 9))) == len(due)
    assert len(traffic.make_plan(spec, 5, 90)["requests"]) > \
        len(plan["requests"])
    tail = traffic.make_plan(spec, 5, 45, tail_s=8.0)["requests"]
    assert max(r["due"] for r in tail) > 45 and \
        [r for r in tail if r["in_window"]] == \
        [r for r in plan["requests"] if r["in_window"]]


def test_chat_prompt_quantiles_sit_inside_bucket_modes():
    """The issue's centring: with buckets 128..2048 the median prompt pads
    to 512, well inside that mode."""
    spec = MAN.traffic("chat-steady")
    buckets = MAN.config("mistral-7b-v0.3-serve")["serve"]["prompt_buckets"]
    lens = traffic.quantile_lengths(spec["prompt"], 1000)
    pad = Counter(min(b for b in buckets if b >= n) for n in lens)
    below = (pad[128] + pad[256]) / 1000.0
    assert 0.2 < below < 0.3 and 0.65 < below + pad[512] / 1000.0 < 0.72
    assert min(lens) >= 32 and max(lens) <= 2048


def test_token_ids_from_seed_and_tag():
    a = traffic.prompt_tokens(2**31 + 5, [1, 3], 50, 32768)
    assert a == traffic.prompt_tokens(2**31 + 5, [1, 3], 50, 32768)
    assert a != traffic.prompt_tokens(2**31 + 5, [1, 4], 50, 32768)
    assert max(a) < 32768 and len(a) == 50
    rows = traffic.train_batch(7, 0, 4, 16, 100)
    assert rows.shape == (4, 17) and len({tuple(r) for r in rows}) == 4
    assert (rows != traffic.train_batch(7, 1, 4, 16, 100)).any()


def _stream(due, t_tokens, max_new=None, status=200, reason="finished",
            in_window=True, cut=False):
    toks = list(range(len(t_tokens)))
    return {"idx": 0, "tag": [0, 0], "prompt_len": 10,
            "max_new": max_new or len(toks), "due": due,
            "in_window": in_window, "sent": (due or 0) + 0.002,
            "status": status, "t_tokens": t_tokens, "tokens": toks,
            "cut": cut, "reason": reason, "request_id": 1,
            "terminal_tokens": toks}


def test_tokens_counted_by_receipt_inside_the_window():
    result = {"seconds": 10.0, "streams": [
        # began before the window, ends inside: 2 of its 4 tokens count
        _stream(None, [-1.0, -0.5, 0.5, 1.0]),
        # cut at the close, unfinished: its 3 tokens inside still count
        _stream(None, [8.0, 9.0, 10.0, 10.5], max_new=9, cut=True,
                reason=None),
    ]}
    assert clientstats.tokens_in_window(result) == 5
    attempted, failed = clientstats.attempted_failed(result)
    assert (attempted, failed) == (2, 0)


def test_latency_is_from_when_due_and_failures_take_the_worst():
    result = {"seconds": 10.0, "unsent": 0, "streams": [
        _stream(1.0, [1.05, 1.07, 1.09]),
        _stream(2.0, [2.10, 2.20]),
        _stream(3.0, [], status=503, reason=None),
        _stream(-1.0, [-0.9, 0.5], in_window=False),
    ]}
    ttft = clientstats.series(result, "ttft_ms")
    assert ttft[:2] == pytest.approx([50.0, 100.0])
    assert ttft[2] >= 10000.0
    assert clientstats.series(result, "late_ms") == pytest.approx([2.0] * 3)
    # gaps of every stream whose later token arrived inside the window
    assert sorted(clientstats.series(result, "itl_ms")) == pytest.approx(
        [20.0, 20.0, 100.0, 1400.0])
    assert clientstats.attempted_failed(result) == (3, 1)
