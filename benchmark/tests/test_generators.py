import collections

import pytest

from benchmark.lib import manifest, stats
from benchmark.lib.lengths import (exponential_gaps, prompt_ids, quantile,
                                   stratified)

CHAT = manifest.load_json(manifest.BENCH + "/traffic/chat.json")["params"]
REASONING = manifest.load_json(
    manifest.BENCH + "/traffic/reasoning.json")["params"]


def open_plan(seed, seconds=45.0, rate=2.25):  # 101 requests
    gen = manifest.load_module("generators", "open_loop")
    return gen.plan(dict(CHAT, rate_rps=rate), seed, seconds)


def test_open_loop_every_seed_plays_the_one_schedule():
    a = open_plan(3_000_000_019)
    assert a == open_plan(3_000_000_019) == open_plan(1)
    assert open_plan(1, rate=2.0) != a
    reqs = a["requests"]
    measured = [r for r in reqs if r["measured"]]
    assert len(measured) == int(2.25 * 45)
    assert all(0 <= r["due"] < 45.0 for r in measured)
    assert all(r["due"] < 0 for r in reqs if not r["measured"])
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)
    # The sizes are the quantiles of their laws, each once.
    assert (collections.Counter(r["prompt_len"] for r in measured)
            == collections.Counter(stratified(CHAT["prompt_tokens"],
                                              len(measured))))


def test_open_loop_lengths_follow_the_traffic_file():
    reqs = open_plan(5)["requests"]
    lens = [r["prompt_len"] for r in reqs if r["measured"]]
    assert min(lens) >= 32 and 2048 < max(lens) <= 4096
    assert 650 <= sum(lens) / len(lens) <= 780  # ISSUE 24: mean about 720
    assert 450 <= stats.percentile(lens, 50) <= 580
    outs = [r["max_tokens"] for r in reqs if r["measured"]]
    assert min(outs) >= 16 and max(outs) <= 768
    assert 140 <= stats.percentile(outs, 50) <= 180


def test_exponential_gaps_sum_to_the_window():
    gaps = exponential_gaps(2.5, 100)
    assert sum(gaps) == pytest.approx(40.0)
    assert min(gaps) > 0


def test_closed_loop_plan():
    gen = manifest.load_module("generators", "closed_loop")
    a, b = gen.plan(REASONING, 11, 45.0), gen.plan(REASONING, 11, 45.0)
    assert a == b
    # Another seed: the same schedule (and the same words: run.timed_words).
    assert gen.plan(REASONING, 12, 45.0) == a
    assert len(a["sequences"]) == 32
    assert a["lead_seconds"] == REASONING["ramp_seconds"]
    flat = lambda p: [r for s in p["sequences"] for r in s[1:]]  # noqa: E731
    assert (collections.Counter(r["prompt_len"] for r in flat(a)).total()
            == 32 * 7)
    for seq in a["sequences"]:
        assert all(128 <= r["prompt_len"] <= 512 for r in seq)
        assert all(512 <= r["max_tokens"] <= 2048 for r in seq[1:])
        assert 2 <= seq[0]["max_tokens"] <= 2048  # a started stream
    outs = [r["max_tokens"] for r in flat(a)]
    assert 1000 <= sum(outs) / len(outs) <= 1200  # ISSUE 24: about 1,100
    ids = [r["id"] for s in a["sequences"] for r in s]
    assert len(set(ids)) == len(ids)


def test_quantiles_and_prompt_ids():
    assert quantile({"dist": "loguniform", "min": 128, "max": 512}, 0.5) == 256
    assert quantile({"dist": "lognormal", "median": 512, "sigma": 0.9,
                     "min": 32, "max": 4096}, 0.5) == 512
    assert stratified({"dist": "fixed", "value": 7}, 3) == [7, 7, 7]
    ids = prompt_ids(2**31 + 5, 3, 50, 152064)
    assert ids == prompt_ids(2**31 + 5, 3, 50, 152064)
    assert len(ids) == 50 and min(ids) >= 16 and max(ids) < 152064
    assert ids != prompt_ids(2**31 + 5, 4, 50, 152064)
    # --seed draws the check's prompts (run.py numbers them from 900,000):
    # another seed, other words.
    assert (prompt_ids(2**31 + 5, 900_000, 50, 152064)
            != prompt_ids(2**31 + 6, 900_000, 50, 152064))


def test_the_timed_words_are_the_replays_whatever_the_seed():
    import inspect

    from benchmark import run
    from benchmark.lib import weights
    gen = manifest.load_module("generators", "closed_loop")
    req = gen.plan(REASONING, 2**31 + 5, 45.0)["sequences"][3][1]
    words = run.timed_words(req, 5, 152064)
    assert len(words) == req["prompt_len"] - 5
    assert words == prompt_ids(weights.CELL_WEIGHTS_SEED, req["id"],
                               len(words), 152064)
    assert run.timed_words({**req, "prompt_ids": [17, 18]}, 5, 152064) == [
        17, 18]  # a generator's own words (a shared prefix) stand
    # No seed reaches them: the one other caller of prompt_ids is the check.
    assert "seed" not in inspect.signature(run.timed_words).parameters
    source = inspect.getsource(run)
    assert source.count("prompt_ids(") == 2
    assert "prompt_ids(seed, 900_000 + k" in source


def test_late_starts_are_timed_from_the_due_time():
    # Due at 10.0, sent half a second late, first chunk at 11.0: the user
    # waited a second, whatever the generator did.
    rec = {"due": 10.0, "sent": 10.5, "chunk_t": [11.0, 11.2]}
    assert stats.ttfts_ms([rec], True, 99.0) == [pytest.approx(1000.0)]
    assert stats.ttfts_ms([rec], False, 99.0) == [pytest.approx(500.0)]
    # No first chunk at all: missing, counted to the end of the run.
    lost = {"due": 10.0, "sent": 10.0, "chunk_t": []}
    assert stats.ttfts_ms([lost], True, 70.0) == [pytest.approx(60000.0)]
