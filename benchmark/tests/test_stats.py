import pytest

from benchmark.lib import measure, stats


def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile([], 50) is None
    assert stats.percentile([4.0], 99) == 4.0
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert stats.percentile(range(101), 90) == pytest.approx(90.0)
    assert stats.percentile([5, 1, 3], 100) == 5


def rec(**kw):
    base = {"id": 0, "due": None, "sent": 0.0, "chunk_t": [], "chunk_n": [],
            "usage": None, "ok": True, "aborted": False, "measured": True,
            "done": 1.0, "status": 200, "prompt_len": 8, "max_tokens": 4}
    base.update(kw)
    return base


def test_tpot_uses_completion_tokens_not_chunks():
    r = rec(chunk_t=[1.0, 1.4, 2.0], chunk_n=[1, 8, 8],
            usage={"completion_tokens": 17})
    assert stats.tpots_ms([r]) == [pytest.approx(1000.0 / 16)]
    assert stats.tpots_ms([rec(chunk_t=[1.0], chunk_n=[1],
                               usage={"completion_tokens": 1})]) == []
    assert stats.tpots_ms([dict(r, ok=False)]) == []


def test_tpot_is_the_median_over_the_stretches_of_the_window():
    # 8 tokens a chunk, 0.3 s apart; the window is [10, 20]. A 3 s stall in
    # the ramp and one of 2 s inside the window move the whole-request
    # figure; the window's median stretch reads 300 / 8 ms all the same.
    ts = ([4.0, 7.3, 7.6, 7.9] + [10.0 + 0.3 * k for k in range(1, 11)]
          + [15.0 + 0.3 * k for k in range(11)])
    long = rec(chunk_t=ts, chunk_n=[8] * len(ts), done=18.0,
               usage={"completion_tokens": 8 * len(ts)})
    got = stats.stretch_tpots_ms([long], 10.0, 20.0, stretch_s=1.0)
    # 10.3 -> 11.5 -> 12.7 -> 15.0 (the stall) -> 16.2 -> 17.4, and the
    # 0.6 s left at the end joins the last stretch
    assert got == [pytest.approx(v) for v in (
        1200 / 32, 1200 / 32, 2300 / 16, 1200 / 32, 1800 / 48)]
    # (32 + 32 + 16 + 32 + 48 tokens: the 20 chunks after the first)
    even = [rec(chunk_t=[10.1 + 0.3 * k for k in range(33)],
                chunk_n=[8] * 33) for _ in range(2)]
    r = reading([long] + even, open_loop=False)
    assert measure.tpot_p50_ms(r) == pytest.approx(300.0 / 8)
    assert measure.tpot_request_p50_ms(r) > 1.3 * 300.0 / 8
    # one chunk gives nothing; a short stream is one stretch; bursts of
    # single-token chunks read as the tokens they carry
    assert stats.stretch_tpots_ms([rec(chunk_t=[11.0], chunk_n=[8])],
                                  10.0, 20.0) == []
    cut = rec(chunk_t=[19.4, 19.7, 20.3], chunk_n=[1, 8, 8], ok=False,
              aborted=True)
    assert stats.stretch_tpots_ms([cut], 10.0, 20.0) == [
        pytest.approx(300.0 / 8)]
    burst = rec(chunk_t=[10.0] + [10.3 + 0.3 * (k // 8) + 1e-4 * (k % 8)
                                  for k in range(80)], chunk_n=[1] * 81)
    assert stats.stretch_tpots_ms([burst], 10.0, 20.0)[0] == pytest.approx(
        300.0 / 8, rel=0.1)


def test_gaps_pool_over_streams_inside_the_window():
    a = rec(chunk_t=[0.5, 1.0, 1.3], chunk_n=[1, 1, 1])
    b = rec(chunk_t=[1.1, 1.9, 5.5], chunk_n=[1, 1, 1])
    assert sorted(stats.chunk_gaps_ms([a, b], 0.9, 5.0)) == [
        pytest.approx(300.0), pytest.approx(800.0)]


def test_tokens_in_window_counts_chunks_by_arrival():
    a = rec(chunk_t=[0.5, 1.0, 2.5], chunk_n=[1, 8, 8])
    assert stats.tokens_in_window([a], 1.0, 2.0) == 8
    assert stats.tokens_in_window([a], 0.0, 3.0) == 17


def reading(records, open_loop):
    return measure.Reading(
        records=records, open_loop=open_loop, t0=10.0, t1=20.0, t_end=25.0,
        before={}, after={}, samples=[], spans=[], emissions={},
        prompt_keys={}, engine={}, model={}, peaks=None, metrics_text="")


def test_selection_of_measured_requests():
    inside = rec(id=1, sent=12.0, done=15.0)
    early = rec(id=2, sent=5.0, done=12.0)
    cut = rec(id=3, sent=19.0, done=20.0, ok=False, aborted=True)
    r = reading([inside, early, cut], open_loop=False)
    assert [x["id"] for x in r.measured()] == [1, 2]
    assert [x["id"] for x in r.completed_in_window()] == [1, 2]
    failed = rec(id=6, sent=11.0, done=11.5, ok=False)
    r = reading([inside, failed], open_loop=False)
    assert [x["id"] for x in r.measured()] == [1, 6]
    assert [x["id"] for x in r.completed_in_window()] == [1]
    pre = rec(id=4, due=9.0, measured=False)
    due = rec(id=5, due=11.0, measured=True, done=23.0)
    r = reading([pre, due], open_loop=True)
    assert [x["id"] for x in r.measured()] == [5]
    assert [x["id"] for x in r.completed_in_window()] == [5]


def test_out_tok_s_is_tokens_received_over_the_window():
    a = rec(chunk_t=[9.0, 11.0, 19.5, 21.0], chunk_n=[8, 8, 8, 8])
    assert measure.out_tok_s(reading([a], False)) == pytest.approx(1.6)


@pytest.mark.parametrize("open_loop", [False, True])
def test_out_tok_s_sees_a_stall_whatever_completes_in_the_drain(open_loop):
    """The same rule in both loops: a request due in the window whose
    tokens arrive after it closes adds nothing to the window's rate."""
    usage = {"completion_tokens": 16}
    steady = rec(id=1, due=11.0, chunk_t=[12.0, 13.0], chunk_n=[8, 8],
                 usage=usage, done=13.0)
    stalled = rec(id=2, due=12.0, chunk_t=[22.0, 23.0], chunk_n=[8, 8],
                  usage=usage, done=23.0)
    r = reading([steady, stalled], open_loop)
    assert measure.out_tok_s(r) == pytest.approx(1.6)
    assert measure.gap_ms(r, 99) == pytest.approx(1000.0)


def test_the_logprob_verdict_rests_on_the_median_and_guards_the_tail():
    from benchmark.lib import reference
    ref = [-7.5 - 0.01 * k for k in range(64)]
    near = [x + (0.005 if k % 2 else -0.005) for k, x in enumerate(ref)]
    assert reference.judge(near, ref)["ok"]
    # A few tokens far off (heavy-tailed rounding) do not move the median...
    tail = list(near)
    tail[3] += 0.1
    verdict = reference.judge(tail, ref)
    assert verdict["ok"] and verdict["worst_nats"] == pytest.approx(0.105)
    # ...a fault in every token does, however small next to the tail.
    shifted = [x + 0.04 for x in ref]
    assert not reference.judge(shifted, ref)["ok"]
    # One token off by whole nats (a wrong page) fails on the worst token.
    tail[3] += 1.0
    assert not reference.judge(tail, ref)["ok"]
    assert not reference.judge(near[:-1], ref)["ok"]
    assert not reference.judge([float("nan")] * 64, ref)["ok"]
