"""The eight readers of PR 36 (lib/admission.py) on a hand-made span ring and
flight ring: a step function whose mean is known, spans that straddle the
window's ends, and a program that lacks the event, the attribute or the
column (the parent), which reads nothing."""
import numpy as np
import pytest

from benchmark.lib import admission, manifest, measure

T0, T1 = 100.0, 150.0

NEW = ("admit_limit_mean", "admit_decreases", "admit_judged_p50_ms",
       "permits_held_mean", "permits_without_row", "slots_live_pct",
       "slots_prefill_pct", "engine_admit_stops")


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=T0, t1=T1, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 4,
                                        "max_num_seqs": 32},
                model={}, peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


@pytest.fixture
def ring(monkeypatch):
    """The program's span ring, empty and the test's own."""
    from dynamo_tpu.runtime import tracing
    rec = tracing.SpanRecorder(capacity=1024)
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    return rec


class FakeFlight:
    def __init__(self, cols, missed=0):
        self.cols, self.missed = cols, missed

    def between(self, lo, hi):
        keep = (self.cols["t_mono"] >= lo) & (self.cols["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": self.missed,
                "columns": {k: v[keep] for k, v in self.cols.items()}}


def limit_event(ring, t, before, after, direction="increase"):
    ring.add("overload.limit", "l" * 32, None, t, t,
             attrs={"before": before, "after": after, "direction": direction,
                    "judged_ms": 1200.0, "inflight": 3, "waiting": 9})


def request(ring, trace, start, end, first_ms=None, **stages):
    """One request past the limiter: its permit's span and the engine's."""
    ring.add("http.request", trace, None, start, end,
             attrs=None if first_ms is None else {
                 "route": "chat", "permit_to_first_ms": first_ms})
    for name, (a, b) in stages.items():
        ring.add("engine." + name, trace, None, a, b)


def test_the_limit_is_a_step_function_weighted_by_time(ring):
    # 16 until t=90 (the ramp), 17 until 110, 18 until 140, then a decrease
    # to int(12.6) = 12: (10 x 17 + 30 x 18 + 10 x 12) / 50.
    limit_event(ring, 90.0, 16.94, 17.0004)
    limit_event(ring, 110.0, 17.95, 18.01)
    limit_event(ring, 140.0, 18.0, 12.6, "decrease")
    limit_event(ring, 155.0, 12.99, 13.02)       # after the window
    r = reading()
    assert reader("admit_limit_mean")(r) == pytest.approx(
        (10 * 17 + 30 * 18 + 10 * 12) / 50)
    assert reader("admit_decreases")(r) == 1
    # A decrease in the ramp counts, one after the window does not.
    limit_event(ring, 60.0, 16.2, 11.34, "decrease")
    limit_event(ring, 158.0, 13.1, 9.17, "decrease")
    assert reader("admit_decreases")(r) == 2
    # Before the first event the limit is what that event rose FROM.
    assert admission.step_mean([(120.0, 16.97, 17.01, "increase")],
                               100.0, 140.0) == pytest.approx(16.5)
    # No event inside the window: the level the last one left.
    assert admission.step_mean([(50.0, 20.9, 21.0, "increase")],
                               100.0, 140.0) == 21.0
    assert admission.step_mean([(500.0, 20.9, 21.0, "increase")],
                               100.0, 140.0) == 20.0


def test_permits_are_spans_clipped_to_the_window(ring):
    # Three permits: one straddles t0 (20 of its 30 s inside), one lies
    # inside (25 s), one straddles t1 (10 s inside); one wholly outside.
    request(ring, "a" * 32, 90.0, 120.0, 1000.0,
            queue_wait=(90.2, 90.3), prefill=(90.3, 91.0),
            decode=(91.0, 119.0))
    request(ring, "b" * 32, 105.0, 130.0, 1400.0,
            queue_wait=(105.5, 106.0), prefill=(106.0, 106.4),
            decode=(106.4, 129.0))
    request(ring, "c" * 32, 140.0, 170.0, 5200.0,
            queue_wait=(140.0, 140.2), prefill=(140.2, 145.2),
            decode=(145.2, 169.0))
    request(ring, "d" * 32, 10.0, 40.0, 900.0, decode=(11.0, 39.0))
    r = reading()
    held = (20.0 + 25.0 + 10.0) / 50.0
    assert reader("permits_held_mean")(r) == pytest.approx(held)
    decoding = ((119.0 - 100.0) + (129.0 - 106.4) + (150.0 - 145.2)) / 50.0
    assert reader("permits_without_row")(r) == pytest.approx(held - decoding)
    parts = admission.stages(r)
    assert parts["engine.decode"] == pytest.approx(decoding)
    assert parts["before_engine"] == pytest.approx(0.5 / 50.0)     # b alone
    assert parts["engine.queue_wait"] == pytest.approx(0.7 / 50.0)
    assert parts["engine.prefill"] == pytest.approx(5.4 / 50.0)
    assert parts["after_last_token"] == pytest.approx(2.0 / 50.0)  # a and b
    assert sum(parts.values()) == pytest.approx(held)
    # Judged: first tokens that FELL in the window (b at 106.4, c at 145.2;
    # a's came at 91.0, in the ramp).
    assert reader("admit_judged_p50_ms")(r) == pytest.approx(3300.0)


def test_the_two_populations_of_the_window_are_told_apart(ring):
    """``ttft_p50_ms.batch`` reads the requests that ENDED in the window,
    ``http_admit_wait_p50_ms`` the permits GRANTED in it: the spans say how
    long each population waited and what its first token took."""
    # Let in during the ramp without a wait, ended in the window.
    request(ring, "a" * 32, 40.0, 120.0, 1000.0, decode=(41.0, 119.0))
    ring.add("http.admit_wait", "a" * 32, None, 40.0, 40.0)
    # Waited 60 s for a's permit, granted in the window, ends after it.
    request(ring, "b" * 32, 120.0, 200.0, 1400.0, decode=(121.4, 199.0))
    ring.add("http.admit_wait", "b" * 32, None, 60.0, 120.0)
    # Granted AND ended in the window.
    request(ring, "c" * 32, 101.0, 141.0, 800.0, decode=(101.8, 140.0))
    ring.add("http.admit_wait", "c" * 32, None, 81.0, 101.0)
    got = admission.populations(reading())
    assert got["ended_in_window"] == {
        "n": 2, "admit_wait_p50_ms": pytest.approx(10_000.0),
        "permit_to_first_p50_ms": pytest.approx(900.0),
        "held_p50_s": pytest.approx(60.0)}
    assert got["granted_in_window"] == {
        "n": 2, "admit_wait_p50_ms": pytest.approx(40_000.0),
        "permit_to_first_p50_ms": pytest.approx(1100.0),
        "held_p50_s": pytest.approx(60.0)}
    assert got["both"] == 1


def test_slots_are_weighted_by_the_time_between_rows(monkeypatch):
    from dynamo_tpu.runtime import flight
    cols = {"t_mono": np.array([99.0, 100.0, 110.0, 140.0, 150.0, 151.0]),
            "host_s": np.zeros(6),
            "rows": np.array([9.0, 16.0, 16.0, 20.0, 24.0, 9.0]),
            "prefilling": np.array([9.0, 0.0, 1.0, 0.0, 2.0, 9.0]),
            "admit_stop": np.array([7.0, 0.0, 0.0, 4.0, 1.0, 7.0])}
    monkeypatch.setattr(flight, "get_recorder", lambda: FakeFlight(cols))
    r = reading()
    # Rows at 110, 140 and 150 weighted 10, 30 and 10 s of 50.
    assert reader("slots_live_pct")(r) == pytest.approx(
        100 * (16 * 10 + 20 * 30 + 24 * 10) / 50 / 32)
    assert reader("slots_prefill_pct")(r) == pytest.approx(
        100 * (1 * 10 + 0 * 30 + 2 * 10) / 50 / 32)
    assert reader("engine_admit_stops")(r) == 2
    # A ring that lacks a row of the window is not averaged.
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeFlight(cols, missed=1))
    for name in ("slots_live_pct", "slots_prefill_pct",
                 "engine_admit_stops"):
        assert reader(name)(r) is None


def test_a_program_without_the_ledger_reads_nothing(ring, monkeypatch):
    """The parent of PR 36 has ``http.request`` and ``engine.decode`` spans
    and a ``rows`` column, but no event, attribute or new column: none of
    the eight reads a number there, and none raises."""
    from dynamo_tpu.runtime import flight
    request(ring, "a" * 32, 90.0, 120.0, None, decode=(91.0, 119.0))
    ring.add("http.admit_wait", "a" * 32, None, 80.0, 90.0,
             attrs={"limit": 16, "waiting": 3, "outcome": "granted"})
    cols = {"t_mono": np.array([100.0, 110.0, 140.0]),
            "host_s": np.zeros(3), "rows": np.array([16.0, 16.0, 20.0])}
    monkeypatch.setattr(flight, "get_recorder", lambda: FakeFlight(cols))
    r = reading()
    for name in NEW:
        assert reader(name)(r) is None, name
    # Nor where the program has no ring at all.
    monkeypatch.setattr(flight, "get_recorder", lambda: object())
    from dynamo_tpu.runtime import tracing
    monkeypatch.setattr(tracing, "_RECORDER", object())
    for name in NEW:
        assert reader(name)(r) is None, name


def test_the_manifest_lists_the_eight_for_every_cell():
    man = manifest.load_manifest()
    entries = {m["name"]: m for m in man["per_layer"]}
    cells = {w["name"] for w in man["workloads"]}
    for name in NEW:
        # A reader that finds nothing to read in a later PR's cell (callers
        # too few for the limiter to judge) lists the cells it reads in:
        # the cells of PR 36 are ON that list, wherever it ends.
        assert set(entries[name].get("workloads", cells)) >= {
            "qwen2.5-7b.reasoning", "smallthinker-21b-a3b.reasoning",
            "command-a-plus.reasoning",
            "deepseek-v3.2-exp.reasoning-long"}, name
        assert entries[name]["moves"] == "out_tok_s"
    # Whatever a later PR appends: the eight are there, once, in order.
    assert [m["name"] for m in man["per_layer"]
            if m["name"] in NEW] == list(NEW)
