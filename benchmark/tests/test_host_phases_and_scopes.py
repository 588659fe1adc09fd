"""lib/host_phases.py, lib/scopes.py and the twelve readers of PR 25 on
hand-made data and on a small scoped trace recorded on a v5e
(benchmark/tests/data/record_scoped_trace.py says how)."""
import json
import os

import numpy as np
import pytest

from benchmark.lib import host_phases as hp
from benchmark.lib import manifest, measure, scopes
from benchmark.lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "scoped_tpu.xplane.pb")
RECORDED_MAP = os.path.join(DATA, "scoped_tpu.scopes.json")


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 2}, model={},
                peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


def synthetic():
    """Two windows of two steps with a prefill between; the engine thread's
    phases beside them. Times in ns."""
    mods = [("jit_run_window(7)", 1000.0, 400.0),
            ("jit_step(2)", 1500.0, 100.0),
            ("jit_run_window(7)", 2000.0, 400.0),
            ("jit_run_window(9)", 2600.0, 100.0)]
    ops = [("%fusion.1 fusion", 1000.0, 200.0), ("%copy.2 copy", 1200.0, 100.0),
           ("%fusion.3 fusion", 1300.0, 60.0), ("%while.4 while", 1000.0, 400.0),
           ("%add.5 add", 1360.0, 40.0),
           ("%fusion.1 fusion", 2000.0, 200.0), ("%copy.2 copy", 2200.0, 100.0),
           ("%fusion.3 fusion", 2300.0, 60.0), ("%add.5 add", 2360.0, 40.0),
           ("%fusion.1 fusion", 2600.0, 100.0),  # another program's fusion.1
           ("%fusion.8 fusion", 1500.0, 100.0)]  # the prefill's
    dev = {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}
    host = {"python3": [
        ("bench.mark mono_ns=500", 900.0, 5.0),
        ("engine.process_window", 1350.0, 300.0),
        ("engine.readback_wait", 1360.0, 60.0),    # inside process_window
        ("engine.admit", 1700.0, 100.0),
        ("engine.dispatch_window", 1850.0, 200.0),
        ("engine.idle", 2450.0, 100.0),
        ("PjitFunction(run_window)", 1860.0, 50.0)]}
    return {"/device:TPU:0": dev, "/host:CPU": host}


OPS_BY_SCOPE = {"%fusion.1": "attn.kv_gather", "%copy.2": "kv.commit",
                "%fusion.3": "attn.qkv+attn.core", "%add.5": None,
                "%while.4": None}


def test_phase_intervals_are_self_time_in_order():
    ivs = hp.phase_intervals(synthetic())
    assert ivs == [(1350.0, 1360.0, "engine.process_window"),
                   (1360.0, 1420.0, "engine.readback_wait"),
                   (1420.0, 1650.0, "engine.process_window"),
                   (1700.0, 1800.0, "engine.admit"),
                   (1850.0, 2050.0, "engine.dispatch_window"),
                   (2450.0, 2550.0, "engine.idle")]
    assert hp.seconds_by_phase(ivs)["engine.process_window"] == \
        pytest.approx(240e-9)


def test_idle_gaps_split_by_the_phase_that_covers_them():
    t = synthetic()
    # Gaps: 1400-1500, 1600-2000, 2400-2600.
    table = hp.idle_by_phase(t)
    assert table["engine.readback_wait"] == pytest.approx(20e-9)
    assert table["engine.process_window"] == pytest.approx(130e-9)
    assert table["engine.admit"] == pytest.approx(100e-9)
    assert table["engine.dispatch_window"] == pytest.approx(150e-9)
    assert table["engine.idle"] == pytest.approx(100e-9)
    assert table[hp.NO_PHASE] == pytest.approx(200e-9)
    assert sum(table.values()) == pytest.approx(700e-9)
    assert hp.idle_unattributed_seconds(table) == pytest.approx(320e-9)
    # Clipped to a span; and nothing to say without phases or devices.
    assert sum(hp.idle_by_phase(t, 1450.0, 1700.0).values()) == \
        pytest.approx(150e-9)
    assert hp.idle_by_phase({"/device:TPU:0": t["/device:TPU:0"]}) is None
    assert hp.idle_by_phase({"/host:CPU": t["/host:CPU"]}) is None
    r = reading(trace=t, trace_span_ns=(1000.0, 3000.0))
    assert reader("idle_unattributed_pct")(r) == pytest.approx(16.0)
    assert reader("idle_unattributed_pct")(reading()) is None


def test_seconds_by_scope_reads_the_program_that_ran_most():
    got = scopes.seconds_by_scope(synthetic(), OPS_BY_SCOPE)
    assert got["module"] == "jit_run_window(7)" and got["executions"] == 2
    assert got["median_ms"] == pytest.approx(400e-6)
    assert got["scopes"] == {"attn.kv_gather": pytest.approx(400e-9),
                             "kv.commit": pytest.approx(200e-9),
                             "attn.qkv": pytest.approx(120e-9)}
    assert got["unscoped"] == pytest.approx(80e-9)   # the while is no leaf
    assert got["leaf_seconds"] == pytest.approx(800e-9)
    assert got["top_unscoped"][0][0] == "%add.5 add"
    assert scopes.primary("attn.qkv+attn.kv_gather+attn.core") == \
        "attn.kv_gather"
    assert scopes.primary("attn.out+mlp") == "mlp"
    assert scopes.primary(None) is None
    # No scopes: nothing, never zero.
    assert scopes.seconds_by_scope(synthetic(), None) is None
    assert scopes.seconds_by_scope(
        synthetic(), dict.fromkeys(OPS_BY_SCOPE)) is None
    assert scopes.seconds_by_scope({"/host:CPU": {}}, OPS_BY_SCOPE) is None


def test_scoped_readers_on_a_hand_made_reading():
    r = reading(trace=synthetic())
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # 800 ns of executions over a 400 ns median: 2 windows of 2 steps.
    assert reader("kv_gather_ms_per_step")(r) == pytest.approx(100e-6)
    assert reader("kv_commit_ms_per_step")(r) == pytest.approx(50e-6)
    assert reader("weights_ms_per_step")(r) == pytest.approx(30e-6)
    assert reader("attn_core_ms_per_step")(r) == pytest.approx(0.0)
    assert reader("sample_ms_per_step")(r) == pytest.approx(0.0)
    assert reader("scoped_device_pct")(r) == pytest.approx(90.0)
    bare = reading(trace=synthetic())
    bare._by_scope = None            # an executable without scopes
    for name in ("kv_gather_ms_per_step", "attn_core_ms_per_step",
                 "weights_ms_per_step", "kv_commit_ms_per_step",
                 "sample_ms_per_step", "scoped_device_pct"):
        assert reader(name)(bare) is None
        assert reader(name)(reading()) is None      # an untraced run


class FakeRing:
    def __init__(self, columns, missed=0):
        self.columns, self.missed = columns, missed

    def between(self, lo, hi):
        keep = (self.columns["t_mono"] >= lo) & (self.columns["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": self.missed,
                "columns": {k: v[keep] for k, v in self.columns.items()}}


def test_flight_readers_take_the_windows_rows_or_nothing(monkeypatch):
    from dynamo_tpu.runtime import flight
    cols = {"t_mono": np.array([90.0, 110.0, 120.0, 130.0, 140.0, 155.0]),
            "host_s": np.array([9.0, 0.02, 0.04, 0.03, 0.03, 9.0]),
            "wait_s": np.array([9.0, 0.27, 0.25, 0.26, 0.30, 9.0]),
            "period_s": np.array([9.0, 0.0, 0.291, 0.293, 0.330, 9.0])}
    ring = FakeRing(cols)
    monkeypatch.setattr(flight, "get_recorder", lambda: ring)
    r = reading()
    assert reader("host_ms_per_window")(r) == pytest.approx(30.0)
    assert reader("host_headroom_pct")(r) == pytest.approx(
        100 * 1.08 / 1.20)
    assert reader("window_period_ms")(r) == pytest.approx(293.0)
    ring.missed = 2     # frozen for a bundle capture inside the window
    for name in ("host_ms_per_window", "host_headroom_pct",
                 "window_period_ms"):
        assert reader(name)(r) is None
    # A program whose ring has no such read, or no such columns (the parent).
    monkeypatch.setattr(flight, "get_recorder", lambda: object())
    assert reader("window_period_ms")(r) is None
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing({"t_mono": cols["t_mono"]}))
    assert reader("host_ms_per_window")(r) is None


def test_span_readers():
    spans = [{"name": "http.admit_wait", "start": 90.0, "end": 108.0},
             {"name": "http.admit_wait", "start": 101.0, "end": 119.0},
             {"name": "http.admit_wait", "start": 120.0, "end": 120.001},
             {"name": "http.admit_wait", "start": 50.0, "end": 99.0},
             {"name": "http.request", "start": 100.0, "end": 140.0}]
    assert reader("http_admit_wait_p50_ms")(reading(spans=spans)) == \
        pytest.approx(18000.0)
    assert reader("http_admit_wait_p50_ms")(reading()) is None
    from dynamo_tpu.runtime import tracing
    assert reader("trace_spans_dropped")(reading()) == \
        tracing.get_recorder().dropped


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_scoped_tpu_trace():
    t = tr.load(RECORDED)
    with open(RECORDED_MAP, encoding="utf-8") as fh:
        ops = json.load(fh)["ops_by_scope"]
    got = scopes.seconds_by_scope(t, ops)
    assert got is not None and got["executions"] == 6
    assert "run_window" in got["module"]
    assert {"attn.kv_gather", "mlp", "kv.commit"} <= set(got["scopes"])
    assert got["scopes"]["attn.kv_gather"] > 0
    assert 0 < got["unscoped"] < got["leaf_seconds"]
    assert sum(got["scopes"].values()) + got["unscoped"] == \
        pytest.approx(got["leaf_seconds"])
    # Leaf time is no more than the executions' time.
    assert got["leaf_seconds"] <= got["module_seconds"] * 1.001
    # The phases the recorder played, on the device's clock.
    seen = {name for _, _, name in hp.phase_events(t)}
    assert seen == {"engine.admit", "engine.dispatch_window",
                    "engine.process_window", "engine.readback_wait",
                    "engine.idle"}
    lo, hi = tr.window_ns(t)
    idle = hp.idle_by_phase(t, lo, hi)
    # The recorder sleeps 2 ms in engine.admit with the device idle before
    # each of the five later executions.
    assert idle["engine.admit"] == pytest.approx(5 * 2e-3, rel=0.25)
    assert idle.get("engine.idle", 0.0) > 2e-3
    assert sum(idle.values()) == pytest.approx(
        sum(d for s, d, _ in tr.idle_gaps(t)) / 1e9)
    r = reading(trace=t, trace_span_ns=(lo, hi), engine={"decode_window": 4})
    r._by_scope = got
    assert reader("kv_gather_ms_per_step")(r) > 0
    assert 50.0 < reader("scoped_device_pct")(r) <= 100.0
    assert 0.0 < reader("idle_unattributed_pct")(r) < 100.0


def test_command_line_prints_the_tables(capsys, tmp_path):
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace")
    import shutil
    shutil.copy(RECORDED, tmp_path / "x.xplane.pb")
    shutil.copy(RECORDED_MAP, tmp_path / scopes.MAP_FILE)
    assert hp.main([str(tmp_path / "x.xplane.pb")]) == 0
    out = capsys.readouterr().out
    assert "engine.readback_wait" in out and "idle time by engine phase" in out
    assert "attn.kv_gather" in out and "(no scope)" in out
