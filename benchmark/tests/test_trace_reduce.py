"""trace_reduce.py on a hand-made trace and on a small trace recorded on a
v5e (benchmark/tests/data/record_small_trace.py says how)."""
import os

import pytest

from benchmark.lib import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_tpu.xplane.pb")


def synthetic():
    mods = [("jit_run_window(1)", 100.0, 50.0), ("jit_step(2)", 200.0, 30.0),
            ("jit_run_window(1)", 300.0, 50.0)]
    ops = [("fusion.1", 100.0, 20.0), ("fusion.2", 110.0, 30.0),
           ("copy.3", 200.0, 30.0), ("fusion.1", 300.0, 25.0),
           ("fusion.2", 325.0, 25.0)]
    dev = {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}
    host = {"python3": [("bench.mark mono_ns=1000", 90.0, 5.0)]}
    return {"/device:TPU:0": dev, "/device:TPU:1": dev, "/host:CPU": host,
            "/device:TPU:0 SparseCore": {"x": [("y", 0.0, 999.0)]}}


def test_union_merges_overlaps_and_clips():
    assert tr.union_ns([(0, 10), (5, 10), (30, 5)]) == 20
    assert tr.union_ns([(0, 10), (5, 10), (30, 5)], lo=8, hi=32) == 9
    assert tr.union_ns([]) == 0


def test_busy_programs_ops_and_gaps_on_a_hand_made_trace():
    t = synthetic()
    assert sorted(tr.device_planes(t)) == [0, 1]
    assert tr.window_ns(t) == (100.0, 350.0)
    # fusion.1 and fusion.2 overlap by 10 ns in the first window.
    assert tr.busy_seconds(t) == {0: pytest.approx(120e-9),
                                  1: pytest.approx(120e-9)}
    assert tr.busy_seconds(t, 100.0, 210.0)[0] == pytest.approx(50e-9)
    progs = tr.program_times(t)
    assert progs["jit_run_window"] == {"count": 2.0,
                                       "seconds": pytest.approx(100e-9)}
    assert progs["jit_step"]["count"] == 1.0
    assert tr.op_times(t)["fusion.1"] == pytest.approx(45e-9)
    assert set(tr.op_times(t, inside="run_window")) == {"fusion.1",
                                                        "fusion.2"}
    assert tr.idle_gaps(t) == [(150.0, 50.0, "jit_step"),
                               (230.0, 70.0, "jit_run_window")]
    assert tr.clock_offset_ns(t) == 90.0 - 1000.0
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                         ["c", 2.0]]


def test_operation_names_are_shortened():
    hlo = ("%while.40 = (s32[]{:T(128)}, bf16[32,3584]{1,0:T(8,128)(2,1)S(1)})"
           " while((s32[]{:T(128)}, bf16[32,3584]{1,0}) %tuple.164), "
           "condition=%cond, body=%body")
    assert tr.short_op(hlo) == "%while.40 while"
    assert tr.is_container("%while.40 while")
    fusion = ("%convolution_tanh_fusion.2 = bf16[512,512]{1,0:T(8,128)(2,1)}"
              " fusion(bf16[512,512]{1,0} %copy-done), kind=kOutput")
    assert tr.short_op(fusion) == "%convolution_tanh_fusion.2 fusion"
    assert not tr.is_container(tr.short_op(fusion))
    assert tr.short_op("fusion.1") == "fusion.1"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="recorded trace not present")
def test_recorded_v5e_trace():
    t = tr.load(RECORDED)
    assert sorted(tr.device_planes(t)) == [0]
    lines = tr.device_planes(t)[0]
    assert tr.MODULES_LINE in lines and tr.OPS_LINE in lines
    progs = tr.program_times(t)
    assert progs["jit_run_window"]["count"] == 6
    assert progs["jit_step"]["count"] == 3
    lo, hi = tr.window_ns(t)
    busy = tr.busy_seconds(t)[0]
    assert 0 < busy < (hi - lo) / 1e9
    # Each window program ran four matrix products: the operations seen
    # inside it are more than those of the small program.
    inside = tr.op_times(t, inside="run_window")
    assert inside and sum(inside.values()) <= busy * 1.0001
    assert len(tr.idle_gaps(t)) == 8
    assert tr.clock_offset_ns(t) is not None
    names = set(tr.op_times(t))
    assert "%convolution_tanh_fusion.2 fusion" in names
    assert all(len(n) < 80 for n in names)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="recorded trace not present")
def test_decode_step_reader_takes_the_median_execution():
    from benchmark.lib import manifest, measure
    reading = measure.Reading(
        records=[], open_loop=False, t0=0.0, t1=1.0, t_end=1.0, before={},
        after={}, samples=[], spans=[], emissions={}, prompt_keys={},
        engine={"decode_window": 4}, model={}, peaks=None, metrics_text="",
        trace=tr.load(RECORDED))
    step = manifest.load_module("layer_metrics", "decode_step_ms").read(
        reading)
    runs = tr.program_runs_ms(reading.trace)["jit_run_window"]
    assert len(runs) == 6
    assert step == pytest.approx(sorted(runs)[2:4][0] / 8 + sorted(runs)[3] / 8)
    reading.trace = None
    assert manifest.load_module("layer_metrics", "decode_step_ms").read(
        reading) is None
