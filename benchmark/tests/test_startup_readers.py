"""The eight readers of PR 50 (lib/startup.py) on a hand-made span ring and a
hand-made compile registry: sums whose values are known, stages that lie
beside one another on two threads (the union counts an instant once), a
program that has no such span or record (the parent), which reads nothing;
and what the PR did to BENCHMARK.json."""
import io
import json
import os

import pytest

from benchmark.lib import manifest, measure, startup

NEW = ("startup_ready_s", "startup_warmup_s", "startup_unattributed_s",
       "startup_programs", "startup_trace_lower_s", "startup_cache_load_s",
       "startup_compile_s", "startup_cache_misses")
UNITS = dict(zip(NEW, ("s", "s", "s", "count", "s", "s", "s", "count")))
SOURCES = dict(zip(NEW, ("program_span",) * 3 + ("program_counter",) * 5))

T = 1000.0      # the launcher's entry on the monotonic clock


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=T + 60.0, t1=T + 111.0,
                t_end=T + 120.0, before={}, after={}, samples=[], spans=[],
                emissions={}, prompt_keys={},
                engine={"decode_window": 4, "max_num_seqs": 32}, model={},
                peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


@pytest.fixture
def program(monkeypatch):
    """The program's span ring and compile registry, empty and the test's
    own; and the report printed once a process, not yet."""
    from dynamo_tpu.engine import perf
    from dynamo_tpu.runtime import tracing
    rec = tracing.SpanRecorder(capacity=256)
    reg = perf.CompileRegistry()
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    monkeypatch.setattr(perf, "_REGISTRY", reg)
    monkeypatch.setattr(startup, "_REPORTED", [])
    return rec, reg


def a_start(rec, trace="t1", at=T, root=True):
    """Root 0-40 s. Launcher: runtime 0-1, engine 1-6 (weights 2-3, pool
    3-5), wait 6-38, http 38.5-40. Engine thread: warm-up 6.5-38 (ladder
    20-37). Uncovered: 38-38.5."""
    def add(name, parent, lo, hi, **attrs):
        return rec.add(name, trace, parent, at + lo, at + hi,
                       attrs=attrs or None)

    from dynamo_tpu.runtime.tracing import Span
    root_id = "root-" + trace
    add("startup.runtime", root_id, 0.0, 1.0)
    engine = add("startup.engine", root_id, 1.0, 6.0)
    add("startup.weights", engine, 2.0, 3.0, source="given", bytes=7)
    add("startup.pool_alloc", engine, 3.0, 5.0, bytes=9)
    add("startup.wait_ready", root_id, 6.0, 38.0)
    warm = add("startup.warmup", root_id, 6.5, 38.0)
    add("startup.prefill_ladder", warm, 20.0, 37.0)
    add("startup.http", root_id, 38.5, 40.0)
    if root:
        span = Span(trace, root_id, None, "startup", 0.0, at)
        span.end_mono = at + 40.0
        rec.record(span)


def first_call(program, when="startup", cache="hit", **seconds):
    base = {"program": program, "key": (program, len(seconds)),
            "labels": {}, "when": when, "t_mono": T + 7.0, "wall_s": 0.0,
            "trace_s": 0.0, "lower_s": 0.0, "cache": cache,
            "cache_load_s": 0.0, "compile_s": 0.0, "builds": 1}
    return {**base, **seconds}


RECORDS = [
    first_call("decode_window", wall_s=3.0, trace_s=1.0, lower_s=0.5,
               cache_load_s=1.25),
    first_call("prefill", wall_s=2.0, trace_s=0.5, lower_s=0.25,
               cache_load_s=0.75),
    first_call("prefill", cache="miss", wall_s=9.0, trace_s=0.5,
               lower_s=0.25, compile_s=8.0),
    first_call("extract", cache="off", wall_s=0.1),
    # drawn lazily after ready: no part of the start
    first_call("prefill", when="serving", cache="miss", wall_s=5.0,
               trace_s=1.0, lower_s=1.0, compile_s=3.0),
]


def test_the_span_readers_read_the_root_the_warmup_and_the_union(program):
    rec, _ = program
    a_start(rec)
    assert reader("startup_ready_s")(reading()) == pytest.approx(40.0)
    assert reader("startup_warmup_s")(reading()) == pytest.approx(31.5)
    # The wait (6-38) and the warm-up (6.5-38) lie beside one another: the
    # union of the direct children is 0-38 and 38.5-40, not their sum; the
    # ladder and the runner's stages are no DIRECT children.
    assert reader("startup_unattributed_s")(reading()) \
        == pytest.approx(0.5)
    assert startup.stage_s("startup.prefill_ladder") == pytest.approx(17.0)
    assert startup.stage_s("startup.checkpoint") is None


def test_the_newest_finished_start_is_the_one_read(program):
    rec, _ = program
    a_start(rec, trace="old", at=T - 500.0)
    a_start(rec, trace="new", at=T)
    a_start(rec, trace="open", at=T + 500.0, root=False)   # not ready yet
    root, spans = startup.start_spans()
    assert root.trace_id == "new" and root.start_mono == T
    assert {s.trace_id for s in spans} == {"new"} and len(spans) == 8
    assert reader("startup_ready_s")(reading()) == pytest.approx(40.0)


def test_the_counter_readers_sum_the_records_taken_before_ready(program):
    _, reg = program
    reg.first_calls.extend(RECORDS)
    assert reader("startup_programs")(reading()) == 4
    assert reader("startup_trace_lower_s")(reading()) \
        == pytest.approx(1.5 + 0.75 + 0.75)
    assert reader("startup_cache_load_s")(reading()) == pytest.approx(2.0)
    assert reader("startup_compile_s")(reading()) == pytest.approx(8.0)
    assert reader("startup_cache_misses")(reading()) == 1
    assert [r["wall_s"] for r in startup.first_calls("serving")] == [5.0]
    families = startup.by_family(startup.first_calls("startup"))
    assert families["prefill"] == {
        "programs": 2, "wall_s": 11.0, "trace_s": 1.0, "lower_s": 0.5,
        "cache_load_s": 0.75, "compile_s": 8.0, "hits": 1, "misses": 1}
    assert families["extract"]["hits"] == families["extract"]["misses"] == 0


def test_a_warm_start_reads_no_miss_and_no_compile(program):
    _, reg = program
    reg.first_calls.extend(r for r in RECORDS if r["cache"] == "hit")
    assert reader("startup_cache_misses")(reading()) == 0
    assert reader("startup_compile_s")(reading()) == 0.0
    assert reader("startup_programs")(reading()) == 2


@pytest.mark.parametrize("name", NEW)
def test_a_parent_without_span_or_record_reads_nothing(
        name, program, monkeypatch):
    """An empty ring, and a registry that keeps no first calls (the parent
    of PR 50): None, never an error, and nothing printed."""
    from dynamo_tpu.engine import perf

    class OldRegistry:      # what the parent's registry offers
        compiles_total = 0

    monkeypatch.setattr(perf, "_REGISTRY", OldRegistry())
    assert reader(name)(reading()) is None


def test_the_start_without_its_root_reads_nothing(program):
    rec, _ = program
    a_start(rec, root=False)    # the start failed or is not ready
    for name in NEW[:3]:
        assert reader(name)(reading()) is None
    assert startup.stage_rows() == []
    assert startup.arithmetic(T - 10.0, T + 60.0) is None


@pytest.mark.parametrize("intervals, seconds", [
    ([], 0.0),
    ([(0.0, 1.0), (2.0, 3.0)], 2.0),
    ([(0.0, 32.0), (0.5, 32.0), (32.5, 34.0)], 33.5),
    ([(5.0, 6.0), (0.0, 10.0)], 10.0),
])
def test_union_seconds(intervals, seconds):
    assert startup.union_seconds(intervals) == pytest.approx(seconds)


def test_setup_s_in_its_three_terms_and_the_table(program, monkeypatch):
    rec, reg = program
    a_start(rec)
    reg.first_calls.extend(RECORDS)
    sums = startup.arithmetic(T - 12.0, T + 60.0)
    assert sums == pytest.approx({
        "before_launcher_s": 12.0, "startup_ready_s": 40.0,
        "ready_to_window_s": 20.0, "setup_s": 72.0})
    assert sums["setup_s"] == pytest.approx(
        sums["before_launcher_s"] + sums["startup_ready_s"]
        + sums["ready_to_window_s"])
    rows = {r["name"]: r for r in startup.stage_rows()}
    assert rows["startup.engine"]["self_s"] == pytest.approx(2.0)
    assert rows["startup.warmup"]["self_s"] == pytest.approx(14.5)
    assert rows["startup.weights"]["parent"] == "startup.engine"
    assert rows["startup.weights"]["attrs"] == {"source": "given",
                                                "bytes": 7}
    assert [r["at_s"] for r in startup.stage_rows()] == sorted(
        r["at_s"] for r in rows.values())
    # In a run's process the ready reader prints the table ONCE, on stderr.
    import sys
    monkeypatch.setattr(sys.modules["__main__"], "_T_START", T - 12.0,
                        raising=False)
    out = io.StringIO()
    startup.report(reading(), out=out)
    startup.report(reading(), out=out)
    text = out.getvalue()
    assert text.count("the start, by the program's own record") == 1
    assert "startup.prefill_ladder" in text and "decode_window" in text
    assert "setup_s 72.000 = before the launcher 12.000 + " \
        "startup_ready_s 40.000 + ready to the window 20.000" in text
    line = next(ln for ln in text.splitlines()
                if ln.startswith("benchmark: startup "))
    told = json.loads(line[len("benchmark: startup "):])
    assert told["families"]["prefill"]["misses"] == 1
    assert told["first_calls_serving"][0]["wall_s"] == 5.0


def test_the_command_prints_a_saved_debug_perf_body(tmp_path, capsys):
    body = {"role": "frontend", "engines": {"m": {"startup": {
        "trace_id": "t", "status": "ok", "ready_s": 40.0,
        "unattributed_s": 0.5,
        "stages": [{"name": "startup.engine", "parent": "startup",
                    "at_s": 1.0, "seconds": 5.0, "self_s": 2.0,
                    "attrs": {"x": 1}}],
        "first_calls": {"families": {"prefill": {
            "programs": 2, "wall_s": 11.0, "trace_s": 1.0, "lower_s": 0.5,
            "cache_load_s": 0.75, "compile_s": 8.0, "hits": 1,
            "misses": 1}}}}}}}
    path = tmp_path / "perf.json"
    path.write_text(json.dumps(body))
    assert startup.main([str(path)]) == 0
    text = capsys.readouterr().out
    assert "startup.engine" in text and "prefill" in text
    path.write_text(json.dumps({"role": "process"}))
    assert startup.main([str(path)]) == 1
    assert startup.main([]) == 2


def test_the_readers_run_on_the_program_itself(program):
    """No hand-made record: a start made by the program's own ``Startup``
    and a first call made by its own wrapper are what the readers find."""
    import jax.numpy as jnp

    from dynamo_tpu.engine import perf
    from dynamo_tpu.runtime import tracing
    rec, reg = program
    start = tracing.Startup(recorder=rec)
    with start.stage("startup.engine"):
        with start.stage("startup.weights", source="given"):
            pass
    with start.stage("startup.warmup"):
        fn = perf.instrumented_jit("unit", lambda x: x * 5, key="k",
                                   registry=reg)
        fn(jnp.ones(3))
    start.finish()
    reg.mark_ready()
    values = {name: reader(name)(reading()) for name in NEW}
    assert all(v is not None for v in values.values()), values
    assert values["startup_programs"] == 1
    assert values["startup_ready_s"] >= values["startup_warmup_s"] > 0
    assert 0 <= values["startup_unattributed_s"] < values["startup_ready_s"]
    assert values["startup_trace_lower_s"] > 0
    record = reg.first_calls[0]
    root = startup.start_spans()[0]
    assert root.start_mono <= record["t_mono"]
    assert record["t_mono"] + record["wall_s"] <= root.end_mono


# -- BENCHMARK.json -----------------------------------------------------------------

def test_the_manifest_lists_the_eight_under_setup_s():
    man = manifest.load_manifest()
    moving = [m for m in man["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in moving[:8]] == list(NEW)
    for entry in moving[:8]:
        assert entry == {"name": entry["name"],
                         "unit": UNITS[entry["name"]], "better": "lower",
                         "source": SOURCES[entry["name"]],
                         "layer": "start-up", "moves": "setup_s"}
        module = manifest.load_module("layer_metrics", entry["name"])
        for key, const in (("name", "NAME"), ("unit", "UNIT"),
                           ("better", "BETTER"), ("layer", "LAYER"),
                           ("moves", "MOVES"), ("source", "SOURCE")):
            assert getattr(module, const) == entry[key]
    # Every cell starts: no list of cells, so every cell reports them.
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell["name"])}
        assert set(NEW) <= names
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in man["end_to_end"])


def test_every_older_entry_is_as_it_was():
    """The 54 entries PR 50 found, in their order: each key as it was; a
    list of cells may have grown at its end since (a later cell joins)."""
    man = manifest.load_manifest()
    before = manifest.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "per_layer_before_pr50.json"))
    assert len(before) == 54
    for old, new in zip(before, man["per_layer"]):
        assert list(new) == list(old), old["name"]
        for key, value in old.items():
            if key == "workloads":
                assert new[key][:len(value)] == value, old["name"]
            else:
                assert new[key] == value, (old["name"], key)
    assert not {m["name"] for m in before} & set(NEW)
