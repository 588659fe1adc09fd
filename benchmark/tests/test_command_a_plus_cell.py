"""What PR 32 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (one chip's share: 8 chips a
layer), the manifest's lookups of its files, the arithmetic of
rooflines/cohere2_moe.py (8 layers, 19 rows: 9.33 GB by hand), each of the
four new readers on a canned Reading (and on a program that lacks the span
or the counter, where it returns nothing), and the cell's harness end to end
on the CPU with the configuration's toy."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "command-a-plus.reasoning"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("moe_shared_ms_per_step", "moe_shared_roofline",
               "moe_local_picks_pct", "moe_held_touched_pct")


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 2, "quant": "int8"},
                model=CFG, peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


# -- the configuration and its files ---------------------------------------------------

def test_the_configuration_is_the_catalog_row_but_for_the_chips_share():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "command-a-plus-05-2026")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert CFG["num_hidden_layers"] == 8            # two whole periods
    assert CFG["layer_types"] == row["config"]["layer_types"][:8]
    assert CFG["num_experts"] == 16 and CFG["vocab_size"] == 262144 // 8
    assert CFG["expert_parallel"] == {"routed_experts": 128,
                                      "first_expert": 0, "chips_per_layer": 8}
    assert CFG["published"]["num_experts"] == 128
    assert "8 chips share each layer" in CFG["stands_for"]
    assert CFG["launch"] == {"quant": "int8"} and CFG["chips"] == 1
    assert FILES["cell"]["traffic"] == "reasoning"
    assert FILES["cell"]["chips"] == 1 and len(CFG["assumed"]) >= 8


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/cohere2_moe.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/cohere2_moe.py"
    assert callable(counts.expert_layer_bytes)
    assert callable(counts.shared_layer_bytes)
    toy = run.rehearsal_cut(FILES)["config"]
    assert (toy["hidden_size"], toy["num_hidden_layers"], toy["num_experts"],
            toy["expert_parallel"]["routed_experts"],
            toy["num_shared_experts"], toy["sliding_window"]) == (
        64, 4, 4, 8, 2, 8)
    assert len(toy["layer_types"]) == 4 and "rehearsal_model" not in toy
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS) <= listed
    assert {"moe_ms_per_step", "moe_roofline",
            "moe_expert_load_max_over_mean"} <= listed
    # The share of HELD experts is its own reader: the older one finds the
    # expert count under two other models' key names.
    assert "moe_experts_touched_pct" not in listed
    for name in NEW_READERS:
        module = manifest.load_module("layer_metrics", name)
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert (module.NAME, module.UNIT, module.BETTER, module.MOVES,
                module.SOURCE, module.LAYER) == (
            name, entry["unit"], entry["better"], entry["moves"],
            entry["source"], entry["layer"])
        assert CELL in entry["workloads"]
    assert {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)
            } == {"tpot_p50_ms", "out_tok_s", "setup_s"}


# -- the roofline's arithmetic: 8 layers, 19 rows, int8, by hand ------------------------

def test_decode_step_bytes_by_hand():
    counts = roofline.counting(CFG)[0]
    attention = (2 * 4096 * 16384 + 2 * 4096 * 1024         # values, 1 byte
                 + 4 * (16384 + 1024 + 1024 + 4096))         # float32 scales
    expert = 3 * 4096 * 4096 + 4 * (4096 + 4096 + 4096)
    router = 4096 * 128 * 2
    assert counts.routed_experts(CFG) == 128
    assert counts.expert_layer_bytes(CFG, "int8", 11.3) == pytest.approx(
        router + 11.3 * expert)
    assert counts.shared_layer_bytes(CFG, "int8") == 4 * expert
    layer = attention + 4096 * 2 + router + 16 * expert + 4 * expert
    head = 32768 * 4096 + 4 * 4096                          # tied, once
    context = 19 * 1400.0                                   # all inside 4096
    kv = (8 * context + 19 * 8) * (2 * 8 * 128 * 2)
    want = 8 * layer + head + 4096 * 2 + 19 * 4096 + kv
    got = counts.decode_step_bytes(CFG, "int8", 1, 19, context)
    assert got == pytest.approx(want)
    weights = want - kv - 19 * 4096
    assert 9.33e9 < weights < 9.36e9      # 9.33 GB of values and their scales
    # Past the window a window layer reads 4096 tokens a row, a full layer
    # all of them: 2 full and 6 window layers.
    long = counts.decode_step_bytes(CFG, "int8", 1, 19, 19 * 6000.0)
    assert long - got == pytest.approx(
        (2 * 19 * 4600 + 6 * 19 * 2696) * 4096)
    floor = roofline.decode_step_floor(CFG, "int8", 1, 19, context, PEAKS)
    assert floor["counted_by"] == "rooflines/cohere2_moe.py"
    assert floor["bound"] == "bandwidth"
    assert floor["seconds"] == pytest.approx(want / 819e9)
    flops = counts.decode_step_flops(CFG, 1, 19, context)
    per_row = 8 * (2 * 4096 * 16384 + 2 * 4096 * 1024 + 4096 * 128
                   + (8 * 16 / 128 + 4) * 3 * 4096 * 4096) + 4096 * 32768
    assert flops == pytest.approx(2 * per_row * 19
                                  + 4 * 128 * 128 * 8 * context)
    with pytest.raises(ValueError, match="one device"):
        counts.decode_step_bytes(CFG, "int8", 4, 19, context)


# -- the readers on canned data -----------------------------------------------------------

class FakeRing:
    def __init__(self, columns, missed=0):
        self.columns, self.missed = columns, missed

    def between(self, lo, hi):
        keep = (self.columns["t_mono"] >= lo) & (self.columns["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": self.missed,
                "columns": {k: v[keep] for k, v in self.columns.items()}}


def ring_columns():
    # Windows of 8 steps x 8 layers = 64 layer-steps each; the first and the
    # last row lie outside the measured window [100, 151].
    return {"t_mono": np.array([90.0, 110.0, 120.0, 130.0, 155.0]),
            "host_s": np.full(5, 0.01),
            "moe_layer_steps": np.full(5, 64.0),
            "moe_touched": np.array([9e9, 64 * 10.0, 64 * 11.0, 64 * 12.0,
                                     9e9]),
            "moe_load": np.array([9e9, 64 * 2.0, 64 * 3.0, 64 * 4.0, 9e9]),
            "moe_local_picks": np.array([9e9, 1200.0, 1250.0, 1300.0, 9e9]),
            "moe_picks": np.array([9e9, 10000.0, 10000.0, 10000.0, 9e9])}


def test_counter_readers_take_the_windows_rows_or_nothing(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    r = reading()
    assert reader("moe_held_touched_pct")(r) == pytest.approx(100 * 11 / 16)
    assert reader("moe_local_picks_pct")(r) == pytest.approx(12.5)
    assert reader("moe_expert_load_max_over_mean")(r) == pytest.approx(3.0)
    # A configuration that states no share has no held count to read.
    other = manifest.cell_files(MAN, "smallthinker-21b-a3b.reasoning")
    assert reader("moe_held_touched_pct")(reading(
        model=other["config"])) is None
    # Another block's windows count no picks; the parent's ring has no such
    # column; a ring that lacks rows of the window is not averaged.
    none = ring_columns()
    for key in ("moe_local_picks", "moe_picks"):
        none[key] = np.zeros(5)
    bare = {k: v for k, v in ring_columns().items()
            if k not in ("moe_local_picks", "moe_picks")}
    for ring in (FakeRing(none), FakeRing(bare),
                 FakeRing(ring_columns(), missed=1), object()):
        monkeypatch.setattr(flight, "get_recorder", lambda ring=ring: ring)
        assert reader("moe_local_picks_pct")(r) is None
    older = {k: v for k, v in ring_columns().items()
             if not k.startswith("moe_")}
    monkeypatch.setattr(flight, "get_recorder", lambda: FakeRing(older))
    assert reader("moe_held_touched_pct")(r) is None


def traced():
    """Two executions of a 2-step window program: the routed product is
    fusion.3 (200 ns), the shared experts fusion.5 (100 ns) and fusion.6
    (60 ns), which the compiler fused with routed work."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 200.0),
                ("%fusion.3 fusion", base + 200, 200.0),
                ("%fusion.4 fusion", base + 400, 40.0),
                ("%fusion.5 fusion", base + 440, 100.0),
                ("%fusion.6 fusion", base + 540, 60.0),
                ("%copy.2 copy", base + 600, 400.0),
                ("%while.9 while", base, 1000.0)]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.1": "attn.qkv", "%fusion.3": "mlp+moe.experts",
                "%fusion.4": "mlp+moe.router", "%fusion.5": "mlp+moe.shared",
                "%fusion.6": "mlp+moe.experts+moe.shared",
                "%copy.2": "kv.commit", "%while.9": None}


def test_trace_readers_on_a_hand_made_reading(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    r = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # 160 ns an execution of 2 steps: 80 ns a step, in milliseconds; the
    # fused instruction counts whole here AND under the routed scopes.
    assert reader("moe_shared_ms_per_step")(r) == pytest.approx(80e-6)
    assert reader("moe_ms_per_step")(r) == pytest.approx(150e-6)
    assert reader("weights_ms_per_step")(r) == pytest.approx(300e-6)
    counts = roofline.counting(CFG)[0]
    seconds = 8 * counts.shared_layer_bytes(CFG, "int8") / 819e9
    assert reader("moe_shared_roofline")(r) == pytest.approx(
        100 * seconds / 80e-9)
    # The traced seconds hold one row: 11 held experts a layer-step touched.
    routed = 8 * counts.expert_layer_bytes(CFG, "int8", 11.0) / 819e9
    assert reader("moe_roofline")(r) == pytest.approx(100 * routed / 150e-9)
    # No such sub-scope in the executable (another block, the parent).
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: {
        k: (v.replace("+moe.shared", "") if v else v)
        for k, v in OPS_BY_SCOPE.items()})
    for name in ("moe_shared_ms_per_step", "moe_shared_roofline"):
        assert reader(name)(r) is None
        assert reader(name)(reading()) is None              # untraced
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: None)
    assert reader("moe_shared_ms_per_step")(r) is None


# -- the harness end to end ----------------------------------------------------------------

def test_the_cell_runs_end_to_end_on_the_cpu_with_its_toy(tmp_path):
    """``run.py --rehearse-cpu``: the configuration's rehearsal_model through
    the launcher, the traffic, the reference's check and every reader; the
    last line says ``correct`` and carries no device number."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "DTPU_FLIGHT_DIR": str(tmp_path / "flight")}
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH, "run.py"),
         "--workload", CELL, "--seed", "2147483777", "--seconds", "6",
         "--trace", "1", "--rehearse-cpu"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines() if ln]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["metrics"] == {}
    shown = next(ln for ln in lines if ln.get("line", "").startswith(
        "rehearsal.cpu_numbers"))
    assert {"moe_local_picks_pct", "moe_held_touched_pct",
            "moe_expert_load_max_over_mean"} <= set(shown)
    checked = next(ln for ln in lines if ln.get("line") == "reference")
    assert checked["module"] == "references/cohere2_moe.py"
