"""What PR 41 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (one chip's share of four: 32 of
128 experts, nothing else cut), the manifest's lookups of its files, the
arithmetic of rooflines/nemotron_h.py against ``param_shapes`` at the
published widths (9,546.7 M values) and at one shape by hand, and each of the
three new readers on a canned ring and trace (and on a program that lacks the
column or the scope, the older cells, where it returns nothing)."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "nemotron-3-nano-30b-a3b.reasoning"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("ssm_ms_per_step", "ssm_roofline", "ssm_state_rows_pct")
JOINED = ("moe_ms_per_step", "moe_expert_load_max_over_mean",
          "moe_shared_ms_per_step", "moe_local_picks_pct",
          "moe_held_touched_pct")
OLDER = ("qwen2.5-7b.reasoning", "smallthinker-21b-a3b.reasoning",
         "command-a-plus.reasoning", "deepseek-v3.2-exp.reasoning-long",
         "glm-4.7-flash.reasoning")
STATE_ROW = 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 2, "quant": "int8",
                                        "max_num_seqs": 32},
                model=CFG, peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


# -- the configuration and its files ------------------------------------------

def test_the_configuration_is_the_catalog_row_but_for_the_chips_share():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == ["n_routed_experts"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # Full depth in the published order, the whole vocabulary; 32 of 128.
    pattern = CFG["hybrid_override_pattern"]
    assert (CFG["num_hidden_layers"], len(pattern), pattern.count("M"),
            pattern.count("E"), pattern.count("*")) == (52, 52, 23, 23, 6)
    assert (CFG["vocab_size"], CFG["n_routed_experts"], CFG["num_experts"],
            CFG["num_experts_per_tok"]) == (131072, 32, 32, 6)
    assert CFG["published"] == {"n_routed_experts": 128}
    assert CFG["expert_parallel"] == {"routed_experts": 128,
                                      "first_expert": 0, "chips_per_layer": 4}
    assert CFG["launch"] == {"quant": "int8"} and CFG["chips"] == 1
    assert "4 chips share each layer" in CFG["stands_for"]
    assert FILES["cell"]["traffic"] == "reasoning"
    assert FILES["cell"]["chips"] == 1 and len(CFG["assumed"]) >= 10
    assert len(FILES["cell"]["why"]) <= 200
    for said in ("does not deepen", "near empty", "1/4 of its rows"):
        assert said in FILES["cell"]["why"], said
    for said in ("rotary", "clamp", "z | xBC | dt", "BEFORE the grouped norm",
                 "unscaled", "scoring_func", "float32", "96 absent"):
        assert any(said in line for line in CFG["assumed"]), said


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/nemotron_h.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    module = manifest.load_module("references", CFG["reference"])
    for name in ("reference_logprobs", "control_logprobs", "layer_of"):
        assert callable(getattr(module, name))
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/nemotron_h.py"
    for name in ("expert_layer_bytes", "shared_layer_bytes",
                 "ssm_layer_bytes", "state_bytes_per_row"):
        assert callable(getattr(counts, name))
    toy = run.rehearsal_cut(FILES)["config"]
    # All three kinds, a * between an M and an E, a share past expert 0 and
    # more than one tap of history.
    assert (toy["hidden_size"], toy["hybrid_override_pattern"],
            toy["num_hidden_layers"], toy["n_routed_experts"],
            toy["expert_parallel"]["first_expert"], toy["conv_kernel"],
            toy["vocab_size"]) == (64, "MEM*EME", 7, 4, 4, 4, 64)
    assert "rehearsal_model" not in toy
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS) <= listed and set(JOINED) <= listed
    # ``moe_roofline`` counts the 23 expert layers the module states since
    # PR 55 and lists this cell; ``moe_shared_roofline`` still multiplies
    # ONE layer's bytes by num_hidden_layers, 52: not this cell's.
    assert "moe_roofline" in listed
    assert not {"moe_shared_roofline", "mtp_roofline",
                "attn_sparse_roofline", "attn_index_roofline"} & listed
    for name in NEW_READERS:
        module = manifest.load_module("layer_metrics", name)
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert (module.NAME, module.UNIT, module.BETTER, module.MOVES,
                module.SOURCE, module.LAYER) == (
            name, entry["unit"], entry["better"], entry["moves"],
            entry["source"], entry["layer"])
        assert CELL in entry["workloads"]
    # (Membership, not "last" or "alone": a later cell is appended BEHIND
    # this one, and its PR may not edit this file.)
    for name in JOINED:
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert CELL in entry["workloads"]
    for cell in OLDER:      # nothing of the older cells' lists moved
        older = {m["name"] for m in manifest.metrics_of(MAN, "per_layer",
                                                        cell)}
        assert not set(NEW_READERS) & older
    assert {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)
            } == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    assert CELL in [w["name"] for w in MAN["workloads"]]
    assert FILES["cell"]["config"] in [c["name"] for c in MAN["configs"]]
    names = [m["name"] for m in MAN["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert names[at:at + 3] == list(NEW_READERS)


# -- the roofline's counts ------------------------------------------------------

def test_the_roofline_counts_what_param_shapes_holds():
    """The weights a step reads, as the roofline module counts them from the
    configuration's keys, are the program's ``param_shapes`` at the
    published widths as stored (int8 values, a float32 scale a channel, the
    rest bf16), the embedding's table left out (rows are gathered); the
    share holds 9,546.7 M values."""
    from benchmark.lib import server
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS
    counts = roofline.counting(CFG)[0]
    spec = server.model_spec("nemotron", CFG, "int8")
    shapes = param_shapes(spec)
    assert spec.num_params() == pytest.approx(9_546.7e6, rel=1e-3)
    assert (spec.num_layers, spec.ssm_layers, spec.expert_layers,
            spec.pool_layers) == (52, 23, 23, 6)

    def stored(name, shape):
        n = int(np.prod(shape))
        if name in QUANT_LAYER_KEYS or name == "lm_head":
            return n + 4 * n // shape[-2]
        return 2 * n

    held = sum(stored(k, s) for k, s in shapes["layers"].items())
    held += stored("lm_head", shapes["lm_head"]) + 2 * spec.hidden_size
    no_state = counts.decode_step_bytes(CFG, "int8", 1, 0, 0) \
        - CFG["hidden_size"]                     # the embedding's row
    assert no_state == pytest.approx(held, rel=1e-6)
    mixers = sum(stored(k, s) for k, s in shapes["layers"].items()
                 if k.startswith("ssm_")) + 23 * 2 * spec.hidden_size
    assert counts.ssm_layer_bytes(CFG, "int8", 0) == pytest.approx(
        mixers, rel=1e-6)
    assert counts.state_bytes_per_row(CFG) == STATE_ROW \
        == spec.ssm_state_bytes_per_row
    assert counts.kv_bytes_per_token(CFG) == 6144 \
        == spec.kv_bytes_per_token()
    assert counts.kinds(CFG) == {"M": 23, "E": 23, "*": 6}
    # A live row's state is read AND written; K and V over 6 layers alone.
    rows, context = 18.0, 18 * 1500.0
    assert counts.ssm_layer_bytes(CFG, "int8", rows) \
        - counts.ssm_layer_bytes(CFG, "int8", 0) == 2 * rows * STATE_ROW
    assert counts.decode_step_bytes(CFG, "int8", 1, rows, context) \
        - counts.decode_step_bytes(CFG, "int8", 1, rows, 0) == context * 6144
    assert counts.decode_step_bytes(CFG, "int8", 1, rows, 0) - no_state \
        == pytest.approx(2 * rows * STATE_ROW + rows * 6144
                         + rows * CFG["hidden_size"])
    # One expert layer by hand: the router and its bias in bf16, 32 experts
    # of two int8 matrices with their scales; the shared expert's width.
    expert = 2 * 2688 * 1856 + 4 * (1856 + 2688)
    assert counts.expert_layer_bytes(CFG, "int8", 32) == \
        (2688 + 1) * 128 * 2 + 32 * expert
    assert counts.shared_layer_bytes(CFG, "int8") == \
        2 * 2688 * 3712 + 4 * (3712 + 2688)
    with pytest.raises(ValueError, match="one device"):
        counts.decode_step_bytes(CFG, "int8", 4, rows, context)


def test_the_operations_of_a_step_by_hand():
    counts = roofline.counting(CFG)[0]
    rows = 10.0
    mixer = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256
    expert = 2688 * 128 + (6 * 32 / 128) * 2 * 2688 * 1856 \
        + 2 * 2688 * 3712
    per_row = 23 * mixer + 6 * attention + 23 * expert + 2688 * 131072
    state = 23 * 64 * 64 * 128
    assert counts.decode_step_flops(CFG, 1, rows, 0) == pytest.approx(
        rows * (2 * per_row + 6 * state))
    assert counts.decode_step_flops(CFG, 1, rows, 1000.0) \
        - counts.decode_step_flops(CFG, 1, rows, 0) == pytest.approx(
        4 * 6 * 32 * 128 * 1000.0)
    floor = roofline.decode_step_floor(CFG, "int8", 1, 18.0, 18 * 1500.0,
                                       PEAKS)
    assert floor["bound"] == "bandwidth"
    assert floor["counted_by"] == "rooflines/nemotron_h.py"


# -- the readers on canned data ---------------------------------------------------

class FakeRing:
    def __init__(self, columns, missed=0):
        self.columns, self.missed = columns, missed

    def between(self, lo, hi):
        keep = (self.columns["t_mono"] >= lo) & (self.columns["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": self.missed,
                "columns": {k: v[keep] for k, v in self.columns.items()}}


def ring_columns():
    # Windows of 2 steps; the first and the last row lie outside the
    # measured window [100, 151], the middle one inside the traced seconds.
    return {"t_mono": np.array([90.0, 110.0, 120.0, 130.0, 155.0]),
            "host_s": np.full(5, 0.01),
            "ssm_row_steps": np.array([9e9, 40.0, 36.0, 20.0, 9e9])}


def test_the_counter_reader_takes_the_windows_rows_or_nothing(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    r = reading()
    # 96 live row-steps over 3 windows x 2 steps x 32 slots.
    assert reader("ssm_state_rows_pct")(r) == pytest.approx(100 * 96 / 192)
    rows = manifest.load_module("layer_metrics", "ssm_state_rows_pct")
    assert rows.per_step(r) == pytest.approx(16.0)
    traced = reading(trace_mono=(115.0, 125.0))
    assert rows.per_step(traced) == pytest.approx(18.0)
    # Windows that counted nothing (the older cells' programs write zeros);
    # the parent's ring has no such column; a ring that lacks rows of the
    # window is not averaged; a recorder without ``between``.
    none = {**ring_columns(), "ssm_row_steps": np.zeros(5)}
    bare = {k: v for k, v in ring_columns().items() if k != "ssm_row_steps"}
    for ring in (FakeRing(none), FakeRing(bare),
                 FakeRing(ring_columns(), missed=1), object()):
        monkeypatch.setattr(flight, "get_recorder", lambda ring=ring: ring)
        assert reader("ssm_state_rows_pct")(r) is None
        assert rows.per_step(r) is None


def traced():
    """Two executions of a 2-step window program: the recurrent layers are
    fusion.2 (300 ns) and fusion.3 (100 ns)."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 200.0),
                ("%fusion.2 fusion", base + 200, 300.0),
                ("%fusion.3 fusion", base + 500, 100.0),
                ("%fusion.5 fusion", base + 660, 200.0),
                ("%while.9 while", base, 1000.0)]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.1": "mlp+moe.experts", "%fusion.2": "ssm",
                "%fusion.3": "ssm", "%fusion.5": "attn.core",
                "%while.9": None}


def test_trace_readers_on_a_hand_made_reading(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    r = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # 400 ns an execution of 2 steps: 200 ns a step, in milliseconds.
    assert reader("ssm_ms_per_step")(r) == pytest.approx(200e-6)
    assert reader("moe_ms_per_step")(r) == pytest.approx(100e-6)
    counts = roofline.counting(CFG)[0]
    # The traced seconds hold one window of 2 steps: 18 live rows a step.
    n_bytes = counts.ssm_layer_bytes(CFG, "int8", 18.0)
    assert n_bytes == counts.ssm_layer_bytes(CFG, "int8", 0) \
        + 36 * STATE_ROW
    assert reader("ssm_roofline")(r) == pytest.approx(
        100 * n_bytes / 819e9 / 200e-9)
    # No such scope in the executable (the older cells, the parent), no
    # trace, no peaks, no column: nothing, and no error.
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: {
        k: (v.replace("ssm", "mlp") if v else v)
        for k, v in OPS_BY_SCOPE.items()})
    for name in ("ssm_ms_per_step", "ssm_roofline"):
        assert reader(name)(r) is None
        assert reader(name)(reading()) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    assert reader("ssm_roofline")(reading(
        trace=traced(), trace_mono=(115.0, 125.0))) is None
    for cell in OLDER:      # a roofline module without ssm_layer_bytes
        other = manifest.cell_files(MAN, cell)["config"]
        assert reader("ssm_roofline")(reading(
            trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
            model=other)) is None
    bare = {k: v for k, v in ring_columns().items() if k != "ssm_row_steps"}
    monkeypatch.setattr(flight, "get_recorder", lambda: FakeRing(bare))
    assert reader("ssm_roofline")(r) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: None)
    assert reader("ssm_ms_per_step")(r) is None
