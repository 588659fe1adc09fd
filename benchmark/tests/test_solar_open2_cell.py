"""What PR 52 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (one chip's share of eight and
three periods of sixteen: 12 of 48 layers, 40 of 320 experts, an eighth of
the vocabulary), the manifest's lookups of its files, the arithmetic of
rooflines/solar_open2.py against ``param_shapes`` at the published widths
(9.52 G values) and at one shape by hand, the reference's controls at the
rehearsal size, and each of the two new readers on a canned ring and trace
(and on a program that lacks the scope, the older cells, where it returns
nothing)."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "solar-open2-250b.reasoning"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("ssm_state_ms_per_step", "ssm_state_roofline")
JOINED = ("ssm_ms_per_step", "ssm_roofline", "ssm_state_rows_pct",
          "moe_ms_per_step", "moe_expert_load_max_over_mean",
          "moe_shared_ms_per_step", "moe_local_picks_pct",
          "moe_held_touched_pct")
OLDER = ("qwen2.5-7b.reasoning", "smallthinker-21b-a3b.reasoning",
         "command-a-plus.reasoning", "deepseek-v3.2-exp.reasoning-long",
         "glm-4.7-flash.reasoning", "nemotron-3-nano-30b-a3b.reasoning",
         "minicpm-sala-9b.doc-reasoning", "ouro-2.6b.reasoning-1k")
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts",
           "vocab_size"]
STATE = 9 * 64 * 128 * 128 * 4
STATE_ROW = STATE + 9 * 3 * 24576 * 2


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
                   if r["name"] == "Solar-Open2-250B")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert CFG[key] == value, key
    assert CFG["published"] == {key: row["config"][key] for key in (
        "num_hidden_layers", "n_routed_experts", "vocab_size")}
    # Three whole periods * K K K; 40 of 320; an eighth of the vocabulary.
    assert (CFG["num_hidden_layers"], CFG["gqa_layers"]) == (12, [0, 4, 8])
    assert CFG["gqa_layers"] == [i for i in row["config"]["gqa_layers"]
                                 if i < 12]
    assert (CFG["vocab_size"] * 8, CFG["n_routed_experts"] * 8,
            CFG["num_experts"], CFG["num_experts_per_tok"]) == (
        196608, 320, 40, 8)
    assert CFG["expert_parallel"] == {"routed_experts": 320,
                                      "first_expert": 0, "chips_per_layer": 8}
    assert CFG["launch"] == {"quant": "int8"} and CFG["chips"] == 1
    assert "8 chips share each layer" in CFG["stands_for"]
    assert FILES["cell"]["traffic"] == "reasoning"
    assert FILES["params"] == manifest.load_json(os.path.join(
        manifest.BENCH, "traffic", "reasoning.json"))["params"]
    assert not os.path.exists(os.path.join(manifest.BENCH, "cells",
                                           CELL + ".json"))
    assert FILES["cell"]["chips"] == 1 and len(CFG["assumed"]) >= 10
    assert len(FILES["cell"]["why"]) <= 200
    for said in ("does not deepen", "near empty", "1/8 of its rows",
                 "BEFORE written"):
        assert said in FILES["cell"]["why"], said
    for said in ("Kimi Delta Attention", "l2-normalised", "low-rank",
                 "kda_allow_neg_eigval", "BEFORE the gate", "use_gqa_gate",
                 "scoring_func", "unscaled", "float32", "280 absent",
                 "A_log [1, 64]"):
        assert any(said in line for line in CFG["assumed"]), said


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/solar_open2.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    module = manifest.load_module("references", CFG["reference"])
    for name in ("reference_logprobs", "control_logprobs", "layer_of"):
        assert callable(getattr(module, name))
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/solar_open2.py"
    for name in ("expert_layer_bytes", "shared_layer_bytes",
                 "ssm_layer_bytes", "state_bytes", "state_bytes_per_row"):
        assert callable(getattr(counts, name))
    toy = run.rehearsal_cut(FILES)["config"]
    # Both mixers, two periods, a share past expert 0, every tap.
    assert (toy["hidden_size"], toy["num_hidden_layers"], toy["gqa_layers"],
            toy["n_routed_experts"], toy["expert_parallel"]["first_expert"],
            toy["linear_attn_config"]["short_conv_kernel_size"],
            toy["vocab_size"]) == (64, 8, [0, 4], 4, 4, 4, 64)
    assert "rehearsal_model" not in toy
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS) <= listed and set(JOINED) <= listed
    # ``moe_roofline`` counts the expert layers the module states since PR
    # 55 and lists this cell. Each of the others multiplies ONE layer's
    # bytes by a layer count that is not this block's, or reads a scope it
    # does not draw.
    assert "moe_roofline" in listed
    assert not {"moe_shared_roofline", "mtp_roofline",
                "attn_sparse_roofline", "attn_index_roofline",
                "loop_weights_roofline"} & listed
    for name in NEW_READERS:
        module = manifest.load_module("layer_metrics", name)
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert (module.NAME, module.UNIT, module.BETTER, module.MOVES,
                module.SOURCE, module.LAYER) == (
            name, entry["unit"], entry["better"], entry["moves"],
            entry["source"], entry["layer"])
        assert entry["workloads"][0] == CELL
    for name in JOINED:
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert CELL in entry["workloads"]
    for cell in OLDER:      # nothing of the older cells' lists moved
        older = {m["name"] for m in manifest.metrics_of(MAN, "per_layer",
                                                        cell)}
        assert not set(NEW_READERS) & older
    assert {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)
            } == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    assert [w["name"] for w in MAN["workloads"]][:8] == list(OLDER)
    assert CELL in [w["name"] for w in MAN["workloads"]]
    names = [m["name"] for m in MAN["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert names[at:at + 2] == list(NEW_READERS)


# -- the roofline's counts ------------------------------------------------------

def test_the_roofline_counts_what_param_shapes_holds():
    """The weights a step reads, as the roofline module counts them from the
    configuration's keys, are the program's ``param_shapes`` at the
    published widths as stored (int8 values, a float32 scale a channel, the
    rest bf16), the embedding's table left out (rows are gathered); the
    share holds 9.52 G values."""
    from benchmark.lib import server
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS
    counts = roofline.counting(CFG)[0]
    spec = server.model_spec("solar", CFG, "int8")
    shapes = param_shapes(spec)
    assert spec.num_params() == pytest.approx(9.52e9, rel=2e-3)
    assert (spec.num_layers, spec.ssm_layers, spec.expert_layers,
            spec.pool_layers) == (12, 9, 12, 3)

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
                 if k.startswith("ssm_")) + 9 * 2 * spec.hidden_size
    assert counts.ssm_layer_bytes(CFG, "int8", 0) == pytest.approx(
        mixers, rel=1e-6)
    assert counts.state_bytes_per_row(CFG) == STATE_ROW \
        == spec.ssm_state_bytes_per_row
    assert counts.state_bytes(CFG, 1.0) == 2 * STATE
    assert counts.kv_bytes_per_token(CFG) == 12288 \
        == spec.kv_bytes_per_token()
    assert counts.kinds(CFG) == {"K": 9, "*": 3, "E": 12}
    # A live row's state is read AND written; K and V over 3 layers alone.
    rows, context = 18.0, 18 * 1500.0
    assert counts.ssm_layer_bytes(CFG, "int8", rows) \
        - counts.ssm_layer_bytes(CFG, "int8", 0) == 2 * rows * STATE_ROW
    assert counts.decode_step_bytes(CFG, "int8", 1, rows, context) \
        - counts.decode_step_bytes(CFG, "int8", 1, rows, 0) \
        == context * 12288
    # One expert layer by hand: the router and its bias in bf16, 40 experts
    # of three int8 matrices with their scales; the shared expert the same.
    expert = 3 * 4096 * 1280 + 4 * (2 * 1280 + 4096)
    assert counts.expert_layer_bytes(CFG, "int8", 40) == \
        (4096 + 1) * 320 * 2 + 40 * expert
    assert counts.shared_layer_bytes(CFG, "int8") == expert
    with pytest.raises(ValueError, match="one device"):
        counts.decode_step_bytes(CFG, "int8", 4, rows, context)


def test_the_operations_of_a_step_by_hand():
    counts = roofline.counting(CFG)[0]
    rows = 10.0
    mixer = 4096 * 24576 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192) \
        + 4096 * 64
    attention = 3 * 4096 * 8192 + 2 * 4096 * 1024
    expert = 4096 * 320 + (8 * 40 / 320 + 1) * 3 * 4096 * 1280
    per_row = 9 * mixer + 3 * attention + 12 * expert + 4096 * 24576
    state = 9 * 64 * 128 * 128
    assert counts.decode_step_flops(CFG, 1, rows, 0) == pytest.approx(
        rows * (2 * per_row + 9 * state))
    assert counts.decode_step_flops(CFG, 1, rows, 1000.0) \
        - counts.decode_step_flops(CFG, 1, rows, 0) == pytest.approx(
        4 * 3 * 64 * 128 * 1000.0)
    floor = roofline.decode_step_floor(CFG, "int8", 1, 18.0, 18 * 1500.0,
                                       PEAKS)
    assert floor["bound"] == "bandwidth"
    assert floor["counted_by"] == "rooflines/solar_open2.py"


# -- the reference's controls ------------------------------------------------------

def test_each_control_changes_the_logprobs_at_the_rehearsal_size():
    """Every switch of ``make_layers`` moves the teacher-forced logprobs of
    the rehearsal model under the benchmark's own weight law, and the plain
    forward is its own fixed point."""
    import jax
    from benchmark.lib import server, weights
    from dynamo_tpu.engine.config import EngineConfig
    toy = run.rehearsal_cut(FILES)["config"]
    spec = server.model_spec("solar-toy", toy, None)
    ref = manifest.load_module("references", CFG["reference"])
    mesh = weights.runner_mesh(EngineConfig(model=spec),
                               jax.devices("cpu")[:1])
    params = weights.make_params(spec, mesh, 3000000019)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, spec.vocab_size, 48).tolist()
    generated = rng.integers(0, spec.vocab_size, 16).tolist()
    full = np.asarray(ref.reference_logprobs(params, spec, prompt, generated))
    again = np.asarray(ref.control_logprobs(params, spec, prompt, generated))
    np.testing.assert_array_equal(full, again)
    for switch in ({"delta": "false"}, {"neg_eigval": "false"},
                   {"channel_decay": "false"}, {"conv": "false"},
                   {"qk_l2norm": "false"}, {"gqa_gate": "false"},
                   {"shared": "false"}, {"scaling": "2"}, {"bias": "false"},
                   {"state": "bfloat16"}, {"precision": "float8_e4m3fn"}):
        wrong = np.asarray(ref.control_logprobs(params, spec, prompt,
                                                generated, **switch))
        assert np.abs(wrong - full).max() > 1e-4, switch
    with pytest.raises(TypeError, match="not the Solar-Open2 block"):
        ref.layer_of(server.model_spec("dense", manifest.cell_files(
            MAN, OLDER[0])["config"], None))


# -- the readers on canned data ---------------------------------------------------

class FakeRing:
    def __init__(self, columns, missed=0):
        self.columns, self.missed = columns, missed

    def between(self, lo, hi):
        keep = (self.columns["t_mono"] >= lo) & (self.columns["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": self.missed,
                "columns": {k: v[keep] for k, v in self.columns.items()}}


def ring_columns():
    return {"t_mono": np.array([90.0, 110.0, 120.0, 130.0, 155.0]),
            "host_s": np.full(5, 0.01),
            "ssm_row_steps": np.array([9e9, 40.0, 36.0, 20.0, 9e9])}


def traced():
    """Two executions of a 2-step window program: the recurrent layers are
    fusion.2 (300 ns: projections and gates) and custom-call.3 (100 ns: the
    state's kernel)."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 200.0),
                ("%fusion.2 fusion", base + 200, 300.0),
                ("%custom-call.3 custom-call", base + 500, 100.0),
                ("%fusion.5 fusion", base + 660, 200.0),
                ("%while.9 while", base, 1000.0)]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.1": "mlp+moe.experts", "%fusion.2": "ssm+ssm.gates",
                "%custom-call.3": "ssm+ssm.state", "%fusion.5": "attn.core",
                "%while.9": None}


def test_the_two_readers_on_a_hand_made_reading(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    r = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # The sub-scope keeps its scope: ``ssm`` still sums both (400 ns an
    # execution of 2 steps), the state's reader the kernel alone (100 ns).
    assert r._by_scope["scopes"]["ssm"] == pytest.approx(800e-9)
    assert reader("ssm_ms_per_step")(r) == pytest.approx(200e-6)
    assert reader("ssm_state_ms_per_step")(r) == pytest.approx(50e-6)
    counts = roofline.counting(CFG)[0]
    # The traced seconds hold one window of 2 steps: 18 live rows a step.
    assert counts.state_bytes(CFG, 18.0) == 36 * STATE
    assert reader("ssm_state_roofline")(r) == pytest.approx(
        100 * 36 * STATE / 819e9 / 50e-9)
    assert reader("ssm_roofline")(r) == pytest.approx(
        100 * counts.ssm_layer_bytes(CFG, "int8", 18.0) / 819e9 / 200e-9)
    # No such sub-scope in the executable (the older cells, the parent of
    # PR 52), no trace, no peaks, no column: nothing, and no error.
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: {
        k: (v.replace("+ssm.state", "") if v else v)
        for k, v in OPS_BY_SCOPE.items()})
    for name in NEW_READERS:
        assert reader(name)(r) is None
        assert reader(name)(reading()) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    assert reader("ssm_state_roofline")(reading(
        trace=traced(), trace_mono=(115.0, 125.0))) is None
    for cell in OLDER:      # a roofline module without state_bytes
        other = manifest.cell_files(MAN, cell)["config"]
        assert reader("ssm_state_roofline")(reading(
            trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
            model=other)) is None
    bare = {k: v for k, v in ring_columns().items() if k != "ssm_row_steps"}
    monkeypatch.setattr(flight, "get_recorder", lambda: FakeRing(bare))
    assert reader("ssm_state_roofline")(r) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: None)
    assert reader("ssm_state_ms_per_step")(r) is None
