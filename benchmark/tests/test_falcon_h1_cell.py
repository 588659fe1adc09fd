"""What PR 54 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (one pipeline stage of six: 12 of
72 layers, nothing of a layer divided), the manifest's lookups of its files,
the arithmetic of rooflines/falcon_h1.py against ``param_shapes`` at the
published widths (7,835,314,304 values) and at one shape by hand, the
reference's controls at the rehearsal size under the benchmark's own weight
law, and each of the two new readers on a canned ring and trace (and on a
program whose mixers do not run side by side, the older cells, where it
returns nothing)."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "falcon-h1-34b.reasoning-long"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("mixers_ms_per_step", "mixers_roofline")
JOINED = ("ssm_ms_per_step", "ssm_roofline", "ssm_state_rows_pct",
          "ssm_state_ms_per_step", "ssm_state_roofline", "attn_kv_roofline")
OLDER = ("qwen2.5-7b.reasoning", "smallthinker-21b-a3b.reasoning",
         "command-a-plus.reasoning", "deepseek-v3.2-exp.reasoning-long",
         "glm-4.7-flash.reasoning", "nemotron-3-nano-30b-a3b.reasoning",
         "minicpm-sala-9b.doc-reasoning", "ouro-2.6b.reasoning-1k",
         "solar-open2-250b.reasoning")
STATE = 12 * 32 * 128 * 256 * 4
STATE_ROW = STATE + 12 * 3 * 5120 * 2
KV_TOKEN = 12 * 4 * 128 * 2 * 2
SIDE_BY_SIDE = ('dynamo_tpu_perf_ssm_state_info{bytes_per_row="50700288",'
                'dtype="float32",parallel="1"} 1.0\n')


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 2, "quant": "int8",
                                        "max_num_seqs": 32},
                model=CFG, peaks=None, metrics_text=SIDE_BY_SIDE)
    base.update(kw)
    return measure.Reading(**base)


# -- the configuration and its files ------------------------------------------

def test_the_configuration_is_the_catalog_row_but_for_the_depth():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert CFG[key] == value, key
    assert CFG["published"] == {"num_hidden_layers": 72}
    assert CFG["num_hidden_layers"] == 12 and 72 % 12 == 0
    assert CFG["launch"] == {"quant": "int8"} and CFG["chips"] == 1
    assert "six pipeline stages" in CFG["stands_for"]
    assert FILES["cell"]["traffic"] == "reasoning-long"
    assert FILES["params"] == manifest.load_json(os.path.join(
        manifest.BENCH, "traffic", "reasoning-long.json"))["params"]
    assert not os.path.exists(os.path.join(manifest.BENCH, "cells",
                                           CELL + ".json"))
    assert FILES["cell"]["chips"] == 1 and len(CFG["assumed"]) >= 10
    assert len(FILES["cell"]["why"]) <= 200 and len(entry["why"]) <= 200
    for said in ("state a row", "K/V a token", "dense MLP"):
        assert said in FILES["cell"]["why"], said
    for said in ("z | xBC | dt", "ssm_multipliers", "mlp_multipliers",
                 "key_multiplier", "attention_in_multiplier",
                 "BEFORE the grouped norm", "no clamp", "rotate-half",
                 "no QK norm", "attn_layer_indices", "float32",
                 "EVERY matrix [in, out]", "six stages"):
        assert any(said in line for line in CFG["assumed"]), said


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/falcon_h1.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    module = manifest.load_module("references", CFG["reference"])
    for name in ("reference_logprobs", "control_logprobs", "layer_of"):
        assert callable(getattr(module, name))
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/falcon_h1.py"
    for name in ("ssm_layer_bytes", "state_bytes", "attention_bytes",
                 "mixer_bytes", "state_bytes_per_row"):
        assert callable(getattr(counts, name))
    toy = run.rehearsal_cut(FILES)["config"]
    # Both branches in every layer, two groups, the multipliers as published.
    assert (toy["hidden_size"], toy["num_hidden_layers"],
            toy["num_attention_heads"], toy["num_key_value_heads"],
            toy["mamba_n_heads"], toy["mamba_n_groups"],
            toy["mamba_d_state"], toy["mamba_chunk_size"],
            toy["intermediate_size"], toy["vocab_size"]) == (
        64, 3, 4, 2, 4, 2, 16, 8, 96, 512)
    for key in ("key_multiplier", "ssm_multipliers", "mlp_multipliers",
                "lm_head_multiplier", "embedding_multiplier"):
        assert toy[key] == CFG[key]
    assert "rehearsal_model" not in toy
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS) <= listed and set(JOINED) <= listed
    assert not {m for m in listed if m.startswith(("moe_", "mtp_", "spec_",
                                                   "loop_", "attn_index",
                                                   "attn_sparse"))}
    for name in NEW_READERS:
        module = manifest.load_module("layer_metrics", name)
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert (module.NAME, module.UNIT, module.BETTER, module.MOVES,
                module.SOURCE, module.LAYER) == (
            name, entry["unit"], entry["better"], entry["moves"],
            entry["source"], entry["layer"])
        assert entry["workloads"] == [CELL]
    for name in JOINED:
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert CELL in entry["workloads"]
    for cell in OLDER:      # nothing of the older cells' lists moved
        older = {m["name"] for m in manifest.metrics_of(MAN, "per_layer",
                                                        cell)}
        assert not set(NEW_READERS) & older
    assert {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)
            } == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    assert [w["name"] for w in MAN["workloads"]] == [*OLDER, CELL]
    assert all(w["chips"] == 1 for w in MAN["workloads"])
    assert [m["name"] for m in MAN["per_layer"]][-2:] == list(NEW_READERS)


# -- the roofline's counts ------------------------------------------------------

def test_the_roofline_counts_what_param_shapes_holds():
    """The weights a step reads, as the roofline module counts them from the
    configuration's keys, are the program's ``param_shapes`` at the
    published widths as stored (every matrix int8 values and a float32
    scale a channel; the rest bf16), the embedding's table left out (rows are gathered); the stage
    holds 7,835,314,304 values."""
    from benchmark.lib import server
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS
    counts = roofline.counting(CFG)[0]
    spec = server.model_spec("falcon", CFG, "int8")
    shapes = param_shapes(spec)
    assert spec.num_params() == counts.resident_values(CFG) == 7_835_314_304
    assert counts.layer_values(CFG) == 430_120_032
    assert (spec.num_layers, spec.ssm_layers, spec.pool_layers) == (12, 12,
                                                                    12)

    def stored(name, shape):
        n = int(np.prod(shape))
        if name in QUANT_LAYER_KEYS or name == "lm_head":
            return n + 4 * n // shape[-2]
        return 2 * n

    held = sum(stored(k, s) for k, s in shapes["layers"].items())
    held += stored("lm_head", shapes["lm_head"]) + 2 * spec.hidden_size
    no_state = counts.decode_step_bytes(CFG, "int8", 1, 0, 0) \
        - CFG["hidden_size"]                     # the embedding's row
    assert no_state == pytest.approx(held, rel=1e-9)
    branch = sum(stored(k, s) for k, s in shapes["layers"].items()
                 if k.startswith("ssm_")) + 12 * 2 * spec.hidden_size
    assert counts.ssm_layer_bytes(CFG, "int8", 0) == pytest.approx(
        branch, rel=1e-9)
    attention = sum(stored(k, shapes["layers"][k])
                    for k in ("wq", "wk", "wv", "wo"))
    assert counts.mixer_bytes(CFG, "int8", 0, 0, 0) == pytest.approx(
        branch + attention, rel=1e-9)
    assert counts.state_bytes_per_row(CFG) == STATE_ROW == 50_700_288 \
        == spec.ssm_state_bytes_per_row
    assert counts.state_bytes(CFG, 1.0) == 2 * STATE
    assert counts.kv_bytes_per_token(CFG) == KV_TOKEN == 24_576 \
        == spec.kv_bytes_per_token()
    # A live row's state is read AND written; K and V in every layer.
    rows, context = 17.0, 17 * 4600.0
    assert counts.ssm_layer_bytes(CFG, "int8", rows) \
        - counts.ssm_layer_bytes(CFG, "int8", 0) == 2 * rows * STATE_ROW
    assert counts.attention_bytes(CFG, rows, context) \
        == (context + rows) * KV_TOKEN
    assert counts.mixer_bytes(CFG, "int8", rows, rows, context) \
        == counts.mixer_bytes(CFG, "int8", 0, 0, 0) \
        + 2 * rows * STATE_ROW + (context + rows) * KV_TOKEN
    step = counts.decode_step_bytes(CFG, "int8", 1, rows, context)
    assert step - counts.decode_step_bytes(CFG, "int8", 1, rows, 0) \
        == context * KV_TOKEN
    # The issue's reckoning: some 10 GB a step, the two mixers about half.
    assert 10.0e9 < step < 10.4e9
    assert 0.45 < counts.mixer_bytes(CFG, "int8", rows, rows, context) \
        / step < 0.5
    with pytest.raises(ValueError, match="one device"):
        counts.decode_step_bytes(CFG, "int8", 4, rows, context)


def test_the_operations_of_a_step_by_hand():
    counts = roofline.counting(CFG)[0]
    rows = 10.0
    ssm = 5120 * (4096 + 5120 + 32) + 4096 * 5120
    attention = 2 * 5120 * 2560 + 2 * 5120 * 512
    per_row = 12 * (ssm + attention + 3 * 5120 * 21504) + 5120 * 261120
    state = 12 * 32 * 128 * 256
    assert counts.decode_step_flops(CFG, 1, rows, 0) == pytest.approx(
        rows * (2 * per_row + 6 * state))
    assert counts.decode_step_flops(CFG, 1, rows, 1000.0) \
        - counts.decode_step_flops(CFG, 1, rows, 0) == pytest.approx(
        4 * 12 * 20 * 128 * 1000.0)
    floor = roofline.decode_step_floor(CFG, "int8", 1, 17.0, 17 * 4600.0,
                                       PEAKS)
    assert floor["bound"] == "bandwidth"
    assert floor["counted_by"] == "rooflines/falcon_h1.py"


# -- the reference's controls ------------------------------------------------------

def test_each_control_changes_the_logprobs_at_the_rehearsal_size():
    """Every switch of ``make_layers`` moves the teacher-forced logprobs of
    the rehearsal model under the benchmark's own weight law (a bfloat16
    state by float32's rounding times a few: the head's 1/128 leaves every
    logprob within hundredths of uniform), and the plain forward is its own
    fixed point."""
    import jax
    from benchmark.lib import server, weights
    from dynamo_tpu.engine.config import EngineConfig
    toy = run.rehearsal_cut(FILES)["config"]
    spec = server.model_spec("falcon-toy", toy, None)
    ref = manifest.load_module("references", CFG["reference"])
    mesh = weights.runner_mesh(EngineConfig(model=spec),
                               jax.devices("cpu")[:1])
    params = weights.make_params(spec, mesh, 3000000019)
    assert params["layers"]["wk"].shape == (3, 64, 32)
    assert params["layers"]["ssm_w_in"].shape == (3, 64, 32 + 96)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, spec.vocab_size, 48).tolist()
    generated = rng.integers(0, spec.vocab_size, 16).tolist()
    full = np.asarray(ref.reference_logprobs(params, spec, prompt, generated))
    again = np.asarray(ref.control_logprobs(params, spec, prompt, generated))
    np.testing.assert_array_equal(full, again)
    assert np.abs(full + np.log(spec.vocab_size)).max() < 0.1
    for switch, least in (
            ({"parallel": "false"}, 1e-5), ({"ssm": "false"}, 1e-5),
            ({"attn": "false"}, 1e-5),
            # (under the weight law every score is key_multiplier's size:
            # the rotation moves a logprob by float32's rounding times ten)
            ({"rope": "false"}, 1e-6),
            ({"key_multiplier": "1"}, 1e-5),
            ({"branch_multipliers": "1"}, 1e-5),
            ({"ssm_multipliers": "1"}, 1e-5),
            ({"mlp_multipliers": "1"}, 1e-5),
            ({"gate_before_norm": "false"}, 1e-5), ({"conv": "false"}, 1e-5),
            ({"skip": "false"}, 1e-5), ({"recurrence": "false"}, 1e-6),
            ({"mlp": "false"}, 1e-6),
            ({"state": "bfloat16"}, 1e-9),
            ({"precision": "float8_e4m3fn"}, 1e-5)):
        wrong = np.asarray(ref.control_logprobs(params, spec, prompt,
                                                generated, **switch))
        assert np.abs(wrong - full).max() > least, switch
    with pytest.raises(TypeError, match="not the Falcon-H1 block"):
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
            "ssm_row_steps": np.array([9e9, 40.0, 34.0, 20.0, 9e9])}


def traced():
    """Two executions of a 2-step window program: fusion.2 (300 ns) is the
    SSM branch's projections and the shared norm, custom-call.3 (100 ns) the
    state's kernel, fusion.4 (60 ns) q, k and v, custom-call.5 (140 ns) the
    pool's reader, fusion.6 (100 ns) the feed-forward."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.2 fusion", base, 300.0),
                ("%custom-call.3 custom-call", base + 300, 100.0),
                ("%fusion.4 fusion", base + 400, 60.0),
                ("%custom-call.5 custom-call", base + 460, 140.0),
                ("%fusion.6 fusion", base + 600, 100.0),
                ("%while.9 while", base, 1000.0)]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.2": "ssm", "%custom-call.3": "ssm+ssm.state",
                "%fusion.4": "attn.qkv+ssm", "%custom-call.5": "attn.core",
                "%fusion.6": "mlp", "%while.9": None}
SAMPLES = [{"t": 118.0, "rows": 17, "context": 17 * 4600, "pages_active": 9},
           {"t": 122.0, "rows": 17, "context": 17 * 4600, "pages_active": 9}]


def test_the_two_readers_on_a_hand_made_reading(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    r = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
                samples=SAMPLES)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # Both branches by lib/scopes.py's own attribution: 600 ns an execution
    # of 2 steps; the feed-forward is not theirs.
    assert reader("mixers_ms_per_step")(r) == pytest.approx(300e-6)
    assert reader("ssm_state_ms_per_step")(r) == pytest.approx(50e-6)
    assert reader("ssm_ms_per_step")(r) == pytest.approx(230e-6)
    counts = roofline.counting(CFG)[0]
    # The traced seconds hold one window of 2 steps: 17 live rows a step.
    want = counts.mixer_bytes(CFG, "int8", 17.0, 17.0, 17 * 4600.0)
    assert reader("mixers_roofline")(r) == pytest.approx(
        100 * want / 819e9 / 300e-9)
    assert reader("ssm_state_roofline")(r) == pytest.approx(
        100 * 34 * STATE / 819e9 / 50e-9)
    assert reader("attn_kv_roofline")(r) == pytest.approx(
        100 * counts.attention_bytes(CFG, 17.0, 17 * 4600.0) / 819e9
        / 70e-9)
    # A program whose mixers do not run side by side (every older cell, the
    # parent of PR 54: no such label), no trace, no peaks, no samples, no
    # column, no scopes: nothing, and no error.
    for text in ("", SIDE_BY_SIDE.replace('parallel="1"', 'parallel="0"'),
                 SIDE_BY_SIDE.replace(',parallel="1"', "")):
        for name in NEW_READERS:
            assert reader(name)(reading(
                trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
                samples=SAMPLES, metrics_text=text)) is None
    for name in NEW_READERS:
        assert reader(name)(reading()) is None
    assert reader("mixers_roofline")(reading(
        trace=traced(), trace_mono=(115.0, 125.0), samples=SAMPLES)) is None
    assert reader("mixers_roofline")(reading(
        trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS)) is None
    for cell in OLDER:      # a roofline module without mixer_bytes
        other = manifest.cell_files(MAN, cell)["config"]
        assert reader("mixers_roofline")(reading(
            trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
            samples=SAMPLES, model=other)) is None
    bare = {k: v for k, v in ring_columns().items() if k != "ssm_row_steps"}
    monkeypatch.setattr(flight, "get_recorder", lambda: FakeRing(bare))
    assert reader("mixers_roofline")(r) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: None)
    fresh = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
                    samples=SAMPLES)
    for name in NEW_READERS:
        assert reader(name)(fresh) is None
