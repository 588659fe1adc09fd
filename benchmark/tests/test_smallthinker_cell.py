"""What PR 28 adds to the benchmark, on hand-made data: the arithmetic of
rooflines/smallthinker.py (24 layers, 18 rows: bytes by hand), the manifest's
lookups of the configuration's three files, the configuration against the
catalog row it was copied from, and each of the four new readers on a canned
Reading (and on a program that lacks the span or the counter, where it
returns nothing)."""
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "smallthinker-21b-a3b.reasoning"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 2, "quant": "int8"},
                model=CFG, peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


# -- the configuration and its three files ------------------------------------------

def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    for key, value in published.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # Six whole periods, both layouts cut to their first 24 entries.
    assert CFG["num_hidden_layers"] == 24
    for key in ("rope_layout", "sliding_window_layout"):
        assert CFG[key] == published[key][:24]
    assert CFG["launch"] == {"quant": "int8"} and CFG["chips"] == 1
    assert FILES["cell"]["traffic"] == "reasoning"
    assert len(CFG["assumed"]) >= 6


def test_the_manifest_finds_reference_roofline_and_rehearsal_model():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/smallthinker.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/smallthinker.py"
    assert callable(counts.expert_layer_bytes)
    toy = run.rehearsal_cut(FILES)["config"]
    assert (toy["hidden_size"], toy["num_hidden_layers"],
            toy["moe_num_primary_experts"],
            toy["moe_num_active_primary_experts"],
            toy["sliding_window_size"]) == (64, 8, 8, 3, 8)
    assert len(toy["rope_layout"]) == len(toy["sliding_window_layout"]) == 8
    assert "rehearsal_model" not in toy
    # The Qwen cell names none of the three and reads what it read.
    dense = manifest.cell_files(MAN, "qwen2.5-7b.reasoning")["config"]
    assert reference.for_config(dense)["module"] == "lib/reference.py"
    assert roofline.counting(dense)[1] == "lib/roofline.py"
    # The cell is ON the list of each of its four readers, wherever later
    # cells stand there; a reader of every cell keeps no list.
    for name in ("moe_ms_per_step", "moe_roofline",
                 "moe_experts_touched_pct", "moe_expert_load_max_over_mean"):
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert CELL in entry["workloads"], name
    assert "workloads" not in manifest.find_named(
        MAN["per_layer"], "decode_window_roofline", "metric")
    import hashlib
    import json
    mix = manifest.load_json(os.path.join(manifest.BENCH, "traffic",
                                          "reasoning.json"))
    assert mix["generator"] == "closed_loop"
    assert hashlib.sha256(json.dumps(mix["params"], sort_keys=True).encode()
                          ).hexdigest() == TRAFFIC_PARAMS_SHA256


#: The parameters of benchmark/traffic/reasoning.json as PR 27 left them: the
#: cells share them (PR 58 corrected the file's ``about``: --seed no longer
#: draws the weights; until then the whole file's bytes were pinned).
TRAFFIC_PARAMS_SHA256 = (
    "c5a231910a762f1dcf4860270e21321646b7aa247645010e3cc6b3356e3054c5")


# -- the roofline's arithmetic: 24 layers, 18 rows, int8, by hand ---------------------

def test_decode_step_bytes_by_hand():
    counts = roofline.counting(CFG)[0]
    attention = (2 * 2560 * 3584 + 2 * 2560 * 512          # values, 1 byte
                 + 4 * (3584 + 512 + 512 + 2560))           # float32 scales
    assert attention == 21_000_192
    expert = 3 * 2560 * 768 + 4 * (768 + 768 + 2560)
    assert expert == 5_914_624
    router = 2560 * 64 * 2
    # The floor is the weights AS STORED: every resident expert, whatever
    # the rows chose (lib/roofline.py's definition); the count of touched
    # experts enters ``expert_layer_bytes``, under ``moe_roofline``, alone.
    assert not hasattr(counts, "experts_touched")
    assert counts.expert_layer_bytes(CFG, "int8", 42.4) == pytest.approx(
        router + 42.4 * expert)
    layer = attention + 2 * 2560 * 2 + router + 64 * expert
    head = 2560 * 151936 + 4 * 151936
    context = 18 * 1000.0                                  # all inside 4096
    kv = (24 * context + 18 * 24) * (2 * 4 * 128 * 2)
    want = 24 * layer + head + 2560 * 2 + 18 * 2560 + kv
    got = counts.decode_step_bytes(CFG, "int8", 1, 18, context)
    assert got == pytest.approx(want)
    assert 10.8e9 < got < 11.0e9          # 9.98 GB of weights, 0.89 of K, V
    # Past the window a window layer reads 4096 tokens a row, a full layer
    # all of them: 6 full and 18 window layers.
    long = counts.decode_step_bytes(CFG, "int8", 1, 18, 18 * 6000.0)
    assert long - got == pytest.approx(
        (6 * 18 * 5000 + 18 * 18 * 3096) * 2048)
    floor = roofline.decode_step_floor(CFG, "int8", 1, 18, context, PEAKS)
    assert floor["counted_by"] == "rooflines/smallthinker.py"
    assert floor["bound"] == "bandwidth"
    assert floor["seconds"] == pytest.approx(want / 819e9)
    flops = counts.decode_step_flops(CFG, 1, 18, context)
    per_row = 24 * (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
                    + 6 * 3 * 2560 * 768) + 2560 * 151936
    assert flops == pytest.approx(2 * per_row * 18
                                  + 4 * 28 * 128 * 24 * context)
    with pytest.raises(ValueError, match="one device"):
        counts.decode_step_bytes(CFG, "int8", 4, 18, context)


# -- the four readers on canned data ---------------------------------------------------

class FakeRing:
    def __init__(self, columns, missed=0):
        self.columns, self.missed = columns, missed

    def between(self, lo, hi):
        keep = (self.columns["t_mono"] >= lo) & (self.columns["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": self.missed,
                "columns": {k: v[keep] for k, v in self.columns.items()}}


def ring_columns():
    # Windows of 4 steps x 24 layers = 96 layer-steps each; the first and
    # the last row lie outside the measured window [100, 151].
    return {"t_mono": np.array([90.0, 110.0, 120.0, 130.0, 155.0]),
            "host_s": np.full(5, 0.01),
            "moe_layer_steps": np.array([96.0, 96.0, 96.0, 96.0, 96.0]),
            "moe_touched": np.array([9e9, 96 * 52.0, 96 * 54.0, 96 * 56.0,
                                     9e9]),
            "moe_load": np.array([9e9, 96 * 2.0, 96 * 2.5, 96 * 3.0, 9e9])}


def test_counter_readers_take_the_windows_rows_or_nothing(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    r = reading()
    assert reader("moe_experts_touched_pct")(r) == pytest.approx(
        100 * 54.0 / 64)
    assert reader("moe_expert_load_max_over_mean")(r) == pytest.approx(2.5)
    # A dense model's windows count nothing; the parent's ring has no such
    # column; a ring that lacks rows of the window is not averaged.
    dense = ring_columns()
    for key in ("moe_layer_steps", "moe_touched", "moe_load"):
        dense[key] = np.zeros(5)
    bare = {k: v for k, v in ring_columns().items()
            if not k.startswith("moe_")}
    for ring in (FakeRing(dense), FakeRing(bare),
                 FakeRing(ring_columns(), missed=1), object()):
        monkeypatch.setattr(flight, "get_recorder", lambda ring=ring: ring)
        assert reader("moe_experts_touched_pct")(r) is None
        assert reader("moe_expert_load_max_over_mean")(r) is None


def traced():
    """Two executions of a 2-step window program; the expert layer is
    fusion.3 and fusion.5 (300 ns an execution), the router fusion.4."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 200.0),
                ("%fusion.3 fusion", base + 200, 200.0),
                ("%fusion.4 fusion", base + 400, 50.0),
                ("%fusion.5 fusion", base + 450, 100.0),
                ("%copy.2 copy", base + 550, 400.0),
                ("%while.9 while", base, 1000.0)]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.1": "attn.qkv", "%fusion.3": "mlp+moe.experts",
                "%fusion.4": "mlp+moe.router", "%fusion.5": "mlp+moe.experts",
                "%copy.2": "kv.commit", "%while.9": None}


def test_trace_readers_on_a_hand_made_reading(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    r = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # 350 ns an execution of 2 steps: 175 ns a step, in milliseconds.
    assert reader("moe_ms_per_step")(r) == pytest.approx(175e-6)
    # The sub-scopes nest under mlp: what weights_ms_per_step sums holds it.
    assert reader("weights_ms_per_step")(r) == pytest.approx(275e-6)
    # The traced seconds hold one row: 54 experts a layer-step touched.
    counts = roofline.counting(CFG)[0]
    seconds = 24 * counts.expert_layer_bytes(CFG, "int8", 54.0) / 819e9
    assert reader("moe_roofline")(r) == pytest.approx(
        100 * seconds / 175e-9)
    # No sub-scope in the executable (a dense program, the parent): nothing.
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: {
        k: (v.split("+")[0] if v else v) for k, v in OPS_BY_SCOPE.items()})
    assert reader("moe_ms_per_step")(r) is None
    assert reader("moe_roofline")(r) is None
    assert reader("weights_ms_per_step")(r) == pytest.approx(275e-6)
    for name in ("moe_ms_per_step", "moe_roofline"):
        assert reader(name)(reading()) is None              # untraced
