"""What PR 39 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (one chip's share of four: 16 of 64
experts, nothing else cut), the manifest's lookups of its files, the arithmetic
of rooflines/glm4_moe_lite.py against ``param_shapes`` at the published widths
and at one shape by hand, each of the four new readers on a canned ring and
trace (and on a program that lacks the columns or the scope, the older cells,
where it returns nothing), and draft_check.py's judge of a draft."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "glm-4.7-flash.reasoning"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("spec_accept_pct", "spec_tokens_per_step", "mtp_ms_per_step",
               "mtp_roofline")
JOINED = ("moe_ms_per_step", "moe_expert_load_max_over_mean",
          "moe_shared_ms_per_step", "moe_local_picks_pct",
          "moe_held_touched_pct", "attn_sparse_roofline")
OLDER = ("qwen2.5-7b.reasoning", "smallthinker-21b-a3b.reasoning",
         "command-a-plus.reasoning", "deepseek-v3.2-exp.reasoning-long")


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 2, "quant": "int8"},
                model=CFG, peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


# -- the configuration and its files ------------------------------------------

def test_the_configuration_is_the_catalog_row_but_for_the_chips_share():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "GLM-4.7-Flash")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == ["n_routed_experts"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # Full depth, the module, the whole vocabulary; 16 of 64 experts held.
    assert (CFG["num_hidden_layers"], CFG["num_nextn_predict_layers"],
            CFG["vocab_size"], CFG["n_routed_experts"],
            CFG["num_experts"]) == (47, 1, 154880, 16, 16)
    assert CFG["published"] == {"n_routed_experts": 64}
    assert CFG["expert_parallel"] == {"routed_experts": 64, "first_expert": 0,
                                      "chips_per_layer": 4}
    assert CFG["launch"] == {"quant": "int8", "spec_decode": "mtp",
                             "spec_k": 1} and CFG["chips"] == 1
    assert "4 chips share each layer" in CFG["stands_for"]
    assert FILES["cell"]["traffic"] == "reasoning"
    assert FILES["cell"]["chips"] == 1 and len(CFG["assumed"]) >= 10
    assert len(FILES["cell"]["why"]) <= 200 and "154,880" in FILES[
        "cell"]["why"]


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/glm4_moe_lite.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    module = manifest.load_module("references", CFG["reference"])
    for name in ("reference_logprobs", "logprobs", "draft_logits",
                 "control_logprobs"):
        assert callable(getattr(module, name))
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/glm4_moe_lite.py"
    for name in ("expert_layer_bytes", "shared_layer_bytes",
                 "sparse_attention_counts", "draft_module_bytes"):
        assert callable(getattr(counts, name))
    toy = run.rehearsal_cut(FILES)["config"]
    assert (toy["hidden_size"], toy["num_hidden_layers"],
            toy["first_k_dense_replace"], toy["n_routed_experts"],
            toy["expert_parallel"]["first_expert"],
            toy["num_nextn_predict_layers"], toy["vocab_size"]) == (
        64, 3, 1, 4, 4, 1, 64)
    assert "rehearsal_model" not in toy
    assert toy["launch"]["spec_decode"] == "mtp"
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS) <= listed and set(JOINED) <= listed
    # ``moe_roofline`` counts the expert layers the module states since PR
    # 55 and lists this cell; ``moe_shared_roofline`` still multiplies by
    # num_hidden_layers, wrong where a leading layer is dense: not this
    # cell's (PERF.md section 7).
    assert "moe_roofline" in listed
    assert not {"moe_shared_roofline", "attn_index_roofline",
                "attn_index_ms_per_step"} & listed
    for name in NEW_READERS:
        module = manifest.load_module("layer_metrics", name)
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert (module.NAME, module.UNIT, module.BETTER, module.MOVES,
                module.SOURCE, module.LAYER) == (
            name, entry["unit"], entry["better"], entry["moves"],
            entry["source"], entry["layer"])
        assert CELL in entry["workloads"]
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


# -- the roofline's counts ------------------------------------------------------

def test_the_roofline_counts_what_param_shapes_holds():
    """The weights a step reads, as the roofline module counts them from the
    configuration's keys, are the program's ``param_shapes`` at the
    published widths as stored (int8 values, a float32 scale a channel, the
    rest bf16), the head once more (the module's draft reads it again) and
    the embedding's table left out (rows are gathered)."""
    from benchmark.lib import server
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS
    counts = roofline.counting(CFG)[0]
    spec = server.model_spec("glm", CFG, "int8")
    shapes = param_shapes(spec)

    def stored(name, shape):
        n = int(np.prod(shape))
        if name in QUANT_LAYER_KEYS or name == "lm_head":
            return n + 4 * n // shape[-2]
        return 2 * n

    held = sum(stored(k, s) for k, s in shapes["layers"].items())
    held += stored("lm_head", shapes["lm_head"]) + 2 * spec.hidden_size
    head = stored("lm_head", shapes["lm_head"])
    no_pool = counts.decode_step_bytes(CFG, "int8", 1, 0, 0) \
        - 3 * CFG["hidden_size"]                 # the embedding's rows
    assert no_pool == pytest.approx(held + head, rel=1e-6)
    module = sum(stored(k, s) for k, s in shapes["layers"].items()
                 if k.startswith("mtp_"))
    assert counts.draft_module_bytes(CFG, "int8") == pytest.approx(
        module + head, rel=1e-6)
    assert counts.pool_layers(CFG) == 48 and counts.entry_bytes(CFG) == 1280
    assert counts.drafts(CFG) == 1
    # Entries are read once whatever k; a row writes what it commits.
    rows, context = 20.0, 20 * 1500.0
    assert counts.decode_step_bytes(CFG, "int8", 1, rows, context) \
        - counts.decode_step_bytes(CFG, "int8", 1, rows, 0) == \
        48 * context * 1280
    # Without drafting: no module, the head once, 47 layers of entries.
    plain = {**CFG, "launch": {"quant": "int8"}}
    assert counts.drafts(plain) == 0 and counts.pool_layers(plain) == 47
    assert counts.decode_step_bytes(CFG, "int8", 1, 0, 0) \
        - counts.decode_step_bytes(plain, "int8", 1, 0, 0) == \
        counts.draft_module_bytes(CFG, "int8") + 2 * CFG["hidden_size"]
    with pytest.raises(ValueError, match="one device"):
        counts.decode_step_bytes(CFG, "int8", 4, 20, context)


def test_attention_is_charged_bytes_once_and_products_twice():
    counts = roofline.counting(CFG)[0]
    keys = 48 * 20 * 1500.0
    n_bytes, ops = counts.sparse_attention_counts(CFG, keys)
    assert n_bytes == keys * 1280
    assert ops == 2 * keys * 20 * (2 * (512 + 64) + 2 * 512)
    plain = {**CFG, "launch": {"quant": "int8"}}
    assert counts.sparse_attention_counts(plain, keys) == (n_bytes, ops / 2)
    flops = counts.decode_step_flops(CFG, 1, 20, 20 * 1500.0)
    assert flops > 2 * counts.decode_step_flops(plain, 1, 20, 0) * 0.99


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
            "spec_drafted": np.array([9e9, 40.0, 40.0, 36.0, 9e9]),
            "spec_accepted": np.array([9e9, 1.0, 0.0, 2.0, 9e9]),
            "spec_row_steps": np.array([9e9, 40.0, 40.0, 38.0, 9e9]),
            "attn_selected": np.array([9e9, 96000.0, 96000.0, 96000.0, 9e9]),
            "attn_context": np.array([9e9, 96000.0, 96000.0, 96000.0, 9e9])}


def test_the_counter_readers_take_the_windows_rows_or_nothing(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    r = reading()
    assert reader("spec_accept_pct")(r) == pytest.approx(100 * 3 / 116)
    assert reader("spec_tokens_per_step")(r) == pytest.approx(121 / 118)
    # Windows that drafted nothing (the older cells' programs count zeros);
    # the parent's ring has no such column; a ring that lacks rows of the
    # window is not averaged.
    none = ring_columns()
    for key in none:
        if key.startswith("spec_"):
            none[key] = np.zeros(5)
    bare = {k: v for k, v in ring_columns().items()
            if not k.startswith("spec_")}
    for ring in (FakeRing(none), FakeRing(bare),
                 FakeRing(ring_columns(), missed=1), object()):
        monkeypatch.setattr(flight, "get_recorder", lambda ring=ring: ring)
        for name in ("spec_accept_pct", "spec_tokens_per_step"):
            assert reader(name)(r) is None


def traced():
    """Two executions of a 2-step window program: the module is fusion.2
    (100 ns) and fusion.3 (60 ns, its expert layer)."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 200.0),
                ("%fusion.2 fusion", base + 200, 100.0),
                ("%fusion.3 fusion", base + 300, 60.0),
                ("%fusion.5 fusion", base + 660, 200.0),
                ("%while.9 while", base, 1000.0)]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.1": "mlp+moe.experts", "%fusion.2": "mtp",
                "%fusion.3": "mtp+moe.experts", "%fusion.5": "attn.core",
                "%while.9": None}


def test_trace_readers_on_a_hand_made_reading(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    r = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # 160 ns an execution of 2 steps: 80 ns a step, in milliseconds.
    assert reader("mtp_ms_per_step")(r) == pytest.approx(80e-6)
    # The module's expert layer is an expert layer too.
    assert reader("moe_ms_per_step")(r) == pytest.approx(130e-6)
    counts = roofline.counting(CFG)[0]
    # The traced seconds hold one window of 2 steps: 48,000 keys a step
    # over 48 layers, 1,000 of them the module's layer's.
    n_bytes = counts.draft_module_bytes(CFG, "int8") + 1000 * 1280
    assert reader("mtp_roofline")(r) == pytest.approx(
        100 * n_bytes / 819e9 / 80e-9)
    # No such scope in the executable (the older cells, the parent), no
    # trace, no peaks: nothing, and no error.
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: {
        k: (v.replace("mtp+", "mlp+").replace("mtp", "mlp") if v else v)
        for k, v in OPS_BY_SCOPE.items()})
    for name in ("mtp_ms_per_step", "mtp_roofline"):
        assert reader(name)(r) is None
        assert reader(name)(reading()) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    for cell in OLDER:      # a roofline module without draft_module_bytes
        other = manifest.cell_files(MAN, cell)["config"]
        assert reader("mtp_roofline")(reading(
            trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
            model=other)) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: None)
    assert reader("mtp_ms_per_step")(r) is None


# -- draft_check.py's judge ---------------------------------------------------------

def test_a_draft_is_judged_by_its_distance_from_the_row_s_largest():
    from benchmark import draft_check as dc
    rows = np.zeros((4, 5), np.float32)
    rows[0] = [0.0, 3.0, 1.0, 0.0, 0.0]        # drafts token 2: argmax 1
    rows[1] = [2.0, 0.0, 0.0, 1.9, 0.0]        # a near-tie: 0 and 3
    rows[2] = [0.0, 0.0, 0.0, 0.0, 5.0]
    got = dc.judge_drafts(rows, [(2, 1), (3, 3), (4, 0), (9, 0)])
    assert got["drafts"] == 3 and got["agree"] == 1
    assert got["margin_max"] == pytest.approx(5.0 / rows[2].std())
    assert got["margin_median"] == pytest.approx(0.1 / rows[1].std())
    # The same drafts against the rows one position on.
    off = dc.judge_drafts(rows, [(2, 1), (3, 3)], offset=1)
    assert off["agree"] == 0 and off["drafts"] == 2
