"""What PR 34 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (one chip's share: 16 chips a
layer), the manifest's lookups of its files, the traffic plan (256 sizes, the
law's ends, one order), the arithmetic of rooflines/deepseek_v32.py at one
shape by hand (9 layers, 20 rows: 8.35 GB read a step), and each of the four new readers
on a canned Reading (and on a program that lacks the scope or the counter,
where it returns nothing)."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "deepseek-v3.2-exp.reasoning-long"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("attn_index_ms_per_step", "attn_index_roofline",
               "attn_sparse_roofline", "attn_selected_pct")
JOINED = ("moe_ms_per_step", "moe_shared_ms_per_step",
          "moe_expert_load_max_over_mean", "moe_local_picks_pct",
          "moe_held_touched_pct")


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
                   if r["name"] == "DeepSeek-V3.2-Exp")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["first_k_dense_replace"],
            CFG["n_routed_experts"], CFG["vocab_size"],
            CFG["num_nextn_predict_layers"]) == (9, 1, 16, 129280 // 8, 0)
    assert CFG["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280,
        "num_nextn_predict_layers": 1}
    assert CFG["expert_parallel"] == {"routed_experts": 256,
                                      "first_expert": 0,
                                      "chips_per_layer": 16}
    # No width is cut: every rank, head size and count as published.
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "index_head_dim",
                "index_n_heads", "index_topk", "num_experts_per_tok",
                "n_group", "topk_group", "num_attention_heads"):
        assert CFG[key] == row["config"][key], key
    assert "16 chips share each layer" in CFG["stands_for"]
    assert CFG["launch"] == {"quant": "int8"} and CFG["chips"] == 1
    assert FILES["cell"]["traffic"] == "reasoning-long"
    assert FILES["cell"]["chips"] == 1 and len(CFG["assumed"]) >= 10


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/deepseek_v32.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/deepseek_v32.py"
    for name in ("expert_layer_bytes", "shared_layer_bytes", "index_counts",
                 "sparse_attention_counts"):
        assert callable(getattr(counts, name))
    toy = run.rehearsal_cut(FILES)["config"]
    assert (toy["hidden_size"], toy["num_hidden_layers"],
            toy["first_k_dense_replace"], toy["n_routed_experts"],
            toy["expert_parallel"]["routed_experts"], toy["index_topk"]) == (
        64, 3, 1, 4, 16, 256)
    assert "rehearsal_model" not in toy
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS) <= listed and set(JOINED) <= listed
    # ``moe_roofline`` counts the expert layers the module states since PR
    # 55 and lists this cell; ``moe_shared_roofline`` still multiplies by
    # num_hidden_layers, wrong by 9/8 where a layer is dense: not this
    # cell's (PERF.md section 7).
    assert "moe_roofline" in listed
    assert not {"moe_shared_roofline", "moe_experts_touched_pct"} & listed
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
    assert {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)
            } == {"tpot_p50_ms", "out_tok_s", "setup_s"}


def test_the_traffic_plan_is_one_replayed_trace():
    """256 sizes (32 callers x 8), the laws' ends, one order whatever the
    seed; rows end under the launcher's 8,192 tokens."""
    generator = manifest.load_module("generators", FILES["generator"])
    assert FILES["generator"] == "closed_loop"
    plan = generator.plan(FILES["params"], 3400000101, 51.0)
    again = generator.plan(FILES["params"], 7, 51.0)
    assert plan["sequences"] == again["sequences"]
    assert plan["lead_seconds"] == 35.0 and plan["mode"] == "closed"
    assert plan["headers"] == {"x-request-deadline-ms": "600000"}
    reqs = [r for seq in plan["sequences"] for r in seq]
    assert len(plan["sequences"]) == 32 and len(reqs) == 256
    prompts = [r["prompt_len"] for r in reqs]
    later = [r["max_tokens"] for seq in plan["sequences"] for r in seq[1:]]
    assert 128 <= min(prompts) <= 132 and 500 <= max(prompts) <= 512
    assert 3072 <= min(later) and max(later) <= 7168
    assert max(r["prompt_len"] + r["max_tokens"] for r in reqs) <= 7680
    # A first request keeps a share of its output: rows at staggered depths.
    firsts = [seq[0]["max_tokens"] for seq in plan["sequences"]]
    assert min(firsts) < 300 and max(firsts) > 4000
    # Past 2,048 tokens for about two thirds of a later request's steps.
    past = sum(max(0, r["prompt_len"] + r["max_tokens"] - 2048)
               for seq in plan["sequences"] for r in seq[1:])
    assert 0.55 < past / sum(later) < 0.75


# -- the roofline's arithmetic: 9 layers, 20 rows, int8, by hand ------------------------

def test_decode_step_counts_by_hand():
    counts = roofline.counting(CFG)[0]
    h, V = 7168, 16160
    attention = (h * 1536 + 1536 * 24576 + h * 576 + 512 * 16384 * 2
                 + 16384 * h                                  # values, 1 byte
                 + 4 * (1536 + 24576 + 576 + 16384 * 2 + h))  # float32 scales
    indexer = (1536 * 8192 + h * 128 + 4 * (8192 + 128)      # int8 matrices
               + (h * 64 + 2 * 128) * 2)                      # bf16
    norms = (2 * h + 1536 + 512) * 2
    expert = 3 * h * 2048 + 4 * (2048 + 2048 + h)
    router = (h + 1) * 256 * 2
    dense = 3 * h * 18432 + 4 * (18432 + 18432 + h)
    assert counts.routed_experts(CFG) == 256
    assert counts.entry_bytes(CFG) == 640 * 2
    assert counts.index_key_bytes(CFG) == 128 * 2
    assert counts.index_layer_bytes(CFG, "int8") == indexer
    assert counts.expert_layer_bytes(CFG, "int8", 5.5) == pytest.approx(
        router + 5.5 * expert)
    assert counts.shared_layer_bytes(CFG, "int8") == expert
    head = h * V + 4 * V
    weights = (9 * (attention + indexer + norms) + 8 * (router + 17 * expert)
               + dense + head + h * 2)
    # The 8.44 GB held, less the embedding's eighth (115.8 MB, of which a
    # step reads a row a sequence), with the float32 scales.
    assert 8.35e9 < weights < 8.36e9
    # 20 rows of 3,000 tokens: a layer reads 20 x 2,048 entries and 60,000
    # index keys, and writes 20 of each.
    context = 20 * 3000.0
    pool = 9 * (20 * 2048 * 1280 + context * 256 + 20 * (1280 + 256))
    got = counts.decode_step_bytes(CFG, "int8", 1, 20, context)
    assert got == pytest.approx(weights + 20 * h + pool)
    assert pool / got < 0.07
    # Shallow rows attend everything they have.
    shallow = counts.decode_step_bytes(CFG, "int8", 1, 20, 20 * 1000.0)
    assert got - shallow == pytest.approx(
        9 * (20 * 1048 * 1280 + 20 * 2000 * 256))
    floor = roofline.decode_step_floor(CFG, "int8", 1, 20, context, PEAKS)
    assert floor["counted_by"] == "rooflines/deepseek_v32.py"
    assert floor["bound"] == "bandwidth"
    assert floor["seconds"] == pytest.approx(got / 819e9)
    # The two counts the new readers use, a step: 9 x 60,000 keys scored by
    # 64 heads of 128; 9 x 40,960 entries scored and weighed by 128 heads.
    n_bytes, ops = counts.index_counts(CFG, "int8", 20, 9 * context)
    assert n_bytes == 9 * indexer + 9 * context * 256
    assert ops == (2 * (1536 * 8192 + h * 128 + h * 64) * 9 * 20
                   + 64 * (2 * 128 + 2) * 9 * context)
    n_bytes, ops = counts.sparse_attention_counts(CFG, 9 * 20 * 2048)
    assert n_bytes == 9 * 20 * 2048 * 1280
    assert ops == 128 * (2 * 576 + 2 * 512) * 9 * 20 * 2048
    flops = counts.decode_step_flops(CFG, 1, 20, context)
    values = (9 * (h * 1536 + 1536 * 24576 + h * 576 + 512 * 16384 * 2
                   + 16384 * h)
              + 8 * (h * 256 + (8 * 16 / 256 + 1) * 3 * h * 2048)
              + 3 * h * 18432 + h * V)
    assert flops == pytest.approx(
        2 * values * 20
        + counts.index_counts(CFG, None, 20, 9 * context)[1]
        + counts.sparse_attention_counts(CFG, 9 * 20 * 2048)[1])
    with pytest.raises(ValueError, match="one device"):
        counts.decode_step_bytes(CFG, "int8", 4, 20, context)


# -- the readers on canned data -----------------------------------------------------------

class FakeRing:
    def __init__(self, columns, missed=0):
        self.columns, self.missed = columns, missed

    def between(self, lo, hi):
        keep = (self.columns["t_mono"] >= lo) & (self.columns["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": self.missed,
                "columns": {k: v[keep] for k, v in self.columns.items()}}


def ring_columns():
    # Windows of 2 steps x 9 layers; the first and the last row lie outside
    # the measured window [100, 151], the middle one inside the traced
    # seconds [115, 125].
    return {"t_mono": np.array([90.0, 110.0, 120.0, 130.0, 155.0]),
            "host_s": np.full(5, 0.01),
            "attn_selected": np.array([9e9, 30000.0, 36000.0, 38000.0, 9e9]),
            "attn_context": np.array([9e9, 30000.0, 60000.0, 70000.0, 9e9])}


def test_the_counter_reader_takes_the_windows_rows_or_nothing(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    r = reading()
    assert reader("attn_selected_pct")(r) == pytest.approx(
        100 * 104000 / 160000)
    module = manifest.load_module("layer_metrics", "attn_selected_pct")
    assert module.per_step(r) == pytest.approx((104000 / 6, 160000 / 6))
    assert module.per_step(reading(trace_mono=(115.0, 125.0))) == \
        pytest.approx((18000.0, 30000.0))
    # Another block's windows count no keys; the parent's ring has no such
    # column; a ring that lacks rows of the window is not averaged.
    none = ring_columns()
    for key in ("attn_selected", "attn_context"):
        none[key] = np.zeros(5)
    bare = {k: v for k, v in ring_columns().items()
            if not k.startswith("attn_")}
    for ring in (FakeRing(none), FakeRing(bare),
                 FakeRing(ring_columns(), missed=1), object()):
        monkeypatch.setattr(flight, "get_recorder", lambda ring=ring: ring)
        assert reader("attn_selected_pct")(r) is None


def traced():
    """Two executions of a 2-step window program: the indexer is fusion.2
    (100 ns) and fusion.3 (60 ns, fused with a gather), the attention
    fusion.4 (gather, 300 ns) and fusion.5 (core, 200 ns)."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 200.0),
                ("%fusion.2 fusion", base + 200, 100.0),
                ("%fusion.3 fusion", base + 300, 60.0),
                ("%fusion.4 fusion", base + 360, 300.0),
                ("%fusion.5 fusion", base + 660, 200.0),
                ("%copy.2 copy", base + 860, 40.0),
                ("%while.9 while", base, 1000.0)]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.1": "attn.qkv", "%fusion.2": "attn.index",
                "%fusion.3": "attn.kv_gather+attn.index",
                "%fusion.4": "attn.kv_gather", "%fusion.5": "attn.core",
                "%copy.2": "kv.commit", "%while.9": None}


def test_trace_readers_on_a_hand_made_reading(monkeypatch):
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns()))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    samples = [{"t": 120.0, "rows": 20, "context": 3000.0}]
    r = reading(trace=traced(), trace_mono=(115.0, 125.0), peaks=PEAKS,
                samples=samples)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    # 160 ns an execution of 2 steps: 80 ns a step, in milliseconds; the
    # instruction fused with a gather counts whole here AND there.
    assert reader("attn_index_ms_per_step")(r) == pytest.approx(80e-6)
    counts = roofline.counting(CFG)[0]
    # The traced seconds hold one window of 2 steps: 30,000 keys in context
    # and 18,000 attended a step.
    n_bytes, ops = counts.index_counts(CFG, "int8", 20, 30000.0)
    assert reader("attn_index_roofline")(r) == pytest.approx(
        100 * max(n_bytes / 819e9, ops / 197e12) / 80e-9)
    n_bytes, ops = counts.sparse_attention_counts(CFG, 18000.0)
    # attn.core and attn.kv_gather: 200 + 300 + 60 ns an execution.
    assert reader("attn_sparse_roofline")(r) == pytest.approx(
        100 * max(n_bytes / 819e9, ops / 197e12) / 280e-9)
    # No such scope in the executable (another block, the parent).
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: {
        k: (v.replace("+attn.index", "").replace("attn.index", "attn.qkv")
            if v else v) for k, v in OPS_BY_SCOPE.items()})
    for name in ("attn_index_ms_per_step", "attn_index_roofline"):
        assert reader(name)(r) is None
        assert reader(name)(reading()) is None              # untraced
    # No counter in the ring (the parent): nothing, and no error.
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    monkeypatch.setattr(flight, "get_recorder", lambda: FakeRing({
        k: v for k, v in ring_columns().items()
        if not k.startswith("attn_")}))
    for name in ("attn_index_roofline", "attn_sparse_roofline"):
        assert reader(name)(r) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: None)
    assert reader("attn_index_ms_per_step")(r) is None


# -- selection_check.py: the served sets, read back --------------------------------

def test_the_served_sets_are_placed_by_position_and_by_layer():
    """Records as ``select_topk`` sees them, two layers, topk 3: a prefill
    chunk of 2 tokens (the second a pad) over 4 history slots of which 3 are
    held, then a window step whose row holds 4 pooled tokens and 1 earlier
    step; a row that sees no more than topk keys chose nothing."""
    from benchmark import selection_check as sc
    chunk_valid = np.array([[[1, 1, 1, 0, 1, 0],
                             [1, 1, 1, 0, 1, 0]]], bool)       # [1, 2, 4 + 2]
    step_valid = np.array([[1, 1, 1, 1, 0, 0, 1, 0, 1],         # hist 4, m 1
                           [0, 0, 0, 0, 0, 0, 0, 0, 1]], bool)  # an idle row
    records = []
    for layer in range(2):
        chosen = chunk_valid.copy()
        chosen[0, :, layer] = False          # layer l drops position l
        records.append((chunk_valid, chosen))
    for layer in range(2):
        chosen = step_valid.copy()
        chosen[0, [layer, 6]] = False        # and the window's first step
        records.append((step_valid, chosen))
    keeps, coverage = sc.served_keeps(records, layers=2, tokens=6, topk=3,
                                      window=2)
    assert coverage == {"queries_choosing": 3, "queries_missing_a_layer": 1,
                        "records_beyond_the_layers": 0}   # query 4: no record
    causal = np.tril(np.ones((6, 6), bool))
    for layer in range(2):
        want = causal.copy()
        want[3, layer] = False               # the chunk's token: position 3
        want[5, [layer, 4]] = False          # the window's: position 4 + 1
        assert (keeps[layer] == want).all(), layer


def test_a_set_s_distance_counts_swapped_keys_and_their_margins():
    from benchmark import selection_check as sc
    scores = np.full((4, 4), -np.inf, np.float32)
    scores[2, :3] = [3.0, 1.0, 2.0]
    scores[3] = [4.0, 1.0, 2.0, 3.0]
    keep = np.tril(np.ones((4, 4), bool))
    keep[2] = [True, False, True, False]       # the reference's two
    keep[3] = [True, False, True, False]       # key 2 where key 3 ranks
    out = sc.set_distance(scores, keep, range(2, 4), topk=2)
    assert out["queries"] == 2 and out["served_set_sizes"] == [2, 2]
    assert out["differ_pct"] == 25.0           # 1 of 2 x 2 chosen
    sd = np.std([4.0, 1.0, 2.0, 3.0])
    assert out["margin_sd"]["max"] == pytest.approx(1.0 / sd)   # 2.0 under 3.0
    assert out["margin_sd"]["p50"] == pytest.approx(0.5 / sd)   # and 3.0 at it
