"""What PR 45 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (nothing cut), the manifest's
lookups of its files, the arithmetic of rooflines/minicpm_sala.py against
``param_shapes`` at the published widths and ISSUE 45's byte table, the
traffic's laws, the one new reader on a canned trace (and on a program that
lacks the scope, where it returns nothing), and the rehearsal's toy through
the program's reader and the reference."""
import json
import os

import pytest

from benchmark import run
from benchmark.lib import manifest, measure, reference, roofline, scopes
from benchmark.lib import server
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "minicpm-sala-9b.doc-reasoning"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
JOINED = ("ssm_ms_per_step", "ssm_roofline", "ssm_state_rows_pct",
          "attn_selected_pct", "attn_sparse_roofline",
          "attn_index_ms_per_step", "attn_index_roofline")
NEW = "attn_compress_ms_per_step"


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 2, "quant": "int8",
                                        "max_num_seqs": 24},
                model=CFG, peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


def test_the_configuration_is_the_catalog_row_and_nothing_is_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "MiniCPM-SALA")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == []
    for key, value in row["config"].items():
        assert CFG[key] == value, key
    mixers = CFG["mixer_types"]
    assert (CFG["num_hidden_layers"], len(mixers),
            mixers.count("lightning-attn"), mixers.count("minicpm4")) \
        == (32, 32, 24, 8)
    assert [i for i, m in enumerate(mixers) if m == "minicpm4"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert (CFG["vocab_size"], CFG["hidden_size"], CFG["intermediate_size"]
            ) == (73448, 4096, 16384)
    assert CFG["launch"] == {
        "quant": "int8", "context_length": 16384, "max_num_seqs": 24,
        "prefill_chunk_tokens": 8192, "page_size": 128,
        "max_pages_per_seq": 128} \
        and CFG["chips"] == 1
    assert CFG["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048}
    assert "whole on one TPU v5e" in CFG["stands_for"]
    assert len(CFG["assumed"]) >= 12 and len(FILES["cell"]["why"]) <= 200
    for said in ("sparse_config", "dense_len", "qk_norm", "exp(-2^(-8 h / 32))",
                 "float32", "BEFORE the gate", "scale_emb", "mup_denominator",
                 "rotary", "q | k | v | z", "int8", "random"):
        assert any(said in line for line in CFG["assumed"]), said


def test_the_traffic_is_the_issue_s():
    """ISSUE 45's, parameter for parameter: the chip runs showed neither a
    preemption nor a pool over 90 %, so ``clients`` stays 24."""
    assert FILES["generator"] == "closed_loop"
    assert FILES["params"] == {
        "clients": 24, "ramp_seconds": 35, "requests_per_client": 8,
        "prompt_tokens": {"dist": "loguniform", "min": 2048, "max": 6144},
        "output_tokens": {"dist": "loguniform", "min": 4096, "max": 9216},
        "headers": {"x-request-deadline-ms": "600000"}}
    assert FILES["cell"]["traffic"] == "doc-reasoning"
    assert FILES["cell"]["chips"] == 1
    plan = manifest.load_module("generators", "closed_loop").plan(
        FILES["params"], 3, 51.0)
    reqs = [r for seq in plan["sequences"] for r in seq]
    assert max(r["prompt_len"] for r in reqs) <= 8192   # one prefill bucket
    assert max(r["prompt_len"] + r["max_tokens"] for r in reqs) < 16384
    # A caller a row of the engine.
    assert len(plan["sequences"]) == 24 == CFG["launch"]["max_num_seqs"]


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/minicpm_sala.py"
    assert set(judged["allowed"]) == {"median", "rms", "worst"}
    module = manifest.load_module("references", CFG["reference"])
    for name in ("reference_logprobs", "control_logprobs", "layer_of",
                 "chosen_blocks"):
        assert callable(getattr(module, name))
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/minicpm_sala.py"
    for name in ("ssm_layer_bytes", "state_bytes_per_row", "index_counts",
                 "sparse_attention_counts", "kv_bytes_per_token"):
        assert callable(getattr(counts, name))
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(JOINED) <= listed and NEW in listed
    for name in JOINED:
        assert CELL in manifest.find_named(MAN["per_layer"], name,
                                           "metric")["workloads"]
    new = manifest.load_module("layer_metrics", NEW)
    entry = manifest.find_named(MAN["per_layer"], NEW, "metric")
    assert (new.NAME, new.UNIT, new.BETTER, new.MOVES, new.SOURCE, new.LAYER
            ) == (NEW, entry["unit"], entry["better"], entry["moves"],
                  entry["source"], entry["layer"])
    assert entry["workloads"] == [CELL]
    assert {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)
            } == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    assert not {"moe_ms_per_step", "mtp_roofline", "moe_roofline"} & listed


def test_the_roofline_counts_what_param_shapes_holds():
    """The weights a step reads, as the roofline module counts them from
    the configuration's keys, are the program's ``param_shapes`` at the
    published widths, and ISSUE 45's byte table."""
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS
    import math
    spec = server.model_spec("sala-count", CFG, "int8")
    shapes = param_shapes(spec)
    counts = roofline.counting(CFG)[0]
    stored = 0
    for name, shape in shapes["layers"].items():
        values = math.prod(shape)
        if name in QUANT_LAYER_KEYS:
            stored += values + 4 * values // shape[-2]
        else:
            stored += 2 * values
    stored += math.prod(shapes["lm_head"]) + 4 * shapes["lm_head"][1]
    stored += 2 * shapes["final_norm"][0]
    # A step with no row in context: the weights, one embedding row, the
    # new token's K and V.
    step = counts.decode_step_bytes(CFG, "int8", 1, 1, 0)
    row = (2 * counts.state_bytes_per_row(CFG) + 4096
           + 8 * 2 * 2 * 128 * 2)
    assert step - row == stored
    assert spec.num_params() == 9_477_206_016
    assert counts.state_bytes_per_row(CFG) == spec.ssm_state_bytes_per_row \
        == 50_331_648
    assert counts.kv_bytes_per_token(CFG) == spec.kv_bytes_per_token() == 8448
    # 24 slots of state; the mixers' own matrices.
    assert 24 * counts.state_bytes_per_row(CFG) == 1_207_959_552
    sizes = counts._sizes(CFG)
    assert sum(v for v, _ in sizes["lightning"]) * 24 \
        + sum(v for v, _ in sizes["attention"]) * 8 == 2_432_696_320
    assert sum(v for v, _ in sizes["mlp"]) * 32 == 6_442_450_944
    flops = counts.decode_step_flops(CFG, 1, 24, 24 * 7000)
    assert flops > 2 * 24 * (spec.num_params() - 73448 * 4096)


def test_the_new_reader_on_a_hand_made_trace(monkeypatch):
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 200.0),
                ("%fusion.2 fusion", base + 200, 300.0),
                ("%fusion.3 fusion", base + 500, 60.0),
                ("%while.9 while", base, 1000.0)]
    trace = {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}
    by_scope = {"%fusion.1": "mlp", "%fusion.2": "attn.index",
                "%fusion.3": "attn.compress", "%while.9": None}
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: by_scope)
    r = reading(trace=trace, trace_mono=(115.0, 125.0))
    r._by_scope = scopes.seconds_by_scope(r.trace, by_scope)
    # 60 ns an execution of 2 steps: 30 ns a step, in milliseconds.
    assert reader(NEW)(r) == pytest.approx(30e-6)
    assert reader("attn_index_ms_per_step")(r) == pytest.approx(150e-6)
    # The parent's programs draw no such scope; no trace: nothing, no error.
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: {
        k: (v.replace("attn.compress", "mlp") if v else v)
        for k, v in by_scope.items()})
    assert reader(NEW)(r) is None
    assert reader(NEW)(reading()) is None
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: None)
    assert reader(NEW)(r) is None


def test_the_rehearsal_s_toy_reads_and_refers():
    """The cell cut for a CPU rehearsal: the toy through the program's
    reader (all 7 layers, blocks of 8 of which 6 stay: a 300-token row
    chooses), and one short forward of the reference on device-made
    weights."""
    from benchmark.lib import weights
    from dynamo_tpu.engine.config import EngineConfig
    toy = run.rehearsal_cut(FILES)["config"]
    assert "rehearsal_model" not in toy
    assert (toy["hidden_size"], toy["num_hidden_layers"], toy["vocab_size"],
            toy["sparse_config"]["block_size"], toy["sparse_config"]["topk"]
            ) == (64, 7, 64, 8, 6)
    spec = server.model_spec("sala-toy", toy, "int8")
    assert spec.layer_pattern == "SDLDLDSDLDLDSD" and spec.compressed_keys
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state_shapes) \
        == (4, 16, ((4, 16, 16), None))
    config = EngineConfig(model=spec, page_size=16, num_pages=32)
    params = weights.make_params(spec, weights.runner_mesh(config), 5)
    module = manifest.load_module("references", "minicpm_sala")
    prompt = list(range(40))
    got = module.reference_logprobs(params, spec, prompt, [1, 2, 3])
    dense = module.control_logprobs(params, spec, prompt, [1, 2, 3],
                                    gate="false")
    assert len(got) == 3 and all(v < 0 for v in got) and got != dense
    kept = module.chosen_blocks(params, spec, prompt + list(range(40)))
    assert len(kept) == 3 and kept[0].shape == (80, 2, 10)
    assert kept[0][-1].sum(-1).tolist() == [6, 6]
