"""What PR 48 adds to the benchmark, on hand-made data: the configuration
against the catalog row it was copied from (nothing cut), the manifest's
lookups of its files, the arithmetic of rooflines/ouro.py against
``param_shapes`` at the published widths and ISSUE 48's bytes (9.97 GB of
weights a step, 1,572,864 B a token), the traffic's laws, the three new
readers on a canned trace and ring (and on a program that lacks the counter,
where they return nothing), and the rehearsal's toy through the program's
reader and the reference."""
import json
import math
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import host_phases, manifest, measure, reference, roofline
from benchmark.lib import scopes, server
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
CELL = "ouro-2.6b.reasoning-1k"
FILES = manifest.cell_files(MAN, CELL)
CFG = FILES["config"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("loop_passes_per_token", "loop_weights_roofline", "attn_kv_roofline")
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}


def reader(name):
    return manifest.load_module("layer_metrics", name).read


def reading(**kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, samples=[], spans=[], emissions={},
                prompt_keys={}, engine={"decode_window": 4, "quant": "int8",
                                        "max_num_seqs": 4},
                model=CFG, peaks=None, metrics_text="")
    base.update(kw)
    return measure.Reading(**base)


def test_the_configuration_is_the_catalog_row_and_nothing_is_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Ouro-2.6B")
    entry = manifest.find_named(MAN["configs"], FILES["cell"]["config"],
                                "config")
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"] == []
    for key, value in row["config"].items():
        assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["total_ut_steps"],
            CFG["early_exit_threshold"], len(CFG["layer_types"])
            ) == (48, 4, 1, 48)
    assert CFG["launch"] == {"quant": "int8", "max_num_seqs": 4,
                             "context_length": 2048} and CFG["chips"] == 1
    assert "whole on one TPU v5e" in CFG["stands_for"]
    assert len(FILES["cell"]["why"]) <= 200
    for said in ("sandwich", "EVERY pass", "QKV bias", "kept a PASS",
                 "exit gate", "rotate-half", "int8", "random", "g2 and g4",
                 "context_length 2048"):
        assert any(said in line for line in CFG["assumed"]), said


def test_the_traffic_is_the_issue_s():
    assert FILES["generator"] == "closed_loop"
    assert FILES["params"] == {
        "clients": 4, "ramp_seconds": 35, "requests_per_client": 8,
        "prompt_tokens": {"dist": "loguniform", "min": 128, "max": 256},
        "output_tokens": {"dist": "loguniform", "min": 512, "max": 1024},
        "headers": {"x-request-deadline-ms": "600000"}}
    assert FILES["cell"]["traffic"] == "reasoning-1k"
    assert FILES["cell"]["chips"] == 1
    plan = manifest.load_module("generators", "closed_loop").plan(
        FILES["params"], 3, 51.0)
    reqs = [r for seq in plan["sequences"] for r in seq]
    assert max(r["prompt_len"] for r in reqs) <= 256
    longest = max(r["prompt_len"] + r["max_tokens"] for r in reqs)
    assert longest <= 1280 < CFG["launch"]["context_length"]
    # A caller a row of the engine, and four of the longest rows with a
    # page each inside the pool the launcher's rule gives.
    assert len(plan["sequences"]) == 4 == CFG["launch"]["max_num_seqs"]
    assert 4 * (longest + 16) <= 339 * 16


def test_the_manifest_finds_every_new_file():
    judged = reference.for_config(CFG)
    assert judged["module"] == "references/ouro.py"
    assert judged["allowed"] == reference.ALLOWED_NATS  # the dense block's
    module = manifest.load_module("references", CFG["reference"])
    for name in ("reference_logprobs", "control_logprobs", "all_logprobs"):
        assert callable(getattr(module, name))
    counts, where = roofline.counting(CFG)
    assert where == "rooflines/ouro.py"
    for name in ("loop_weight_bytes", "attention_bytes", "kv_token_bytes"):
        assert callable(getattr(counts, name))
    listed = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW) <= listed
    for name in NEW:
        new = manifest.load_module("layer_metrics", name)
        entry = manifest.find_named(MAN["per_layer"], name, "metric")
        assert (new.NAME, new.UNIT, new.BETTER, new.MOVES, new.SOURCE,
                new.LAYER) == (name, entry["unit"], entry["better"],
                               entry["moves"], entry["source"],
                               entry["layer"])
        assert CELL in entry["workloads"]
    assert {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)
            } == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    # Readers of other blocks' scopes and counters find nothing here.
    assert not {"moe_ms_per_step", "ssm_roofline", "attn_index_roofline",
                "attn_sparse_roofline", "spec_accept_pct", "mtp_roofline"
                } & listed
    assert {"decode_window_roofline", "kv_pages_peak_pct",
            "weights_ms_per_step", "attn_core_ms_per_step"} <= listed


def test_the_roofline_counts_what_param_shapes_holds_once_a_pass():
    """The weights a step reads, as the roofline module counts them from
    the configuration's keys: the program's ``param_shapes`` at the
    published widths, the layers four times; ISSUE 48's bytes."""
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS
    spec = server.model_spec("ouro-count", CFG, "int8")
    shapes = param_shapes(spec)
    counts = roofline.counting(CFG)[0]
    layers = 0
    for name, shape in shapes["layers"].items():
        values = math.prod(shape)
        layers += (values + 4 * values // shape[-2]
                   if name in QUANT_LAYER_KEYS else 2 * values)
    head = math.prod(shapes["lm_head"]) + 4 * shapes["lm_head"][1]
    stored = 4 * layers + head + 2 * shapes["final_norm"][0]
    # A step of one row with nothing in context: the weights, one row of
    # the int8 embedding table, the new token's K and V.
    step = counts.decode_step_bytes(CFG, "int8", 1, 1, 0)
    assert step - 2048 - 1_572_864 == stored
    assert counts.loop_weight_bytes(CFG, "int8", 1) == stored + 2048
    assert 9.96e9 < counts.loop_weight_bytes(CFG, "int8", 4) < 9.99e9
    assert spec.num_params() == 2_667_972_608
    assert counts.pool_layers(CFG) == spec.pool_layers == 192
    assert counts.kv_token_bytes(CFG) == spec.kv_bytes_per_token() \
        == 1_572_864
    assert counts.attention_bytes(CFG, 4, 3000) == 3004 * 1_572_864
    # ISSUE 48's floor: 17.9 ms a step at 3,000 live tokens, 819 GB/s.
    floor = roofline.decode_step_floor(CFG, "int8", 1, 4, 3000, PEAKS)
    assert 17.8e-3 < floor["seconds"] < 18.0e-3
    assert floor["counted_by"] == "rooflines/ouro.py"
    assert floor["bound"] == "bandwidth"
    flops = counts.decode_step_flops(CFG, 1, 4, 3000)
    assert flops > 2 * 4 * 4 * 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    with pytest.raises(ValueError):
        counts.decode_step_bytes(CFG, "int8", 2, 1, 0)


def test_the_new_readers_on_a_hand_made_trace_and_ring(monkeypatch):
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = []
    for base in (1000.0, 3000.0):
        ops += [("%fusion.1 fusion", base, 400.0),
                ("%custom-call.2 custom-call", base + 400, 300.0),
                ("%fusion.3 fusion", base + 700, 20.0),
                ("%while.9 while", base, 1000.0)]
    trace = {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}
    by_scope = {"%fusion.1": "mlp", "%custom-call.2": "attn.core",
                "%fusion.3": "loop.norm", "%while.9": None}
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: by_scope)
    samples = [{"t": 120.0, "rows": 4, "context": 3000}]
    r = reading(trace=trace, trace_mono=(115.0, 125.0), peaks=PEAKS,
                samples=samples)
    r._by_scope = scopes.seconds_by_scope(r.trace, by_scope)
    counts = roofline.counting(CFG)[0]
    # 400 ns of weights and 300 ns of attention an execution of 4 steps.
    weights = counts.loop_weight_bytes(CFG, "int8", 4) / 819e9 / 100e-9
    assert reader("loop_weights_roofline")(r) == pytest.approx(
        100.0 * weights)
    attn = counts.attention_bytes(CFG, 4, 3000) / 819e9 / 75e-9
    assert reader("attn_kv_roofline")(r) == pytest.approx(100.0 * attn)
    # A scope outside lib/scopes.py PRECEDENCE passes by its own name.
    assert scopes.ms_per_step(r, ("loop.norm",)) == pytest.approx(5e-6)
    # Another block's configuration: its roofline module has no such
    # function; no trace: nothing, no error.
    other = manifest.cell_files(MAN, "qwen2.5-7b.reasoning")["config"]
    for name in ("loop_weights_roofline", "attn_kv_roofline"):
        assert reader(name)(reading(
            trace=trace, trace_mono=(115.0, 125.0), peaks=PEAKS,
            samples=samples, model=other)) is None
        assert reader(name)(reading()) is None
    # The counter: passes over live row-steps of the window's flight rows;
    # a ring without the columns (the parent's) gives nothing.
    cols = {"host_s": np.ones(3), "loop_passes": np.array([64.0, 48.0, 0.0]),
            "loop_row_steps": np.array([16.0, 12.0, 0.0])}
    monkeypatch.setattr(host_phases, "window_rows", lambda r: cols)
    assert reader("loop_passes_per_token")(reading()) == 4.0
    monkeypatch.setattr(host_phases, "window_rows",
                        lambda r: {"host_s": np.ones(3)})
    assert reader("loop_passes_per_token")(reading()) is None
    monkeypatch.setattr(host_phases, "window_rows", lambda r: None)
    assert reader("loop_passes_per_token")(reading()) is None


def test_the_rehearsal_s_toy_reads_and_refers():
    """The cell cut for a CPU rehearsal: the toy through the program's
    reader (3 layers run 3 times: 9 pool layers), and one short forward of
    the reference and both controls on device-made weights."""
    from benchmark.lib import weights
    from dynamo_tpu.engine.config import EngineConfig
    toy = run.rehearsal_cut(FILES)["config"]
    assert "rehearsal_model" not in toy
    assert (toy["hidden_size"], toy["num_hidden_layers"], toy["vocab_size"],
            toy["total_ut_steps"]) == (64, 3, 64, 3)
    spec = server.model_spec("ouro-toy", toy, "int8")
    assert (spec.loop_passes, spec.pool_layers, spec.sandwich_norm
            ) == (3, 9, True)
    config = EngineConfig(model=spec, page_size=16, num_pages=32)
    params = weights.make_params(spec, weights.runner_mesh(config), 5)
    # The weight maker draws every *_norm leaf as ones and the OUTPUT
    # norms' gains, columns, normal / sqrt(64).
    layers = params["layers"]
    assert float(np.asarray(layers["input_norm"], np.float32).min()) == 1.0
    gains = np.asarray(layers["attn_out_gain"], np.float32)
    assert gains.shape == (3, 64, 1) and 0.08 < gains.std() < 0.17
    module = manifest.load_module("references", "ouro")
    prompt = list(range(40))
    got = module.reference_logprobs(params, spec, prompt, [1, 2, 3])
    layer = module.reference_logprobs(params, spec, prompt, [1, 2, 3],
                                      skip_layer=2)
    whole = module.control_logprobs(params, spec, prompt, [1, 2, 3],
                                    skip_pass="1")
    assert len(got) == 3 and all(v < 0 for v in got)
    assert got != layer and got != whole and layer != whole
