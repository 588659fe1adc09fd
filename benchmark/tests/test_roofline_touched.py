"""What PR 55 changes of the yardstick, on hand-made data: the floor under
``decode_window_roofline`` takes the count of experts a layer-step TOUCHED
through ONE seam (lib/roofline.py ``decode_step_floor(..., touched=None)``
-> ``rooflines/<name>.py decode_step_bytes(..., touched=None)`` where the
module takes one), the reader hands on the program's own count as
``moe_roofline`` reads it, and ``moe_roofline`` multiplies by the expert
layers the module states. One test a property, one case a cell."""
import inspect
import json

import numpy as np
import pytest

from benchmark.lib import manifest, measure, roofline
from benchmark.lib import trace_reduce as tr

MAN = manifest.load_manifest()
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
#: cell -> (the key its file holds the HELD experts under, expert layers the
#: model has, layers the program's count is a mean over)
ROUTED = {
    "smallthinker-21b-a3b.reasoning": ("moe_num_primary_experts", 24, 24),
    "command-a-plus.reasoning": ("num_experts", 8, 8),
    "deepseek-v3.2-exp.reasoning-long": ("n_routed_experts", 8, 8),
    # The drafting window counts the prediction module's expert layer too.
    "glm-4.7-flash.reasoning": ("n_routed_experts", 46, 47),
    "nemotron-3-nano-30b-a3b.reasoning": ("n_routed_experts", 23, 23),
    "solar-open2-250b.reasoning": ("n_routed_experts", 12, 12),
}
UNROUTED = ("qwen2.5-7b.reasoning", "minicpm-sala-9b.doc-reasoning",
            "ouro-2.6b.reasoning-1k", "falcon-h1-34b.reasoning-long")
#: The ledger's PR 54 lines, change side: rows_per_window, decode_step_ms,
#: decode_window_roofline (over every held expert), and the share of the
#: held experts a layer-step touched (moe_experts_touched_pct in the first
#: cell, moe_held_touched_pct in the others). The ledger keeps no context:
#: it is solved from the share, which also shows that the floor without a
#: count is still the one those lines were read against.
LEDGER_PR54 = {
    "smallthinker-21b-a3b.reasoning": (19.108, 15.507, 85.128, 66.614),
    "command-a-plus.reasoning": (18.721, 15.303, 79.233, 63.434),
    "deepseek-v3.2-exp.reasoning-long": (16.789, 14.425, 73.99, 29.265),
    "glm-4.7-flash.reasoning": (17.716, 18.645, 68.824, 50.689),
    "nemotron-3-nano-30b-a3b.reasoning": (18.904, 16.582, 82.2, 10.385),
    "solar-open2-250b.reasoning": (18.826, 16.736, 81.078, 21.794),
}
#: What ISSUE 55 expected the share to fall to, within 3 points.
EXPECTED_SHARE = {
    "smallthinker-21b-a3b.reasoning": 61, "command-a-plus.reasoning": 60,
    "deepseek-v3.2-exp.reasoning-long": 40, "glm-4.7-flash.reasoning": 46,
    "nemotron-3-nano-30b-a3b.reasoning": 34,
    "solar-open2-250b.reasoning": 38}
ROWS, CONTEXT = 18.0, 20000.0


def config(cell):
    return manifest.cell_files(MAN, cell)["config"]


def counting(cell):
    return roofline.counting(config(cell))[0]


def one_expert(cell):
    counts = counting(cell)
    return counts.stored(counts._sizes(config(cell))["expert"], "int8")


def step_bytes(cell, *more, **kw):
    return counting(cell).decode_step_bytes(config(cell), "int8", 1, ROWS,
                                            CONTEXT, *more, **kw)


def floor(cell, rows=ROWS, context=CONTEXT, **kw):
    return roofline.decode_step_floor(config(cell), "int8", 1, rows, context,
                                      PEAKS, **kw)


# -- the seam: lib/roofline.py and the six routed modules ---------------------------

@pytest.mark.parametrize("cell", ROUTED)
def test_no_count_is_the_call_without_one_to_the_byte(cell):
    cfg, counts = config(cell), counting(cell)
    assert roofline.takes_touched(counts)
    whole = step_bytes(cell)
    assert step_bytes(cell, touched=None) == whole
    assert step_bytes(cell, None) == whole          # the seventh argument
    got, plain = floor(cell, touched=None), floor(cell)
    assert got == plain and got["experts_touched"] is None
    assert got["bytes_seconds"] == whole / 819e9
    assert counts.expert_layers(cfg) == ROUTED[cell][1]


@pytest.mark.parametrize("cell", ROUTED)
def test_every_held_expert_touched_is_no_count(cell):
    held = config(cell)[ROUTED[cell][0]]
    assert counting(cell).experts_read(config(cell), None) == held
    assert step_bytes(cell, touched=held) == step_bytes(cell)
    got = floor(cell, touched=float(held))
    assert got["experts_touched"] == held
    assert got["seconds"] == floor(cell)["seconds"]


@pytest.mark.parametrize("cell", ROUTED)
def test_two_counts_differ_by_their_experts_and_nothing_else(cell):
    """layers x (a - b) x one expert's stored bytes: routers, selection
    biases, shared experts, dense leading layers, attention, state, head
    and pool are counted whole whatever the count."""
    cfg, counts = config(cell), counting(cell)
    held = cfg[ROUTED[cell][0]]
    layers = getattr(counts, "counted_expert_layers",
                     counts.expert_layers)(cfg)
    assert layers == ROUTED[cell][2]
    a, b = 0.75 * held, 0.25 * held
    assert step_bytes(cell, touched=a) - step_bytes(cell, touched=b) == (
        pytest.approx(layers * (a - b) * one_expert(cell), rel=1e-12))
    assert step_bytes(cell) - step_bytes(cell, touched=0.0) == (
        pytest.approx(layers * held * one_expert(cell), rel=1e-12))
    # Operations count a row's picks, never the held experts: untouched.
    assert "touched" not in inspect.signature(
        counts.decode_step_flops).parameters
    assert floor(cell, touched=b)["flops_seconds"] == (
        floor(cell)["flops_seconds"])


@pytest.mark.parametrize("cell", ROUTED)
def test_a_count_is_clipped_to_what_the_chip_holds(cell):
    held = config(cell)[ROUTED[cell][0]]
    assert step_bytes(cell, touched=held + 7.5) == step_bytes(cell)
    assert step_bytes(cell, touched=-3.0) == step_bytes(cell, touched=0.0)
    assert step_bytes(cell, touched=0.0) < step_bytes(cell, touched=1.0)


@pytest.mark.parametrize("cell", UNROUTED)
def test_a_module_that_takes_no_count_is_called_without_one(cell):
    counts = counting(cell)
    assert not roofline.takes_touched(counts)
    assert not hasattr(counts, "expert_layers")
    with pytest.raises(TypeError):      # it would refuse the keyword
        step_bytes(cell, touched=5.0)
    got = floor(cell, touched=5.0)
    assert got["experts_touched"] is None
    assert got == floor(cell)


def context_at(cell, rows, seconds):
    """The live context at which the floor without a count is ``seconds``."""
    lo, hi = 0.0, 1e7
    for _ in range(100):
        mid = (lo + hi) / 2
        if floor(cell, rows, mid)["seconds"] < seconds:
            lo = mid
        else:
            hi = mid
    return lo


@pytest.mark.parametrize("cell", ROUTED)
def test_the_floor_at_the_ledgers_count_lies_under_the_step(cell):
    rows, step_ms, share, pct = LEDGER_PR54[cell]
    held = config(cell)[ROUTED[cell][0]]
    context = context_at(cell, rows, share / 100 * step_ms / 1e3)
    # A context the cell's traffic holds: 10 to 40 thousand live tokens.
    assert 1e4 < context < 4e4
    got = floor(cell, rows, context, touched=pct / 100 * held)
    assert got["bound"] == "bandwidth"
    assert got["seconds"] * 1e3 < step_ms
    assert abs(got["seconds"] * 1e5 / step_ms - EXPECTED_SHARE[cell]) < 3


# -- the readers on a canned Reading -----------------------------------------------------

class FakeRing:
    def __init__(self, columns):
        self.columns = columns

    def between(self, lo, hi):
        keep = (self.columns["t_mono"] >= lo) & (self.columns["t_mono"] <= hi)
        return {"rows": int(keep.sum()), "missed": 0,
                "columns": {k: v[keep] for k, v in self.columns.items()}}


def ring_columns(touched, moe=True):
    """Five windows of 96 layer-steps; the third alone lies in the traced
    seconds [115, 125] and touched ``touched`` experts a layer-step."""
    cols = {"t_mono": np.array([90.0, 110.0, 120.0, 130.0, 155.0]),
            "host_s": np.full(5, 0.01)}
    if moe:
        cols.update(
            moe_layer_steps=np.full(5, 96.0),
            moe_touched=96 * np.array([9e9, 1.0, touched, 2.0, 9e9]),
            moe_load=np.full(5, 96 * 2.0))
    return cols


def traced():
    """Two executions of a 2-step window program, 1,000 ns each: 500 ns a
    step; the expert layers are fusion.3 (300 ns an execution)."""
    mods = [("jit_run_window(7)", 1000.0, 1000.0),
            ("jit_run_window(7)", 3000.0, 1000.0)]
    ops = [(name, base + at, dur) for base in (1000.0, 3000.0)
           for name, at, dur in (("%fusion.1 fusion", 0, 200.0),
                                 ("%fusion.3 fusion", 200, 300.0),
                                 ("%while.9 while", 0, 1000.0))]
    return {"/device:TPU:0": {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}}


OPS_BY_SCOPE = {"%fusion.1": "attn.qkv", "%fusion.3": "mlp+moe.experts",
                "%while.9": None}


def reading(cell, **kw):
    base = dict(records=[], open_loop=False, t0=100.0, t1=151.0, t_end=160.0,
                before={}, after={}, spans=[], emissions={}, prompt_keys={},
                samples=[{"t": 118.0, "rows": 17, "context": 19000.0},
                         {"t": 122.0, "rows": 19, "context": 21000.0},
                         {"t": 140.0, "rows": 30, "context": 90000.0}],
                engine={"decode_window": 2, "quant": "int8"},
                model=config(cell), peaks=PEAKS, metrics_text="",
                trace=traced(), trace_mono=(115.0, 125.0))
    base.update(kw)
    return measure.Reading(**base)


def read_floor(cell, ring, monkeypatch, capsys):
    """decode_window_roofline over a canned Reading: (its value, the keyword
    arguments it gave decode_step_floor, the run's ``floor`` line)."""
    from dynamo_tpu.runtime import flight
    monkeypatch.setattr(flight, "get_recorder", lambda: ring)
    handed = []
    real = roofline.decode_step_floor

    def spy(*args, **kw):
        handed.append(kw)
        return real(*args, **kw)
    monkeypatch.setattr(roofline, "decode_step_floor", spy)
    value = manifest.load_module(
        "layer_metrics", "decode_window_roofline").read(reading(cell))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    monkeypatch.undo()
    return value, handed, line


@pytest.mark.parametrize("cell", ROUTED)
def test_the_reader_hands_on_the_programs_own_count(cell, monkeypatch,
                                                    capsys):
    held = config(cell)[ROUTED[cell][0]]
    count = 0.4 * held
    value, handed, line = read_floor(
        cell, FakeRing(ring_columns(count)), monkeypatch, capsys)
    # The traced seconds' row alone: got[0] / got[2], as moe_roofline has it.
    assert [kw for kw in handed if kw] == [{"touched": pytest.approx(count)}]
    want = floor(cell, touched=count)
    assert value == pytest.approx(100 * want["seconds"] / 500e-9)
    assert value < 100 * floor(cell)["seconds"] / 500e-9
    assert line["line"] == "floor"
    assert (line["rows"], line["context_tokens"]) == (ROWS, CONTEXT)
    assert line["experts_touched"] == pytest.approx(count)
    assert line["floor_ms"] == pytest.approx(want["seconds"] * 1e3)
    assert line["floor_every_held_expert_ms"] == pytest.approx(
        floor(cell)["seconds"] * 1e3)
    assert line["decode_step_ms"] == pytest.approx(500e-6)
    # The share is the parent's share times the two floors' ratio.
    assert value == pytest.approx(
        100 * line["floor_every_held_expert_ms"] / line["decode_step_ms"]
        * line["floor_ms"] / line["floor_every_held_expert_ms"])


@pytest.mark.parametrize("cell", (*ROUTED, *UNROUTED))
def test_without_counters_the_reader_hands_nothing(cell, monkeypatch, capsys):
    """A program without the columns (the parent of PR 28, a dense block);
    and a cell whose module takes no count reads what it read whatever a
    ring holds."""
    rings = [FakeRing(ring_columns(3.0, moe=False)), object()]
    if cell in UNROUTED:
        rings.append(FakeRing(ring_columns(3.0)))
    for ring in rings:
        value, handed, line = read_floor(cell, ring, monkeypatch, capsys)
        assert value == pytest.approx(100 * floor(cell)["seconds"] / 500e-9)
        assert line["experts_touched"] is None
        assert line["floor_ms"] == line["floor_every_held_expert_ms"]
        if cell in ROUTED or not hasattr(ring, "columns") \
                or "moe_touched" not in ring.columns:
            assert not any(handed)
    # Untraced, or no peaks (a CPU rehearsal): no share at all.
    read = manifest.load_module("layer_metrics", "decode_window_roofline").read
    assert read(reading(cell, trace=None, trace_mono=None)) is None
    assert read(reading(cell, peaks=None)) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", ROUTED)
def test_moe_roofline_counts_the_expert_layers_the_module_states(
        cell, monkeypatch):
    from benchmark.lib import scopes
    from dynamo_tpu.runtime import flight
    cfg, counts = config(cell), counting(cell)
    held = cfg[ROUTED[cell][0]]
    count = 0.4 * held
    monkeypatch.setattr(flight, "get_recorder",
                        lambda: FakeRing(ring_columns(count)))
    monkeypatch.setattr(scopes, "_ops_by_scope", lambda r: OPS_BY_SCOPE)
    r = reading(cell)
    r._by_scope = scopes.seconds_by_scope(r.trace, OPS_BY_SCOPE)
    read = manifest.load_module("layer_metrics", "moe_roofline").read
    # 300 ns an execution of 2 steps: 150 ns a step of expert layers.
    seconds = (ROUTED[cell][1]
               * counts.expert_layer_bytes(cfg, "int8", count) / 819e9)
    assert read(r) == pytest.approx(100 * seconds / 150e-9)
    # A product over every held expert reads at most touched / held of 100.
    every = (ROUTED[cell][1]
             * counts.expert_layer_bytes(cfg, "int8", held) / 819e9)
    assert seconds / every < 0.4 + 0.01
    assert cell in manifest.find_named(MAN["per_layer"], "moe_roofline",
                                       "metric")["workloads"]
