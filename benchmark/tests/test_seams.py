"""What a configuration's file may name, and what it gets when it names
nothing: the ModelSpec through the program's reader, the reference, the
roofline, the rehearsal model. The defaults are today's code, so the one
dense cell reads what it read before PR 27; the fixture under
data/new_block/ names all four (test_new_block.py takes it further)."""
import dataclasses
import inspect
import os

import pytest

from benchmark import run
from benchmark.lib import manifest, reference, roofline, scopes, server

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "new_block")
MAN = manifest.load_manifest()
DENSE = manifest.cell_files(MAN, "qwen2.5-7b.reasoning")
ROUTED = manifest.cell_files(manifest.load_manifest(FIXTURE),
                             "toy-moe.few-callers", root=FIXTURE)

# What lib/server.py's hand-written key map gave at commit c2678d8, written
# out: the same spec is the same programs and the same compile-cache keys.
SPEC_7B = dict(
    name="qwen2.5-7b-int8", vocab_size=152064, hidden_size=3584,
    intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
    head_dim=128, rope_theta=1000000.0, rms_norm_eps=1e-06, qkv_bias=True,
    tie_word_embeddings=False, max_position_embeddings=32768, num_experts=0,
    num_experts_per_tok=2, quant="int8")
SPEC_7B_REHEARSED = dict(
    SPEC_7B, vocab_size=2048, hidden_size=128, intermediate_size=352,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
    max_position_embeddings=2048)


pytestmark = pytest.mark.usefixtures("run_dir")


def spec_of(files: dict):
    config = files["config"]
    return server.model_spec(files["cell"]["config"], config,
                             config["launch"].get("quant"))


@pytest.mark.parametrize("files, expected", [
    (DENSE, SPEC_7B), (run.rehearsal_cut(DENSE), SPEC_7B_REHEARSED)],
    ids=["as-served", "rehearsed"])
def test_model_spec_is_what_the_key_map_gave(files, expected):
    assert dataclasses.asdict(spec_of(files)) == expected


def test_model_spec_maps_no_key_itself():
    source = inspect.getsource(server.model_spec)
    assert "from_hf_config" in source
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "vocab_size", "rope_theta"):
        assert key not in source


def test_model_spec_reads_what_the_program_reads():
    assert spec_of(ROUTED).num_experts == 8
    toy = spec_of(run.rehearsal_cut(ROUTED))
    assert (toy.num_experts, toy.num_experts_per_tok, toy.hidden_size,
            toy.num_layers, toy.quant) == (4, 2, 128, 2, "int8")


def test_rehearsal_model_replaces_tiny_json_model_and_nothing_else():
    tiny = manifest.load_json(os.path.join(manifest.BENCH, "rehearsal",
                                           "tiny.json"))
    dense, routed = run.rehearsal_cut(DENSE), run.rehearsal_cut(ROUTED)
    for key, value in tiny["model"].items():
        assert dense["config"][key] == value
    own = ROUTED["config"]["rehearsal_model"]
    for key, value in own.items():
        assert routed["config"][key] == value
    assert "rehearsal_model" not in routed["config"]
    assert routed["config"]["model_type"] == "mixtral"  # not tiny.json's
    assert routed["config"]["reference"] == "topk_moe"
    for cut in (dense, routed):
        for key, value in tiny["launch_extra"].items():
            assert cut["config"]["launch"][key] == value
        assert cut["config"]["launch"]["quant"] == "int8"
        assert cut["params"]["clients"] <= tiny["max_clients"]
    assert routed["params"]["prompt_tokens"]["value"] == 12
    # The cell's own files are left as they were.
    assert DENSE["config"]["hidden_size"] == 3584
    assert "rehearsal_model" in ROUTED["config"]


def test_a_configuration_that_names_nothing_gets_todays_code():
    judged = reference.for_config(DENSE["config"])
    assert judged["module"] == "lib/reference.py"
    assert judged["logprobs"] is reference.reference_logprobs
    assert judged["allowed"] == {"median": 0.02, "rms": 0.06, "worst": 0.25}
    assert (reference.MEDIAN_NATS, reference.RMS_NATS,
            reference.WORST_NATS) == (0.02, 0.06, 0.25)
    module, where = roofline.counting(DENSE["config"])
    assert module is roofline and where == "lib/roofline.py"


def test_a_configuration_that_names_them_gets_its_own():
    config = ROUTED["config"]
    judged = reference.for_config(config, root=FIXTURE)
    assert judged["module"] == "references/topk_moe.py"
    assert judged["logprobs"].__module__.endswith("references_topk_moe")
    assert judged["allowed"] == {"median": 0.05, "rms": 0.6, "worst": 10.0}
    module, where = roofline.counting(config, root=FIXTURE)
    assert where == "rooflines/topk_moe.py" and module is not roofline


@pytest.mark.parametrize("key, missing", [
    ("reference", "benchmark/references/no_such_block.py"),
    ("roofline", "benchmark/rooflines/no_such_block.py")])
def test_an_unknown_name_is_an_error_that_names_the_file(key, missing):
    config = {**DENSE["config"], key: "no_such_block"}
    with pytest.raises(manifest.ManifestError, match=missing):
        if key == "reference":
            reference.for_config(config)
        else:
            roofline.decode_step_floor(config, "int8", 1, 18, 19800,
                                       {"hbm_gbps": 819.0,
                                        "bf16_tflops": 197.0})


def test_a_named_file_without_the_callables_is_an_error(tmp_path):
    os.makedirs(tmp_path / "benchmark" / "rooflines")
    (tmp_path / "benchmark" / "rooflines" / "half.py").write_text(
        "def decode_step_bytes(cfg, quant, tp, rows, context_tokens):\n"
        "    return 1.0\n")
    with pytest.raises(manifest.ManifestError, match="decode_step_flops"):
        roofline.counting({"roofline": "half"}, root=str(tmp_path))


def test_judge_takes_the_allowed_values():
    served = [-1.0, -2.0, -0.5, -1.5]
    near = [x - 0.03 for x in served]
    verdict = reference.judge(served, near)
    assert not verdict["ok"]  # median 0.03 over the dense block's 0.02
    assert verdict["allowed_nats"] == reference.ALLOWED_NATS
    loose = {"median": 0.05, "rms": 0.6, "worst": 10.0}
    verdict = reference.judge(served, near, loose)
    assert verdict["ok"] and verdict["allowed_nats"] == loose
    assert not reference.judge(served, [x - 0.7 for x in served], loose)["ok"]
    assert not reference.judge([0.1] * 4, [0.1] * 4, loose)["ok"]  # no logprob


def test_dense_reference_refuses_an_expert_layer_by_name():
    spec = spec_of(run.rehearsal_cut(ROUTED))
    with pytest.raises(NotImplementedError, match="references/<name>.py"):
        reference.reference_logprobs({}, spec, [1, 2], [3])


def test_a_scope_outside_precedence_passes_by_its_own_name():
    assert scopes.primary("moe.experts") == "moe.experts"
    assert scopes.primary("moe.router+moe.experts") == "moe.router"
    assert scopes.primary("moe.experts+mlp") == "mlp"


# -- PR 58: a cell replays one trace over ONE draw of the weights --------------

class _Launched(Exception):
    """The fake server's: everything up to the launch has run."""


@pytest.fixture
def seams_given(monkeypatch):
    """The seeds ``server.Seams`` is built with; no server is started."""
    given = []

    class Seams:
        def __init__(self, name, spec, seed, shapes):
            self.seed, self.timings = seed, {}
            given.append(seed)

        def install(self):
            pass

        restore = install

    def no_server(argv):
        raise _Launched

    monkeypatch.setattr(server, "Seams", Seams)
    monkeypatch.setattr(server, "Server", no_server)
    return given


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_run_serves_the_cells_one_draw_whatever_the_seed(
        seed, seams_given, capsys):
    import argparse
    import asyncio
    import json

    from benchmark.lib import weights
    args = argparse.Namespace(seed=seed, seconds=8, trace=0)
    with pytest.raises(_Launched):
        asyncio.run(run.run_cell(args, run.rehearsal_cut(DENSE), MAN, None))
    assert seams_given == [weights.CELL_WEIGHTS_SEED] == [58]
    launch = [json.loads(line) for line in capsys.readouterr().out.split("\n")
              if '"launch"' in line]
    assert len(launch) == 1
    assert (launch[0]["seed"], launch[0]["weights_seed"]) == (seed, 58)
    # One value in use, one constant: no flag, key or variable selects it.
    source = inspect.getsource(run)
    # the docstring, run_cell's weights, timed_words' words
    assert source.count("CELL_WEIGHTS_SEED") == 3
    assert "weights" not in " ".join(vars(run.parse_args(
        ["--workload", "x"])))


def test_a_builders_tool_draws_the_weights_from_its_own_seed(seams_given):
    import argparse
    import asyncio

    from benchmark import long_prompt
    args = argparse.Namespace(seed=4_100_000_007, prompt_tokens=40, decode=4,
                              control=[])
    with pytest.raises(_Launched):
        asyncio.run(long_prompt.check(args, run.rehearsal_cut(DENSE)))
    assert seams_given == [4_100_000_007]


def test_the_cells_draw_is_one_set_of_weights():
    import jax
    import numpy as np
    from dynamo_tpu.engine.config import EngineConfig

    from benchmark.lib import weights
    spec = spec_of(run.rehearsal_cut(ROUTED))  # a router among its leaves
    mesh = weights.runner_mesh(EngineConfig(model=spec))
    here, again, other = (
        jax.tree_util.tree_leaves_with_path(weights.make_params(
            spec, mesh, seed))
        for seed in (weights.CELL_WEIGHTS_SEED, weights.CELL_WEIGHTS_SEED, 59))
    differ = set()
    for (path, leaf), (_, same), (_, drawn_anew) in zip(here, again, other):
        assert np.array_equal(leaf, same)
        if not np.array_equal(leaf, drawn_anew):
            differ.add(jax.tree_util.keystr(path))
    # The router is what the rule is about; norm scales are one under any seed.
    assert "['layers']['moe_gate']" in differ
    assert {"['embed'].q", "['lm_head'].q", "['layers']['wq'].q",
            "['layers']['moe_w_up'].q"} <= differ
