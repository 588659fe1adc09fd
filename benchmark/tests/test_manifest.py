"""BENCHMARK.json against the contract's limits and against the files."""
import inspect
import os
import re

import pytest

from benchmark.lib import manifest, reference, roofline
from benchmark.lib import tokenizer as bench_tok

MAN = manifest.load_manifest()
#: A checkout in miniature whose configuration names all it may name.
FIXTURE = os.path.join(os.path.dirname(__file__), "data", "new_block")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def all_metrics():
    return MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    runs = 2 + 14 * 24  # a full check with every cell the contract allows
    assert (runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    assert MAN["command"][1].startswith(MAN["paths"][0] + "/")


def test_names_and_units():
    names = [m["name"] for m in all_metrics()]
    assert len(set(names)) == len(names)
    for m in all_metrics():
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for entry in MAN["configs"] + MAN["workloads"]:
        assert NAME.match(entry["name"]), entry["name"]
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for cell in MAN["workloads"]:
        assert NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200


def test_every_file_a_cell_names_exists():
    pairs = set()
    for cell in MAN["workloads"]:
        files = manifest.cell_files(MAN, cell["name"])
        manifest.load_module("generators", files["generator"])
        assert files["config"]["chips"] == cell["chips"]
        pairs.add((cell["config"], cell["traffic"]))
    assert len(pairs) == len(MAN["workloads"])
    used = {c["config"] for c in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    for cfg in MAN["configs"]:
        data = manifest.load_json(os.path.join(manifest.ROOT, cfg["file"]))
        assert data["source"] == cfg["source"]
        assert data["reduced"] == cfg["reduced"]


@pytest.mark.parametrize("root", [manifest.ROOT, FIXTURE],
                         ids=["repo", "fixture"])
def test_what_a_configuration_names_exists_and_has_the_signatures(root):
    """``reference`` / ``roofline`` / ``rehearsal_model`` are optional; a
    name is a file with the callables of lib/'s own, never a default."""
    def parameters(fn):
        return list(inspect.signature(fn).parameters)

    for entry in manifest.load_manifest(root)["configs"]:
        config = manifest.load_json(os.path.join(root, entry["file"]))
        judged = reference.for_config(config, root=root)
        assert (judged["module"] == "lib/reference.py") == (
            "reference" not in config)
        assert parameters(judged["logprobs"]) == parameters(
            reference.reference_logprobs)
        assert set(judged["allowed"]) == {"median", "rms", "worst"}
        assert all(0 < v < float("inf") for v in judged["allowed"].values())
        counts, where = roofline.counting(config, root=root)
        assert (where == "lib/roofline.py") == ("roofline" not in config)
        for name in ("decode_step_bytes", "decode_step_flops"):
            assert parameters(getattr(counts, name)) == parameters(
                getattr(roofline, name)) + (
                    ["touched"] if name == "decode_step_bytes"
                    and roofline.takes_touched(counts) else [])
        # A count of touched experts goes to a routed block's module alone.
        assert roofline.takes_touched(counts) == hasattr(
            counts, "expert_layers")
        toy = config.get("rehearsal_model", {})
        assert set(toy) <= set(config), "toy sizes under the public keys"
        for key in ("reference", "roofline"):
            with pytest.raises(manifest.ManifestError,
                               match=f"benchmark/{key}s/no_such.py"):
                manifest.config_module({**config, key: "no_such"}, key,
                                       None, (), root)


def cells_of(metric):
    return set(metric.get("workloads")
               or [c["name"] for c in MAN["workloads"]])


def test_each_per_layer_metric_has_its_reader_and_its_arrow():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e
    for m in MAN["per_layer"]:
        mod = manifest.load_module("layer_metrics", m["name"])
        for key, const in (("name", "NAME"), ("unit", "UNIT"),
                           ("better", "BETTER"), ("layer", "LAYER"),
                           ("moves", "MOVES"), ("source", "SOURCE")):
            assert getattr(mod, const) == m[key], (m["name"], key)
        assert callable(mod.read)
        # The metric it moves is reported wherever this one is.
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
    for cell in MAN["workloads"]:
        mine = manifest.metrics_of(MAN, "end_to_end", cell["name"])
        assert {"setup_s"} < {m["name"] for m in mine}
        assert manifest.metrics_of(MAN, "per_layer", cell["name"])


def test_roofline_shares_are_named_and_in_percent():
    for m in MAN["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_tokenizer_one_word_one_token(tmp_path):
    from dynamo_tpu.llm.model_card import DEFAULT_CHAT_TEMPLATE
    from tokenizers import Tokenizer
    path = bench_tok.write_tokenizer(str(tmp_path / "tok.json"), 4096)
    tok = Tokenizer.from_file(path)
    assert tok.get_vocab_size() == 4096
    ids = [16, 4095, 77, 16]
    assert tok.encode(bench_tok.text_of(ids)).ids == ids
    assert tok.decode(ids, skip_special_tokens=True).split() == [
        "w16", "w4095", "w77", "w16"]
    # Marker ids are words too: a sampled one still counts on the stream.
    assert len(tok.decode([1, 2, 50]).split()) == 3
    assert bench_tok.template_overhead(path, DEFAULT_CHAT_TEMPLATE) == 5
