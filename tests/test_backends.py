"""engine/backends.py: the ONE record of who runs what, and the ONE place a
program's labels are assembled.

(a) the choice as a table: what a runner observes -> the record, or the
refusal's words; (b) for a tiny model of each of the seven block kinds the
benchmark serves, the names its programs are published under (the record's
labels, the compile registry's, /debug/perf's) against ONE literal each, so
the names the benchmark prints are pinned where a PR can see them.
"""

import dataclasses
import json
import os
import tempfile
from types import SimpleNamespace

import pytest

from dynamo_tpu.engine import model, perf
from dynamo_tpu.engine.backends import XLA, Backends, choose
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.engine import TPUEngine

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmark")

# -- (a) the choice ------------------------------------------------------------

#: What a token leaves in the pool, as backends.choose reads a spec.
POOLS = {
    "kv": dict(latent=False, index_topk=0, recurrent=False),
    "kv 16 x 1": dict(latent=False, index_topk=0, recurrent=False,
                      num_heads=16, num_kv_heads=16),
    "indexed": dict(latent=True, index_topk=2048, recurrent=False),
    "latent": dict(latent=True, index_topk=0, recurrent=False),
    "recurrent": dict(latent=False, index_topk=0, recurrent=True),
    "blocks": dict(latent=False, index_topk=0, recurrent=True,
                   compressed_keys=True),
}
TABLE = 64      # max_pages_per_seq of the stub: the latent readers' table


def on(platform, mesh=1, **kw):
    """The record of a runner on ``platform`` over ``mesh`` devices, but
    for what the row names."""
    return Backends(**{**dict(experts_whole=mesh == 1,
                              interpret=platform == "cpu"), **kw})


@pytest.mark.parametrize(
    "platform, mesh, head_dim, quant_kv, pool, asked, want", [
        # K and V pages: the kernel on one TPU device at head_dim 128.
        ("tpu", 1, 128, None, "kv", "auto",
         on("tpu", attention="pallas", kv_commit="in_place")),
        ("tpu", 1, 128, "int8", "kv", "auto", on("tpu", attention="pallas")),
        # ... whose chunk turn follows the heads (attention.reader_turn):
        # 16 KV heads of one query row take the turn over all heads.
        ("tpu", 1, 128, None, "kv 16 x 1", "auto",
         on("tpu", attention="pallas", kv_commit="in_place",
            kv_reader_turn="heads")),
        ("tpu", 1, 128, "int8", "kv 16 x 1", "auto",
         on("tpu", attention="pallas")),
        ("tpu", 1, 128, None, "kv 16 x 1", "xla", on("tpu")),
        ("cpu", 1, 128, None, "kv 16 x 1", "pallas",
         on("cpu", attention="pallas", kv_commit="in_place",
            kv_reader_turn="heads")),
        ("tpu", 1, 64, None, "kv", "auto", on("tpu")),
        ("tpu", 1, 96, None, "kv", "auto", on("tpu")),
        ("tpu", 4, 128, None, "kv", "auto", on("tpu", 4)),
        ("cpu", 1, 128, None, "kv", "auto", on("cpu")),
        ("cpu", 8, 64, None, "kv", "auto", on("cpu", 8)),
        ("tpu", 1, 128, None, "kv", "xla", on("tpu")),
        # A requested kernel runs (the CPU interprets it) or is an error.
        ("cpu", 1, 128, None, "kv", "pallas",
         on("cpu", attention="pallas", kv_commit="in_place")),
        ("cpu", 1, 64, None, "kv", "pallas", on("cpu", attention="pallas")),
        ("tpu", 1, 96, None, "kv", "pallas", "needs head_dim 128, or a "
         "head_dim that packs into 128 lanes"),
        ("tpu", 4, 128, None, "kv", "pallas", "runs on one device: the "
         "kernel has no partitioning rule"),
        ("cpu", 2, 64, None, "kv", "pallas", "attention_backend='pallas' "
         "runs on one device"),
        ("tpu", 1, 128, None, "kv", "flash", "attention_backend must be "
         "'auto', 'xla' or 'pallas', got 'flash'"),
        # Latent entries and their index keys: the heads' width decides
        # nothing; the indexer follows the reader; in place on one TPU
        # device under EITHER reader.
        ("tpu", 1, 192, None, "indexed", "auto",
         on("tpu", attention="pallas", index="pallas", kv_commit="in_place",
            table=TABLE)),
        ("tpu", 1, 96, None, "indexed", "auto",
         on("tpu", attention="pallas", index="pallas", kv_commit="in_place",
            table=TABLE)),
        ("tpu", 1, 192, None, "indexed", "xla",
         on("tpu", index="xla", kv_commit="in_place", table=TABLE)),
        ("cpu", 1, 192, None, "indexed", "auto",
         on("cpu", index="xla", table=TABLE)),
        ("tpu", 4, 192, None, "indexed", "auto",
         on("tpu", 4, index="xla", table=TABLE)),
        ("tpu", 1, 192, "int8", "indexed", "auto",
         on("tpu", index="xla", table=TABLE)),
        ("tpu", 1, 192, "int8", "indexed", "pallas", "walks a latent pool "
         "of bfloat16 entries; no kernel reads int8 latent pages"),
        # ... without an indexer: no such name.
        ("tpu", 1, 192, None, "latent", "auto",
         on("tpu", attention="pallas", kv_commit="in_place", table=TABLE)),
        ("cpu", 1, 192, None, "latent", "pallas",
         on("cpu", attention="pallas", table=TABLE)),
        # Recurrent layers beside a pool of K and V pages: the state's
        # kernel beside the Pallas reader on a TPU, XLA's everywhere else.
        ("tpu", 1, 128, None, "recurrent", "auto",
         on("tpu", attention="pallas", kv_commit="in_place", ssm="kernel")),
        ("tpu", 1, 128, None, "recurrent", "xla", on("tpu", ssm="xla")),
        ("cpu", 1, 128, None, "recurrent", "auto", on("cpu", ssm="xla")),
        ("cpu", 1, 128, None, "recurrent", "pallas",
         on("cpu", attention="pallas", kv_commit="in_place", ssm="xla")),
        # ... whose attention reads chosen blocks of keys: who scores the
        # compressed-key array's stripes follows the reader too.
        ("tpu", 1, 128, None, "blocks", "auto",
         on("tpu", attention="pallas", index="pallas", kv_commit="in_place",
            ssm="kernel")),
        ("tpu", 1, 128, None, "blocks", "xla",
         on("tpu", index="xla", ssm="xla")),
        ("cpu", 1, 128, None, "blocks", "auto",
         on("cpu", index="xla", ssm="xla")),
        ("cpu", 1, 128, None, "blocks", "pallas",
         on("cpu", attention="pallas", index="pallas", kv_commit="in_place",
            ssm="xla")),
    ])
def test_the_record_is_decided_from_what_a_runner_observes(
        platform, mesh, head_dim, quant_kv, pool, asked, want):
    config = SimpleNamespace(attention_backend=asked, page_size=16,
                             max_pages_per_seq=TABLE, spec_decode=None)
    spec = SimpleNamespace(**{**dict(head_dim=head_dim, num_experts=0,
                                     compressed_keys=False, num_heads=28,
                                     num_kv_heads=4), **POOLS[pool]})
    if isinstance(want, str):
        with pytest.raises(ValueError) as refused:
            choose(config, spec, platform, mesh, quant_kv)
        assert want in str(refused.value)
        return
    got = choose(config, spec, platform, mesh, quant_kv)
    assert got == want and hash(got) == hash(want)
    # What the record binds follows from its fields alone.
    kernel = got.attention == "pallas"
    assert (got.kv_reader(window=True) is not None) == kernel
    assert (model.kv_attention(got, window=True)
            is model.paged_window_attention_xla) == (not kernel)
    for bound in (got.kv_reader(False), got.block_reader(),
                  got.stripe_scorer(), *got.latent_readers()):
        assert (bound is None) == (not kernel)
        if kernel:
            assert bound.keywords["interpret"] == (platform == "cpu")


def test_the_default_record_is_xla_s_on_any_platform():
    """What the references and the plain-forward tests get by naming no
    record: XLA's gather, the scatter, the masked product, no kernel."""
    assert XLA == Backends() == Backends(
        attention="xla", index=None, kv_commit="scatter", ssm=None,
        experts_whole=False, interpret=False, table=None)
    assert XLA.kv_reader(True) is XLA.block_reader() is None
    assert XLA.latent_readers() == (None, None)
    assert model.expert_product(10 ** 6, XLA) == "masked"
    assert XLA.labels("prefill", "masked") == {}   # a dense block: no label


def test_the_reader_s_turn_is_named_only_where_it_is_taken():
    """``kv_reader_turn`` is a label of the window program where the
    kernel's turn runs over all heads at once, and of no other program."""
    heads = Backends(attention="pallas", kv_reader_turn="heads")
    assert heads.labels("decode_window")["kv_reader_turn"] == "heads"
    assert "kv_reader_turn" not in heads.labels("prefill")
    assert "kv_reader_turn" not in Backends(attention="pallas").labels(
        "decode_window")


@pytest.mark.parametrize("kv_heads, want", [(8, "heads"), (2, None)])
def test_debug_perf_names_the_reader_s_turn_where_it_is_taken(kv_heads,
                                                             want):
    """/debug/perf and the window program's labels carry ``kv_reader_turn``
    for a runner whose kernel takes the turn over all heads (8 KV heads of
    one query row at head_dim 128) and do not have the key for one that
    multiplies a KV head at a time."""
    spec = ModelSpec(name="turn", vocab_size=128, hidden_size=1024,
                     intermediate_size=128, num_layers=1, num_heads=8,
                     num_kv_heads=kv_heads)
    engine = TPUEngine(EngineConfig(
        model=spec, num_pages=16, max_pages_per_seq=4, max_num_seqs=2,
        attention_backend="pallas", page_size=16))
    try:
        assert engine.runner.backends.kv_reader_turn == want
        assert engine.perf_status().get("kv_reader_turn") == want
        assert engine.runner._get_window(4, 4)._labels.get(
            "kv_reader_turn") == want
    finally:
        engine.stop()


# -- (b) the names the benchmark prints ------------------------------------------

OFF = "off (recurrent state has no snapshot)"
#: configuration -> the labels of its window programs and of its prefill
#: programs at 32 and at 256 rows, on the CPU under "auto" (XLA's reader;
#: the experts whole on the one device, so a window's step and 32 rows walk
#: the touched experts and 256 rows take the grouped product), as the cell
#: launches it.
NAMES = {
    "qwen2.5-7b-int8": {
        "decode_window": {"attention_backend": "xla",
                          "kv_commit_backend": "scatter", "page_size": 16,
                          "draft": "none"},
        "prefill": ({}, {})},
    "smallthinker-21b-a3b-int8": {
        "decode_window": {"attention_backend": "xla",
                          "kv_commit_backend": "scatter", "page_size": 16,
                          "draft": "none", "expert_product": "touched"},
        "prefill": ({"expert_product": "touched"},
                    {"expert_product": "grouped"})},
    "command-a-plus-ep8-int8": {
        "decode_window": {"attention_backend": "xla",
                          "kv_commit_backend": "scatter", "page_size": 16,
                          "draft": "none", "expert_product": "touched"},
        "prefill": ({"expert_product": "touched"},
                    {"expert_product": "grouped"})},
    "deepseek-v3.2-exp-ep16-int8": {
        "decode_window": {"attention_backend": "xla",
                          "kv_commit_backend": "scatter", "page_size": 16,
                          "index_backend": "xla", "draft": "none",
                          "expert_product": "touched"},
        "prefill": ({"expert_product": "touched"},
                    {"expert_product": "grouped"})},
    "glm-4.7-flash-ep4-int8": {
        "decode_window": {"attention_backend": "xla",
                          "kv_commit_backend": "scatter", "page_size": 16,
                          "draft": "mtp", "expert_product": "touched"},
        "prefill": ({"expert_product": "touched"},
                    {"expert_product": "grouped"})},
    "nemotron-3-nano-30b-a3b-ep4-int8": {
        "decode_window": {"attention_backend": "xla",
                          "kv_commit_backend": "scatter", "page_size": 16,
                          "draft": "none", "expert_product": "touched",
                          "ssm_state": "float32", "prefix_reuse": OFF,
                          "ssm_backend": "xla"},
        "prefill": ({"expert_product": "touched", "ssm_state": "float32",
                     "prefix_reuse": OFF},
                    {"expert_product": "grouped", "ssm_state": "float32",
                     "prefix_reuse": OFF})},
    "minicpm-sala-9b-int8": {
        "decode_window": {"attention_backend": "xla",
                          "kv_commit_backend": "scatter", "page_size": 16,
                          "index_backend": "xla", "draft": "none",
                          "ssm_state": "float32", "prefix_reuse": OFF,
                          "ssm_backend": "xla"},
        "prefill": ({"ssm_state": "float32", "prefix_reuse": OFF},) * 2},
}
#: ... and the record's for the configuration AS PUBLISHED on its cell's
#: chip (one v5e under "auto", at the page "auto" derives there): what the
#: `server` line and the registry say in a benchmark run.
ON_THE_CHIP = {
    "qwen2.5-7b-int8": {"attention_backend": "pallas",
                        "kv_commit_backend": "in_place", "page_size": 64},
    "smallthinker-21b-a3b-int8": {"attention_backend": "pallas",
                                  "kv_commit_backend": "in_place",
                                  "page_size": 64},
    "command-a-plus-ep8-int8": {"attention_backend": "pallas",
                                "kv_commit_backend": "in_place",
                                "page_size": 32},
    "deepseek-v3.2-exp-ep16-int8": {"attention_backend": "pallas",
                                    "kv_commit_backend": "in_place",
                                    "page_size": 64,
                                    "index_backend": "pallas"},
    "glm-4.7-flash-ep4-int8": {"attention_backend": "pallas",
                               "kv_commit_backend": "in_place",
                               "page_size": 64},
    "nemotron-3-nano-30b-a3b-ep4-int8": {"attention_backend": "pallas",
                                         "kv_commit_backend": "in_place",
                                         "page_size": 128,
                                         "ssm_backend": "kernel"},
    "minicpm-sala-9b-int8": {"attention_backend": "pallas",
                             "kv_commit_backend": "in_place",
                             "page_size": 128, "index_backend": "pallas",
                             "ssm_backend": "kernel"},
}


def cell_config(name: str, rehearsal: bool, **kw) -> EngineConfig:
    """The configuration as its cell launches it: as published, or
    ``rehearsal``: its rehearsal model (benchmark/run.py ``rehearsal_cut``),
    small enough to build."""
    with open(os.path.join(BENCH, "configs", name + ".json"),
              encoding="utf-8") as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "rehearsal", "tiny.json"),
              encoding="utf-8") as fh:
        toy = json.load(fh)["model"]
    cut = config.pop("rehearsal_model", None) or toy
    if rehearsal:
        config.update(cut)
    # The launcher's own words (the cell's sizes are the chip's: a
    # rehearsal builds at the sizes below).
    launch = {k: v for k, v in config["launch"].items()
              if k in ("spec_decode", "spec_k")}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(config, fh)
        spec = ModelSpec.from_hf_config(path)
    return EngineConfig(**{**dict(
        model=spec, num_pages=64, max_pages_per_seq=16, max_num_seqs=4,
        prefill_buckets=(32, 256), **launch), **kw})


@pytest.mark.parametrize("name", sorted(NAMES))
def test_the_names_a_block_kind_s_programs_are_published_under(name):
    want = NAMES[name]
    perf.get_registry().reset()
    engine = TPUEngine(cell_config(name, rehearsal=True))
    try:
        runner = engine.runner
        # Built, never compiled: a program carries its labels from birth.
        programs = [runner._get_window(4, 8), runner._get_prefill(32, 1, False),
                    runner._get_prefill(256, 1, False)]
        assert [fn._labels for fn in programs] == [
            want["decode_window"], *want["prefill"]]
        rows = engine.config.max_num_seqs * (
            2 if want["decode_window"]["draft"] == "mtp" else 1)
        assert runner.backends.labels("decode_window", model.expert_product(
            rows, runner.backends)) == want["decode_window"]
        # The compile registry: each label with the values its programs
        # carry.
        status = engine.perf_status()
        listed = status["compiles"]["programs"]
        assert listed["decode_window"]["labels"] == {
            k: [v] for k, v in want["decode_window"].items()}
        assert listed["prefill"]["labels"] == {
            k: list(dict.fromkeys(p[k] for p in want["prefill"]))
            for k in want["prefill"][0]}
        # /debug/perf: the same names at its top, and where a block has
        # them under "ssm" and "moe".
        window = want["decode_window"]
        assert {k: status[k] for k in (
            "attention_backend", "kv_commit_backend", "index_backend",
            "draft", "page_size")} == {
                "attention_backend": window["attention_backend"],
                "kv_commit_backend": window["kv_commit_backend"],
                "index_backend": window.get("index_backend"),
                "draft": window["draft"], "page_size": window["page_size"]}
        assert runner.attention_backend == window["attention_backend"]
        if "ssm_backend" in window:
            assert {k: status["ssm"][k] for k in (
                "backend", "state_dtype", "prefix_reuse")} == {
                    "backend": window["ssm_backend"],
                    "state_dtype": window["ssm_state"],
                    "prefix_reuse": window["prefix_reuse"]}
        else:
            assert "ssm" not in status
        if "expert_product" in window:
            assert status["moe"]["expert_product"] == {
                "decode_window": ["touched"],
                "prefill": ["touched", "grouped"]}
        else:
            assert "moe" not in status
    finally:
        engine.stop()
    # On the cell's chip, at the published widths: the record alone
    # (nothing is built for a device that is not there).
    config = cell_config(name, rehearsal=False, page_size="auto")
    config = dataclasses.replace(
        config, page_size=config.resolve_page_size("tpu"))
    chip = choose(config, config.model, "tpu", 1, None).labels(
        "decode_window")
    assert {k: chip[k] for k in ON_THE_CHIP[name]} == ON_THE_CHIP[name]
    assert set(chip) == set(want["decode_window"]) - {"expert_product"}
