"""A configuration of a block kind the dense reference does not cover, taken
through every lookup on the CPU: the fixture tree data/new_block/ (a toy of
the Mixtral-style expert layer the program already has) gives the ModelSpec
through the program's reader, device-made weights with int8 on and off, the
program's own forward against the fixture's reference inside the fixture's
tolerance, a skipped layer outside it, and the fixture's roofline in
``decode_step_floor``. This file compiles a toy model (some tens of
seconds); nothing it reads is a device number."""
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest, reference, roofline, server, weights
from benchmark.lib.lengths import prompt_ids

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "new_block")
FILES = run.rehearsal_cut(manifest.cell_files(
    manifest.load_manifest(FIXTURE), "toy-moe.few-callers", root=FIXTURE))
CONFIG = FILES["config"]
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
pytestmark = pytest.mark.usefixtures("run_dir")


def program_greedy(params, spec, prompt: list[int], n_gen: int,
                   page: int = 16) -> tuple[list[int], list[float]]:
    """Greedy tokens and their logprobs from the program's own forward
    (engine/model.py prefill_forward, then decode_forward a token) over a
    little cache of its own; page 0 is the scratch page."""
    import jax
    import jax.numpy as jnp
    from dynamo_tpu.engine.model import decode_forward, prefill_forward
    n_prompt, bucket = len(prompt), 64
    assert n_prompt <= bucket
    pages = -(-(bucket + n_gen) // page)
    kv = jnp.zeros((spec.num_layers, spec.num_kv_heads, pages + 1, page,
                    spec.head_dim), jnp.bfloat16)
    table = np.arange(1, pages + 1, dtype=np.int32)[None]
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :n_prompt] = prompt
    positions = np.minimum(np.arange(bucket), n_prompt - 1)[None].astype(
        np.int32)
    prefill = jax.jit(lambda p, k, v, t, po, pt, sl: prefill_forward(
        p, spec, k, v, t, po, pt, sl))
    decode = jax.jit(lambda p, k, v, t, po, pt, sl: decode_forward(
        p, spec, k, v, t, po, pt, sl))
    logits, k, v = prefill(params, kv, kv + 0, tokens, positions,
                           table[:, :bucket // page],
                           np.asarray([n_prompt], np.int32))
    out, logprobs = [], []
    for i in range(n_gen):
        row = jax.nn.log_softmax(logits[0].astype(jnp.float32))
        out.append(int(jnp.argmax(row)))
        logprobs.append(float(row[out[-1]]))
        at = n_prompt + i  # position of the token fed next
        logits, k, v = decode(params, k, v, np.asarray(out[-1:], np.int32),
                              np.asarray([at], np.int32), table,
                              np.asarray([at + 1], np.int32))
    return out, logprobs


@pytest.mark.parametrize("quant", ["int8", None], ids=["int8", "bf16"])
def test_served_checked_and_rooflined_through_what_the_file_names(quant):
    import jax
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.quant import QTensor

    spec = server.model_spec("toy-moe", CONFIG, quant)
    assert (spec.num_experts, spec.num_experts_per_tok) == (4, 2)

    # lib/weights.py follows the program's tree: expert leaves and all.
    mesh = weights.runner_mesh(EngineConfig(model=spec))
    params = weights.make_params(spec, mesh, seed=2_147_483_659)
    layers = params["layers"]
    assert "w_gate" not in layers
    assert layers["moe_gate"].shape == (2, 128, 4)
    assert layers["moe_gate"].dtype == jax.numpy.bfloat16  # never quantised
    experts = layers["moe_w_down"]
    assert isinstance(experts, QTensor) == (quant == "int8")
    assert (experts.q if quant else experts).shape == (2, 4, 256, 128)

    judged = reference.for_config(CONFIG, root=FIXTURE)
    served, full, skipped = [], [], []
    for k in range(4):
        prompt = prompt_ids(7, 900_000 + k, 59, spec.vocab_size, 16)
        tokens, logprobs = program_greedy(params, spec, prompt, 16)
        served += logprobs
        full += judged["logprobs"](params, spec, prompt, tokens)
        skipped += judged["logprobs"](params, spec, prompt, tokens,
                                      skip_layer=spec.num_layers - 1)
    verdict = reference.judge(served, full, judged["allowed"])
    assert verdict["ok"], verdict
    assert verdict["median_nats"] > 0.0  # two computations, not one
    faulty = reference.judge(served, skipped, judged["allowed"])
    assert not faulty["ok"]
    assert faulty["median_nats"] > 10 * judged["allowed"]["median"]

    # The dense reference would not have judged it, and says what to do.
    with pytest.raises(NotImplementedError):
        reference.reference_logprobs(params, spec, prompt, tokens)


def test_floor_is_counted_by_the_named_roofline():
    counts, where = roofline.counting(CONFIG, root=FIXTURE)
    floor = roofline.decode_step_floor(CONFIG, "int8", 1, 4, 400, PEAKS,
                                       root=FIXTURE)
    assert floor["counted_by"] == where == "rooflines/topk_moe.py"
    assert floor["bytes_seconds"] == pytest.approx(
        counts.decode_step_bytes(CONFIG, "int8", 1, 4, 400) / 819e9)
    assert floor["flops_seconds"] == pytest.approx(
        counts.decode_step_flops(CONFIG, 1, 4, 400) / 197e12)
    # The dense reckoning of the same dictionary counts ONE expert a layer.
    dense = {k: v for k, v in CONFIG.items() if k != "roofline"}
    assert (roofline.decode_step_floor(dense, "int8", 1, 4, 400, PEAKS)
            ["bytes_seconds"] < 0.7 * floor["bytes_seconds"])


def test_the_fixture_roofline_by_hand():
    counts, _ = roofline.counting(CONFIG, root=FIXTURE)
    # hidden 128, width 256, 2 layers, 4 heads and 2 KV heads of 32, 4
    # experts with 2 a token, vocabulary 2048; int8, 4 rows, 400 tokens.
    assert counts.experts_touched(CONFIG, 1) == 2.0
    touched = 4 * (1 - 0.5 ** 4)
    assert counts.experts_touched(CONFIG, 4) == touched == 3.75
    attention = (2 * 128 * 128 + 2 * 128 * 64) + 4 * (128 + 64 + 64 + 128)
    expert = 3 * 128 * 256 + 4 * (256 + 256 + 128)
    layer = attention + 2 * 128 * 2 + 128 * 4 * 2 + touched * expert
    head = 128 * 2048 + 4 * 2048
    kv = 2 * 2 * 2 * 32 * 2
    expected = 2 * layer + head + 128 * 2 + 4 * 128 + (400 + 4) * kv
    assert counts.decode_step_bytes(CONFIG, "int8", 1, 4, 400) == expected
    per_row = 2 * (2 * 128 * 128 + 2 * 128 * 64 + 128 * 4
                   + 2 * 3 * 128 * 256) + 128 * 2048
    assert (counts.decode_step_flops(CONFIG, 1, 4, 400)
            == 2 * per_row * 4 + 4 * 2 * 4 * 32 * 400)


def test_the_repos_manifest_names_nothing_of_the_fixture():
    man = manifest.load_manifest()
    assert "toy-moe" not in {c["name"] for c in man["configs"]}
    assert all("tests/" not in c["file"] for c in man["configs"])
