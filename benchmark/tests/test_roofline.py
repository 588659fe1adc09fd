"""roofline.py against values worked by hand from the published sizes."""
import pytest

from benchmark.lib import manifest, roofline

Q7 = manifest.load_json(manifest.BENCH + "/configs/qwen2.5-7b-int8.json")
# Qwen2.5-14B-Instruct config.json; the tp=4 cell is not in BENCHMARK.json yet
# (PERF.md, Open questions), its arithmetic is kept ready.
Q14 = {"model_type": "qwen2", "hidden_size": 5120, "intermediate_size": 13824,
       "num_hidden_layers": 48, "num_attention_heads": 40,
       "num_key_value_heads": 8, "vocab_size": 152064,
       "tie_word_embeddings": False}


def test_7b_int8_weight_bytes_one_chip():
    # Per layer: wq, wo 3584x3584; wk, wv 3584x512; gate, up, down
    # 3584x18944. One byte a value, a float32 scale per output channel.
    values = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert values == 233_046_016
    scales = 4 * (3584 + 512 + 512 + 3584 + 18944 + 18944 + 3584)
    norms = 2 * 3584 * 2
    biases = (28 + 2 * 4) * 128 * 2
    layer = values + scales + norms + biases
    assert layer == 233_268_224
    head = 3584 * 152064 + 4 * 152064
    expected = 28 * layer + head + 3584 * 2 + 1 * 3584
    assert roofline.weight_bytes_per_step(Q7, "int8", 1, 1) == expected
    assert expected == 7_077_126_656
    # 32 sequences read 32 embedding rows instead of one.
    assert (roofline.weight_bytes_per_step(Q7, "int8", 1, 32) - expected
            == 31 * 3584)


def test_14b_bf16_weight_bytes_per_chip_at_tp4():
    values = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 13824
    layer_sharded = 2 * values / 4 + (40 + 16) * 128 * 2 / 4
    layer = layer_sharded + 2 * 5120 * 2
    head = 2 * 5120 * 152064 / 4
    expected = 48 * layer + head + 5120 * 2 + 1 * 5120 * 2 / 4
    assert roofline.weight_bytes_per_step(Q14, None, 4, 1) == expected
    assert expected == 6_996_480_512  # 7.0 GB a chip, a step


def test_kv_bytes_per_token():
    assert roofline.kv_bytes_per_token(Q7) == 2 * 28 * 4 * 128 * 2 == 57_344
    assert roofline.kv_bytes_per_token(Q14, tp=4) == 2 * 48 * 8 * 128 * 2 / 4


def test_decode_step_floor_says_which_bound():
    peaks = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
    floor = roofline.decode_step_floor(Q7, "int8", 1, 32, 32 * 1500, peaks)
    data = (roofline.weight_bytes_per_step(Q7, "int8", 1, 32)
            + (32 * 1500 + 32) * 57_344)
    assert floor["bound"] == "bandwidth"
    assert floor["seconds"] == pytest.approx(data / 819e9)
    assert 0.011 < floor["seconds"] < 0.013
    ops = roofline.decode_step_flops(Q7, 1, 32, 32 * 1500)
    weights = 28 * 233_046_016 + 3584 * 152064
    assert ops == 2 * weights * 32 + 4 * 28 * 28 * 128 * 32 * 1500
    # A batch of 512 sequences would be bound by the matrix unit instead.
    assert roofline.decode_step_floor(Q7, "int8", 1, 512, 512, peaks)[
        "bound"] == "compute"


def test_default_floor_of_the_dense_cell_is_the_number_of_pr_26():
    # qwen2.5-7b.reasoning in its traced seconds: about 18 rows, 19,800
    # live tokens. What lib/roofline.py gave at commit c2678d8, before a
    # configuration could name a roofline of its own; Q7 names none.
    peaks = roofline.peaks_of("TPU v5 lite", manifest.load_json(
        manifest.BENCH + "/peaks.json"))
    data = 7_077_126_656 + 17 * 3584 + (19800 + 18) * 57_344
    assert roofline.decode_step_bytes(Q7, "int8", 1, 18, 19800) == data
    assert data == 8_213_630_976
    assert roofline.decode_step_flops(Q7, 1, 18, 19800) == 262_478_168_064
    assert roofline.decode_step_floor(Q7, "int8", 1, 18, 19800, peaks) == {
        "seconds": 0.010028853450549451, "bound": "bandwidth",
        "bytes_seconds": 0.010028853450549451,
        "flops_seconds": 0.0013323764876345178,
        "counted_by": "lib/roofline.py", "experts_touched": None}


def test_unknown_device_kind_is_an_error():
    table = manifest.load_json(manifest.BENCH + "/peaks.json")
    assert roofline.peaks_of("TPU v5 lite", table)["hbm_gbps"] == 819.0
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks_of("TPU v9", table)
