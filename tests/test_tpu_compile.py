"""The Pallas decode kernel compiles for a TPU v5e — without the chip.

The TPU's compiler is installed with jax and compiles for a chip that is
described, not attached (section 2.3 of the on-chip-measurement guide).
Interpret mode (tests/test_attention_pallas.py, test_kv_quant.py) checks the
kernel's results; it cannot see what Mosaic refuses. PR 12's int8-KV kernel
passed every interpret test and had never compiled: Mosaic refused the scale
reshape. These compiles, at the real widths of the two head sizes the repo
serves, guard every later PR at no chip time. Nothing runs here, so nothing
is said about results or speed.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.engine.attention import (paged_decode_attention_pallas,
                                         paged_window_attention_pallas)
from dynamo_tpu.engine.backends import Backends, choose
from dynamo_tpu.engine.kv_quant import QuantKV

#: A runner's record on one TPU device, as the expert layer reads it.
WHOLE = Backends(experts_whole=True)

PAGE = 16


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the module is skipped where the topology
    cannot be described (no libtpu in the installation)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any reason means "not here"
        pytest.skip(f"TPU topology cannot be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again): keep these out of it."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# (head_dim, kv heads, q heads per kv head, layers): qwen2.5-0.5b, llama-3-8b,
# and the benchmark cell's qwen2.5-7b.
WIDTHS = {"qwen2.5-0.5b": (64, 2, 7, 24), "llama-3-8b": (128, 8, 4, 32),
          "qwen2.5-7b": (128, 4, 7, 28)}
# SmallThinker-21B-A3B's attention is qwen2.5-7b's geometry over 24 layers.
WIDTHS["smallthinker-21b-a3b"] = (128, 4, 7, 24)
# Command A+'s share: 128 query heads over 8 KV heads (16 query rows a KV
# head), 8 layers, over a stream of 4096 (not heads x head_dim).
WIDTHS["command-a-plus"] = (128, 8, 16, 8)
HIDDEN = {"command-a-plus": 4096}
#: The page "auto" derives where the kernel reads the pool: 64 KB a copy.
DERIVED = {"qwen2.5-7b": 64, "smallthinker-21b-a3b": 64, "llama-3-8b": 32,
           "command-a-plus": 32}


def derived_page(model) -> int:
    """What page_size="auto" resolves to for the model's KV geometry where
    the kernel reads the pool on one TPU device."""
    from dynamo_tpu.engine.config import EngineConfig, ModelSpec
    d, nkv, qpk, layers = WIDTHS[model]
    return EngineConfig(
        model=ModelSpec(hidden_size=nkv * qpk * d, num_heads=nkv * qpk,
                        num_kv_heads=nkv, num_layers=layers),
        page_size=PAGE).resolve_page_size("tpu")


def _shapes(one, model, quantized, b=40, maxp=64, page=PAGE):
    d, nkv, qpk, layers = WIDTHS[model]
    maxp = maxp * PAGE // page
    pool = (layers, nkv, b * maxp + 16, page, d)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if quantized:
        cache = QuantKV(s(pool, jnp.int8), s(pool[:-1], jnp.float32))
    else:
        cache = s(pool, jnp.bfloat16)
    q = s((b, nkv * qpk, d), jnp.bfloat16)
    self_kv = s((b, nkv, d), jnp.bfloat16)
    return (q, cache, cache, s((), jnp.int32), s((b, maxp), jnp.int32),
            s((b,), jnp.int32)), self_kv, qpk


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("model", list(WIDTHS))
def test_decode_kernel_compiles_for_v5e(v5e, model, quantized):
    head, self_kv, qpk = _shapes(v5e, model, quantized)
    compiled = jax.jit(
        lambda *a: paged_decode_attention_pallas(*a, q_per_kv=qpk)
    ).lower(*head, self_kv, self_kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("model, page", [
    ("qwen2.5-7b", 64), ("smallthinker-21b-a3b", 64), ("llama-3-8b", 32),
    ("command-a-plus", 32), ("qwen2.5-7b", 128)])
def test_decode_kernel_compiles_at_the_derived_page(v5e, model, page):
    """The page "auto" derives where the kernel reads the pool (64 tokens
    at 4 KV heads of 128, 32 at 8), and the largest a page may be."""
    if page != 128:
        assert derived_page(model) == page
    head, self_kv, qpk = _shapes(v5e, model, False, page=page)
    compiled = jax.jit(
        lambda *a: paged_decode_attention_pallas(*a, q_per_kv=qpk)
    ).lower(*head, self_kv, self_kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("model, steps", [
    ("qwen2.5-0.5b", 32), ("qwen2.5-7b", 8), ("command-a-plus", 8),
    ("llama-3-8b", 32)])
def test_window_kernel_compiles_for_v5e(v5e, model, steps, quantized):
    """The variant the serving window program calls: kernel over the
    cache-resident history, three chunk buffers deep (at 4 and 8 KV heads of
    128 and packed, over bf16 and int8 pages); the in-window buffer merged
    in XLA."""
    head, self_kv, qpk = _shapes(v5e, model, quantized)
    b, nkv, d = self_kv.shape
    win = jax.ShapeDtypeStruct((nkv, b, steps, d), jnp.bfloat16,
                               sharding=v5e)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda *a: paged_window_attention_pallas(*a, q_per_kv=qpk)
    ).lower(*head, win, win, step, self_kv, self_kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page", [PAGE, 64])
def test_windowed_window_kernel_compiles_for_v5e(v5e, page):
    """The variant a model with sliding-window layers runs: a fourth
    prefetched vector, the first token each row still sees, and a walk that
    starts at its chunk."""
    head, self_kv, qpk = _shapes(v5e, "smallthinker-21b-a3b", False,
                                 page=page)
    b, nkv, d = self_kv.shape
    win = jax.ShapeDtypeStruct((nkv, b, 4, d), jnp.bfloat16, sharding=v5e)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)
    lo = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda *a, lo: paged_window_attention_pallas(*a, q_per_kv=qpk, lo=lo)
    ).lower(*head, win, win, step, self_kv, self_kv, lo=lo).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("table", [512, 8192])
def test_latent_kernel_compiles_for_v5e(v5e, table):
    """The reader of a latent pool at the DeepSeek-V3.2 cell's widths: 128
    absorbed query heads against entries of 640 lanes of which 512 are the
    value, a page of 64 (one copy of 80 KB a page, eight pages a chunk), 32
    rows, nine layers, at the table the short check runs (512 tokens) and
    the launcher's limit (8,192): the mask block of the whole table, three
    chunk buffers and the [128, 512] accumulator fit the kernel's VMEM."""
    from dynamo_tpu.engine.attention import (latent_history_pallas,
                                             pages_per_chunk)
    assert pages_per_chunk(64, 1, 640 // 2, 2) == 8
    b, page = 32, 64

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(lambda *a: latent_history_pallas(
        *a, scale=0.135, rank=512)).lower(
        s((b, 128, 640), jnp.bfloat16),
        s((9, 1, 5742, page, 640), jnp.bfloat16), s((), jnp.int32),
        s((b, table // page), jnp.int32), s((b,), jnp.int32),
        s((b, table), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("table", [512, 8192])
def test_latent_index_kernel_compiles_for_v5e(v5e, table):
    """The indexer of a latent pool at the DeepSeek-V3.2 cell's widths: 64
    index heads of 128 against index keys of 128 lanes, a page of 64 (one
    copy of 16 KB a page, 32 pages a turn), 32 rows, nine layers, bound to
    the launcher's limit as a runner binds it: three chunk buffers of 512
    KB, every row's head weights and a row's scores of the whole table fit
    the kernel's VMEM."""
    from dynamo_tpu.engine.attention import latent_index_pallas
    b, page = 32, 64

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    lowered = jax.jit(lambda *a: latent_index_pallas(*a, table=128)).lower(
        s((b, 64, 128), jnp.bfloat16), s((b, 64), jnp.float32),
        s((9, 1, 5742, page, 128), jnp.bfloat16), s((), jnp.int32),
        s((b, table // page), jnp.int32), s((b,), jnp.int32))
    assert lowered.out_info.shape == (b, table)
    assert "tpu_custom_call" in lowered.compile().as_text()


def _expert_layer(v5e, spec, h, i, held, routed, shared=0):
    """(x maker, leaves) of one expert layer of int8 stacks for the
    described chip; a two-matrix expert ("relu2") has no gate leaf."""
    from dynamo_tpu.engine.quant import QTensor

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def q(shape):
        return QTensor(s(shape, jnp.int8),
                       s((*shape[:-2], 1, shape[-1]), jnp.float32))

    lp = {"moe_gate": s((h, routed), jnp.bfloat16),
          "moe_w_gate": q((held, h, i)), "moe_w_up": q((held, h, i)),
          "moe_w_down": q((held, i, h)),
          **({f"shared_w_{k}": q((shared, *d)) for k, d in (
              ("gate", (h, i)), ("up", (h, i)), ("down", (i, h)))}
             if shared else {})}
    if spec.ffn_act == "relu2":
        lp = {k: v for k, v in lp.items() if "_w_gate" not in k}
    return (lambda rows: s((rows, h), jnp.bfloat16)), lp


@pytest.mark.parametrize("rows", [32, 64, 128, 512, 4096],
                         ids=["a decode step", "a verify step",
                              "at the threshold", "a prompt",
                              "a prefill group"])
def test_smallthinker_expert_layer_compiles_for_v5e(v5e, rows):
    """The expert layer at the published widths (64 int8 experts of 2560 x
    768) on both sides of MOE_DENSE_MAX_ROWS: the walk over the touched
    experts up to it (a decode step's 32 rows and a verify step's 64: ONE
    custom call, Mosaic's, and no temporary of a matrix's size) and the
    kernel over pairs sorted by expert above it (two custom calls), whose
    operations XLA counts no more (a custom call's are its own) and whose
    temporaries are the sorted pairs', never an [experts, rows, width]
    intermediate; the product over every resident expert under the gate
    mask where the record says the experts may be partitioned."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.engine.backends import XLA
    from dynamo_tpu.engine.config import SmallThinkerSpec
    spec = SmallThinkerSpec(
        hidden_size=2560, intermediate_size=768, num_layers=4, num_heads=28,
        num_kv_heads=4, head_dim=128, num_experts=64, num_experts_per_tok=6,
        moe_intermediate_size=768, quant="int8")
    x, lp = _expert_layer(v5e, spec, 2560, 768, 64, 64)
    chosen = 2 * rows * 6 * 3 * 2560 * 768

    def compiled(record):
        c = jax.jit(lambda x, lp: model.ffn_block(
            x, lp, spec, router_in=x, backends=record)).lower(
                x(rows), lp).compile()
        return (c.as_text().count("tpu_custom_call"),
                c.cost_analysis()["flops"],
                c.memory_analysis().temp_size_in_bytes)

    kernels, flops, temp = compiled(WHOLE)
    if rows > model.MOE_DENSE_MAX_ROWS:
        assert model.expert_product(rows, WHOLE) == "grouped"
        assert kernels == 2 and flops < 0.1 * chosen, (kernels, flops)
        # The pairs' rows gathered, their gated unit, their outputs twice.
        assert temp < (
            rows * 6 * (2560 * 2 + 768 * 2 + 2 * 2560 * 4) * 1.2 + (1 << 20))
        return
    assert model.expert_product(rows, WHOLE) == "touched"
    assert kernels == 1 and flops < 0.1 * chosen, (kernels, flops)
    assert temp < 1 << 20, temp     # the gates, the walk, the output
    assert model.expert_product(rows, XLA) == "masked"
    kernels, flops, _ = compiled(XLA)
    assert kernels == 0 and flops > 64 / 6 * 0.9 * chosen, (flops, chosen)


#: The six routed cells' expert shapes as one chip holds them: (hidden,
#: expert width, experts held, matrices an expert, activation).
CELL_EXPERTS = {"smallthinker": (2560, 768, 64, 3, "relu"),
                "command-a-plus": (4096, 4096, 16, 3, "silu"),
                "deepseek-v3.2": (7168, 2048, 16, 3, "silu"),
                "glm-4.7-flash": (2048, 1536, 16, 3, "silu"),
                "nemotron-3-nano": (2688, 1856, 32, 2, "relu2"),
                "solar-open2": (4096, 1280, 40, 3, "silu")}


@pytest.mark.parametrize("rows", [32, 64], ids=["a decode step",
                                                "a verify step"])
@pytest.mark.parametrize("cell", list(CELL_EXPERTS))
def test_the_walk_compiles_at_the_six_cells_widths_for_v5e(v5e, cell, rows):
    """``experts.touched_product`` over int8 stacks of three layers at each
    routed cell's expert shape: Mosaic compiles it within
    experts.VMEM_LIMIT_BYTES (an expert's three matrices whole at 2,560 x
    768; tiles of the expert's width where they do not fit: 512 of 4,096,
    256 of 2,048 under a hidden size of 7,168; a two-matrix expert's 1,856
    whole, its way up as the chip holds it), ONE custom call, and the
    stacks reach it where they lie: no temporary."""
    from dynamo_tpu.engine import experts
    h, i, e, n_w, act = CELL_EXPERTS[cell]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ws = (*[s((3, e, h, i), jnp.int8)] * (n_w - 1), s((3, e, i, h), jnp.int8))
    scales = (*[s((3, e, 1, i), jnp.float32)] * (n_w - 1),
              s((3, e, 1, h), jnp.float32))
    compiled = jax.jit(lambda *a: experts.touched_product(
        *a, act=act)).lower(
            s((rows, h), jnp.bfloat16), s((rows, e), jnp.float32), ws, scales,
            s((), jnp.int32), s((e,), jnp.int32), s((), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("whole", [True, False],
                         ids=["experts whole", "experts sliced a layer"])
def test_a_scanned_expert_layer_reads_the_stack_where_it_lies(v5e, whole):
    """Inside a program's layer scan the kernel takes the expert stacks over
    ALL layers and the layer's index (``scan_layers(whole_experts=True)``,
    what prefill_forward asks for above MOE_DENSE_MAX_ROWS): sliced a layer
    ahead of a custom call, a layer's three matrices are COPIED (two buffers
    of 64 x 2,560 x 768 bytes in the program's temporaries; XLA fuses such a
    slice into its own products alone)."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.engine.config import SmallThinkerSpec
    spec = SmallThinkerSpec(
        hidden_size=2560, intermediate_size=768, num_layers=3, num_heads=28,
        num_kv_heads=4, head_dim=128, num_experts=64, num_experts_per_tok=6,
        moe_intermediate_size=768, quant="int8")
    x, lp = _expert_layer(v5e, spec, 2560, 768, 64, 64)
    stacked = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (3, *a.shape), a.dtype, sharding=v5e), lp)

    def body(x, lp):
        return x + model.ffn_block(x, lp, spec, router_in=x,
                                   backends=WHOLE), None

    compiled = jax.jit(lambda x, lps: model.scan_layers(
        body, x, lps, spec, whole_experts=whole)[0]).lower(
            x(512), stacked).compile()
    matrix = 64 * 2560 * 768
    copied = compiled.memory_analysis().temp_size_in_bytes >= 2 * matrix
    assert copied != whole
    assert compiled.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("widths", ["command", "glm"])
@pytest.mark.parametrize("rows", [32, 4096], ids=["a decode step",
                                                  "a prefill group"])
def test_a_shares_expert_layer_compiles_for_v5e(v5e, rows, widths):
    """An expert layer told its share at the published widths as one chip
    holds it (Command A+: a router over 128, 16 int8 experts of 4096 x 4096
    held, 4 shared; GLM-4.7-Flash: a router over 64, 16 of 2048 x 1536 held
    from the sixteenth on, 1 shared): the walk over the held
    experts it touched at a decode step's rows (ONE custom call: a choice
    held elsewhere touches nothing here); above MOE_DENSE_MAX_ROWS the
    kernel over the pairs sorted by held expert (a share TAKES it: pairs
    held elsewhere sort behind the last group and are never visited), and
    the shared experts' mean as XLA's product either way."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.engine.config import Cohere2MoeSpec
    h, i, routed, k, shared, first = {
        "command": (4096, 4096, 128, 8, 4, 0),
        "glm": (2048, 1536, 64, 4, 1, 16)}[widths]
    spec = Cohere2MoeSpec(
        hidden_size=h, intermediate_size=i, num_layers=4, num_heads=16,
        num_kv_heads=8, head_dim=128, num_experts=16, num_experts_per_tok=k,
        moe_intermediate_size=i, num_routed_experts=routed,
        first_expert=first, num_shared_experts=shared, quant="int8")
    x, lp = _expert_layer(v5e, spec, h, i, 16, routed, shared)
    compiled = jax.jit(lambda x, lp: model.ffn_block(
        x, lp, spec, backends=WHOLE)).lower(x(rows), lp).compile()
    flops = compiled.cost_analysis()["flops"]
    grouped = rows > model.MOE_DENSE_MAX_ROWS
    assert model.expert_product(rows, WHOLE) == (
        "grouped" if grouped else "touched")
    assert compiled.as_text().count("tpu_custom_call") == 1 + grouped
    # The shared experts over every row; the held ones in a kernel
    # (uncounted) on either side.
    every = 2 * 3 * h * i * rows * shared
    assert 0.9 * every < flops < 1.2 * every, (flops, every)
    assert compiled.memory_analysis().temp_size_in_bytes < 5 << 28


@pytest.mark.parametrize("rows", [256, 4096],
                         ids=["a prompt", "a prefill group"])
def test_a_two_matrix_expert_layer_reads_its_stacks_as_the_chip_holds_them(
        v5e, rows):
    """A share of two-matrix relu2 experts at Nemotron-3-Nano's widths (32
    int8 experts held of a router over 128; up [2,688, 1,856], 14.5 lane
    tiles wide; down [1,856, 2,688]) above MOE_DENSE_MAX_ROWS: two custom
    calls that Mosaic compiles within experts.VMEM_LIMIT_BYTES (up: ONE
    stack with its activation, whole-width tiles of W^T; down: tiles of
    896), and nothing in the optimised program has a stack's shape but the
    arguments and their bitcasts. The chip holds ``up`` with 2,688 minor
    (the TPU's default layout of such a shape): handed to the kernel as
    [.., K, N] it is copied whole first (experts.lies_turned)."""
    from dynamo_tpu.engine import experts, model
    from dynamo_tpu.engine.config import Cohere2MoeSpec
    h, i, held = 2688, 1856, 32
    spec = Cohere2MoeSpec(
        hidden_size=h, intermediate_size=i, num_layers=4, num_heads=32,
        num_kv_heads=2, head_dim=128, num_experts=held,
        num_experts_per_tok=6, moe_intermediate_size=i,
        num_routed_experts=128, ffn_act="relu2", quant="int8")
    x, lp = _expert_layer(v5e, spec, h, i, held, 128)
    assert experts.lies_turned(h, i) and not experts.lies_turned(i, h)
    assert model.expert_product(rows, WHOLE) == "grouped"
    compiled = jax.jit(lambda x, lp: model.ffn_block(
        x, lp, spec, backends=WHOLE)).lower(x(rows), lp).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for stack in ((h, i), (i, h)):
        for shape in ((held, *stack), (1, held, *stack)):
            assert pool_sized_ops(text, shape) == [], shape
    # The pairs' rows gathered, their unit, their outputs twice.
    assert compiled.memory_analysis().temp_size_in_bytes < (
        rows * 6 * (h * 2 + i * 2 + 2 * h * 4) * 1.2 + (1 << 20))


# -- the decode window program: what it does to the KV pool --------------------

#: Result kinds that move no pool: the entry's arguments, the loop that
#: carries the pool through its steps untouched, and tuple plumbing.
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def pool_sized_ops(text: str, pool: tuple) -> list[tuple[str, str]]:
    """(name, kind) of every instruction of an optimised HLO module whose
    result holds an array of the pool's shape and that is neither plumbing
    nor a kernel call aliased to its operand (written in place)."""
    import re
    shape = "[" + ",".join(map(str, pool)) + "]"
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%?[\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not m or shape not in m.group(2) or m.group(3) in _PLUMBING:
            continue
        if m.group(3) == "custom-call" and "output_to_operand_aliasing" in line:
            continue
        out.append((m.group(1), m.group(3)))
    return out


def _window_program(one, model, commit, pool_tokens=24000, rows=32, window=8,
                    page=PAGE, platform="tpu", mesh_size=1, quant_kv=None):
    """The runner's own decode window program, lowered for the described
    chip: a ModelRunner that places nothing (no device to hold an array),
    with the cell's attention geometry and depth and a narrow MLP and
    vocabulary (they never touch the pool, and keep the compile short).
    ``commit`` None: what backends.choose picks (the XLA side of
    config.pool_access: another platform, a mesh, a packed head).
    Returns (lowered, pool shape)."""
    from dynamo_tpu.engine.config import EngineConfig, ModelSpec
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.runner import PK_PREFIX, ModelRunner
    d, nkv, qpk, layers = WIDTHS[model]
    spec = ModelSpec(name=model, vocab_size=1024,
                     hidden_size=HIDDEN.get(model, nkv * qpk * d),
                     intermediate_size=1024, num_layers=layers,
                     num_heads=nkv * qpk, num_kv_heads=nkv, head_dim=d)
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    pages = pool_tokens // page
    runner.config = EngineConfig(model=spec, page_size=page, num_pages=pages,
                                 max_num_seqs=rows)
    assert runner.config.max_model_len == 8192
    table = runner.config.max_pages_per_seq // 4
    runner.quant_kv, runner.lora = quant_kv, None
    runner._window_cache = {}
    runner.backends = choose(runner.config, spec, platform, mesh_size,
                             quant_kv)
    assert (runner.backends.attention, runner.backends.kv_commit) == (
        ("xla", "scatter") if commit is None else ("pallas", "in_place"))
    if commit is not None:      # "scatter": steer the twin, same reader
        runner.backends = dataclasses.replace(runner.backends,
                                              kv_commit=commit)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(lambda shape: s(shape, jnp.bfloat16),
                          param_shapes(spec),
                          is_leaf=lambda x: isinstance(x, tuple))
    pool = (layers, nkv, pages, page, d)
    cache = (QuantKV(s(pool, jnp.int8), s(pool[:-1], jnp.float32))
             if quant_kv else s(pool, jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    lowered = runner._get_window(window, table).lower(
        params, cache, cache,
        s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype))
    return lowered, pool


CELLS = [("qwen2.5-7b", 8), ("smallthinker-21b-a3b", 4)]


@pytest.mark.parametrize("page", [PAGE, "derived"])
@pytest.mark.parametrize("model, window", CELLS + [("command-a-plus", 8)])
def test_window_program_commits_in_place_for_v5e(v5e, model, window, page):
    """The window program of the benchmark cells' geometry, pools donated,
    at the page "auto" derives for them (64 tokens at 4 KV heads, 32 at 8:
    the first cell on that side of the rule) and at 16: a loop of
    steps that read the pool through the attention kernel, then the
    commit. Nothing in the optimised program has the pool's shape but the
    arguments, the loop's carry and the commit kernel aliased to them: no
    copy (not the scatter's four, not a defensive one before the aliased
    call), no transpose, no scatter."""
    if page == "derived":
        page = derived_page(model)
        assert page == DERIVED[model]
    lowered, pool = _window_program(v5e, model, "in_place", window=window,
                                    page=page)
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # the reader and the commit
    assert "output_to_operand_aliasing" in text
    assert pool_sized_ops(text, pool) == []


def lowered_text(lowered) -> str:
    """The lowered module with each Mosaic kernel's body (MLIR bytecode in
    the custom call's backend_config, which carries the checkout's path and
    source lines) printed without positions."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body,
                  lowered.as_text())


def digest(lowered) -> str:
    import hashlib
    return hashlib.sha256(lowered_text(lowered).encode()).hexdigest()[:16]


#: sha256 of lowered_text of the window programs below, kernel side, as PR 33
#: lowers them (the reader's copies run three buffers deep from a fetch
#: cursor; until then 988aad9f87818035 and 2a965d7c6ecbfe81, PR 31's
#: parent's). A PR that changes the window program on purpose lowers them
#: again and replaces these.
KERNEL_SIDE_AT_16 = {"qwen2.5-7b": "76873333d324d04b",
                     "smallthinker-21b-a3b": "5059697b68ad77c8"}


@pytest.mark.parametrize("model, window", CELLS)
def test_window_program_at_an_explicit_page_of_16_is_the_parent_s(
        v5e, model, window):
    """A derived page changes what is chosen, not the program at 16: with
    page_size=16 given, the reader's chunks, the commit's schedule (four
    prefetched vectors, whole pages) and everything around them lower to
    the text they lowered to before a page could be anything else."""
    lowered, _ = _window_program(v5e, model, "in_place", window=window)
    assert digest(lowered) == KERNEL_SIDE_AT_16[model]


#: The same of the window programs on the XLA side of config.pool_access, as
#: the PARENT of PR 33 lowered them: nothing that reads the pool through the
#: gather may move when the kernel's side does.
XLA_SIDE = {
    "the CPU under auto": (dict(platform="cpu"), "adf2608560ac8010"),
    "a mesh of four": (dict(mesh_size=4), "adf2608560ac8010"),
    "head_dim 64": (dict(model="qwen2.5-0.5b"), "7467e907cda3f83e"),
    "an int8 pool on a mesh": (dict(mesh_size=4, quant_kv="int8"),
                               "964e27d79521bfe5"),
}


@pytest.mark.parametrize("case", list(XLA_SIDE))
def test_window_program_on_the_xla_side_is_the_parent_s(v5e, case):
    """Where the gather reads the pool (another platform, any mesh, a
    packed head, and an int8 pool there) the window program lowers to the
    parent's text: the pipeline's depth is the kernel's alone."""
    kwargs, want = XLA_SIDE[case]
    kwargs = {"model": "qwen2.5-7b", **kwargs}
    lowered, _ = _window_program(v5e, commit=None, **kwargs)
    assert digest(lowered) == want


def test_the_pool_guard_sees_the_scatter_s_copies(v5e):
    """The same check on the same program with kv_quant.scatter_tokens as
    its commit FAILS: XLA's scatter wants the pool in another layout than
    the kernel reads, and converts both pools in and out."""
    lowered, pool = _window_program(v5e, "qwen2.5-7b", "scatter")
    found = pool_sized_ops(lowered.compile().as_text(), pool)
    assert [kind for _, kind in found].count("copy") >= 4, found


def test_latent_window_program_commits_in_place_for_v5e(v5e):
    """The window program of a latent pool at the DeepSeek-V3.2 cell's
    widths (entries of 640 lanes, index keys of 128, one page table; 128
    heads, 64 index heads, 2,048 keys kept; a leading dense layer and two
    expert layers, narrow feed-forwards), pools donated, at the page "auto"
    derives (64) and a table of 4,096 tokens: the kernel walks the entries'
    live pages, XLA's gather reads whole pages of index keys from the
    row-major pool as it lies and the commit kernel rewrites the touched
    rows of both widths, so nothing in the optimised program has either
    pool's shape but the arguments and the commit aliased to them."""
    from dynamo_tpu.engine.config import DeepseekV32Spec, EngineConfig
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.runner import PK_PREFIX, ModelRunner
    spec = DeepseekV32Spec(
        name="latent", vocab_size=1024, hidden_size=7168,
        intermediate_size=512, num_layers=3, num_heads=128, num_kv_heads=128,
        head_dim=192, rms_norm_eps=1e-6, num_experts=4,
        num_experts_per_tok=8, moe_intermediate_size=256,
        num_routed_experts=64, num_shared_experts=1, first_k_dense=1,
        n_group=8, topk_group=4, routed_scaling_factor=2.5,
        rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0))
    assert spec.kv_entry == (1, (640, 128))
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    rows, window, pages = 32, 8, 1500
    runner.config = EngineConfig(model=spec, num_pages=pages,
                                 max_num_seqs=rows)
    page = runner.config.resolve_page_size("tpu")
    assert page == 64
    runner.config = EngineConfig(model=spec, page_size=page, num_pages=pages,
                                 max_num_seqs=rows)
    table = runner.config.max_pages_per_seq // 2
    runner.quant_kv, runner.lora = None, None
    runner._window_cache = {}
    runner.backends = choose(runner.config, spec, "tpu", 1, None)
    assert (runner.backends.attention, runner.backends.kv_commit) == (
        "pallas", "in_place")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree.map(lambda shape: s(shape, jnp.bfloat16),
                          param_shapes(spec),
                          is_leaf=lambda x: isinstance(x, tuple))
    pools = [(3, 1, pages, page, width) for width in (640, 128)]
    key = jax.eval_shape(lambda: jax.random.key(0))
    lowered = runner._get_window(window, table).lower(
        params, *(s(pool, jnp.bfloat16) for pool in pools),
        s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype))
    # ONE kernel for both layer scans, and for every table (the widest).
    assert lowered.as_text().count("func.func private @_latent_flash") == 1
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # the reader and the commit
    assert "output_to_operand_aliasing" in text
    for pool in pools:
        assert pool_sized_ops(text, pool) == []


def _glm_runner(v5e, spec_decode, rows=32, pages=1200, layers=3):
    """A ModelRunner that places nothing, at GLM-4.7-Flash's attention
    geometry (20 heads of 192 | 64, v 256, q rank 768; entries of 640 lanes
    and NO second array) with a dense layer, ``layers`` - 1 expert layers,
    the prediction module, narrow feed-forwards and a small vocabulary."""
    from dynamo_tpu.engine.config import DeepseekV32Spec, EngineConfig
    from dynamo_tpu.engine.runner import ModelRunner
    spec = DeepseekV32Spec(
        name="glm", vocab_size=1024, hidden_size=2048,
        intermediate_size=512, num_layers=layers, num_heads=20,
        num_kv_heads=20, head_dim=256, rms_norm_eps=1e-5, rope_theta=1e6,
        num_experts=4, num_experts_per_tok=4, moe_intermediate_size=256,
        num_routed_experts=16, num_shared_experts=1, first_k_dense=1,
        routed_scaling_factor=1.8, kv_lora_rank=512, q_lora_rank=768,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        index_n_heads=0, index_head_dim=0, index_topk=0, mtp_layers=1)
    assert spec.kv_entry == (1, (640, 0)) and spec.pool_layers == layers + 1
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    runner.config = EngineConfig(model=spec, num_pages=pages,
                                 max_num_seqs=rows)
    page = runner.config.resolve_page_size("tpu")
    assert page == 64
    runner.config = EngineConfig(model=spec, page_size=page, num_pages=pages,
                                 max_num_seqs=rows, spec_decode=spec_decode,
                                 spec_k=1)
    runner.quant_kv, runner.lora = None, None
    runner._window_cache, runner._prefill_cache = {}, {}
    runner.backends = choose(runner.config, spec, "tpu", 1, None)
    assert (runner.backends.attention, runner.backends.kv_commit,
            runner.backends.index) == ("pallas", "in_place", None)
    return runner, spec, page, pages


@pytest.mark.parametrize("spec_decode, layers", [("mtp", 3), (None, 3),
                                                 ("mtp", 47)])
def test_glm_window_program_commits_in_place_for_v5e(v5e, spec_decode,
                                                     layers):
    """The window program of a latent pool WITHOUT an indexer at
    GLM-4.7-Flash's widths, drafting with the model's own module (two query
    positions a row: 40 query rows a slot padded to 48, the reader without
    a mask operand; two commits, the module's layer one slot on) and plain:
    the pool of ONE array is donated, walked by the kernel and rewritten in
    place, and nothing in the optimised program has its shape but the
    arguments and the commits aliased to them. At the cell's depth (47
    layers and the module's, 32 slots, 8 steps of 2 rows) also what the
    step's write relies on: the window's buffer [48, 32, 16, 640] is
    carried through the steps as the write's kernel takes it, aliased
    through it, and no ``dynamic-update-slice`` of its shape stands alone
    in the program (XLA's went a sublane at a time, 0.36 ms a step)."""
    import re
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.runner import PK_PREFIX
    runner, spec, page, pages = _glm_runner(v5e, spec_decode, layers=layers)
    rows, window = 32, 8
    table = runner.config.max_pages_per_seq // 2

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree.map(lambda shape: s(shape, jnp.bfloat16),
                          param_shapes(spec),
                          is_leaf=lambda x: isinstance(x, tuple))
    pool = (layers + 1, 1, pages, page, 640)
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = [s((rows,), jnp.int32)] * (3 if spec_decode else 1)
    if spec_decode:     # mtp_hidden: a page's last position's output
        state.append(s((pages, spec.hidden_size), jnp.bfloat16))
    lowered = runner._get_window(window, table).lower(
        params, s(pool, jnp.bfloat16), s((*pool[:-1], 0), jnp.bfloat16),
        *state, s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype))
    if spec_decode:
        # ONE reader for both layer scans and the module's layer.
        assert lowered.as_text().count(
            "func.func private @_latent_block_flash") == 1
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= (3 if spec_decode else 2)
    assert "output_to_operand_aliasing" in text
    assert pool_sized_ops(text, pool) == []
    if layers == 47:
        buf = r"bf16\[48,32,16,640\]\{3,2,1,0:"
        writes = [line for line in text.splitlines() if re.search(
            rf"= {buf}\S* custom-call\(", line)]
        assert len(writes) == 1 and "while/body" in writes[0]
        assert "output_to_operand_aliasing={{}: (2, {})}" in writes[0]
        # In no order of its dimensions.
        assert not re.search(
            r"= bf16\[(48,32,16|16,48,32),640\]\S* dynamic-update-slice\(",
            text)


def _hybrid_runner(v5e, rows=32, pages=3000, experts=4):
    """A ModelRunner that places nothing, for a block with recurrent layers
    at Nemotron-3-Nano's widths (Mamba-2 mixers of 64 heads of 64 over a
    state of 128, 8 groups; 32 query heads over 2 KV heads of 128, a page
    of 128; a pool of the ONE attention layer; 4 two-matrix relu2 experts
    held of a router over 128; pattern MEM*EME, a narrow vocabulary), int8
    weights. Returns (runner, spec, params as shapes, s)."""
    from dynamo_tpu.engine.config import EngineConfig, NemotronHSpec
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, QTensor
    from dynamo_tpu.engine.runner import ModelRunner
    spec = NemotronHSpec(
        name="hybrid", vocab_size=1024, hidden_size=2688,
        intermediate_size=1856, num_layers=7, num_heads=32, num_kv_heads=2,
        head_dim=128, rms_norm_eps=1e-5, num_experts=experts,
        num_experts_per_tok=6, moe_intermediate_size=1856,
        num_routed_experts=128, num_shared_experts=1,
        shared_intermediate_size=3712, routed_scaling_factor=2.5,
        layer_pattern="MEM*EME", ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
        ssm_state=128, ssm_conv=4, ssm_chunk=128, quant="int8")
    assert (spec.pool_layers, spec.ssm_layers, spec.kv_entry) == (
        1, 3, (2, (128, 128)))
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    runner.config = EngineConfig(model=spec, num_pages=pages,
                                 max_num_seqs=rows)
    page = runner.config.resolve_page_size("tpu")
    assert page == 128
    runner.config = EngineConfig(model=spec, page_size=page, num_pages=pages,
                                 max_num_seqs=rows)
    runner.quant_kv, runner.lora, runner.draft_dev = None, None, None
    runner._window_cache, runner._prefill_cache = {}, {}
    runner.backends = choose(runner.config, spec, "tpu", 1, None)
    assert (runner.backends.attention, runner.backends.kv_commit,
            runner.backends.ssm) == ("pallas", "in_place", "kernel")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def q(shape):
        return QTensor(s(shape, jnp.int8),
                       s((*shape[:-2], 1, shape[-1]), jnp.float32))

    shapes = param_shapes(spec)
    params = {"layers": {k: q(v) if k in QUANT_LAYER_KEYS
                         else s(v, jnp.bfloat16)
                         for k, v in shapes["layers"].items()},
              "embed": QTensor(s(shapes["embed"], jnp.int8),
                               s((1, shapes["embed"][1]), jnp.float32)),
              "final_norm": s(shapes["final_norm"], jnp.bfloat16),
              "lm_head": q(shapes["lm_head"])}
    return runner, spec, params, s


def test_hybrid_window_program_keeps_the_state_where_it_lies_for_v5e(v5e):
    """The window program of a block with recurrent layers at
    Nemotron-3-Nano's widths (Mamba-2 mixers of 64 heads of 64 over a state
    of 128, 8 groups; 32 query heads over 2 KV heads of 128, a page of 128;
    a pool of the ONE attention layer; two-matrix relu2 experts; pattern
    MEM*EME, a narrow vocabulary), int8 weights, pool and state donated:
    the attention layer reads the pool through the kernel and commits in
    place with ITS index in the pool, and the float32 state (32 slots x 3
    layers x 2 MB) rides the pairs' carry and the steps' carry into ONE
    more kernel (``ssm_backend`` ``kernel``: engine/recurrence.py compiles
    for Mosaic at these widths, three row buffers of 2 MB in VMEM), which
    is handed the whole stack aliased to its output and rewrites the live
    slots of one layer where they lie: NOTHING else in the optimised
    program has the state's shape (no copy: at the cell's depth one is 1.5
    GB, 3.8 ms a step; no ``dynamic-update-slice`` of a layer's slice; no
    fusion that reads it a second time). The convolution's carried inputs
    are taps-major, [3, 3, 32, 6144] bfloat16."""
    from dynamo_tpu.engine.runner import PK_PREFIX
    # A pool past the chip's 128 MiB of VMEM, as the cell's is: one of 600
    # pages (39 MB) the compiler prefetches whole into VMEM, a copy-start of
    # the pool's shape that says nothing of the program at the cell's size.
    rows, window, pages = 32, 8, 3000
    runner, spec, params, s = _hybrid_runner(v5e, rows, pages)
    page = runner.config.page_size
    table = runner.config.max_pages_per_seq // 2
    pool = (1, 2, pages, page, 128)
    s_shape, _ = spec.ssm_state_shapes
    state, carried = (3, rows, *s_shape), spec.conv_state_shape(rows)
    assert carried == (3, 3, 32, 6144)
    key = jax.eval_shape(lambda: jax.random.key(0))
    fn = runner._get_window(window, table)
    assert fn._labels["prefix_reuse"].startswith("off")
    assert fn._labels["expert_product"] == "touched"
    assert fn._labels["ssm_backend"] == "kernel"
    lowered = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype),
        state=(s(state, jnp.float32), s(carried, jnp.bfloat16)))
    # ONE kernel for the mixers of every pair and step.
    assert lowered.as_text().count("func.func private @state_step") == 1
    text = lowered.compile().as_text()
    # The reader, the commit and the recurrence.
    assert text.count("tpu_custom_call") >= 3
    assert pool_sized_ops(text, pool) == []
    shape = "f32[" + ",".join(map(str, state)) + "]"
    aliased = [line for line in text.splitlines()
               if "ssm_state_step" in line and "custom-call(" in line]
    assert len(aliased) == 1 and shape in aliased[0] \
        and "output_to_operand_aliasing" in aliased[0], aliased
    assert pool_sized_ops(text, state) == []


@pytest.mark.parametrize("experts", [16, 4])
def test_hybrid_prefill_program_reads_the_expert_stacks_where_they_lie(
        v5e, experts):
    """The prefill program of the same block for two prompts of 256 tokens
    (512 rows: over MOE_DENSE_MAX_ROWS, labelled ``grouped``): the scan over
    pairs hands the kernel of engine/experts.py the expert stacks over ALL
    expert layers and the pair's index (hybrid.scan_pairs, as
    model.scan_layers hands them), so NOTHING in the optimised program has
    the shape of a stack or of a layer's slice of one but the arguments and
    their bitcasts (sliced a pair ahead of a custom call a layer's experts
    are copied; handed as [.., K, N] the whole ``up`` stack is: it lies
    with K minor); the program at 128 rows walks the touched experts. At 16
    experts held the stack is past the chip's 128 MiB of VMEM, as the
    cell's is, and the rule holds to the letter. At 4 (60 MB a stack) the
    compiler MAY place the ``up`` stack in VMEM ahead of the scan: since
    PR 45's one scan over groups it does (three asynchronous slices of a
    layer's experts and the bitcast that joins them, 33 MB of temporaries
    where the scan over pairs had 22: PERF.md section 6), where the scan
    over pairs did not; nothing else has a stack's shape there either."""
    from dynamo_tpu.engine.runner import _PF_HDR
    runner, spec, params, s = _hybrid_runner(v5e, experts=experts)
    page, bucket, batch = runner.config.page_size, 256, 2
    pool = (1, 2, 3000, page, 128)
    s_shape, _ = spec.ssm_state_shapes
    key = jax.eval_shape(lambda: jax.random.key(0))
    assert runner._get_prefill(128, 1, False)._labels[
        "expert_product"] == "touched"
    fn = runner._get_prefill(bucket, batch, False)
    assert fn._labels["expert_product"] == "grouped"
    lowered = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((batch, _PF_HDR + bucket + bucket // page + 1), jnp.int32),
        s(key.shape, key.dtype),
        state=(s((3, 32, *s_shape), jnp.float32),
               s(spec.conv_state_shape(32), jnp.bfloat16)))
    compiled = lowered.compile()
    text = compiled.as_text()
    # Two calls a pair, traced once each inside the scan's body.
    assert text.count("tpu_custom_call") == 2
    up, down = (2688, 1856), (1856, 2688)
    # A stack that fits VMEM may be fetched there whole: asynchronous
    # slices and the bitcast that joins them, no copy inside the scan.
    vmem = {"slice-start", "slice-done", "custom-call"} \
        if 3 * experts * 2688 * 1856 < 128 << 20 else set()
    for stack in (up, down):
        for lead in ((3, experts), (1, experts), (experts,)):
            found = pool_sized_ops(text, (*lead, *stack))
            assert {kind for _, kind in found} <= vmem, (lead, stack, found)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 4 * 2688 * 1856


# -- a routed block's window program: the walk over the stacks where they lie ----

def _smallthinker_runner(v5e, rows=32, pages=1500):
    """A ModelRunner that places nothing, for SmallThinker-21B-A3B's block
    three layers deep at the published widths (64 int8 experts of 2,560 x
    768, 6 a row, routed by the layer's input; 28 query heads over 4 KV
    heads of 128, a page of 64), a narrow vocabulary. Returns (runner, spec,
    params as shapes, s)."""
    from dynamo_tpu.engine.config import EngineConfig, SmallThinkerSpec
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, QTensor
    from dynamo_tpu.engine.runner import ModelRunner
    spec = SmallThinkerSpec(
        name="routed", vocab_size=1024, hidden_size=2560,
        intermediate_size=768, num_layers=3, num_heads=28, num_kv_heads=4,
        head_dim=128, num_experts=64, num_experts_per_tok=6,
        moe_intermediate_size=768, quant="int8")
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    runner.config = EngineConfig(model=spec, page_size=64, num_pages=pages,
                                 max_num_seqs=rows)
    runner.quant_kv, runner.lora = None, None
    runner._window_cache, runner._prefill_cache = {}, {}
    runner.backends = choose(runner.config, spec, "tpu", 1, None)
    assert runner.backends.experts_whole

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def q(shape):
        return QTensor(s(shape, jnp.int8),
                       s((*shape[:-2], 1, shape[-1]), jnp.float32))

    shapes = param_shapes(spec)
    params = {"layers": {k: q(v) if k in QUANT_LAYER_KEYS
                         else s(v, jnp.bfloat16)
                         for k, v in shapes["layers"].items()},
              "embed": QTensor(s(shapes["embed"], jnp.int8),
                               s((1, shapes["embed"][1]), jnp.float32)),
              "final_norm": s(shapes["final_norm"], jnp.bfloat16)}
    if "lm_head" in shapes:
        params["lm_head"] = q(shapes["lm_head"])
    return runner, spec, params, s


def _routed_window(v5e, kind):
    """(the lowered window program of a routed block of ``kind`` on one
    described chip, its expert stacks' (layers, held, [matrices])).
    "dense block": SmallThinker's; "hybrid": the recurrent block's, 16
    experts held (a stack past the chip's VMEM, as the cell's is);
    "drafting": the latent block's with its prediction module, whose verify
    step multiplies 64 rows and whose module has an expert layer of its
    own."""
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.runner import PK_PREFIX
    rows, window = 32, 8
    key = jax.eval_shape(lambda: jax.random.key(0))
    if kind == "drafting":
        runner, spec, page, pages = _glm_runner(v5e, "mtp")

        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

        params = jax.tree.map(lambda shape: s(shape, jnp.bfloat16),
                              param_shapes(spec),
                              is_leaf=lambda x: isinstance(x, tuple))
        table = runner.config.max_pages_per_seq // 2
        pool = (4, 1, pages, page, 640)
        fn = runner._get_window(window, table)
        lowered = fn.lower(
            params, s(pool, jnp.bfloat16), s((*pool[:-1], 0), jnp.bfloat16),
            *[s((rows,), jnp.int32)] * 3,
            s((pages, spec.hidden_size), jnp.bfloat16),
            s((rows, PK_PREFIX + table), jnp.int32), s(key.shape, key.dtype))
        return fn, lowered, (2, 4, [(2048, 256), (256, 2048)])
    if kind == "hybrid":
        pages = 3000
        runner, spec, params, s = _hybrid_runner(v5e, rows, pages, experts=16)
        table = runner.config.max_pages_per_seq // 2
        pool = (1, 2, pages, runner.config.page_size, 128)
        fn = runner._get_window(window, table)
        lowered = fn.lower(
            params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
            s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
            s(key.shape, key.dtype),
            state=(s((3, rows, *spec.ssm_state_shapes[0]), jnp.float32),
                   s(spec.conv_state_shape(rows), jnp.bfloat16)))
        return fn, lowered, (3, 16, [(2688, 1856), (1856, 2688)])
    runner, spec, params, s = _smallthinker_runner(v5e, rows)
    table = runner.config.max_pages_per_seq // 4
    pool = (3, 4, runner.config.num_pages, 64, 128)
    fn = runner._get_window(window, table)
    lowered = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype))
    return fn, lowered, (3, 64, [(2560, 768), (768, 2560)])


def stack_shaped_ops(text: str, stacks: tuple) -> list:
    """Every instruction of an optimised program whose result has the shape
    of an expert stack, of a layer's slice of one or of such a slice as a
    stack of one (``pool_sized_ops``: arguments and bitcasts are none)."""
    layers, held, matrices = stacks
    return [(lead, matrix, found) for matrix in matrices
            for lead in ((layers, held), (1, held), (held,))
            if (found := pool_sized_ops(text, (*lead, *matrix)))]


@pytest.mark.parametrize("kind", ["dense block", "hybrid", "drafting"])
def test_a_routed_window_program_walks_the_stacks_where_they_lie(v5e, kind):
    """The window program of each routed block kind on one chip is labelled
    ``touched`` and holds ONE custom call an expert layer (traced once in
    the layer scan's body; the drafting window's second is its module's own
    layer, 32 rows where the verify step has 64), which reads the expert
    stacks over ALL layers with the layer's index: nothing in the optimised
    program has the shape of a stack, of a layer's slice or of a slice as a
    stack of one but the arguments and their bitcasts, and no temporary is
    the size of a layer's matrix."""
    import re
    fn, lowered, stacks = _routed_window(v5e, kind)
    assert fn._labels["expert_product"] == "touched"
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and re.search(
                 r'op_name="[^"]*moe\.experts/[^"]*pallas_call', line)]
    assert len(calls) == (2 if kind == "drafting" else 1), calls
    assert all("while/body" in line for line in calls)
    assert stack_shaped_ops(text, stacks) == []
    _, held, (matrix, _) = stacks
    assert compiled.memory_analysis().temp_size_in_bytes < max(
        held * matrix[0] * matrix[1], 64 << 20)


def test_the_stack_guard_sees_a_layer_s_slice_ahead_of_the_walk(
        v5e, monkeypatch):
    """The same check on the same program whose layer scan slices the
    expert leaves a layer FAILS: a layer's three matrices are copied ahead
    of the custom call (126 MB each)."""
    from dynamo_tpu.engine import model
    real = model.scan_layers
    monkeypatch.setattr(model, "scan_layers", lambda *a, whole_experts=False,
                        **kw: real(*a, **kw))
    _, lowered, stacks = _routed_window(v5e, "dense block")
    compiled = lowered.compile()
    assert stack_shaped_ops(compiled.as_text(), stacks)
    assert compiled.memory_analysis().temp_size_in_bytes >= 2 * 64 * 2560 * 768


def _sala_runner(v5e, rows=24, pages=3000):
    """A ModelRunner that places nothing, for the MiniCPM-SALA block at its
    published widths (lightning mixers of 32 heads over a state of 128 x
    128 a head; 32 query heads over 2 KV heads of 128, a page of 128 that
    is two blocks of 64, 64 of them kept; a dense feed-forward of 16,384)
    and 7 of its layers (S L L S L L S: a pool and a compressed-key array
    of three attention layers, a state of four mixers), a narrow
    vocabulary, int8 weights. Returns (runner, spec, params as shapes,
    s)."""
    from dynamo_tpu.engine.backends import choose
    from dynamo_tpu.engine.config import EngineConfig, MiniCPMSALASpec
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, QTensor
    from dynamo_tpu.engine.runner import ModelRunner
    spec = MiniCPMSALASpec(
        name="sala", vocab_size=1024, hidden_size=4096,
        intermediate_size=16384, num_layers=7, num_heads=32, num_kv_heads=2,
        head_dim=128, rms_norm_eps=1e-6, layer_pattern="SDLDLDSDLDLDSD",
        ssm_heads=32, ssm_head_dim=128, ssm_groups=32, ssm_state=128,
        scale_emb=12.0, residual_scale=1.4 / 32 ** 0.5, logit_divisor=16.0,
        quant="int8")
    assert (spec.pool_layers, spec.ssm_layers, spec.kv_entry) == (
        3, 4, (2, (128, 128)))
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    runner.config = EngineConfig(model=spec, num_pages=pages,
                                 max_num_seqs=rows)
    page = runner.config.resolve_page_size("tpu")
    assert page == 128
    runner.config = EngineConfig(model=spec, page_size=page, num_pages=pages,
                                 max_num_seqs=rows, max_pages_per_seq=128)
    runner.quant_kv, runner.lora, runner.draft_dev = None, None, None
    runner._window_cache, runner._prefill_cache = {}, {}
    runner.backends = choose(runner.config, spec, "tpu", 1, None)
    assert (runner.backends.attention, runner.backends.kv_commit,
            runner.backends.ssm) == ("pallas", "in_place", "kernel")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def q(shape):
        return QTensor(s(shape, jnp.int8),
                       s((*shape[:-2], 1, shape[-1]), jnp.float32))

    shapes = param_shapes(spec)
    params = {"layers": {k: q(v) if k in QUANT_LAYER_KEYS
                         else s(v, jnp.bfloat16)
                         for k, v in shapes["layers"].items()},
              "embed": QTensor(s(shapes["embed"], jnp.int8),
                               s((1, shapes["embed"][1]), jnp.float32)),
              "final_norm": s(shapes["final_norm"], jnp.bfloat16),
              "lm_head": q(shapes["lm_head"])}
    return runner, spec, params, s


def test_sala_programs_compile_for_v5e_with_the_state_where_it_lies(v5e):
    """The window program of the MiniCPM-SALA block at its widths: FOUR
    kernels (the recurrence at a group a head, three row buffers of 2 MB
    in VMEM; the scores of a row's stripes, a page's 8 x 128 of both heads
    one copy out of the compressed-key array where it lies: nothing of its
    shape but the argument and the commit's copy in place; the pool's
    reader over the chosen blocks' table, the pool
    seen as blocks of ONE KV head; the window's commit in place), the
    float32 state (24 slots x 4 layers x 2 MB) aliased to the recurrence's
    output and nothing else of its shape (it never rides a conditional: a
    group without a mixer visits no row), nothing of the pool's shape but
    arguments and what is written in place. And the prefill program of one
    prompt of 8,192 tokens, whole: it fits beside the weights (its
    temporaries under 2 GB: the state is carried through the scan where it
    lies, the lightning recurrence goes 2,048 tokens at a time, the
    attention's scores a chunk of queries at a time)."""
    from dynamo_tpu.engine.runner import _PF_HDR, PK_PREFIX
    # The compressed-key array at the cell's bytes (3,453 pages over 8
    # layers are 113 MB; three layers here): under some 100 MB the compiler
    # copies a conditional's operand whole into VMEM ahead of the branch,
    # a ``copy-start`` of the array's shape that the cell never sees.
    rows, window, pages = 24, 8, 9300
    runner, spec, params, s = _sala_runner(v5e, rows, pages)
    page, table = runner.config.page_size, 128
    pool = (3, 2, pages, page, 128)
    states = (4, rows, *spec.ssm_state_shapes[0])
    state = (s(states, jnp.float32),
             s(spec.comp_key_shape(pages, page), jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    fn = runner._get_window(window, table)
    assert fn._labels["ssm_backend"] == "kernel"
    assert fn._labels["index_backend"] == "pallas"
    assert fn._labels["prefix_reuse"].startswith("off")
    lowered = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype), state=state)
    assert lowered.as_text().count("func.func private @state_step") == 1
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 4
    assert pool_sized_ops(text, pool) == []
    # The compressed-key array: the commit's update in place (the update
    # and the fusion it is the root of), no copy.
    assert [kind for _, kind in pool_sized_ops(
        text, spec.comp_key_shape(pages, page))] == [
            "dynamic-update-slice", "fusion"]
    shape = "f32[" + ",".join(map(str, states)) + "]"
    aliased = [line for line in text.splitlines()
               if "ssm_state_step" in line and "custom-call(" in line]
    assert len(aliased) == 1 and shape in aliased[0] \
        and "output_to_operand_aliasing" in aliased[0], aliased
    assert pool_sized_ops(text, states) == []
    bucket = 8192
    fn = runner._get_prefill(bucket, 1, False)
    compiled = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((1, _PF_HDR + bucket + bucket // page + 1), jnp.int32),
        s(key.shape, key.dtype), state=state).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def _looped_runner(v5e, rows=4, pages=339):
    """A runner that places nothing, with Ouro-2.6B's layers, passes and
    attention geometry (48 layers run 4 times: a pool of 192 layers of 16
    KV heads of 128, pages of 16) and a narrow MLP and vocabulary."""
    from dynamo_tpu.engine.config import EngineConfig, OuroSpec
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.runner import ModelRunner
    spec = OuroSpec(name="ouro", vocab_size=1024, hidden_size=2048,
                    intermediate_size=1024, num_layers=48, num_heads=16,
                    num_kv_heads=16, head_dim=128, loop_passes=4)
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    runner.config = EngineConfig(model=spec, page_size="auto",
                                 num_pages=pages, max_num_seqs=rows)
    runner.quant_kv, runner.lora = None, None
    runner._window_cache, runner._prefill_cache = {}, {}
    runner.mesh = None
    runner.backends = choose(runner.config, spec, "tpu", 1, None)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree.map(lambda shape: s(shape, jnp.bfloat16),
                          param_shapes(spec),
                          is_leaf=lambda x: isinstance(x, tuple))
    return runner, spec, params, s


def test_looped_programs_compile_for_v5e_with_the_pool_where_it_lies(v5e):
    """The window program of a looped stack at Ouro-2.6B's geometry: the
    reader at ONE query row a KV head over pages of 16 (one call inside the
    scan over passes and layers; its chunk turn the one over all heads), the commit in place by layer ranges (six
    calls of 32 pool layers: 192 at once are 50 MB of tiles and windows),
    nothing of the pool's shape copied; and a prefill program whose fresh K
    and V of all 192 (pass, layer) pairs stay under the runner's bound."""
    from dynamo_tpu.engine.runner import _PF_HDR, PK_PREFIX
    rows, pages, table = 4, 339, 128
    runner, spec, params, s = _looped_runner(v5e, rows, pages)
    # On a described chip the page is what "auto" derives on the chip.
    page = runner.config.resolve_page_size("tpu")
    assert page == PAGE == runner.config.page_size
    assert (runner.backends.attention, runner.backends.kv_commit) == (
        "pallas", "in_place")
    pool = (192, 16, pages, page, 128)
    key = jax.eval_shape(lambda: jax.random.key(0))
    cache = s(pool, jnp.bfloat16)
    fn = runner._get_window(4, table)
    # The reader's turn over all 16 heads at once (attention.reader_turn),
    # compiled by Mosaic inside the program below.
    assert fn._labels["kv_reader_turn"] == "heads"
    text = fn.lower(params, cache, cache, s((rows,), jnp.int32),
                    s((rows, PK_PREFIX + table), jnp.int32),
                    s(key.shape, key.dtype)).compile().as_text()
    assert text.count("tpu_custom_call") == 7
    assert text.count("output_to_operand_aliasing") >= 6
    assert pool_sized_ops(text, pool) == []
    bucket = 512
    compiled = runner._get_prefill(bucket, 1, False).lower(
        params, cache, cache,
        s((1, _PF_HDR + bucket + bucket // page), jnp.int32),
        s(key.shape, key.dtype)).compile()
    # The pages' update in place, a pool (the update and the fusion it is
    # the root of): no copy.
    assert sorted(kind for _, kind in pool_sized_ops(
        compiled.as_text(), pool)) == ["dynamic-update-slice"] * 2 + [
            "fusion"] * 2
    # 0.75 GiB of fresh K and V and as much of their page blocks.
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def _delta_runner(v5e, rows=32, pages=3000):
    """A ModelRunner that places nothing, for the Solar-Open2 block at its
    published widths (delta-rule mixers of 64 heads over a state of 128 x
    128 a head, a convolution over 24,576 channels; 64 query heads over 8
    KV heads of 128, gated; 4 SwiGLU experts of 1,280 held of a router over
    320 and one shared) and ONE period of its layers (* K K K, an expert
    layer behind each), a narrow vocabulary, int8 weights. Returns (runner,
    spec, params as shapes, s)."""
    from dynamo_tpu.engine.config import EngineConfig, SolarOpen2Spec
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, QTensor
    from dynamo_tpu.engine.runner import ModelRunner
    spec = SolarOpen2Spec(
        name="delta", vocab_size=1024, hidden_size=4096,
        intermediate_size=10240, num_layers=4, num_heads=64, num_kv_heads=8,
        head_dim=128, rms_norm_eps=1e-5, num_experts=4,
        num_experts_per_tok=8, moe_intermediate_size=1280,
        num_routed_experts=320, num_shared_experts=1,
        layer_pattern="*EKEKEKE", ssm_heads=64, ssm_head_dim=128,
        ssm_groups=64, ssm_state=128, ssm_conv=4, ssm_low_rank=128,
        quant="int8")
    assert (spec.pool_layers, spec.ssm_layers, spec.kv_entry) == (
        1, 3, (8, (128, 128)))
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    runner.config = EngineConfig(model=spec, num_pages=pages,
                                 max_num_seqs=rows)
    page = runner.config.resolve_page_size("tpu")
    assert page == 32       # as Command A+'s 8 KV heads
    runner.config = EngineConfig(model=spec, page_size=page, num_pages=pages,
                                 max_num_seqs=rows)
    runner.quant_kv, runner.lora, runner.draft_dev = None, None, None
    runner._window_cache, runner._prefill_cache = {}, {}
    runner.backends = choose(runner.config, spec, "tpu", 1, None)
    assert (runner.backends.attention, runner.backends.kv_commit,
            runner.backends.ssm) == ("pallas", "in_place", "kernel")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def q(shape):
        return QTensor(s(shape, jnp.int8),
                       s((*shape[:-2], 1, shape[-1]), jnp.float32))

    shapes = param_shapes(spec)
    params = {"layers": {k: q(v) if k in QUANT_LAYER_KEYS
                         else s(v, jnp.bfloat16)
                         for k, v in shapes["layers"].items()},
              "embed": QTensor(s(shapes["embed"], jnp.int8),
                               s((1, shapes["embed"][1]), jnp.float32)),
              "final_norm": s(shapes["final_norm"], jnp.bfloat16),
              "lm_head": q(shapes["lm_head"])}
    return runner, spec, params, s


def test_delta_programs_compile_for_v5e_with_the_state_where_it_lies(v5e):
    """The window program of the Solar-Open2 block at its published widths,
    one period, pool and state donated: the delta rule's kernel
    (engine/recurrence.py ``delta_state_step``: three row buffers of 4 MB,
    the decay, k, q and v of every slot 1 MB each in VMEM) compiles for
    Mosaic, is handed the float32 state (32 slots x 3 layers x 4 MB) whole
    and aliased to its output, and NOTHING else in the optimised program has
    the state's shape (the update reads the state before it writes it: in
    VMEM, not by a second pass over HBM); the convolution's carried inputs
    are taps-major, [3, 3, 32, 24576] bfloat16. And a prefill program of 2 x 512
    tokens: the chunked solve (``triangular_solve`` a chunk of 32) compiles
    for the chip within a quarter of what is free beside weights, state
    and pool."""
    from dynamo_tpu.engine.runner import _PF_HDR, PK_PREFIX
    rows, window, pages = 32, 8, 3000
    runner, spec, params, s = _delta_runner(v5e, rows, pages)
    page = runner.config.page_size
    table = runner.config.max_pages_per_seq // 2
    pool = (1, 8, pages, page, 128)
    s_shape, c_shape = spec.ssm_state_shapes
    assert (s_shape, c_shape) == ((64, 128, 128), (3, 24576))
    state, carried = (3, rows, *s_shape), spec.conv_state_shape(rows)
    assert carried == (3, 3, 32, 24576)
    arrays = (s(state, jnp.float32), s(carried, jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    fn = runner._get_window(window, table)
    assert fn._labels["ssm_backend"] == "kernel"
    lowered = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype), state=arrays)
    assert lowered.as_text().count(
        "func.func private @delta_state_step") == 1
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    shape = "f32[" + ",".join(map(str, state)) + "]"
    aliased = [line for line in text.splitlines()
               if "ssm_delta_step" in line and "custom-call(" in line]
    assert len(aliased) == 1 and shape in aliased[0] \
        and "output_to_operand_aliasing" in aliased[0], aliased
    assert pool_sized_ops(text, state) == []
    bucket, batch = 512, 2
    fn = runner._get_prefill(bucket, batch, False)
    compiled = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((batch, _PF_HDR + bucket + bucket // page + 1), jnp.int32),
        s(key.shape, key.dtype), state=arrays).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 600 << 20


def _parallel_runner(v5e, rows=32, pages=3000):
    """A ModelRunner that places nothing, for the Falcon-H1 block at its
    published widths (every layer a Mamba-2 mixer of 32 heads of 128 over a
    state of 256, 2 groups, AND 20 query heads over 4 KV heads of 128,
    rotary, on one normed input; a dense SwiGLU of 21,504; the muP
    constants as published), 3 layers, a narrow vocabulary, int8 weights.
    Returns (runner, spec, params as shapes, s)."""
    from dynamo_tpu.engine.config import EngineConfig, FalconH1Spec
    from dynamo_tpu.engine.model import param_shapes
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, QTensor
    from dynamo_tpu.engine.runner import ModelRunner
    spec = FalconH1Spec(
        name="parallel", vocab_size=1024, hidden_size=5120,
        intermediate_size=21504, num_layers=3, num_heads=20, num_kv_heads=4,
        head_dim=128, rope_theta=1e11, rms_norm_eps=1e-5,
        layer_pattern="M*D" * 3, ssm_heads=32, ssm_head_dim=128,
        ssm_groups=2, ssm_state=256, ssm_conv=4, ssm_chunk=128,
        scale_emb=5.6569, logit_divisor=128.0, key_multiplier=0.011049,
        attn_out_multiplier=0.0375, ssm_in_multiplier=0.25,
        ssm_multipliers=(0.35355, 0.25, 0.17678, 0.5, 0.35355),
        ssm_out_multiplier=0.088388, mlp_multipliers=(0.17678, 0.011161),
        quant="int8")
    assert (spec.pool_layers, spec.ssm_layers, spec.kv_entry) == (
        3, 3, (4, (128, 128)))
    runner = object.__new__(ModelRunner)
    runner.spec = spec
    runner.config = EngineConfig(model=spec, num_pages=pages,
                                 max_num_seqs=rows)
    page = runner.config.resolve_page_size("tpu")
    assert page == 64       # as Qwen2.5-7B's 4 KV heads of 128
    runner.config = EngineConfig(model=spec, page_size=page, num_pages=pages,
                                 max_num_seqs=rows)
    runner.quant_kv, runner.lora, runner.draft_dev = None, None, None
    runner._window_cache, runner._prefill_cache = {}, {}
    runner.backends = choose(runner.config, spec, "tpu", 1, None)
    assert (runner.backends.attention, runner.backends.kv_commit,
            runner.backends.ssm) == ("pallas", "in_place", "kernel")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def q(shape):
        return QTensor(s(shape, jnp.int8),
                       s((*shape[:-2], 1, shape[-1]), jnp.float32))

    shapes = param_shapes(spec)
    assert shapes["layers"]["wk"] == (3, 5120, 512)
    assert shapes["layers"]["ssm_w_in"] == (3, 5120, 9216)
    params = {"layers": {k: q(v) if k in QUANT_LAYER_KEYS
                         else s(v, jnp.bfloat16)
                         for k, v in shapes["layers"].items()},
              "embed": QTensor(s(shapes["embed"], jnp.int8),
                               s((1, shapes["embed"][1]), jnp.float32)),
              "final_norm": s(shapes["final_norm"], jnp.bfloat16),
              "lm_head": q(shapes["lm_head"])}
    return runner, spec, params, s


def test_parallel_programs_compile_for_v5e_with_state_and_pool_in_place(v5e):
    """The window program of the Falcon-H1 block at its published widths,
    three layers, pool and state donated: EVERY layer reads the pool through
    the Pallas reader (5 query rows a KV head) AND hands the float32 state
    (32 slots x 3 layers x 4 MB: a head of [128, 256] is ONE copy of 128 KB,
    two lane tiles a row of S, three row buffers of 4 MB in VMEM) whole and
    aliased to the recurrence's kernel, which compiles for Mosaic at this
    head; NOTHING else in the optimised program has the state's shape or
    the pool's. And a prefill program of 2 x 512 tokens compiles beside
    them."""
    from dynamo_tpu.engine.runner import _PF_HDR, PK_PREFIX
    rows, window, pages = 32, 8, 3000
    runner, spec, params, s = _parallel_runner(v5e, rows, pages)
    page = runner.config.page_size
    table = runner.config.max_pages_per_seq // 2
    pool = (3, 4, pages, page, 128)
    s_shape, c_shape = spec.ssm_state_shapes
    assert (s_shape, c_shape) == ((32, 128, 256), (3, 5120))
    state, carried = (3, rows, *s_shape), spec.conv_state_shape(rows)
    assert carried == (3, 3, 32, 5120)
    arrays = (s(state, jnp.float32), s(carried, jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    fn = runner._get_window(window, table)
    assert fn._labels["ssm_backend"] == "kernel"
    lowered = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((rows,), jnp.int32), s((rows, PK_PREFIX + table), jnp.int32),
        s(key.shape, key.dtype), state=arrays)
    assert lowered.as_text().count("func.func private @state_step") == 1
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert pool_sized_ops(text, pool) == []
    shape = "f32[" + ",".join(map(str, state)) + "]"
    aliased = [line for line in text.splitlines()
               if "ssm_state_step" in line and "custom-call(" in line]
    assert len(aliased) == 1 and shape in aliased[0] \
        and "output_to_operand_aliasing" in aliased[0], aliased
    assert pool_sized_ops(text, state) == []
    bucket, batch = 512, 2
    fn = runner._get_prefill(bucket, batch, False)
    compiled = fn.lower(
        params, s(pool, jnp.bfloat16), s(pool, jnp.bfloat16),
        s((batch, _PF_HDR + bucket + bucket // page + 1), jnp.int32),
        s(key.shape, key.dtype), state=arrays).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 600 << 20
