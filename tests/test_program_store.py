"""The program store (engine/program_store.py; docs/OBSERVABILITY.md "Program
store"): a warm start through the launcher loads each wrapped program's
executable under a key that costs no trace. Every test keeps its store in a
``tmp_path`` of its own. Times here are CPU times and go nowhere.
"""

import ast
import dataclasses
import os
import pathlib
import pickle
import subprocess
import sys
import textwrap
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import TINY_LAUNCH, async_test, launched
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine import backends, perf, program_store
from dynamo_tpu.engine.config import PRESETS, EngineConfig
from dynamo_tpu.engine.perf import CompileRegistry, instrumented_jit
from dynamo_tpu.runtime.context import Context

ENGINE_DIR = pathlib.Path(program_store.__file__).parent

if not jax.config.jax_enable_compilation_cache:
    pytest.skip("the store lives in the persistent compile cache's "
                "directory", allow_module_level=True)


@pytest.fixture
def store_dir(tmp_path, monkeypatch):
    """The directory this test's store lies in."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    return pathlib.Path(perf.compile_cache_dir()) / program_store.SUBDIR


def _entries(store_dir):
    return sorted(store_dir.glob("*" + program_store.SUFFIX))


def _mesh(n=2):
    return Mesh(np.array(jax.devices()[:n]), ("x",))


def _starting():
    reg = CompileRegistry()
    reg.mark_starting()
    return reg


def _unit(reg, context, fun=None, key="k", labels=None, **jit_kwargs):
    """A wrapped ``pool + x * n`` that donates its pool."""
    jit_kwargs.setdefault("donate_argnums", (0,))
    return instrumented_jit(
        "unit", fun or (lambda pool, x, n: pool + x * n), key=key,
        registry=reg, labels=labels, context=context, **jit_kwargs)


def _args(mesh, dtype=jnp.float32, spec=P("x")):
    sharding = NamedSharding(mesh, spec)
    return (jax.device_put(jnp.zeros((4, 8), dtype), sharding),
            jax.device_put(jnp.ones((4, 8), jnp.float32), sharding), 3)


# -- a start through the launcher ---------------------------------------------------

async def _one_start(registry):
    """A start, a greedy request, and what the start left."""
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    before = len(registry.first_calls)

    async def inside(runtime, service, engine):
        pool = jax.tree.leaves(engine.runner.k_cache)[0]
        req = PreprocessedRequest(model="m", token_ids=list(range(3, 40)))
        req.stop_conditions.max_tokens = 12
        req.stop_conditions.ignore_eos = True
        tokens = []
        async for out in engine.generate(req, Context()):
            tokens.extend(out["token_ids"])
        return {"tokens": tokens, "pool_donated": pool.is_deleted(),
                "scopes": registry.ops_by_scope("decode_window"),
                "perf": engine.perf_status()}

    out = await launched(TINY_LAUNCH, inside)
    out["records"] = registry.first_calls[before:]
    return out


@async_test(timeout=480)
async def test_a_second_start_loads_every_program_and_serves_the_same_tokens(
        store_dir):
    registry = perf.get_registry()
    jax.clear_caches()
    cold = await _one_start(registry)
    written = _entries(store_dir)
    jax.clear_caches()
    warm = await _one_start(registry)

    assert {r["store"] for r in cold["records"]} == {"miss"}
    assert len(written) == len(cold["records"]) > 0
    assert _entries(store_dir) == written and not list(
        store_dir.glob("*.tmp"))
    assert [(r["program"], r["key"]) for r in warm["records"]] \
        == [(r["program"], r["key"]) for r in cold["records"]]
    for r in warm["records"]:
        assert (r["source"], r["store"], r["cache"]) == (
            "store", "hit", "hit"), r
        assert r["trace_s"] == r["lower_s"] == r["compile_s"] == 0.0, r
        assert r["builds"] == 0 and r["cache_load_s"] > 0.0, r
        assert r["wall_s"] + 1e-3 >= r["cache_load_s"]
    for r in cold["records"]:
        assert r["source"] == "compiled" and r["cache"] == "miss", r
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["builds"] >= 1
    # The same executables on the same inputs.
    assert warm["tokens"] == cold["tokens"] and len(cold["tokens"]) == 12
    assert cold["pool_donated"] and warm["pool_donated"]
    assert cold["scopes"] and warm["scopes"] == cold["scopes"]
    # What the operator sees: the family's counters, and the estimate a
    # loaded program brought with it.
    for name in {r["program"] for r in warm["records"]}:
        family = warm["perf"]["compiles"]["programs"][name]
        mine = [r for r in warm["records"] if r["program"] == name]
        assert family["store_hits"] >= len(mine)
        assert family["store_rejects"] == 0 == family["store_fallbacks"]
        assert family["cost"] and "flops" in family["cost"]
    told = warm["perf"]["startup"]["first_calls"]
    assert told["store_hits"] == told["programs"] == len(warm["records"])
    assert told["store_misses"] == 0


def test_a_runner_built_without_the_launcher_writes_and_reads_nothing(
        store_dir):
    from dynamo_tpu.engine.runner import ModelRunner, PrefillSeq
    registry = perf.get_registry()
    if registry.starting:
        pytest.skip("a start is under way in this process")
    before = len(registry.first_calls)
    runner = ModelRunner(EngineConfig(num_pages=32, max_num_seqs=2), seed=1)
    assert runner._store_context is None
    runner.prefill_batch([PrefillSeq(
        tokens=np.arange(8, dtype=np.int32), start_pos=0,
        chunk_pages=np.array([1], np.int32), hist_pages=None,
        sampling=(0.0, 0, 1.0))])
    mine = registry.first_calls[before:]
    assert mine and {r["store"] for r in mine} == {None}
    assert not store_dir.exists()


def test_the_store_opens_for_a_launchers_start_alone(store_dir):
    spec, config = PRESETS["tiny-test"], EngineConfig()
    reg = CompileRegistry()
    assert perf.program_context(spec, config, mesh=_mesh(),
                                registry=reg) is None
    reg.mark_starting()
    context = perf.program_context(spec, config, mesh=_mesh(), registry=reg)
    assert context.directory == str(store_dir)
    assert [d.id for d in context.devices] == [0, 1]
    with perf.program_store_closed():
        assert perf.program_context(spec, config, mesh=_mesh(),
                                    registry=reg) is None
    reg.mark_ready()        # what is built while serving is no start's
    assert perf.program_context(spec, config, mesh=_mesh(),
                                registry=reg) is None


# -- the key ------------------------------------------------------------------------------

def _context(reg, spec=None, config=None, chosen=None, mesh=None):
    return perf.program_context(
        spec or PRESETS["tiny-test"], config or EngineConfig(),
        chosen or backends.XLA, mesh=mesh or _mesh(), registry=reg)


def _changed_spec(reg, monkeypatch):
    return {"context": _context(reg, spec=dataclasses.replace(
        PRESETS["tiny-test"], rope_theta=20000.0))}


def _changed_config(reg, monkeypatch):
    return {"context": _context(reg, config=EngineConfig(max_num_seqs=7))}


def _changed_backends(reg, monkeypatch):
    return {"context": _context(reg, chosen=dataclasses.replace(
        backends.XLA, interpret=True))}


def _changed_mesh(reg, monkeypatch):
    mesh = Mesh(np.array(jax.devices()[2:4]), ("x",))
    return {"context": _context(reg, mesh=mesh), "args": _args(mesh)}


def _changed_env(reg, monkeypatch):
    monkeypatch.setenv("DTPU_WINDOW_TARGET_MS", "60")
    return {}


def _changed_xla_flags(reg, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                       + " --xla_cpu_enable_fast_math=false")
    return {}


def _changed_source(reg, monkeypatch):
    program_store.source_digest()
    monkeypatch.setattr(program_store, "_source_digest", "another tree")
    return {}


def _changed_jax(reg, monkeypatch):
    monkeypatch.setattr(jax, "__version__", "0.0.1")
    return {}


def _changed_dtype(reg, monkeypatch):
    return {"args": _args(_mesh(), dtype=jnp.bfloat16)}


def _changed_sharding(reg, monkeypatch):
    return {"args": _args(_mesh(), spec=P(None, "x"))}


def _changed_weak_type(reg, monkeypatch):
    pool, x, _ = _args(_mesh())
    return {"args": (pool, x, jnp.asarray(3, jnp.int32))}


def _changed_scalar_type(reg, monkeypatch):
    pool, x, _ = _args(_mesh())
    return {"args": (pool, x, 3.0)}


def _changed_donation(reg, monkeypatch):
    return {"jit": {"donate_argnums": ()}}


def _changed_key(reg, monkeypatch):
    return {"key": "another bucket"}


def _changed_labels(reg, monkeypatch):
    return {"labels": {"attention_backend": "pallas"}}


CHANGES = [_changed_spec, _changed_config, _changed_backends, _changed_mesh,
           _changed_env, _changed_xla_flags, _changed_source,
           _changed_jax, _changed_dtype, _changed_sharding,
           _changed_weak_type, _changed_scalar_type, _changed_donation,
           _changed_key, _changed_labels]


@pytest.mark.parametrize("change", CHANGES,
                         ids=lambda f: f.__name__.removeprefix("_changed_"))
def test_what_a_program_is_made_of_changes_its_key(store_dir, monkeypatch,
                                                   change):
    """The unchanged wrapper hits what the first wrote; one thing changed
    is a miss that writes an entry of its own."""
    reg = _starting()
    _unit(reg, _context(reg))(*_args(_mesh()))
    again = _unit(reg, _context(reg))
    again(*_args(_mesh()))
    assert [r["store"] for r in reg.first_calls] == ["miss", "hit"]
    assert len(_entries(store_dir)) == 1

    how = change(reg, monkeypatch)
    changed = _unit(reg, how.get("context") or _context(reg),
                    key=how.get("key", "k"), labels=how.get("labels"),
                    **how.get("jit", {}))
    out = changed(*how.get("args") or _args(_mesh()))
    assert float(np.asarray(out, np.float32)[0, 0]) == 3.0
    assert reg.first_calls[-1]["store"] == "miss", reg.first_calls[-1]
    assert len(_entries(store_dir)) == 2


def test_a_scalars_value_and_a_directory_variable_are_no_part_of_the_key(
        store_dir, monkeypatch):
    reg = _starting()
    _unit(reg, _context(reg))(*_args(_mesh()))
    monkeypatch.setenv("DTPU_FLIGHT_DIR", "/somewhere/else")
    pool, x, _ = _args(_mesh())
    out = _unit(reg, _context(reg))(pool, x, 5)
    assert reg.first_calls[-1]["store"] == "hit"
    assert float(np.asarray(out)[0, 0]) == 5.0 and pool.is_deleted()


def test_code_from_outside_the_package_closes_the_store(store_dir,
                                                        monkeypatch):
    from dynamo_tpu.engine import model
    reg = _starting()
    assert program_store.foreign_code() == []

    def hook(scores, valid, k):
        raise AssertionError("bound, never traced here")

    monkeypatch.setattr(model, "select_topk", hook)
    assert program_store.foreign_code() == [
        "dynamo_tpu.engine.model.select_topk"]
    _unit(reg, _context(reg))(*_args(_mesh()))
    assert reg.first_calls[-1]["store"] is None
    assert reg.first_calls[-1]["source"] in ("compiled", "jax_cache")
    assert not store_dir.exists()


def test_a_static_argument_has_no_store(store_dir):
    reg = _starting()
    fn = _unit(reg, _context(reg), fun=lambda pool, x, n: pool + x * n,
               static_argnums=(2,))
    fn(*_args(_mesh()))
    assert reg.first_calls[-1]["store"] is None and not store_dir.exists()


# -- a hit ----------------------------------------------------------------------------------

def test_a_hit_reads_as_a_load_and_brings_its_familys_estimate(store_dir):
    cold, warm = _starting(), _starting()
    first = _unit(cold, _context(cold))
    first(*_args(_mesh()))
    cost = cold.snapshot()["programs"]["unit"]["cost"]
    assert cost and cost["source"] == "lower" and cost["flops"] > 0

    jax.clear_caches()
    loaded = _unit(warm, _context(warm))
    pool, x, n = _args(_mesh())
    out = loaded(pool, x, n)
    assert pool.is_deleted() and not x.is_deleted()    # still donated
    np.testing.assert_array_equal(np.asarray(out), 3.0)
    r, = warm.first_calls
    assert (r["source"], r["cache"], r["builds"]) == ("store", "hit", 0)
    assert r["trace_s"] == r["lower_s"] == r["compile_s"] == 0.0
    assert 0.0 < r["cache_load_s"] <= r["wall_s"]
    family = warm.snapshot()["programs"]["unit"]
    assert (family["store_hits"], family["store_misses"]) == (1, 0)
    assert family["compiles"] == 1 and family["cache_loads"] == 1
    assert family["cost"] == cost       # nothing was lowered for it
    assert loaded.ops_by_scope() == first.ops_by_scope() != None  # noqa: E711
    # A hundred more calls: the loaded executable, no record, no trace.
    for _ in range(100):
        out = loaded(out, x, n)
    assert len(warm.first_calls) == 1
    assert float(np.asarray(out)[0, 0]) == 303.0


def test_a_second_call_with_other_shardings_falls_back_and_is_recorded(
        store_dir):
    cold, warm = _starting(), _starting()
    _unit(cold, _context(cold))(*_args(_mesh()))
    fn = _unit(warm, _context(warm))
    fn(*_args(_mesh()))
    whole = _args(_mesh(), spec=P())
    out = fn(*whole)            # the loaded executable refuses; the jit runs
    np.testing.assert_array_equal(np.asarray(out), 3.0)
    assert whole[0].is_deleted()
    first, second = warm.first_calls
    assert (first["store"], second["store"]) == ("hit", "fallback")
    assert second["source"] == "fallback" and second["trace_s"] > 0
    family = warm.snapshot()["programs"]["unit"]
    assert (family["store_hits"], family["store_fallbacks"]) == (1, 1)
    # For good: either sharding goes through the jit now, with no record.
    fn(*_args(_mesh()))
    fn(*_args(_mesh(), spec=P()))
    assert len(warm.first_calls) == 3   # the jit's first build of P("x")
    assert warm.snapshot()["programs"]["unit"]["store_fallbacks"] == 1
    # An argument no jit takes either still raises.
    with pytest.raises(TypeError):
        _unit(_starting(), None)(*_args(_mesh())[:2])


# -- entries that cannot be used --------------------------------------------------------

def _truncate(path):
    path.write_bytes(path.read_bytes()[:200])


def _rewritten(path, change):
    header, payload, in_tree, out_tree = pickle.loads(
        zlib.decompress(path.read_bytes()))
    path.write_bytes(zlib.compress(pickle.dumps(
        change(header, payload) + (in_tree, out_tree))))


def _other_jax(path):
    _rewritten(path, lambda h, payload: ({**h, "key": h["key"].replace(
        f"jax={jax.__version__} ", "jax=0.0.1 ")}, payload))


def _other_key(path):
    _rewritten(path, lambda h, payload: ({**h, "key": "x"}, payload))


def _no_executable(path):
    _rewritten(path, lambda h, payload: (h, b"not one"))


@pytest.mark.parametrize("damage", [_truncate, _other_jax, _other_key,
                                    _no_executable],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_damaged_entry_is_thrown_away_and_the_program_built(store_dir,
                                                              damage):
    reg = _starting()
    _unit(reg, _context(reg))(*_args(_mesh()))
    entry, = _entries(store_dir)
    damage(entry)
    out = _unit(reg, _context(reg))(*_args(_mesh()))
    np.testing.assert_array_equal(np.asarray(out), 3.0)
    r = reg.first_calls[-1]
    assert (r["store"], r["source"], r["cache"]) == (
        "reject", "compiled", "miss")
    family = reg.snapshot()["programs"]["unit"]
    assert family["store_rejects"] == 1 and family["store_reject_reason"]
    # What was built took the damaged entry's place: the next one hits.
    _unit(reg, _context(reg))(*_args(_mesh()))
    assert reg.first_calls[-1]["store"] == "hit"
    assert _entries(store_dir) == [entry]


def test_a_directory_that_cannot_be_used_serves_and_writes_nothing(
        store_dir):
    store_dir.parent.mkdir(parents=True)
    store_dir.write_text("a file where the directory would be")
    reg = _starting()
    for _ in range(2):
        out = _unit(reg, _context(reg))(*_args(_mesh()))
        np.testing.assert_array_equal(np.asarray(out), 3.0)
    assert [r["store"] for r in reg.first_calls] == ["reject", "reject"]
    assert store_dir.is_file()


WRITER = textwrap.dedent("""
    import sys
    sys.path[:0] = [{tests!r}, {repo!r}]
    import conftest  # the tests' platform and devices
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
    from test_program_store import _args, _context, _mesh, _starting, _unit
    reg = _starting()
    for i in range(20):
        _unit(reg, _context(reg), key=i % 2)(*_args(_mesh()))
    print([r["store"] for r in reg.first_calls])
""")


def test_two_processes_writing_one_key_leave_one_whole_entry(store_dir):
    tests = str(pathlib.Path(__file__).parent)
    script = WRITER.format(tests=tests, repo=str(pathlib.Path(tests).parent),
                           cache=os.environ["JAX_COMPILATION_CACHE_DIR"])
    writers = [subprocess.Popen([sys.executable, "-c", script],
                                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                                text=True) for _ in range(2)]
    outs = [w.communicate(timeout=300) for w in writers]
    assert [w.returncode for w in writers] == [0, 0], outs
    assert len(_entries(store_dir)) == 2 and not list(
        store_dir.glob("*.tmp"))
    for out, _ in outs:     # whatever the other wrote was whole when read
        assert "reject" not in out and "hit" in out, outs
    reg = _starting()
    for key in (0, 1):
        out = _unit(reg, _context(reg), key=key)(*_args(_mesh()))
        np.testing.assert_array_equal(np.asarray(out), 3.0)
    assert [r["store"] for r in reg.first_calls] == ["hit", "hit"]


# -- what the key leans on ------------------------------------------------------------

#: Engine modules that read the environment: each before a program is built
#: (a configuration's defaults, the engine's own threshold, a multi-host
#: bring-up, the perf plane's cache directory and cost mode, the hub's
#: message, the store's key). A trace reads none, so the store's key need
#: not hold a variable's MEANING, only its text.
READ_THE_ENVIRONMENT = {"config.py", "engine.py", "multihost.py", "perf.py",
                        "hub.py", "program_store.py"}


def test_no_engine_module_reads_the_environment_under_a_trace():
    reads = set()
    for path in sorted(ENGINE_DIR.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Attribute)
                    and node.attr in ("environ", "getenv", "environb")):
                reads.add(path.name)
    assert reads == READ_THE_ENVIRONMENT
