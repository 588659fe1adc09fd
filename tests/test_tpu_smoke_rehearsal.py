"""What PR 21 (bring-up on the local chip) made true, checked on the CPU.

- ``chip_smoke.py --rehearse-cpu`` runs every phase on tiny-test and never
  claims a TPU; without the option it refuses a machine with no TPU.
- The persistent compile cache resolves to ``JAX_COMPILATION_CACHE_DIR`` or
  to a fixed path inside the checkout.
- An accelerator without a row in the peak table is an error; the CPU has
  an explicit "no peak".
- A requested attention backend is what runs, or the runner refuses.
- A warm-up failure fails the engine's start-up.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from dynamo_tpu.engine import model, perf
from dynamo_tpu.engine.backends import choose
from dynamo_tpu.engine.config import (DEVICE_PEAKS, EngineConfig, ModelSpec,
                                      PRESETS, device_peaks)
from dynamo_tpu.engine.runner import ModelRunner

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*argv, timeout=600):
    # JAX_PLATFORMS=cpu and the tests' compile cache come from conftest.
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_rehearsal_runs_every_phase_and_claims_no_tpu():
    proc = _smoke("--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    phases = {line.get("phase") for line in lines[:-1]}
    assert {"preflight", "server.start", "reference", "server.requests",
            "kernels.compare", "disagg", "done"} <= phases, phases
    assert "failed" not in phases
    last = lines[-1]
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert "tpu" not in json.dumps(last).lower()
    compare = [ln for ln in lines if ln.get("phase") == "kernels.compare"]
    assert {c["quant_kv"] for c in compare} == {"bf16", "int8"}
    assert all(c["max_abs_logprob_diff"] <= c["tolerance"] for c in compare)


def test_chip_smoke_refuses_without_a_tpu():
    proc = _smoke(timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line, nothing a driver could read
    assert "needs a TPU" in proc.stderr


def test_compile_cache_dir_resolution(monkeypatch, tmp_path):
    """Inside the placed (or the fixed in-checkout) directory, the
    sub-directory of the scope vocabulary's version: an executable cached
    by a tree with other scopes would be loaded with that tree's names."""
    sub = f"scopes-v{perf.SCOPES_VERSION}"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert perf.compile_cache_dir() == str(tmp_path / sub)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = perf.compile_cache_dir()
    assert fixed == os.path.join(REPO, ".jax_cache", sub)
    assert fixed == perf.compile_cache_dir()  # no pid, no timestamp


def test_configure_compile_cache_stays_inside_the_placed_directory():
    """With the variable set (conftest sets the tests' own directory) the
    cache stays inside that directory; the thresholds drop to zero."""
    import jax
    placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
    got = perf.configure_compile_cache()
    assert os.path.dirname(got) == placed
    assert jax.config.jax_compilation_cache_dir == got
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_peak_table_known_unknown_and_cpu():
    v5e = device_peaks(SimpleNamespace(platform="tpu",
                                       device_kind="TPU v5 lite"))
    assert v5e is DEVICE_PEAKS["TPU v5 lite"]
    assert (v5e.hbm_gbps, v5e.bf16_tflops, v5e.int8_tops) == (819, 197, 393)
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks(SimpleNamespace(platform="tpu", device_kind="TPU v9"))
    # The CPU backend: an explicit "no peak", never a v5e number.
    assert device_peaks(SimpleNamespace(platform="cpu",
                                        device_kind="cpu")) is None


def _tiny(**kw) -> EngineConfig:
    defaults = dict(model=PRESETS["tiny-test"], page_size=16, num_pages=32,
                    max_pages_per_seq=8, max_num_seqs=2,
                    prefill_buckets=(32,), max_prefill_tokens=32)
    defaults.update(kw)
    return EngineConfig(**defaults)


def test_requested_pallas_is_pallas_or_an_error():
    """No silent XLA: a head_dim the kernel cannot pack, or a mesh it
    cannot be partitioned over, refuses at construction (before any weight
    is made); a packable one resolves to the kernel, interpreted on CPU."""
    odd = ModelSpec(name="odd", vocab_size=64, hidden_size=96,
                    intermediate_size=64, num_layers=1, num_heads=2,
                    num_kv_heads=1)  # head_dim 48
    with pytest.raises(ValueError, match="head_dim"):
        ModelRunner(_tiny(model=odd, attention_backend="pallas"))
    with pytest.raises(ValueError, match="one device"):
        ModelRunner(_tiny(attention_backend="pallas", tp=2))
    with pytest.raises(ValueError, match="attention_backend"):
        ModelRunner(_tiny(attention_backend="flash"))
    runner = ModelRunner(_tiny(attention_backend="pallas"))
    assert runner.attention_backend == "pallas"
    assert runner.backends.kv_reader(window=False).keywords == {
        "interpret": True}
    assert ModelRunner(_tiny()).attention_backend == "xla"  # "auto"
    assert runner.hbm_stats() == {}  # the CPU has no memory_stats: explicit


def _picked(platform, mesh_size, head_dim, backend="auto", page_size=16):
    """backends.choose on a stubbed platform and mesh: the choice reads only
    platform, mesh size, head_dim and page size. Returns (the reader's
    name, who attends in the single step, who attends in a window's)."""
    config = SimpleNamespace(attention_backend=backend, page_size=page_size,
                             max_pages_per_seq=8, spec_decode=None)
    spec = SimpleNamespace(head_dim=head_dim, latent=False, recurrent=False,
                           compressed_keys=False,
                           index_topk=0, num_experts=0, num_heads=28,
                           num_kv_heads=4)
    record = choose(config, spec, platform, mesh_size, None)
    return (record.attention, model.kv_attention(record, window=False),
            model.kv_attention(record, window=True))


@pytest.mark.parametrize("platform, mesh_size, head_dim, want", [
    ("tpu", 1, 128, "pallas"),   # the benchmark cell: one v5e, qwen2.5-7b
    ("tpu", 1, 64, "xla"),       # packs, but the packed view copies the pool
    ("tpu", 1, 48, "xla"),       # a head the kernel cannot pack
    ("tpu", 4, 128, "xla"),      # tp / dp / pp / sp: no partitioning rule
    ("tpu", 2, 64, "xla"),
    ("cpu", 1, 128, "xla"),      # the CPU would interpret the kernel
    ("cpu", 8, 64, "xla"),
    ("gpu", 1, 128, "xla"),      # Mosaic TPU kernels compile for a TPU
])
def test_auto_attention_is_decided_from_platform_mesh_and_head(
        platform, mesh_size, head_dim, want):
    backend, step, window = _picked(platform, mesh_size, head_dim)
    assert backend == want
    if want == "pallas":
        # Compiled through Mosaic on the chip, never interpreted there.
        assert step.keywords == window.keywords == {"interpret": False}
        assert step.func.__name__ == "paged_decode_attention_pallas"
        assert window.func.__name__ == "paged_window_attention_pallas"
    else:
        assert step.__name__ == "paged_decode_attention_xla"
        assert window.__name__ == "paged_window_attention_xla"


@pytest.mark.parametrize("platform, mesh_size, head_dim, match", [
    ("tpu", 4, 128, "one device"),
    ("tpu", 1, 48, "head_dim"),
    ("cpu", 2, 64, "one device"),
])
def test_requested_pallas_that_cannot_be_had_is_still_an_error(
        platform, mesh_size, head_dim, match):
    with pytest.raises(ValueError, match=match):
        _picked(platform, mesh_size, head_dim, backend="pallas")
    # ... and an explicit "xla" is XLA wherever it runs.
    assert _picked(platform, mesh_size, head_dim, backend="xla")[0] == "xla"


def test_auto_attention_on_a_real_cpu_runner_and_mesh_is_xla():
    assert ModelRunner(_tiny()).attention_backend == "xla"
    assert ModelRunner(_tiny(tp=2)).attention_backend == "xla"


def test_warmup_failure_fails_startup(monkeypatch):
    from dynamo_tpu.engine.engine import TPUEngine

    def boom(self):
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(TPUEngine, "_warmup_window_programs", boom)
    engine = TPUEngine(_tiny(warmup_windows=True))
    engine.start()
    with pytest.raises(RuntimeError, match="start-up failed") as err:
        engine.wait_ready(timeout=60)
    assert "kernel refused" in str(err.value)
    with pytest.raises(RuntimeError, match="start-up failed"):
        engine.start()  # generate() starts lazily: it must not hang either
    engine.stop()
