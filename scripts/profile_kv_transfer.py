"""Measure the disaggregation KV data plane on the real chip (ladder
step 3 evidence; round-3 VERDICT weak #4 / next-round #4).

For llama-3-8b-L8 KV shapes (and any BENCH_MODEL preset), measures per
transfer leg, per token:

  extract   — device gather + D2H fetch (runner.extract_pages)
  serialize — v0 parcel path framing (kv_to_chunks: bytes + chunking)
  socket    — direct KV-plane pull over loopback TCP (KvPlaneServer ->
              KvPlaneClient, the NIXL-role path)
  insert    — H2D upload + scatter (runner.insert_pages)

and prints a JSON summary with achieved GB/s per leg plus an
agg-vs-1P1D projection: decode-side TTFT for a remote prefill =
(remote prefill compute ≈ local prefill compute) + transfer legs +
insert, vs local prefill alone — i.e. the disagg TAX per request — and
the decode-throughput headroom freed by moving prefill off the chip
(prefill share of the aggregated engine's step budget).

Run: python scripts/profile_kv_transfer.py            (real chip)
     JAX_PLATFORMS=cpu python scripts/profile_kv_transfer.py  (smoke)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_PAGES = int(os.environ.get("PROF_PAGES", "8"))    # 8 pages x 16 = 128 tok
REPS = int(os.environ.get("PROF_REPS", "5"))
MODEL = os.environ.get("BENCH_MODEL", "llama-3-8b-L8")


def timed(fn, reps=REPS):
    fn()  # warm (compile)
    ts = []
    for _ in range(reps):
        t0 = time.monotonic()
        fn()
        ts.append(time.monotonic() - t0)
    return float(np.median(ts))


def main() -> None:
    import jax

    from dynamo_tpu.engine.config import (EngineConfig, PRESETS,
                                          device_peaks)
    from dynamo_tpu.engine.runner import ModelRunner, PrefillSeq
    from dynamo_tpu.llm.kv_plane import KvPlaneClient, KvPlaneServer
    from dynamo_tpu.llm.kv_transfer import kv_from_chunks, kv_to_chunks

    peaks = device_peaks(jax.devices()[0])
    if peaks is None:
        raise SystemExit("this script times a TPU; jax found the CPU backend")
    spec = PRESETS[MODEL]
    page = 16
    cfg = EngineConfig(model=spec, page_size=page, num_pages=N_PAGES * 4 + 16,
                       max_pages_per_seq=64, max_num_seqs=8,
                       prefill_buckets=(128, 256, 512, 1024),
                       attention_backend="xla")
    runner = ModelRunner(cfg)
    tokens = np.random.default_rng(0).integers(
        0, spec.vocab_size, N_PAGES * page).astype(np.int32)
    pages = list(range(1, N_PAGES + 1))
    runner.prefill_batch([PrefillSeq(
        tokens=tokens, start_pos=0,
        chunk_pages=np.asarray(pages, np.int32), hist_pages=None,
        sampling=(0.0, 0, 1.0))])

    kv = runner.extract_pages(pages)
    nbytes = kv.nbytes
    n_tokens = N_PAGES * page

    t_extract = timed(lambda: runner.extract_pages(pages))
    t_serialize = timed(lambda: kv_to_chunks(kv))
    meta, chunks = kv_to_chunks(kv)
    t_deserialize = timed(lambda: kv_from_chunks(meta, chunks))
    t_insert = timed(lambda: runner.insert_pages(kv, pages))

    # Direct socket path (loopback): stage + pull, reusing one connection.
    server = KvPlaneServer(use_jax_path=False)
    server.start()
    client = KvPlaneClient()

    def socket_leg():
        ticket = server.stage(kv=kv)
        client.pull_sync(ticket)

    t_socket = timed(socket_leg)

    # End-to-end staged paths, extract INCLUDED (what a disagg decode
    # worker actually waits for): single deferred resolve (round-4
    # behavior) vs PIPELINED page groups (round-5: group i rides the
    # wire while group i+1's D2H completes).
    def staged_single():
        h = runner.extract_pages_async(pages)
        ticket = server.stage(
            meta={"shape": list(kv.shape), "dtype": str(kv.dtype)},
            resolve=lambda: runner.finalize_extract(h))
        client.pull_sync(ticket)

    def staged_pipelined(n_groups=4):
        per = -(-len(pages) // n_groups)
        hs = [runner.extract_pages_async(pages[i:i + per])
              for i in range(0, len(pages), per)]
        groups = [(h[1], (lambda hh=h: runner.finalize_extract(hh)))
                  for h in hs]
        ticket = server.stage(
            meta={"shape": list(kv.shape), "dtype": str(kv.dtype)},
            resolve_groups=groups)
        client.pull_sync(ticket)

    t_staged_single = timed(staged_single)
    t_staged_pipelined = timed(staged_pipelined)
    client.close()
    server.close()

    gbps = lambda t: nbytes / t / 1e9 if t else 0.0  # noqa: E731
    # Aggregated engine prefill compute estimate for this prompt: the
    # engine's own weight-read model (the same estimate auto-window uses).
    step_ms = spec.weight_read_step_ms(peaks.hbm_gbps)
    parcel_ms = 1e3 * (t_extract + t_serialize + t_deserialize + t_insert)
    plane_ms = 1e3 * (t_extract + t_socket + t_insert)
    out = {
        "metric": f"kv_transfer_{spec.name}_{N_PAGES}pages",
        "parcel_bytes": nbytes,
        "tokens": n_tokens,
        "extract_ms": round(1e3 * t_extract, 2),
        "extract_gb_s": round(gbps(t_extract), 2),
        "serialize_ms": round(1e3 * (t_serialize + t_deserialize), 2),
        "socket_ms": round(1e3 * t_socket, 2),
        "socket_gb_s": round(gbps(t_socket), 2),
        "insert_ms": round(1e3 * t_insert, 2),
        "insert_gb_s": round(gbps(t_insert), 2),
        "staged_single_ms": round(1e3 * t_staged_single, 2),
        "staged_pipelined_ms": round(1e3 * t_staged_pipelined, 2),
        "pipelining_speedup": round(
            t_staged_single / t_staged_pipelined, 2)
        if t_staged_pipelined else 0.0,
        "parcel_path_ms_total": round(parcel_ms, 2),
        "plane_path_ms_total": round(plane_ms, 2),
        "us_per_token_plane": round(1e3 * plane_ms / n_tokens, 1),
        "kv_bytes_per_token": nbytes // n_tokens,
        "projection": {
            "assumptions": "transfer tax rides the decode-side TTFT of a "
                           "remote prefill; prefill compute itself moves "
                           "off-chip. Decode step estimate = bf16 "
                           "weight-read model (PERF_NOTES roofline).",
            "decode_step_ms_est": round(step_ms, 2),
            "disagg_ttft_tax_ms": round(plane_ms, 2),
            "tax_in_decode_windows_M32": round(plane_ms / (32 * step_ms), 2),
        },
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
