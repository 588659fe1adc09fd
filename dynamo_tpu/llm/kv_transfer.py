"""KV parcel serialization for disaggregated prefill->decode transfer.

The host-staged v0 data plane (SURVEY.md §5.8): the prefill worker extracts
the prompt's KV pages ([2, L, Nkv, n_pages, page, D] bf16), serializes them,
and streams them INLINE over the request plane as chunked response frames —
the role NIXL RDMA plays in the reference (lib/llm/src/block_manager/storage/
nixl.rs; vllm handlers.py kv_transfer_params). A device-to-device ICI path
(jax.experimental.transfer) can replace the wire format transparently later:
the metadata contract (shape + dtype + chunk count) stays.

TP-mismatch handling: the parcel is the FULL unsharded KV — the decode
worker's mesh re-shards on upload (runner.insert_pages), so 1-TP prefill ->
2-TP decode works without the reference's block_copy.cu transpose kernel.
"""

from __future__ import annotations

import time
from typing import AsyncIterator

import numpy as np

from dynamo_tpu.runtime.tracing import span

CHUNK_BYTES = 8 << 20  # 8 MiB response frames

_DTYPES = {"bfloat16": None, "float32": np.float32, "float16": np.float16}


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def foreign_pages(kv_shape, page_size: int) -> str | None:
    """Why a parcel [2, L, Nkv, n, page, D] cannot enter a pool of
    ``page_size``-token pages, or None. Pages of another size are refused
    and never reshaped: a page is the allocator's unit, the prefix cache's
    hash block and the router's block, so workers that exchange pages
    resolve the same page (they do when they share a configuration and a
    platform: EngineConfig.resolve_page_size)."""
    if kv_shape[4] == page_size:
        return None
    return (f"KV parcel holds pages of {kv_shape[4]} tokens and this "
            f"worker's pool pages of {page_size}: refused, not reshaped "
            f"(give both workers the same --page-size)")


def kv_to_chunks(kv: np.ndarray) -> tuple[dict, list[bytes]]:
    """Serialize a KV parcel: returns (meta, chunk list)."""
    raw = np.ascontiguousarray(kv).tobytes()
    chunks = [raw[i:i + CHUNK_BYTES] for i in range(0, len(raw), CHUNK_BYTES)]
    if not chunks:
        chunks = [b""]
    meta = {"shape": list(kv.shape), "dtype": str(kv.dtype),
            "n_chunks": len(chunks)}
    return meta, chunks


def kv_from_chunks(meta: dict, chunks: list[bytes]) -> np.ndarray:
    assert len(chunks) == meta["n_chunks"], (len(chunks), meta)
    dtype = (_bf16() if meta["dtype"] == "bfloat16"
             else np.dtype(meta["dtype"]))
    raw = b"".join(chunks)
    return np.frombuffer(raw, dtype=dtype).reshape(meta["shape"])


async def collect_prefill_response(stream: AsyncIterator[dict],
                                   plane_client=None,
                                   metrics=None, page_size: int | None = None
                                   ) -> tuple[int, np.ndarray]:
    """Assemble a prefill worker's response into (first_token, kv parcel).

    Two wire forms: a transfer TICKET (the worker staged the parcel on
    the direct KV data plane, llm/kv_plane.py — pull the bulk bytes
    there), or inline chunks (the v0 host-staged path, still emitted by
    plane-less workers). ``metrics`` (a tracing.PhaseMetrics) feeds the
    kv_transfer_seconds/bytes histograms; the recv span records either
    way. ``page_size`` (the receiving pool's): a parcel of another page
    size is refused here, by name (foreign_pages)."""
    import asyncio

    t0 = time.monotonic()
    with span("kv.transfer.recv") as sp:
        chunks: list[bytes] = []
        meta = None
        ticket = None
        first_token = None
        pull_task: asyncio.Task | None = None
        try:
            async for out in stream:
                dp = out.get("disagg_params") or {}
                if "ticket" in dp and pull_task is None \
                        and plane_client is not None:
                    # Start pulling the MOMENT the ticket lands: with a
                    # chunk-streamed prefill worker the ticket precedes
                    # the first token, so the bulk KV bytes cross the
                    # wire while the remaining chunks still compute —
                    # the transfer tax hides behind prefill instead of
                    # serializing after it.
                    ticket = dp["ticket"]
                    pull_task = asyncio.ensure_future(
                        plane_client.pull(ticket))
                elif "ticket" in dp:
                    ticket = dp["ticket"]
                if "kv_chunk" in dp:
                    chunks.append(dp["kv_chunk"])
                if "shape" in dp:
                    meta = dp
                toks = out.get("token_ids") or []
                if toks:
                    first_token = toks[0]
        except BaseException:
            # The stream died with a pull in flight (prefill aborted
            # mid-chunk): don't leak the executor-backed task.
            if pull_task is not None:
                pull_task.cancel()
            raise
        if first_token is None or (meta is None and ticket is None):
            if pull_task is not None:
                pull_task.cancel()
            raise RuntimeError("incomplete disaggregated prefill response")
        if ticket is not None:
            if plane_client is None:
                raise RuntimeError(
                    "prefill worker sent a KV-plane ticket but this worker "
                    "has no plane client")
            kv = await pull_task
            sp.set(path="plane", nbytes=int(kv.nbytes))
        else:
            kv = kv_from_chunks(meta, chunks)
            sp.set(path="inline", nbytes=int(kv.nbytes),
                   chunks=len(chunks))
    refusal = page_size and foreign_pages(kv.shape, page_size)
    if refusal:
        raise RuntimeError(refusal)
    if metrics is not None:
        metrics.kv_transfer.observe(time.monotonic() - t0,
                                    direction="recv")
        metrics.kv_transfer_bytes.observe(kv.nbytes, direction="recv")
    return first_token, kv
