"""int8 KV-cache quantization (``--quant-kv int8``).

Decode at long context is bound by attention bandwidth — every step reads
the sequence's whole cache-resident history from HBM — and the pool's
page count caps concurrent sequences per chip. ``--quant int8`` halved
the weight side of the bandwidth budget (engine/quant.py); this module
halves the KV side, KIVI-style: paged K/V blocks store int8 with one
float32 absmax scale PER TOKEN PER HEAD (per-page scale rows — the
scales array is indexed [L, Nkv, page_id, page_off] right beside the
pages), dequantized in the same fused expression that reads them:

- the Pallas decode kernel (engine/attention.py) DMAs int8 pages plus the
  small scale rows HBM->VMEM and dequantizes in-register — no bf16 copy
  of the history is ever materialized;
- the XLA gather paths multiply the gathered pages by the gathered
  scales, which XLA fuses into the gather consumer;
- quantization is fused into every KV write: the prefill page scatter
  and the per-window decode commit scatter quantize in-graph (an int8
  pool's window program keeps that scatter; a bf16 pool under the Pallas
  kernel commits in place, see scatter_tokens).

Per-token scales (not one scale per page) are what make the decode
commit correct: a page fills across multiple windows, and a
whole-page absmax could not be recomputed without reading the page
back. Cost: 4 bytes per (layer, kv-head, token) next to head_dim int8
bytes — ~1.9x pool compression at head_dim 64–128, so ~2x resident
slots per HBM GB (PageAllocator pages at equal budget).

Wire/tier parcel format: host-side parcels pack data + scales into one
uint8 array ``[..., page, head_dim + 4]`` (the last 4 "lanes" are the
f32 scale bytes), so every existing parcel path — host/disk tiers,
KV-plane tickets, G4 block fetches, np.stack/slicing — carries the
compressed form unchanged, at ~half the bf16 bytes. ``pack_parcel`` /
``unpack_parcel`` are the codec; a parcel's dtype says which form it is
(uint8 = packed int8+scales, bfloat16 = raw).

QuantKV is a NamedTuple, hence a pytree: jit signatures, donation and
sharding trees compose without special cases, exactly like QTensor.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

# f32 scale bytes appended per (layer, head, token) row in packed parcels.
KV_SCALE_BYTES = 4


class QuantKV(NamedTuple):
    """int8 paged KV pool + per-token-per-head scales.

    data  int8    [L, Nkv, P, page, D]
    scale float32 [L, Nkv, P, page]
    """
    data: Any
    scale: Any

    @property
    def shape(self):
        # The logical (value) shape: call sites size buffers and read
        # page/head dims off ``cache.shape`` exactly as for a bf16 pool.
        return self.data.shape

    @property
    def dtype(self):
        # The VALUE dtype: buffers holding unquantized K/V (window
        # buffers, the self column) allocate with ``cache.dtype``.
        import jax.numpy as jnp

        return jnp.bfloat16


def is_quantized(cache) -> bool:
    return isinstance(cache, QuantKV)


# ---------------------------------------------------------------------------
# Traceable quantize/dequantize (inside jitted programs)
# ---------------------------------------------------------------------------

def kv_quantize(x):
    """Symmetric per-token absmax int8 over the last (head_dim) axis.
    x [..., D] -> (q int8 [..., D], s float32 [...]). All-zero rows get
    s=1 so dequant stays exact (matches quantize_weight's convention)."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def kv_dequantize(q, s):
    """(int8 [..., D], f32 [...]) -> bf16 [..., D]."""
    import jax.numpy as jnp

    return (q.astype(jnp.float32) * s[..., None]).astype(jnp.bfloat16)


def gather_pages(cache, idx_l, page_table):
    """The layer-folded history gather ``cache[idx_l, :, page_table]``
    ([B, maxP, Nkv, page, D] bf16), dequantizing int8 pools in the same
    expression (XLA fuses the scale multiply into the gather consumer)."""
    if isinstance(cache, QuantKV):
        return kv_dequantize(cache.data[idx_l, :, page_table],
                             cache.scale[idx_l, :, page_table])
    return cache[idx_l, :, page_table]


def gather_pages_folded(cache, layer, page_table):
    """History gather with the LAYER AND HEAD axes both folded into one
    gather: ``[Nkv, B, maxP*page, D]`` — exactly the attention dot's
    K/V operand layout. gather_pages' natural output puts the advanced
    (batch, page) indices first, so every attention consumer paid a
    ``transpose(2,0,1,3,4)`` relayout of the WHOLE gathered history —
    a full extra HBM round-trip per step per cache. A gather is already
    arbitrary data movement, so asking it for the permuted layout
    directly is free; the reshape that follows is contiguous (no copy).
    The layer index stays an ADVANCED index on purpose — a basic
    ``cache[layer]`` scalar index is a dynamic-slice copy of cache/L
    (the 50 ms-per-step failure mode gather_pages exists to avoid)."""
    import jax.numpy as jnp

    b, maxp = page_table.shape
    data = cache.data if isinstance(cache, QuantKV) else cache
    nkv, page, d = data.shape[1], data.shape[3], data.shape[4]
    idx_l = jnp.broadcast_to(layer, (nkv, b, maxp))
    idx_n = jnp.arange(nkv)[:, None, None]
    pt = jnp.broadcast_to(page_table[None], (nkv, b, maxp))
    if isinstance(cache, QuantKV):
        out = kv_dequantize(cache.data[idx_l, idx_n, pt],
                            cache.scale[idx_l, idx_n, pt])
    else:
        out = cache[idx_l, idx_n, pt]
    return out.reshape(nkv, b, maxp * page, d)


def scatter_pages(cache, blocks, flat_pages):
    """Whole-page commit ``cache.at[:, :, flat_pages].set(blocks)`` with
    quantization fused in for int8 pools. blocks [L, Nkv, n, page, D]."""
    if isinstance(cache, QuantKV):
        q, s = kv_quantize(blocks)
        return QuantKV(cache.data.at[:, :, flat_pages].set(q),
                       cache.scale.at[:, :, flat_pages].set(s))
    if blocks.shape[0] != cache.shape[0]:
        # The model's layers of a pool that holds a prediction module's
        # layer behind them (ModelSpec.pool_layers).
        return cache.at[:blocks.shape[0], :, flat_pages].set(blocks)
    return cache.at[:, :, flat_pages].set(blocks)


def window_token_slots(positions0, cap, seq_lens0, page_table, window: int,
                       page_size: int):
    """(dest, off) [M, B] for scatter_tokens: the pool page and the in-page
    row of token m of row b of a decode window, which lands at position
    positions0[b] + m while that is under cap[b] and the row is live
    (seq_lens0[b] > 0); frozen and inactive entries land on scratch page 0,
    row 0."""
    import jax.numpy as jnp

    m_idx = jnp.arange(window)[:, None]                              # [M,1]
    adv = jnp.clip(jnp.minimum(m_idx, cap[None, :] - positions0), 0, None)
    pos_m = positions0[None, :] + adv                                # [M,B]
    live_m = (seq_lens0[None, :] > 0) & (pos_m < cap[None, :])
    pidx = jnp.clip(pos_m // page_size, 0, page_table.shape[1] - 1)
    dest = jnp.take_along_axis(
        jnp.broadcast_to(page_table[None], (window, *page_table.shape)),
        pidx[:, :, None], axis=2)[:, :, 0]                           # [M,B]
    return jnp.where(live_m, dest, 0), jnp.where(live_m, pos_m % page_size, 0)


def scatter_tokens(cache, vals, dest, off):
    """Per-token commit ``cache.at[:, :, dest, off].set(vals)`` with
    quantization fused in. vals [L, Nkv, ..., D]; dest/off broadcastable
    index arrays.

    Who still calls it: the single-step ``model.decode_forward``, the
    speculative window (``runner._get_spec_window``), and the decode window
    program wherever ``Backends.kv_commit`` is "scatter": a mesh, a
    packed head (head_dim 64), an int8 pool, the CPU under "auto". On a TPU
    XLA's scatter wants the pool as ``{4,1,3,2,0:T(4,128)}`` and converts a
    row-major pool in and out, two pool-sized copies per cache per call
    (PERF.md 6, PR 29). That is paid where the reader is XLA's gather too,
    and not where the Pallas kernel reads a plain bf16 pool at head_dim
    128: there the window program writes its pages in place
    (``attention.commit_window_pallas``). Each of the others waits for a
    reader whose layout its writer can match: int8 tiles are 32 rows (a
    page of 32 or more tokens holds them, an int8 pool still resolves a
    page of 16: config.resolve_page_size) and the scales are a second
    array; a packed head's pool
    rests lane-padded (ROADMAP D3); the kernel has no partitioning rule."""
    if isinstance(cache, QuantKV):
        q, s = kv_quantize(vals)
        return QuantKV(cache.data.at[:, :, dest, off].set(q),
                       cache.scale.at[:, :, dest, off].set(s))
    if vals.shape[0] != cache.shape[0]:     # as scatter_pages
        return cache.at[:vals.shape[0], :, dest, off].set(vals)
    return cache.at[:, :, dest, off].set(vals)


# ---------------------------------------------------------------------------
# Host-side (numpy) twins + the packed parcel codec
# ---------------------------------------------------------------------------

def quantize_np(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host twin of kv_quantize (f32 math, round-half-even like
    jnp.round, so host- and device-quantized blocks agree bit-for-bit)."""
    xf = np.asarray(x, np.float32)
    amax = np.max(np.abs(xf), axis=-1)
    s = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(xf / s[..., None]), -127, 127).astype(np.int8)
    return q, s


def dequantize_np(q: np.ndarray, s: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return (q.astype(np.float32) * np.asarray(s, np.float32)[..., None]) \
        .astype(ml_dtypes.bfloat16)


def pack_parcel(data: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """(int8 [..., page, D], f32 [..., page]) -> uint8 [..., page, D+4].
    One contiguous array so every tier/wire path (np.stack, page-axis
    slicing, msgpack raw bytes) carries the compressed form unchanged."""
    d = data.shape[-1]
    out = np.empty((*data.shape[:-1], d + KV_SCALE_BYTES), np.uint8)
    out[..., :d] = data.view(np.uint8)
    out[..., d:] = np.ascontiguousarray(
        np.asarray(scale, np.float32)).view(np.uint8) \
        .reshape(*scale.shape, KV_SCALE_BYTES)
    return out


def unpack_parcel(packed: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """uint8 [..., page, D+4] -> (int8 [..., page, D], f32 [..., page])."""
    d = packed.shape[-1] - KV_SCALE_BYTES
    data = np.ascontiguousarray(packed[..., :d]).view(np.int8)
    scale = np.ascontiguousarray(packed[..., d:]).view(np.float32)[..., 0]
    return data, scale


def is_packed_parcel(arr: np.ndarray) -> bool:
    """Parcel form by dtype: uint8 = packed int8+scales, else raw bf16."""
    return arr.dtype == np.uint8


def parcel_to_bf16(arr: np.ndarray) -> np.ndarray:
    return dequantize_np(*unpack_parcel(arr)) if is_packed_parcel(arr) \
        else arr


def parcel_to_packed(arr: np.ndarray) -> np.ndarray:
    return arr if is_packed_parcel(arr) else pack_parcel(*quantize_np(arr))
