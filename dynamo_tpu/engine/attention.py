"""Pallas TPU paged-attention decode kernel.

The hot op of the decode step (the role block_copy.cu + engine attention
kernels play on the reference's GPUs). One grid program per (sequence,
kv-head): it walks the sequence's page table (scalar-prefetched into SMEM),
DMAs K/V pages HBM->VMEM in double-buffered chunks of PAGES_PER_CHUNK pages,
and accumulates flash-style online softmax for the q_per_kv grouped query
heads. Only live pages are read — unlike the XLA gather fallback
(model.paged_decode_attention_xla) which touches max_len for every sequence.

Lane packing: Mosaic DMAs want the trailing dim = 128 lanes, but head_dim 64
models (qwen2.5-0.5b etc.) have 64-wide K/V rows. The kernel therefore views
each page as [page_size*D/128, 128] — for D=64 each 128-lane row packs
tpr=2 consecutive tokens — and runs the flash accumulation in packed space:

- queries are pre-expanded to q2 [tpr*qpk, 128] where group t occupies lanes
  [t*D,(t+1)*D) (so dot(q2, K2^T) yields group t's scores against packed
  rows, i.e. tokens r*tpr+t);
- each packed row keeps its own (m, l, acc) flash stats — no cross-group
  communication inside the kernel (Mosaic relayouts across sublane groups
  are fragile); the kernel emits unnormalized acc plus m and l;
- the wrapper merges the tpr groups per head in XLA (standard flash merge:
  rescale by exp(m_t - m*), sum, divide by combined l) and sums the
  per-group lane windows.

For D >= 128 this degenerates (tpr=1) to the natural unpacked layout with
the same merge doing only the final normalization.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.engine.kv_quant import QuantKV

PAGES_PER_CHUNK = 8  # tokens per chunk = 8 * page_size (128 for 16-tok pages)
NEG_INF = -1e30


class _ChunkCopy:
    """Async copy of PAGES_PER_CHUNK K/V pages for one (layer, head, chunk)
    into a VMEM slot (idiom after the stock multi-page copy descriptor)."""

    def __init__(self, hbm_ref, buf, sem, layer, page_table_ref, b, h, chunk,
                 max_pages):
        self._copies = []
        for j in range(PAGES_PER_CHUNK):
            idx = jnp.minimum(chunk * PAGES_PER_CHUNK + j, max_pages - 1)
            pid = page_table_ref[b, idx]
            self._copies.append(pltpu.make_async_copy(
                hbm_ref.at[layer].at[h].at[pid], buf.at[j], sem))

    def start(self):
        for c in self._copies:
            c.start()

    def wait(self):
        for c in self._copies:
            c.wait()


def _decode_kernel(layer_ref, page_table_ref, seq_lens_ref,  # SMEM prefetch
                   q_ref, k_hbm, v_hbm,  # q2 VMEM block; k/v packed (ANY)
                   *rest,  # [ks_ref, vs_ref if quantized], outputs, scratch
                   page_size: int, max_pages: int, tpr: int, qpk: int,
                   quantized: bool = False):
    if quantized:
        # int8 pages; the per-token f32 scales arrive as a VMEM block
        # already laid out per chunk in score space ([chunks, tpr, rows],
        # see _chunk_scales) and multiply the scores / probabilities
        # below — no bf16 copy of the history is ever materialized and
        # the kernel never reshapes a scale vector (Mosaic refuses the
        # [pages, page] -> [rows, tpr] shape cast).
        ks_ref, vs_ref, acc_ref, m_ref, l_ref, k_buf, v_buf, sems = rest
    else:
        acc_ref, m_ref, l_ref, k_buf, v_buf, sems = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    layer = layer_ref[0]
    seq_len = seq_lens_ref[b]
    chunk_tokens = PAGES_PER_CHUNK * page_size
    rows = chunk_tokens // tpr  # packed rows per chunk
    num_chunks = jnp.maximum(1, pl.cdiv(seq_len, chunk_tokens))

    n = tpr * qpk
    q2 = q_ref[0, 0].astype(jnp.float32)  # [n, 128]
    d = 128 // tpr
    scale = 1.0 / (d ** 0.5)

    def make_copies(c, slot):
        return [
            _ChunkCopy(k_hbm, k_buf.at[slot], sems.at[0, slot], layer,
                       page_table_ref, b, h, c, max_pages),
            _ChunkCopy(v_hbm, v_buf.at[slot], sems.at[1, slot], layer,
                       page_table_ref, b, h, c, max_pages)]

    for cp in make_copies(0, 0):
        cp.start()

    # token index of (row-group t, packed row r) is chunk_start + r*tpr + t
    # where t = sublane // qpk.
    group = jax.lax.broadcasted_iota(jnp.int32, (n, rows), 0) // qpk
    row = jax.lax.broadcasted_iota(jnp.int32, (n, rows), 1)

    def score_scales(s_ref, c):
        # Chunk c's scales [tpr, rows] -> [n, rows]: score row t*qpk+i,
        # column r belongs to token r*tpr+t, whose scale is s[t, r]. A
        # sublane broadcast per group; nothing crosses lanes.
        s = s_ref[0, 0, c]
        out = jnp.broadcast_to(s[0:1, :], (n, rows))
        for t in range(1, tpr):
            out = jnp.where(group == t,
                            jnp.broadcast_to(s[t:t + 1, :], (n, rows)), out)
        return out

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
        def _():
            for cp in make_copies(c + 1, jax.lax.rem(c + 1, 2)):
                cp.start()

        for cp in make_copies(c, slot):
            cp.wait()
        k2 = k_buf[slot].astype(jnp.float32).reshape(rows, 128)
        v2 = v_buf[slot].astype(jnp.float32).reshape(rows, 128)
        scores = jax.lax.dot_general(
            q2, k2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [n, rows]
        if quantized:
            # q . (k_int8 * s) == (q . k_int8) * s: dequantize the scores.
            scores = scores * score_scales(ks_ref, c)
        token_idx = c * chunk_tokens + row * tpr + group
        scores = jnp.where(token_idx < seq_len, scores, NEG_INF)
        # Per-row online softmax (groups merged outside the kernel).
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            # p . (v_int8 * s) == (p * s) . v_int8 (l keeps the bare p).
            p = p * score_scales(vs_ref, c)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((n, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, 1), jnp.float32)
    acc0 = jnp.zeros((n, 128), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))
    acc_ref[0, 0] = acc.astype(acc_ref.dtype)
    m_ref[0, 0] = jnp.broadcast_to(m, (n, 128))
    l_ref[0, 0] = jnp.broadcast_to(l, (n, 128))


def _chunk_scales(scale, layer, page_table, tpr: int):
    """Per-token scales of the sequences' pages, gathered in XLA and laid
    out the way the kernel multiplies them: [B, Nkv, chunks, tpr, rows]
    with [c, t, r] = the scale of token c*chunk_tokens + r*tpr + t.
    scale [L, Nkv, P, page] f32. The gather covers the page-table bucket
    (not only live pages), but scales are 4 bytes per token beside D of
    data; page-table padding is masked by seq_len in the kernel."""
    b, maxp = page_table.shape
    nkv, page = scale.shape[1], scale.shape[3]
    chunks = pl.cdiv(maxp, PAGES_PER_CHUNK)
    pt = jnp.pad(page_table, ((0, 0), (0, chunks * PAGES_PER_CHUNK - maxp)))
    # Layer and head stay ADVANCED indices (kv_quant.gather_pages_folded:
    # a basic cache[layer] is a dynamic-slice copy of the pool).
    idx_l = jnp.broadcast_to(layer, (b, nkv, pt.shape[1]))
    idx_n = jnp.arange(nkv)[None, :, None]
    s = scale[idx_l, idx_n, pt[:, None, :]]     # [B, Nkv, pages, page]
    rows = PAGES_PER_CHUNK * page // tpr
    return s.reshape(b, nkv, chunks, rows, tpr).transpose(0, 1, 2, 4, 3)


def _hist_flash_pallas(q, k_cache, v_cache, layer, page_table, hist_lens,
                       q_per_kv, interpret: bool):
    """Run the kernel over the cache-resident history; returns the flash
    triple (num [b,nkv,qpk,d] unnormalized, l_star [b,nkv,qpk,1],
    m_s [b,nkv,qpk,1]) for the wrapper to merge with out-of-cache columns
    (the in-window buffer and/or the current token)."""
    b, nh, d = q.shape
    _, nkv, num_pages, page_size, _ = k_cache.shape
    maxp = page_table.shape[1]
    seq_lens = hist_lens
    q_per_kv = int(q_per_kv)
    if d >= 128:
        # The packed-row math assumes one token per 128-lane row; d > 128
        # would need a multi-row-per-token variant (no current model needs
        # it: Llama/Qwen/Mistral families are all D=64 or D=128).
        assert d == 128, f"head_dim {d} > 128 unsupported by this kernel"
        tpr = 1
    else:
        assert 128 % d == 0 and (page_size * d) % 128 == 0, (
            f"head_dim {d} cannot pack into 128 lanes")
        tpr = 128 // d
    qpk = q_per_kv
    n = tpr * qpk
    rows_per_page = page_size * d // 128

    # Pack the caches: view each page as [rows_per_page, 128] (zero-cost
    # reshape: same row-major layout). int8 pools (QuantKV) pack their
    # data pages the same way; their scales ride in as a blocked VMEM
    # operand (_chunk_scales) and the kernel dequantizes in-register.
    quantized = isinstance(k_cache, QuantKV)
    L = k_cache.shape[0]
    k_pages = k_cache.data if quantized else k_cache
    v_pages = v_cache.data if quantized else v_cache
    kp = k_pages.reshape(L, nkv, num_pages, rows_per_page, 128)
    vp = v_pages.reshape(L, nkv, num_pages, rows_per_page, 128)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    # Expand q: group t occupies rows [t*qpk,(t+1)*qpk) and lanes
    # [t*d,(t+1)*d).
    qg = q.reshape(b, nkv, qpk, d)
    if tpr == 1:
        q2 = qg
    else:
        q2 = jnp.zeros((b, nkv, n, 128), q.dtype)
        for t in range(tpr):
            q2 = q2.at[:, :, t * qpk:(t + 1) * qpk, t * d:(t + 1) * d].set(qg)

    blk = pl.BlockSpec((1, 1, n, tpr * d), lambda i, j, *_: (i, j, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [blk, any_spec, any_spec]
    operands = [q2, kp, vp]
    if quantized:
        ks = _chunk_scales(k_cache.scale, layer, page_table, tpr)
        vs = _chunk_scales(v_cache.scale, layer, page_table, tpr)
        s_blk = pl.BlockSpec((1, 1, *ks.shape[2:]),
                             lambda i, j, *_: (i, j, 0, 0, 0))
        in_specs += [s_blk, s_blk]
        operands += [ks, vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nkv),
        in_specs=in_specs,
        out_specs=(blk, blk, blk),
        scratch_shapes=[
            pltpu.VMEM((2, PAGES_PER_CHUNK, rows_per_page, 128), kp.dtype),
            pltpu.VMEM((2, PAGES_PER_CHUNK, rows_per_page, 128), vp.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_decode_kernel, page_size=page_size,
                               max_pages=maxp, tpr=tpr, qpk=qpk,
                               quantized=quantized)
    shape = jax.ShapeDtypeStruct((b, nkv, n, tpr * d), jnp.float32)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(shape, shape, shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer_arr, page_table, seq_lens, *operands)
    m = m[..., :1]  # broadcast lanes -> scalar stat per row
    l = l[..., :1]
    if tpr == 1:
        num = acc.reshape(b, nkv, qpk, d)
        l_star = l.reshape(b, nkv, qpk, 1)
        m_s = m.reshape(b, nkv, qpk, 1)
    else:
        # Flash-merge the tpr groups of each head, then sum each group's
        # valid lane window.
        acc4 = acc.reshape(b, nkv, tpr, qpk, 128)
        m4 = m.reshape(b, nkv, tpr, qpk, 1)
        l4 = l.reshape(b, nkv, tpr, qpk, 1)
        m_star = jnp.max(m4, axis=2, keepdims=True)
        w = jnp.exp(m4 - m_star)
        l_star = jnp.sum(w * l4, axis=2)  # [b,nkv,qpk,1]
        num = sum((w[:, :, t] * acc4[:, :, t])[..., t * d:(t + 1) * d]
                  for t in range(tpr))  # [b,nkv,qpk,d]
        m_s = m_star.reshape(b, nkv, qpk, 1)
    return num, l_star, m_s


def _merge_extra(q, num, l_star, m_s, k_extra, v_extra, s_mask, q_per_kv):
    """Flash-merge the kernel's history block with explicit extra columns
    (window buffer tokens and/or the current token). k_extra/v_extra
    [b,nkv,J,d]; s_mask [b,1,1,J] bool (True = valid)."""
    b, nh, d = q.shape
    nkv = k_extra.shape[1]
    qpk = q_per_kv
    qg = q.reshape(b, nkv, qpk, d).astype(jnp.float32)
    s = jnp.einsum("bngd,bnjd->bngj", qg,
                   k_extra.astype(jnp.float32)) / (d ** 0.5)
    s = jnp.where(s_mask, s, NEG_INF)
    m_b = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m_b)
    l_b = jnp.sum(p, axis=-1, keepdims=True)
    acc_b = jnp.einsum("bngj,bnjd->bngd", p, v_extra.astype(jnp.float32))
    m_t = jnp.maximum(m_s, m_b)
    w_h = jnp.exp(m_s - m_t)
    w_b = jnp.exp(m_b - m_t)
    out = ((num * w_h + acc_b * w_b)
           / jnp.maximum(l_star * w_h + l_b * w_b, 1e-30))
    return out.astype(q.dtype).reshape(b, nh, d)


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("q_per_kv", "interpret"))
def paged_decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                                  v_cache: jax.Array, layer: jax.Array,
                                  page_table: jax.Array, hist_lens: jax.Array,
                                  k_self: jax.Array, v_self: jax.Array,
                                  q_per_kv: int, interpret: bool = False
                                  ) -> jax.Array:
    """Drop-in replacement for model.paged_decode_attention_xla.

    q [B,Nh,D]; k_cache/v_cache [L,Nkv,P,page,D] (the FULL stacked cache —
    the kernel DMAs pages of the given layer directly, never slicing);
    layer: scalar layer index; page_table [B,maxP]; hist_lens [B] (tokens
    already cache-resident); k_self/v_self [B,Nkv,D] (the new token's K/V,
    merged as an extra flash column outside the kernel). Returns [B,Nh,D].
    Requires page_size*D % 128 == 0 and 128 % D == 0 (packed) or
    D % 128 == 0 (natural). ``interpret`` runs the kernel through the
    Pallas interpreter (the only way it runs on a CPU); the caller decides
    it from the platform its arrays live on (ModelRunner does, once), never
    from the process default — a TPU run compiles through Mosaic or fails.
    """
    b = q.shape[0]
    nkv = k_cache.shape[1]
    num, l_star, m_s = _hist_flash_pallas(q, k_cache, v_cache, layer,
                                          page_table, hist_lens, q_per_kv,
                                          interpret)
    mask = jnp.ones((b, 1, 1, 1), bool)
    return _merge_extra(q, num, l_star, m_s, k_self[:, :, None, :],
                        v_self[:, :, None, :], mask, q_per_kv)


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("q_per_kv", "interpret"))
def paged_window_attention_pallas(q: jax.Array, k_cache: jax.Array,
                                  v_cache: jax.Array, layer: jax.Array,
                                  page_table: jax.Array, hist_lens: jax.Array,
                                  k_win: jax.Array, v_win: jax.Array,
                                  m: jax.Array, k_self: jax.Array,
                                  v_self: jax.Array, q_per_kv: int,
                                  interpret: bool = False) -> jax.Array:
    """Window variant (model.paged_window_attention_xla interface): kernel
    over the cache-resident history + XLA flash-merge of the in-window
    buffer (cols j < m) and the current token. k_win/v_win [Nkv,B,M,D]."""
    b = q.shape[0]
    M = k_win.shape[2]
    num, l_star, m_s = _hist_flash_pallas(q, k_cache, v_cache, layer,
                                          page_table, hist_lens, q_per_kv,
                                          interpret)
    k_extra = jnp.concatenate(
        [k_win.transpose(1, 0, 2, 3), k_self[:, :, None, :]], axis=2)
    v_extra = jnp.concatenate(
        [v_win.transpose(1, 0, 2, 3), v_self[:, :, None, :]], axis=2)
    win_valid = jnp.arange(M)[None, :] < m          # [1,M] (m traced)
    col_mask = jnp.concatenate(
        [jnp.broadcast_to(win_valid, (b, M)),
         jnp.ones((b, 1), bool)], axis=1)[:, None, None, :]
    return _merge_extra(q, num, l_star, m_s, k_extra, v_extra, col_mask,
                        q_per_kv)
