"""Pallas TPU paged-attention decode kernels.

The hot op of the decode step (the role block_copy.cu + engine attention
kernels play on the reference's GPUs), and what attention_backend="auto"
runs on one TPU device: over K and V pages at head_dim 128 (_decode_kernel,
below), over a pool of latent entries (_latent_kernel, further down: the
same walk and the same fetch pipeline over ONE array whose rows are key and
value, under the indexer's choice as a mask). One grid program per sequence: it
walks the sequence's page table (scalar-prefetched into SMEM), DMAs the
live K/V pages HBM->VMEM in chunks, three buffers deep (pages_per_chunk pages,
one strided copy per page across all KV heads), and accumulates
flash-style online softmax for the q_per_kv grouped query heads of every
KV head, bf16 operands into float32 scores, statistics and accumulator. The chunk
pipeline runs on from one sequence's program into the next, and a slot
without history fetches nothing. Only live pages of live rows are read —
unlike the XLA gather (model.paged_window_attention_xla), which
materializes the page-table bucket of the longest row for every slot.
Each page copy is issued from a scalar loop after the turn that frees its
buffer (a fetch cursor walks the live rows' chunks SLOTS turns ahead of the
multiplication, so copies are in flight through a row's end and the grid's
step: PERF.md section 6, PR 33), so where this kernel reads the pool the
page is as large as makes one copy worth its descriptor
(config.resolve_page_size: 64 tokens at 4 KV heads of 128; PERF.md section
6, PR 31: 2.77 ms at pages of 16, 2.00 at 64, of which the dots are 1.35).

A chunk turn comes in two forms, chosen by reader_turn from the reader's
shapes (PR 49). "rows": a KV head at a time, its query rows against the
head's chunk; what every geometry of 7 or 16 query rows a head takes.
"heads": where a head has one or two query rows (Ouro-2.6B: 16 KV heads of
ONE) the rows turn is 2 x nkv small products and nkv flash updates over
tiles that use an eighth of their sublanes, 1.80 us a turn of 16 heads
with no page copied; there the chunk buffer as it lies, [heads x tokens,
128], streams past ONE tile of all heads' queries, a constant mask keeps
lane h of head h's slab, the folded tile is transposed to [heads, tokens],
which is the rows turn's layout for one head of nkv rows, the flash update
runs once, and every head's page of V meets all heads' probabilities (its
own rows kept): 1.01 us of products, under the 1.50 us its page copies
take (scripts/kv_reader_bench.py; PERF.md section 6, PR 49).

Measured on one v5e (PERF.md section 6, PR 26), attention of one decode
step of Qwen2.5-7B, 17 live rows of 32 at about 950 tokens: the gather
21.4 ms, this kernel 3 ms (as it stood before that PR, a program per
(row, head), 8-page chunks, float32 dots: 9.0 ms).

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

What the packing costs on the chip (PERF.md, PR 26): the [page, 64] ->
[page/2, 128] view is free in row-major memory and NOT on a TPU, where the
pool rests lane-padded; XLA copies the whole pool into the packed layout
around the kernel, once per layer (qwen2.5-0.5b: 247 ms a decode step
against 8.5 through the gather). So the packed variant is correct, is
compiled and compared on the chip by chip_smoke.py, and is not what "auto"
selects; it waits for a lane-dense pool (ROADMAP D3).

The writer beside the reader (commit_window_pallas, PR 29): the decode
window's commit, for the pool this kernel reads as it lies (plain bf16,
head_dim 128, one device). XLA's scatter wants the pool in another layout
and converted it in and out, four pool-sized copies a window; the commit
kernel copies in, merges and copies back only the pages a live row's window
touched, all layers and KV heads of a page in one strided copy, the pools
aliased to its outputs. Measured on one v5e (PERF.md section 6, PR 29), 18
live rows of 32, both pools of the Qwen2.5-7B cell (2 x 2.8 GB): the
scatter 33.7 ms a window, this 0.30 ms.

The reader of a latent pool (latent_history_pallas, PR 35): the DeepSeek-V3.2
block's decode attention in the absorbed form. What a token leaves in a
layer is ONE entry of 640 lanes (the latent 512, the rope key 64, zeros)
that every one of the 128 heads reads as its key, whole, and as its value,
the first 512 lanes: the queries are the dot's 128 sublanes, a page is one
copy of 80 KB, a chunk 8 pages. The indexer's choice of 2,048 keys a row
(model.select_topk, XLA's) comes in as an additive bias a token, so the
kernel walks every live page and masks; it gathers no chosen row (26 ns a
gathered row, PERF.md section 6, PR 34). Measured on one v5e (PERF.md
section 6, PR 35): 17 live rows of 32 slots at 3,000 to 5,000 tokens, nine
layers: XLA's walk (the bucket of all slots gathered, read twice more)
11.3 ms a step, this 1.7.

The indexer's scores over a latent pool (latent_index_pallas, PR 37): the
same walk over the pool's other array. A row's live pages of index keys (16
KB a copy, 32 pages a turn) meet its 64 index queries in one product a 512
tokens, then the ReLU and the sum over the heads under the float32 head
weights: one float32 score a key, model.index_scores' value. The choice
over them stays model.select_topk's, in XLA. XLA's indexer gathers every
slot's whole bucket and scores the copy: alone on one v5e, nine layers, 32
slots, a bucket of 5,120 tokens, 1.45 ms a step at 17 live rows and 1.46 at
32, this 0.73 and 1.18 (PERF.md section 6, PR 37, call 3).

The scores of a row's stripes (stripe_scores_pallas, PR 46): the same walk
over the compressed-key array of a block that attends chosen BLOCKS of keys
(ModelSpec.comp_key_shape: a page's stripes, the means of every
sparse_stride keys, are [8, 128] bfloat16 a KV head, whole tiles of the
array as the chip holds it). A live row's pages come in one copy of both
heads' stripes each, 32 pages a turn, and a KV group's 16 query heads meet
a chunk's 256 stripes in one product, bfloat16 into float32, written where
hybrid.choose_blocks reads them; the choice over them stays XLA's. XLA's
path gathers every slot's whole bucket of stripes and scores the copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.engine.kv_quant import QuantKV

#: K (and as much V) one chunk holds across all KV heads of its pages. The
#: chunk is what one loop turn fetches, waits for and multiplies: large
#: enough that the turn's fixed costs (loop, semaphore waits, a flash
#: update per head) are paid once per few hundred tokens, small enough
#: that SLOTS buffers of K and of V stay a few MB of VMEM at any head count.
CHUNK_BYTES = 512 * 1024
MIN_CHUNK_TOKENS = 128     # a chunk's tokens are the scores' lanes: one tile
MAX_PAGES_PER_CHUNK = 64
NEG_INF = -1e30
#: Chunk buffers of the K/V pipeline: one being multiplied and two in
#: flight. With two, a chunk's copies could start only when the turn before
#: it had finished with the buffer, and the copy engine stood through every
#: row's end; the third keeps it fed (PERF.md section 6, PR 33: attention
#: of a Qwen2.5-7B step 3.20 -> 2.78 ms; a fourth adds nothing).
SLOTS = 3
#: Token rows one entry of the window's commit moves: a bfloat16 tile's
#: sublanes, and the page the commit was measured at (PERF.md, PR 29).
COMMIT_TILE = 16
#: What one call of the window's commit may hold in VMEM (its tiles of both
#: pools and its blocks of both windows, every layer it moves): half of a
#: v5e's 16 MiB scoped default. Over it commit_window_pallas commits by
#: layer ranges.
COMMIT_VMEM_BYTES = 8 << 20


def pages_per_chunk(page_size: int, nkv: int, d: int, itemsize: int) -> int:
    """Pages one chunk holds: the power of two that fills CHUNK_BYTES with
    the pages' K across all heads, from MIN_CHUNK_TOKENS of pages up to
    MAX_PAGES_PER_CHUNK. At Qwen2.5-7B's 4 x 128 bf16 a chunk is 512
    tokens whatever the page (32 pages of 16, 8 of 64); at Llama-3-8B's
    8 x 128 it is 256 tokens; small or int8 pages of 16 tokens come 64 to
    a chunk."""
    fit = CHUNK_BYTES // (nkv * page_size * d * itemsize)
    ppc = max(1, MIN_CHUNK_TOKENS // page_size)
    while ppc * 2 <= min(fit, MAX_PAGES_PER_CHUNK):
        ppc *= 2
    return ppc


def reader_turn(q_per_kv: int, nkv: int, tpr: int, quantized: bool) -> str:
    """Which chunk turn _decode_kernel takes, from the reader's shapes alone:
    "rows" (a KV head at a time: its q_per_kv query rows against the head's
    chunk, scores and values, a flash update a head) or "heads" (every
    head's keys stream past ONE tile of all queries, the flash update runs
    once over [heads x rows, tokens], and every head's page of V meets all
    of them: _heads_scores, _heads_values).

    Placed by the reader alone on one v5e (scripts/kv_reader_bench.py;
    PERF.md section 6, PR 49, call 2): four rows of 200 to 1,000 tokens,
    pages of 16, microseconds a chunk turn, rows | heads, and in brackets
    the same with no page copied (the products alone):

        KV heads x query rows   rows   heads    [rows   heads]
        16 x 1 (Ouro-2.6B)      2.14   1.54     [1.80   1.01]
         8 x 1                  2.62   1.84     [2.02   1.18]
        32 x 1                  3.86   2.90     [3.40   1.74]
        16 x 2                  2.17   1.59     [1.83   1.15]
         4 x 1                  2.56   2.56     [1.54   1.51]
         4 x 7 (Qwen2.5-7B)     2.69   2.66     [1.65   1.61]
         4 x 7 at pages of 64   1.40   1.49     [1.08   1.12]
         8 x 16 (Command A+)    2.77   2.66     [2.13   2.02]
         2 x 16 (Nemotron)      3.23   3.45     [1.68   1.94]

    At one or two query rows a head the rows turn spends itself on 2 x nkv
    small products and nkv flash updates over tiles that use one or two of
    their eight sublanes; from 8 heads up the heads turn is a quarter to
    three tenths shorter and then waits for its page copies (1.50 us a turn
    at 16 x 1 with both products taken out). At 4 heads a turn is 64 page
    copies of 16 KB and either form waits for them; at 7 or 16 rows a head
    the rows turn's tiles are full and the heads turn buys nothing (level
    to a fifteenth worse), so every such geometry stays where it was."""
    if quantized or tpr != 1:
        # An int8 page's scales multiply scores laid [rows of one head,
        # tokens], and a packed head's row groups are not heads: neither
        # was measured on the heads turn.
        return "rows"
    return ("heads" if q_per_kv <= 2 and nkv >= 8 and nkv * q_per_kv <= 128
            else "rows")


def _heads_scores(q, k_all, nkv: int, qpk: int, rows: int):
    """Every head's scores of a chunk in ONE product with the keys as the
    streamed operand: k_all [nkv * tokens, 128] (the chunk buffer as it
    lies) against q [128, 128] (row h * qpk + i: query i of head h; zeros
    past the heads). Row (h, t) of the product holds head h's scores in
    lanes h * qpk .. and other heads' queries against this head's keys in
    the others, which a constant mask drops as the nkv slabs fold into one
    tile [tokens, rows]; its transpose is what the parent's turn holds for
    ONE head of ``rows`` query rows: [rows, tokens] float32."""
    tokens = k_all.shape[0] // nkv
    r = jax.lax.dot_general(k_all, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    head = jax.lax.broadcasted_iota(jnp.int32, (tokens, 128), 1) // qpk
    s = r[:tokens]
    for h in range(1, nkv):
        s = jnp.where(head == h, r[h * tokens:(h + 1) * tokens], s)
    return s.T[:rows]


def _heads_values(p, v_of, nkv: int, qpk: int):
    """The value product of all heads' probabilities p [rows, tokens]: a
    head's page of V is still the MXU's weights (v_of(h): [tokens, 128]),
    but all rows stream past it and the head's own rows are kept."""
    head = jax.lax.broadcasted_iota(jnp.int32, (p.shape[0], 128), 0) // qpk
    out = None
    for h in range(nkv):
        v = v_of(h)
        o = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = o if out is None else jnp.where(head == h, o, out)
    return out


def _fetch_pipeline(page_table_ref, seq_lens_ref, cur_ref, pools, sems,
                    layer, nb, page_size: int, first_chunk):
    """The page fetch both readers run, ONE pipeline across the whole grid:
    chunk g (counted over the live rows' chunks in row order) lands in slot
    g % SLOTS of every pool's buffer, and a fetch cursor in SMEM (``cur_ref``:
    row, chunk, turn) walks the live rows' chunks SLOTS turns ahead of the
    multiplication. ``pools``: (array in HBM [L, heads, P, ...], its buffer
    [slot, heads, pages, ...]) pairs that share a page table, pool s on the
    semaphores ``sems[s]``; ``first_chunk(row)``: where a live row's walk
    starts. Returns (issue_fetch, wait_fetch, prime)."""
    ppc = pools[0][1].shape[2]
    chunk_tokens = ppc * page_size

    def live_pages(row, chunk):
        """How many of the chunk's ppc pages hold tokens of this row."""
        return jnp.minimum(
            ppc, pl.cdiv(seq_lens_ref[row], page_size) - chunk * ppc)

    def start_fetch(row, chunk, slot):
        """Per live page of the chunk ONE strided copy of its [heads, page]
        rows from each pool, all on the slot's semaphores."""
        def one(j, carry):
            pid = page_table_ref[row, chunk * ppc + j]
            for s, (hbm, buf) in enumerate(pools):
                pltpu.make_async_copy(hbm.at[layer, :, pid],
                                      buf.at[slot, :, j],
                                      sems.at[s, slot]).start()
            return carry

        jax.lax.fori_loop(0, live_pages(row, chunk), one, 0)

    def wait_fetch(row, chunk, slot):
        """A DMA semaphore counts bytes, so the page copies of a slot are
        awaited in powers of two of pages (a full chunk: one wait a pool)
        instead of page by page; only a wait's shape and semaphore matter,
        not where its descriptor points."""
        count = live_pages(row, chunk)
        bit = ppc
        while bit:
            @pl.when((count & bit) != 0)
            def _(bit=bit):
                for s, (hbm, buf) in enumerate(pools):
                    pltpu.make_async_copy(hbm.at[layer, :, pl.ds(0, bit)],
                                          buf.at[slot, :, pl.ds(0, bit)],
                                          sems.at[s, slot]).wait()
            bit //= 2

    def next_live(after, searching=True):
        """The first row past ``after`` with history, or nb."""
        return jax.lax.while_loop(
            lambda i: (i < nb) & (seq_lens_ref[jnp.minimum(i, nb - 1)] == 0),
            lambda i: i + 1, jnp.where(searching, after + 1, nb))

    def issue_fetch():
        """Start the copies of the fetch cursor's chunk (turn k of the
        grid's walk, into slot k % SLOTS) and move the cursor to the chunk
        after it: this row's next, or the next live row's first."""
        row, chunk, k = cur_ref[0], cur_ref[1], cur_ref[2]

        @pl.when(row < nb)
        def _():
            start_fetch(row, chunk, jax.lax.rem(k, SLOTS))
            more = chunk + 1 < pl.cdiv(seq_lens_ref[row], chunk_tokens)
            nxt = next_live(row, ~more)
            cur_ref[0] = jnp.where(more, row, nxt)
            cur_ref[1] = jnp.where(
                more, chunk + 1, first_chunk(jnp.minimum(nxt, nb - 1)))
            cur_ref[2] = k + 1

    def prime():
        """The cursor at the first live row's first chunk; every slot
        primed."""
        first = next_live(-1)
        cur_ref[0] = first
        cur_ref[1] = first_chunk(jnp.minimum(first, nb - 1))
        cur_ref[2] = 0
        jax.lax.fori_loop(0, SLOTS, lambda _, c: (issue_fetch(), c)[1], 0)

    return issue_fetch, wait_fetch, prime


def _decode_kernel(layer_ref, page_table_ref, seq_lens_ref,  # SMEM prefetch
                   *rest,  # [lo_ref if windowed], q2 VMEM block, k/v packed
                   # (ANY), [ks_ref, vs_ref if quantized], outputs, scratch
                   page_size: int, tpr: int, qpk: int,
                   quantized: bool = False, windowed: bool = False,
                   heads: bool = False):
    """One grid program per batch row, all KV heads inside it. The K/V
    fetch is ONE pipeline across the whole grid (_fetch_pipeline): while
    chunk g is multiplied the chunks after it are in flight, be they this
    row's next chunks or the next live rows' first, and a turn that frees
    its buffer issues the cursor's chunk into it. A row with no history
    costs an empty grid step; only live pages are ever copied.

    ``windowed`` (a model with sliding-window layers): a fourth prefetched
    vector, lo [B], is the first token each row's query still sees in THIS
    layer (0 in a full layer). The walk starts at the chunk that holds lo,
    so chunks wholly before the window are never fetched, and tokens before
    lo inside that chunk are masked.

    ``heads`` (reader_turn's "heads"; tpr 1, bfloat16 pages): q_ref is
    [1, heads padded to a bfloat16 tile, 128] and so are the outputs; a
    turn multiplies every head's keys in one product (_heads_scores), runs
    the flash update ONCE over [heads, tokens] and takes the values of all
    heads' probabilities (_heads_values). The walk, the fetch pipeline, the
    masks and the update's arithmetic are the other turn's."""
    if windowed:
        lo_ref, *rest = rest
    q_ref, k_hbm, v_hbm, *rest = rest
    if quantized:
        # int8 pages; the per-token f32 scales arrive as a VMEM block
        # already laid out per chunk in score space ([chunks, tpr, rows],
        # see _chunk_scales) and multiply the scores / probabilities
        # below — no bf16 copy of the history is ever materialized and
        # the kernel never reshapes a scale vector (Mosaic refuses the
        # [pages, page] -> [rows, tpr] shape cast).
        ks_ref, vs_ref, *rest = rest
    acc_ref, m_ref, l_ref, k_buf, v_buf, sems, g_ref, cur_ref = rest
    _, nkv, ppc, _, _ = k_buf.shape  # [slot, Nkv, pages, rows/page, 128]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    seq_len = seq_lens_ref[b]
    chunk_tokens = ppc * page_size
    rows = chunk_tokens // tpr  # packed rows per chunk
    num_chunks = pl.cdiv(seq_len, chunk_tokens)

    def first_chunk(r):
        """The chunk a LIVE row's walk starts at (0 without windows)."""
        return lo_ref[r] // chunk_tokens if windowed else 0

    chunk0 = jnp.where(seq_len > 0, first_chunk(b), 0)  # a dead row: 0..0

    # Score rows of one flash update: a KV head's query rows, or all heads'.
    n = q_ref.shape[1] if heads else tpr * qpk
    d = 128 // tpr
    scale = 1.0 / (d ** 0.5)

    @pl.when(b == 0)
    def _():
        # Chunk turns computed so far; and finite K/V under the masked
        # columns of a chunk's unfetched tail (0 * stale NaN would poison
        # acc).
        g_ref[0] = 0
        for slot in range(SLOTS):
            for h in range(nkv):
                k_buf[slot, h] = jnp.zeros(k_buf.shape[2:], k_buf.dtype)
                v_buf[slot, h] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    issue_fetch, wait_fetch, prime = _fetch_pipeline(
        page_table_ref, seq_lens_ref, cur_ref,
        ((k_hbm, k_buf), (v_hbm, v_buf)), sems, layer, nb, page_size,
        first_chunk)

    # token index of (row-group t, packed row r) is chunk_start + r*tpr + t
    # where t = sublane // qpk.
    group = (0 if heads else
             jax.lax.broadcasted_iota(jnp.int32, (n, rows), 0) // qpk)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, rows), 1)

    def score_scales(s_ref, h, c):
        # Chunk c's scales [tpr, rows] -> [n, rows]: score row t*qpk+i,
        # column r belongs to token r*tpr+t, whose scale is s[t, r]. A
        # sublane broadcast per group; nothing crosses lanes.
        s = s_ref[0, h, c]
        out = jnp.broadcast_to(s[0:1, :], (n, rows))
        for t in range(1, tpr):
            out = jnp.where(group == t,
                            jnp.broadcast_to(s[t:t + 1, :], (n, rows)), out)
        return out

    def pages_of(buf, slot, h, dtype):
        x = buf[slot, h]  # [ppc, rows_per_page, 128]
        if x.dtype != dtype:
            # int8 pages (every int8 is a bf16): through f32, the one
            # integer conversion every TPU generation's VPU has.
            x = x.astype(jnp.float32).astype(dtype)
        return x.reshape(rows, 128)

    pl.when(b == 0)(prime)

    if heads:
        # All heads' queries as the scores' stationary tile, once a row.
        q_all = q_ref[0]
        if n < 128:
            q_all = jnp.concatenate(
                [q_all, jnp.zeros((128 - n, 128), q_all.dtype)], axis=0)

    def body(c, carry, g0):
        slot = jax.lax.rem(g0 + c - chunk0, SLOTS)
        wait_fetch(b, c, slot)
        token_idx = c * chunk_tokens + row * tpr + group
        live = token_idx < seq_len
        if windowed:
            live = live & (token_idx >= lo_ref[b])
        if heads:
            m, l, acc = carry
            scores = _heads_scores(
                q_all, k_buf[slot].reshape(nkv * rows, 128), nkv, qpk,
                n) * scale
            scores = jnp.where(live, scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + _heads_values(
                p, lambda h: pages_of(v_buf, slot, h, q_all.dtype), nkv, qpk)
            issue_fetch()
            return m_new, l_new, acc_new
        out = []
        for h in range(nkv):
            m, l, acc = carry[3 * h:3 * h + 3]
            q2 = q_ref[0, h]  # [n, 128]
            # bf16 operands, f32 accumulation: what the XLA path's einsums
            # do, and one MXU pass where an f32 product takes several.
            k2 = pages_of(k_buf, slot, h, q2.dtype)
            v2 = pages_of(v_buf, slot, h, q2.dtype)
            scores = jax.lax.dot_general(
                q2, k2, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [n, rows]
            if quantized:
                # q . (k_int8 * s) == (q . k_int8) * s: dequantize the scores.
                scores = scores * score_scales(ks_ref, h, c)
            scores = jnp.where(live, scores, NEG_INF)
            # Per-row online softmax (groups merged outside the kernel).
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                # p . (v_int8 * s) == (p * s) . v_int8 (l keeps the bare p).
                p = p * score_scales(vs_ref, h, c)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(v2.dtype), v2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out += [m_new, l_new, acc_new]
        # The slot is free: the chunk SLOTS turns on takes it, and flies
        # under the next turns, a row's end and the grid's step.
        issue_fetch()
        return tuple(out)

    init = (jnp.full((n, 1), NEG_INF, jnp.float32),
            jnp.zeros((n, 1), jnp.float32),
            jnp.zeros((n, 128), jnp.float32)) * (1 if heads else nkv)

    # A row without history walks no chunk and emits the neutral triple,
    # which the wrapper's merge weighs exp(-inf).
    g0 = g_ref[0]
    stats = jax.lax.fori_loop(chunk0, num_chunks,
                              functools.partial(body, g0=g0), init)
    g_ref[0] = g0 + num_chunks - chunk0
    if heads:
        m, l, acc = stats
        acc_ref[0] = acc
        m_ref[0] = jnp.broadcast_to(m, (n, 128))
        l_ref[0] = jnp.broadcast_to(l, (n, 128))
        return
    for h in range(nkv):
        m, l, acc = stats[3 * h:3 * h + 3]
        acc_ref[0, h] = acc
        m_ref[0, h] = jnp.broadcast_to(m, (n, 128))
        l_ref[0, h] = jnp.broadcast_to(l, (n, 128))


def _chunk_scales(scale, layer, page_table, tpr: int, ppc: int):
    """Per-token scales of the sequences' pages, gathered in XLA and laid
    out the way the kernel multiplies them: [B, Nkv, chunks, tpr, rows]
    with [c, t, r] = the scale of token c*chunk_tokens + r*tpr + t.
    scale [L, Nkv, P, page] f32. The gather covers the page-table bucket
    (not only live pages), but scales are 4 bytes per token beside D of
    data; page-table padding is masked by seq_len in the kernel."""
    b, maxp = page_table.shape
    nkv, page = scale.shape[1], scale.shape[3]
    chunks = pl.cdiv(maxp, ppc)
    pt = jnp.pad(page_table, ((0, 0), (0, chunks * ppc - maxp)))
    # Layer and head stay ADVANCED indices (kv_quant.gather_pages_folded:
    # a basic cache[layer] is a dynamic-slice copy of the pool).
    idx_l = jnp.broadcast_to(layer, (b, nkv, pt.shape[1]))
    idx_n = jnp.arange(nkv)[None, :, None]
    s = scale[idx_l, idx_n, pt[:, None, :]]     # [B, Nkv, pages, page]
    rows = ppc * page // tpr
    return s.reshape(b, nkv, chunks, rows, tpr).transpose(0, 1, 2, 4, 3)


def _hist_flash_pallas(q, k_cache, v_cache, layer, page_table, hist_lens,
                       q_per_kv, interpret: bool, lo=None):
    """Run the kernel over the cache-resident history; returns the flash
    triple (num [b,nkv,qpk,d] unnormalized, l_star [b,nkv,qpk,1],
    m_s [b,nkv,qpk,1]) for the wrapper to merge with out-of-cache columns
    (the in-window buffer and/or the current token). ``lo`` [B] (None: a
    model without window layers, whose kernel has no such operand): the
    first history token each row still sees."""
    b, nh, d = q.shape
    _, nkv, num_pages, page_size, _ = k_cache.shape
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
    ppc = pages_per_chunk(page_size, nkv, d, kp.dtype.itemsize)
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

    heads = reader_turn(qpk, nkv, tpr, quantized) == "heads"
    if heads:
        # All heads' rows in one tile, h * qpk + i as q has them, padded
        # with zero queries to whole bfloat16 tiles (none at 16 heads).
        n = -(-nkv * qpk // 16) * 16
        q2 = q if n == nkv * qpk else jnp.pad(
            q, ((0, 0), (0, n - nkv * qpk), (0, 0)))

    windowed = lo is not None
    # A row whose window starts past its history sees none of it.
    prefetch = [layer_arr, page_table,
                seq_lens if not windowed
                else jnp.where(lo < seq_lens, seq_lens, 0)]
    if windowed:
        prefetch.append(jnp.minimum(lo, jnp.maximum(seq_lens - 1, 0))
                        .astype(jnp.int32))
    if heads:
        blk = pl.BlockSpec((1, n, 128), lambda i, *_: (i, 0, 0))
    else:
        blk = pl.BlockSpec((1, nkv, n, 128), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [blk, any_spec, any_spec]
    operands = [q2, kp, vp]
    if quantized:
        ks = _chunk_scales(k_cache.scale, layer, page_table, tpr, ppc)
        vs = _chunk_scales(v_cache.scale, layer, page_table, tpr, ppc)
        s_blk = pl.BlockSpec((1, *ks.shape[1:]),
                             lambda i, *_: (i, 0, 0, 0, 0))
        in_specs += [s_blk, s_blk]
        operands += [ks, vs]
    buf = pltpu.VMEM((SLOTS, nkv, ppc, rows_per_page, 128), kp.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs,
        out_specs=(blk, blk, blk),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, SLOTS)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.SMEM((3,), jnp.int32)],
    )
    kernel = functools.partial(_decode_kernel, page_size=page_size, tpr=tpr,
                               qpk=qpk, quantized=quantized,
                               windowed=windowed, heads=heads)
    shape = jax.ShapeDtypeStruct((b, n, 128) if heads else (b, nkv, n, 128),
                                 jnp.float32)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(shape, shape, shape),
        # Sequential by construction: the fetch pipeline and its chunk
        # counter run from one row's program into the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *operands)
    if heads:
        acc, m, l = (x[:, :nkv * qpk] for x in (acc, m, l))
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
                                  q_per_kv: int, interpret: bool = False,
                                  lo: jax.Array | None = None) -> jax.Array:
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
                                          interpret, lo)
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
                                  interpret: bool = False,
                                  lo: jax.Array | None = None) -> jax.Array:
    """Window variant (model.paged_window_attention_xla interface): kernel
    over the cache-resident history + XLA flash-merge of the in-window
    buffer (cols j < m) and the current token. k_win/v_win [Nkv,B,M,D].
    ``lo`` [B]: the first position a row still sees (window layers)."""
    b = q.shape[0]
    M = k_win.shape[2]
    num, l_star, m_s = _hist_flash_pallas(q, k_cache, v_cache, layer,
                                          page_table, hist_lens, q_per_kv,
                                          interpret, lo)
    k_extra = jnp.concatenate(
        [k_win.transpose(1, 0, 2, 3), k_self[:, :, None, :]], axis=2)
    v_extra = jnp.concatenate(
        [v_win.transpose(1, 0, 2, 3), v_self[:, :, None, :]], axis=2)
    win_valid = jnp.arange(M)[None, :] < m          # [1,M] (m traced)
    if lo is not None:  # column j stands at position hist_lens + j
        win_valid = win_valid & (hist_lens[:, None] + jnp.arange(M)[None, :]
                                 >= lo[:, None])
    col_mask = jnp.concatenate(
        [jnp.broadcast_to(win_valid, (b, M)),
         jnp.ones((b, 1), bool)], axis=1)[:, None, None, :]
    return _merge_extra(q, num, l_star, m_s, k_extra, v_extra, col_mask,
                        q_per_kv)


def _latent_kernel(layer_ref, page_table_ref, seq_lens_ref,  # SMEM prefetch
                   q_ref, *rest,               # [bias_ref if masked], then
                   # e_hbm: the pool (ANY); acc_ref, m_ref, l_ref: outputs;
                   # e_buf, sems, g_ref, cur_ref: scratch
                   page_size: int, scale: float, masked: bool = True):
    """The reader of a LATENT pool, one grid program per batch row: every
    head's absorbed query (q_ref [1, Nh, width]: the dot's left side, Nh
    sublanes) against the row's live pages of latent entries, which are key
    AND value: ONE copy a page into e_buf [slot, 1, pages, page, width]
    through _fetch_pipeline, the score over the whole row (the query's
    padding lanes are zeros), the value the row's first lanes, as many as
    acc_ref [1, Nh, value lanes] is wide: a slice of the buffer at a lane
    tile's edge. Which keys a row attends comes in as bias_ref [1, chunks,
    1, chunk tokens] float32, 0 at an attended key and NEG_INF at every other
    (the indexer's choice, or every key in context; nothing past the row's
    length is ever attended), so the kernel walks every LIVE page and masks:
    it compares no token index and gathers no chosen row. Out come the
    unnormalised sum and the running maximum and sum, for XLA to merge with
    the window's own columns and the self token.

    Not ``masked`` (a block without an indexer: latent_block_pallas): no
    bias operand; a key is attended iff its index in the row lies in
    [layer_ref[1], seq_lens_ref[row]), compared against an iota of the
    chunk's lanes, and q_ref's rows are every query position's heads."""
    if masked:
        bias_ref, *rest = rest
    e_hbm, acc_ref, m_ref, l_ref, e_buf, sems, g_ref, cur_ref = rest
    ppc = e_buf.shape[2]
    width, value = e_buf.shape[4], acc_ref.shape[2]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    chunk_tokens = ppc * page_size
    num_chunks = pl.cdiv(seq_lens_ref[b], chunk_tokens)
    issue_fetch, wait_fetch, prime = _fetch_pipeline(
        page_table_ref, seq_lens_ref, cur_ref, ((e_hbm, e_buf),), sems,
        layer_ref[0], nb, page_size, lambda row: 0)

    @pl.when(b == 0)
    def _():
        # Finite entries under the masked columns of a chunk's unfetched
        # tail, as _decode_kernel has them.
        g_ref[0] = 0
        for slot in range(SLOTS):
            e_buf[slot] = jnp.zeros(e_buf.shape[1:], e_buf.dtype)
        prime()

    nh = q_ref.shape[1]
    q = q_ref[0]
    acc_ref[0] = jnp.zeros((nh, value), jnp.float32)

    def body(c, carry, g0):
        m, l = carry
        slot = jax.lax.rem(g0 + c, SLOTS)
        wait_fetch(b, c, slot)
        e = e_buf[slot, 0].reshape(chunk_tokens, width)
        scores = jax.lax.dot_general(
            q, e, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            scores = scores + bias_ref[0, c]
        else:
            key = c * chunk_tokens + jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk_tokens), 1)
            scores = jnp.where(
                (key >= layer_ref[1]) & (key < seq_lens_ref[b]), scores,
                NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        v = e_buf[slot, 0, :, :, :value].reshape(chunk_tokens, value)
        acc_ref[0] = acc_ref[0] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        issue_fetch()
        return m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True)

    g0 = g_ref[0]
    m, l = jax.lax.fori_loop(
        0, num_chunks, functools.partial(body, g0=g0),
        (jnp.full((nh, 1), NEG_INF, jnp.float32),
         jnp.zeros((nh, 1), jnp.float32)))
    g_ref[0] = g0 + num_chunks
    m_ref[0] = jnp.broadcast_to(m, m_ref.shape[1:])
    l_ref[0] = jnp.broadcast_to(l, l_ref.shape[1:])


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _latent_flash(qe, e_cache, layer, page_table, hist_lens, bias,
                  scale: float, rank: int, interpret: bool):
    """_latent_kernel over bias [B, chunks, 1, chunk tokens]. Its own jit:
    callers whose operands have one shape share ONE trace of the kernel
    (latent_history_pallas)."""
    b, nh, width = qe.shape
    page_size = e_cache.shape[3]
    _, chunks, _, tokens = bias.shape
    value = pl.cdiv(rank, 128) * 128    # whole lane tiles of the entry
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, nh, width), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((1, chunks, 1, tokens),
                               lambda i, *_: (i, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec((1, nh, value), lambda i, *_: (i, 0, 0)),)
        + (pl.BlockSpec((1, nh, 128), lambda i, *_: (i, 0, 0)),) * 2,
        scratch_shapes=[
            pltpu.VMEM((SLOTS, 1, tokens // page_size, page_size, width),
                       e_cache.dtype),
            pltpu.SemaphoreType.DMA((1, SLOTS)),
            pltpu.SMEM((1,), jnp.int32), pltpu.SMEM((3,), jnp.int32)],
    )
    stat = jax.ShapeDtypeStruct((b, nh, 128), jnp.float32)
    acc, m, l = pl.pallas_call(
        functools.partial(_latent_kernel, page_size=page_size, scale=scale),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, nh, value), jnp.float32),
                   stat, stat),
        # Sequential: the fetch pipeline runs from one row into the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), page_table, hist_lens,
      qe, bias, e_cache)
    return acc[..., :rank], m[..., 0], l[..., 0]


def latent_history_pallas(qe: jax.Array, e_cache: jax.Array,
                          layer: jax.Array, page_table: jax.Array,
                          hist_lens: jax.Array, keep: jax.Array,
                          scale: float, rank: int, interpret: bool = False,
                          table: int | None = None):
    """Flash attention of the absorbed queries qe [B, Nh, width] over the
    cache-resident history of a latent pool e_cache [L, 1, P, page, width]
    (the FULL stacked pool: the kernel copies pages of ``layer``), keys
    where ``keep`` [B, maxP * page] (bool; nothing at or past hist_lens [B])
    says so, scores times ``scale``, the value an entry's first ``rank``
    lanes. Returns (unnormalised sum [B, Nh, rank], maximum [B, Nh], sum
    [B, Nh]), float32: what model.latent_window_attention merges with the
    columns that are not in the pool yet. ``interpret`` as in
    paged_decode_attention_pallas.

    ``table`` (pages): the widest page table its caller ever passes. The
    table and the mask are padded to it, so the window programs of every
    page-table bucket and both of a program's layer scans call ONE kernel
    and trace it once a process: a trace of the kernel took 0.3 s on the
    benchmark's host, twice a program, thirteen programs a start-up
    (PERF.md section 6, PR 35). The kernel walks live pages alone, so the
    width costs it a mask block of 4 B a token of the table a row."""
    b = qe.shape[0]
    page_size, width = e_cache.shape[3], e_cache.shape[4]
    # An entry is key and value in one: a chunk holds the bytes of a K chunk
    # and a V chunk together (8 pages of 64 x 640: 512 tokens; on one v5e
    # the walk took 2.52 ms a step at 4 pages, 1.94 at 8 and 1.84 at 16).
    ppc = pages_per_chunk(page_size, 1, width // 2, e_cache.dtype.itemsize)
    tokens = ppc * page_size
    maxp = page_table.shape[1]
    pages = max(table or 0, maxp)
    chunks = pl.cdiv(pages * page_size, tokens)
    bias = jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)
    bias = jnp.pad(bias, ((0, 0), (0, chunks * tokens - keep.shape[1])),
                   constant_values=NEG_INF).reshape(b, chunks, 1, tokens)
    page_table = jnp.pad(page_table, ((0, 0), (0, pages - maxp)))
    return _latent_flash(qe, e_cache, layer, page_table, hist_lens, bias,
                         scale=scale, rank=rank, interpret=interpret)


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("scale", "rank", "tokens",
                                             "interpret"))
def _latent_block_flash(qe, e_cache, layer_lo, page_table, hist_lens,
                        scale: float, rank: int, tokens: int,
                        interpret: bool):
    """_latent_kernel without its mask over qe [B, rows, width]; layer_lo
    int32 [2] (the pool's layer, the first slot attended). Its own jit, as
    _latent_flash."""
    b, rows, width = qe.shape
    page_size = e_cache.shape[3]
    value = pl.cdiv(rank, 128) * 128
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, rows, width), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec((1, rows, value), lambda i, *_: (i, 0, 0)),)
        + (pl.BlockSpec((1, rows, 128), lambda i, *_: (i, 0, 0)),) * 2,
        scratch_shapes=[
            pltpu.VMEM((SLOTS, 1, tokens // page_size, page_size, width),
                       e_cache.dtype),
            pltpu.SemaphoreType.DMA((1, SLOTS)),
            pltpu.SMEM((1,), jnp.int32), pltpu.SMEM((3,), jnp.int32)],
    )
    stat = jax.ShapeDtypeStruct((b, rows, 128), jnp.float32)
    acc, m, l = pl.pallas_call(
        functools.partial(_latent_kernel, page_size=page_size, scale=scale,
                          masked=False),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, rows, value), jnp.float32),
                   stat, stat),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer_lo, page_table, hist_lens, qe, e_cache)
    return acc[..., :rank], m[..., 0], l[..., 0]


#: Query rows of a slot are padded to a multiple of this for the block
#: reader: a bfloat16 tile's sublanes.
BLOCK_ROW_TILE = 16


def latent_block_pallas(qe: jax.Array, e_cache: jax.Array, layer: jax.Array,
                        page_table: jax.Array, hist_lens: jax.Array,
                        scale: float, rank: int, lo: int = 0,
                        interpret: bool = False, table: int | None = None):
    """``latent_history_pallas`` for a latent block WITHOUT an indexer and
    a block of query positions a slot: qe [B, S * Nh, width] (every
    position's absorbed heads: a row's live pages are walked ONCE for all
    of them), every key of slots ``lo`` to hist_lens - 1 attended, so no
    mask operand: the kernel compares a key's index with the row's bounds.
    The rows are padded with zeros to a multiple of BLOCK_ROW_TILE (40
    rows of 2 x 20 heads to 48: a fifth more of the kernel's arithmetic,
    which rides under the page copies, and 16 KB more of accumulator a
    slot) and cut off again. ``table`` as latent_history_pallas: the page
    table is padded to it so that every bucket's window program and both
    layer scans call one kernel. Returns (unnormalised sum [B, S * Nh,
    rank], maximum, sum [B, S * Nh]), float32."""
    b, rows, width = qe.shape
    page_size = e_cache.shape[3]
    ppc = pages_per_chunk(page_size, 1, width // 2, e_cache.dtype.itemsize)
    maxp = page_table.shape[1]
    page_table = jnp.pad(page_table,
                         ((0, 0), (0, max(table or 0, maxp) - maxp)))
    pad = -rows % BLOCK_ROW_TILE
    qe = jnp.pad(qe, ((0, 0), (0, pad), (0, 0)))
    layer_lo = jnp.stack([jnp.asarray(layer, jnp.int32),
                          jnp.asarray(lo, jnp.int32)])
    acc, m, l = _latent_block_flash(
        qe, e_cache, layer_lo, page_table, hist_lens, scale=scale,
        rank=rank, tokens=ppc * page_size, interpret=interpret)
    return acc[:, :rows], m[:, :rows], l[:, :rows]


#: Tokens of index keys one turn of the indexer's kernel fetches and waits
#: for: four of the reader's chunks. An index-key page is a fifth of an
#: entry's bytes and a turn's arithmetic a tenth of the reader's, so the
#: turn's fixed costs (cursor, waits) show sooner: on one v5e, nine layers,
#: 17 live rows of 32 at 3,000 to 5,020 tokens, the indexer with its choice
#: took 0.93 ms a step at 1,024 tokens a turn, 0.92 at 2,048 and 0.92 at
#: 4,096 (PERF.md section 6, PR 37, call 3).
INDEX_CHUNK_TOKENS = 2048
#: Tokens one product of that kernel scores: [64 heads, 512] float32 is half
#: the vector registers.
INDEX_SCORE_TOKENS = 512


def _index_kernel(layer_ref, page_table_ref, seq_lens_ref,  # SMEM prefetch
                  iq_ref, iw_ref, k_hbm,    # VMEM blocks; the pool (ANY)
                  out_ref,                  # output
                  k_buf, sems, g_ref, cur_ref,  # scratch
                  *, page_size: int):
    """The indexer's scores over a latent pool, one grid program per batch
    row: the row's index query (iq_ref [1, J, Di] bfloat16, the dot's J
    sublanes) against its LIVE pages of index keys, ONE copy a page into
    k_buf [slot, 1, pages, page, Di] through _fetch_pipeline. A chunk is
    scored out_ref's last axis of tokens at a time: the products in
    bfloat16 into float32, the ReLU, and the sum over the heads under the
    row's float32 head weights (iw_ref [J, rows on the lanes], every row's,
    whole in VMEM: a [J, 1] block a row would reach the kernel padded to
    128 lanes, a copy of 1 MB a layer): model.index_scores. Out go the
    scores of what the row walked, out_ref [1, table tokens / tokens,
    tokens] float32; a part of a chunk past the row's length is not scored
    and what lies there is its caller's to mask."""
    ppc, di = k_buf.shape[2], k_buf.shape[4]
    tokens = out_ref.shape[2]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    chunk_tokens = ppc * page_size
    parts, part_pages = chunk_tokens // tokens, tokens // page_size
    seq_len = seq_lens_ref[b]
    num_chunks = pl.cdiv(seq_len, chunk_tokens)
    issue_fetch, wait_fetch, prime = _fetch_pipeline(
        page_table_ref, seq_lens_ref, cur_ref, ((k_hbm, k_buf),), sems,
        layer_ref[0], nb, page_size, lambda row: 0)

    @pl.when(b == 0)
    def _():
        g_ref[0] = 0
        prime()

    iq = iq_ref[0]
    weights = iw_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, weights.shape, 1)
    iw = jnp.sum(jnp.where(lane == b, weights, 0.0), axis=1, keepdims=True)

    def body(c, carry, g0):
        slot = jax.lax.rem(g0 + c, SLOTS)
        wait_fetch(b, c, slot)
        for j in range(parts):
            @pl.when(c * chunk_tokens + j * tokens < seq_len)
            def _(j=j):
                keys = k_buf[slot, 0, j * part_pages:(j + 1) * part_pages]
                dots = jax.lax.dot_general(
                    iq, keys.reshape(tokens, di), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [J, tokens]
                out_ref[0, pl.ds(c * parts + j, 1), :] = jnp.sum(
                    jnp.maximum(dots, 0.0) * iw, axis=0, keepdims=True)
        issue_fetch()
        return carry

    g0 = g_ref[0]
    jax.lax.fori_loop(0, num_chunks, functools.partial(body, g0=g0), 0)
    g_ref[0] = g0 + num_chunks


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("tokens", "chunk_tokens",
                                             "interpret"))
def _latent_index(iq, iw, i_cache, layer, page_table, hist_lens,
                  tokens: int, chunk_tokens: int, interpret: bool):
    """_index_kernel over a page table of whole chunks, iw [J, lanes]. Its
    own jit, as _latent_flash."""
    b, heads, di = iq.shape
    page_size = i_cache.shape[3]
    parts = page_table.shape[1] * page_size // tokens
    return pl.pallas_call(
        functools.partial(_index_kernel, page_size=page_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, heads, di), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(iw.shape, lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, parts, tokens),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((SLOTS, 1, chunk_tokens // page_size, page_size,
                            di), i_cache.dtype),
                pltpu.SemaphoreType.DMA((1, SLOTS)),
                pltpu.SMEM((1,), jnp.int32), pltpu.SMEM((3,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, parts, tokens), jnp.float32),
        # Sequential: the fetch pipeline runs from one row into the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), page_table, hist_lens,
      iq, iw, i_cache)


def latent_index_pallas(iq: jax.Array, iw: jax.Array, i_cache: jax.Array,
                        layer: jax.Array, page_table: jax.Array,
                        hist_lens: jax.Array, interpret: bool = False,
                        table: int | None = None) -> jax.Array:
    """The decode indexer's scores of the cache-resident index keys of a
    latent pool: index queries iq [B, J, Di] under head weights iw [B, J]
    float32 against i_cache [L, 1, P, page, Di] of ``layer`` (the FULL
    stacked pool: the kernel copies pages), a row's first hist_lens [B]
    tokens under ``page_table`` [B, maxP]. Returns [B, maxP * page]
    float32, model.index_scores' value (bfloat16 products, float32 sums;
    the heads summed in another order) at every token a row holds and
    UNDEFINED past it: model.select_topk takes them under its ``valid``.
    XLA's indexer gathers every slot's whole bucket and scores the copy;
    this reads a row's live pages once (PERF.md section 6, PR 37).
    ``table``, ``interpret``: as latent_history_pallas (one trace of the
    kernel for every page-table bucket)."""
    b, heads = iw.shape
    page_size = i_cache.shape[3]
    tokens = max(INDEX_SCORE_TOKENS, page_size)
    chunk_tokens = max(INDEX_CHUNK_TOKENS, tokens)
    maxp = page_table.shape[1]
    pages = pl.cdiv(max(table or 0, maxp) * page_size, chunk_tokens) \
        * chunk_tokens // page_size
    page_table = jnp.pad(page_table, ((0, 0), (0, pages - maxp)))
    iw = jnp.pad(iw.T, ((0, 0), (0, pl.cdiv(b, 128) * 128 - b)))
    scores = _latent_index(iq, iw, i_cache, layer, page_table, hist_lens,
                           tokens=tokens, chunk_tokens=chunk_tokens,
                           interpret=interpret)
    return scores.reshape(b, -1)[:, :maxp * page_size]


#: Tokens whose stripes one turn of the stripes' kernel fetches, waits for
#: and scores: 32 pages of 128 at a stride of 16 are 256 scores a head, two
#: lane tiles of the output, and a copy of 128 KB.
STRIPE_CHUNK_TOKENS = 4096


def _stripe_kernel(layer_ref, page_table_ref, seq_lens_ref,  # SMEM prefetch
                   q_ref, c_hbm,            # VMEM block; the array (ANY)
                   out_ref,                 # output
                   c_buf, sems, g_ref, cur_ref,  # scratch
                   *, page_size: int):
    """The scores of a row's stripes (the compressed-key array of a block
    that attends chosen blocks of keys: ModelSpec.comp_key_shape), one grid
    program per batch row: every KV group's query heads (q_ref [1, Nkv, Hg,
    D] bfloat16, a group's heads the dot's sublanes) against the row's LIVE
    pages of stripes, ONE strided copy a page of both heads' [stripes a
    page, D] into c_buf [slot, Nkv, pages, stripes, D] through
    _fetch_pipeline; a chunk's stripes are one product a KV head, bfloat16
    into float32, and stripe i of the row lands at out_ref[0, n, :, i]: what
    hybrid.choose_blocks takes. A row without tokens walks nothing, and what
    lies past a row's pages (a chunk's tail, a dead slot's whole block) is
    whatever the buffers held: its caller's to mask."""
    nkv, ppc, per, d = c_buf.shape[1:]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    chunk_tokens = ppc * page_size
    lanes = ppc * per
    num_chunks = pl.cdiv(seq_lens_ref[b], chunk_tokens)
    issue_fetch, wait_fetch, prime = _fetch_pipeline(
        page_table_ref, seq_lens_ref, cur_ref, ((c_hbm, c_buf),), sems,
        layer_ref[0], nb, page_size, lambda row: 0)

    @pl.when(b == 0)
    def _():
        g_ref[0] = 0
        prime()

    def body(c, carry, g0):
        slot = jax.lax.rem(g0 + c, SLOTS)
        wait_fetch(b, c, slot)
        for n in range(nkv):
            out_ref[0, n, :, pl.ds(pl.multiple_of(c * lanes, lanes), lanes)] \
                = jax.lax.dot_general(
                    q_ref[0, n], c_buf[slot, n].reshape(lanes, d),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [Hg, lanes]
        issue_fetch()
        return carry

    g0 = g_ref[0]
    jax.lax.fori_loop(0, num_chunks, functools.partial(body, g0=g0), 0)
    g_ref[0] = g0 + num_chunks


def stripe_scores_pallas(qg: jax.Array, comp: jax.Array, layer: jax.Array,
                         page_table: jax.Array, lens: jax.Array,
                         page_size: int, interpret: bool = False
                         ) -> jax.Array:
    """The decode step's scores of the stripes a row holds in the
    compressed-key array ``comp`` [A, Nkv, P, stripes a page, D] of
    ``layer`` (the FULL array: the kernel copies pages): the queries qg [B,
    Nkv, Hg, D] against a row's first lens [B] tokens' stripes (lens a
    whole number of stripes; 0: the row walks nothing) under ``page_table``
    [B, maxP], ``page_size`` tokens a page. Returns [B, Nkv, Hg, maxP x
    stripes a page] float32: hybrid.pool_stripes and its product (bfloat16
    into float32) at every stripe under lens and UNDEFINED past it, which
    may be a NaN: read it under a ``where``. XLA's path gathers every
    slot's whole bucket and scores the copy (24 slots x 128 pages x 2 heads
    x 2 KB = 12.6 MB a layer where 9 live rows of 6,000 tokens hold under 2);
    this reads a row's live pages once (PERF.md section 6, PR 46). The
    page table is padded to whole chunks of STRIPE_CHUNK_TOKENS."""
    b, nkv, group, d = qg.shape
    maxp, per = page_table.shape[1], comp.shape[3]
    ppc = max(1, STRIPE_CHUNK_TOKENS // page_size)
    page_table = jnp.pad(page_table, ((0, 0), (0, -maxp % ppc)))
    lanes = page_table.shape[1] * per
    dots = pl.pallas_call(
        functools.partial(_stripe_kernel, page_size=page_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, nkv, group, d),
                                   lambda i, *_: (i, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, nkv, group, lanes),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((SLOTS, nkv, ppc, per, d), comp.dtype),
                pltpu.SemaphoreType.DMA((1, SLOTS)),
                pltpu.SMEM((1,), jnp.int32), pltpu.SMEM((3,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, nkv, group, lanes), jnp.float32),
        # Sequential: the fetch pipeline runs from one row into the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), page_table, lens, qg, comp)
    return dots[..., :maxp * per]


def _commit_kernel(pid_ref, r0_ref, m0_ref, n_ref,  # SMEM prefetch, [B*J]
                   *rest,  # [t0_ref if tiled], then
                   # kwin_ref, vwin_ref: VMEM blocks [L, Nkv, 1, M, D] f32
                   # k_in, v_in: the pools (ANY), aliased to k_hbm / v_hbm
                   # k_hbm, v_hbm, k_buf, v_buf, sems
                   tiled: bool = False, layers: tuple | None = None):
    """One grid program per (row, touched tile of a page): the tile's K and
    V rows of every layer and KV head come into VMEM (one strided copy
    each, the reader's ``hbm.at[layer, :, pid]`` turned to
    ``hbm.at[:, :, pid]``), the window's tokens that fall on the tile are
    selected into them, and the tile goes back where it came from. A
    program whose tile takes no token (a dead or frozen row, a window that
    stayed on its first tile) copies nothing.

    ``tiled`` (a page of several COMMIT_TILEs): a fifth prefetched vector,
    t0, is the tile's first row in its page, and the copies move those
    rows alone, so the commit costs what a 16-token page costs whatever
    the page. Where the page is one tile the whole page moves.
    ``layers`` (first, count): the pools' layers the window holds, where
    that is not all of them."""
    if tiled:
        t0_ref, *rest = rest
    # ``pools`` arrays (two; one where the second pool has no width): their
    # windows, the pools in (the same buffers as the outputs), the pools
    # out, their tile buffers, then the semaphores.
    n_pools = (len(rest) - 1) // 4
    wins, hbms, bufs = (rest[:n_pools], rest[2 * n_pools:3 * n_pools],
                        rest[3 * n_pools:4 * n_pools])
    sems = rest[-1]
    k_buf = bufs[0]
    i = pl.program_id(0)
    n = n_ref[i]

    @pl.when(n > 0)
    def _():
        pid, r0, m0 = pid_ref[i], r0_ref[i], m0_ref[i]
        pools = tuple(zip(hbms, bufs, wins))
        tile = k_buf.shape[2]

        def page_copy(s, hbm, buf, out: bool):
            rows = hbm.at[:, :, pid] if layers is None else hbm.at[
                pl.ds(layers[0], layers[1]), :, pid]
            if tiled:
                rows = rows.at[:, :, pl.ds(
                    pl.multiple_of(t0_ref[i], tile), tile)]
            src, dst = (buf, rows) if out else (rows, buf)
            return pltpu.make_async_copy(src, dst, sems.at[s])

        for s, (hbm, buf, _) in enumerate(pools):
            page_copy(s, hbm, buf, False).start()
        # Row r of this tile takes window token m0 + (r - r0), r0 <= r <
        # r0 + n: for token m, the rows whose offset from m0 - r0 is m.
        def tokens_of(shape):
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
            return jnp.where((row >= r0) & (row < r0 + n), row - r0 + m0, -1)

        token = tokens_of(k_buf.shape)
        for s, (hbm, buf, win_ref) in enumerate(pools):
            page_copy(s, hbm, buf, False).wait()
            # Through float32 (exact both ways): the one width whose rows
            # every TPU generation's VPU selects and broadcasts singly.
            x = buf[...].astype(jnp.float32)
            if buf.shape != k_buf.shape:    # a latent pool's narrower rows
                token = tokens_of(buf.shape)
            for m in range(win_ref.shape[3]):
                x = jnp.where(token == m, win_ref[:, :, 0, m:m + 1, :], x)
            buf[...] = x.astype(buf.dtype)
            page_copy(s, hbm, buf, True).start()
        for s, (hbm, buf, _) in enumerate(pools):
            page_copy(s, hbm, buf, True).wait()


def window_pages(positions0, cap, seq_lens0, page_table, window: int,
                 page_size: int, tile: int | None = None):
    """Where a window's tokens land, tile by tile (``tile`` rows of a
    page; None: the page whole). A row's ``window`` tokens from
    ``positions0`` on touch at most J = ceil((window - 1) / tile) + 1
    tiles; for each (row, j), flattened to [B*J]: the pool page ``pid``,
    the in-tile row ``r0`` of the first token it takes, that token's index
    ``m0`` in the window, and how many it takes, ``n`` (0: a dead row, a
    row at its cap, a tile the window did not reach); where a page holds
    several tiles also ``t0``, the tile's first row in its page."""
    tile = tile or page_size
    per_page = page_size // tile
    J = -(-(window - 1) // tile) + 1
    n_live = jnp.where(seq_lens0 > 0,
                       jnp.clip(cap - positions0, 0, window), 0)     # [B]
    tj = positions0[:, None] // tile + jnp.arange(J)[None, :]        # [B,J]
    first = jnp.maximum(positions0[:, None], tj * tile)
    last = jnp.minimum((positions0 + n_live)[:, None], (tj + 1) * tile)
    n = jnp.maximum(last - first, 0)
    pj = tj if per_page == 1 else tj // per_page
    pid = jnp.take_along_axis(
        page_table, jnp.clip(pj, 0, page_table.shape[1] - 1), axis=1)
    out = (jnp.where(n > 0, pid, 0), first - tj * tile,
           first - positions0[:, None], n)
    if per_page > 1:
        out += ((tj % per_page) * tile,)
    return tuple(a.astype(jnp.int32).reshape(-1) for a in out)


def commit_window_pallas(k_cache: jax.Array, v_cache: jax.Array,
                         k_win: jax.Array, v_win: jax.Array,
                         positions0: jax.Array, cap: jax.Array,
                         seq_lens0: jax.Array, page_table: jax.Array,
                         interpret: bool = False,
                         layers: tuple | None = None):
    """The decode window's commit, in place: the pools [L,Nkv,P,page,D]
    stay where and how they lie (row-major, what the decode kernel reads)
    and are aliased to the outputs; of each live row only the pages its
    window touched are read, merged with the window's buffer k_win/v_win
    [L,Nkv,B,M,D] and written back. Token m of row b lands at position
    positions0[b] + m while that is under cap[b] (and seq_lens0[b] > 0),
    where kv_quant.scatter_tokens puts it; a token that does not land is
    written nowhere (the scatter sends it to scratch page 0). A written
    page must belong to one row: the page being appended to is private
    (kv_cache.PageAllocator shares full pages only). What moves is the
    COMMIT_TILE rows of a page that the tokens fall on, so a larger page
    costs the commit nothing (a page that is no whole number of tiles
    moves whole). A pool array of no width (a latent block without an
    indexer) is handed back as it came. ``layers`` (first, count): the
    windows hold those layers of the pools alone (a prediction module's
    layer, whose tokens land one slot on). A commit whose tiles and window
    blocks of all its layers would pass COMMIT_VMEM_BYTES runs as several
    calls, each a range of layers, over the same aliased pools."""
    L, nkv, _, page_size, _ = k_cache.shape
    if layers is not None:
        L = layers[1]
    b, window = k_win.shape[2], k_win.shape[3]
    tile = COMMIT_TILE if page_size % COMMIT_TILE == 0 else page_size
    tiled = tile < page_size
    # What a program holds in VMEM a layer: a tile's rows of each pool and
    # its block of each window in float32, twice (the pipeline's buffers).
    per_layer = sum(nkv * c.shape[4] * (tile * c.dtype.itemsize
                                        + 2 * window * 4)
                    for c in (k_cache, v_cache))
    span = next(n for n in range(L, 0, -1) if L % n == 0
                and n * per_layer <= max(per_layer, COMMIT_VMEM_BYTES))
    if span < L:
        # Layer ranges that fit, one call each over the same aliased pools
        # (a looped stack's 192 (pass, layer) pairs of 16 heads are 50 MB
        # of tiles and windows at once).
        first = layers[0] if layers is not None else 0
        for lo in range(0, L, span):
            k_cache, v_cache = commit_window_pallas(
                k_cache, v_cache, k_win[lo:lo + span], v_win[lo:lo + span],
                positions0, cap, seq_lens0, page_table, interpret=interpret,
                layers=(first + lo, span))
        return k_cache, v_cache
    prefetch = window_pages(positions0, cap, seq_lens0, page_table, window,
                            page_size, tile)
    J = prefetch[0].shape[0] // b
    # The two pools share everything but their rows' width (K and V of
    # one head_dim; a latent pool's entry and index key).
    caches = [(c, w) for c, w in ((k_cache, k_win), (v_cache, v_win))
              if c.shape[4]]
    n = len(caches)
    wins = [pl.BlockSpec((L, nkv, 1, window, cache.shape[4]),
                         lambda i, *_: (0, 0, i // J, 0, 0))
            for cache, _ in caches]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    bufs = [pltpu.VMEM((L, nkv, tile, cache.shape[4]), cache.dtype)
            for cache, _ in caches]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b * J,),
        in_specs=[*wins, *[any_spec] * n],
        out_specs=(any_spec,) * n,
        scratch_shapes=[*bufs, pltpu.SemaphoreType.DMA((n,))],
    )
    n_pre = len(prefetch)
    kernel = _commit_kernel
    if tiled or layers is not None:
        kernel = functools.partial(_commit_kernel, tiled=tiled,
                                   layers=layers)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(jax.ShapeDtypeStruct(c.shape, c.dtype)
                        for c, _ in caches),
        input_output_aliases={n_pre + n + j: j for j in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *(w.astype(jnp.float32) for _, w in caches),
      *(c for c, _ in caches))
    return (out[0], out[1]) if n == 2 else (out[0], v_cache)


#: Rows of a window buffer one block of write_window_rows_pallas holds: the
#: rows of one bfloat16 tile as the chip lays the buffer out, T(8,128)(2,1),
#: the least a copy may take of it (Mosaic: "Slice shape along dimension 2
#: must be aligned to tiling (8)").
ROWS_TILE = 8
#: Bytes of the buffer one grid program of that kernel brings in.
ROWS_BLOCK_BYTES = 1 << 20


def _rows_kernel(start_ref,  # SMEM prefetch, [1]
                 new_ref, buf_ref, out_ref):
    """One grid program per block of layers: the tile of rows that holds
    rows start .. start + S - 1 of every (layer, slot) of the block is in
    VMEM (BlockSpec's own pipeline), takes the S fresh rows and goes back
    where it came from."""
    rows = buf_ref.shape[2]
    r0 = start_ref[0] % rows
    # Through float32 (exact both ways), as _commit_kernel selects.
    x = buf_ref[...].astype(jnp.float32)
    new = new_ref[...].astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    for j in range(new.shape[2]):
        x = jnp.where(row == r0 + j, new[:, :, j:j + 1, :], x)
    out_ref[...] = x.astype(out_ref.dtype)


def write_window_rows_pallas(buf: jax.Array, new: jax.Array,
                             start: jax.Array,
                             interpret: bool = False) -> jax.Array:
    """A window buffer's step write, in place: ``jax.lax.
    dynamic_update_slice(buf, new, (0, 0, start, 0))`` for buf
    [L, B, W, width] (a drafting window's columns of every pool layer, as
    the chip holds them: a (layer, slot)'s W rows are whole tiles of
    ROWS_TILE) and new [L, B, S, width], S dividing ROWS_TILE and
    ``start`` a multiple of S, so that the S rows fall in ONE tile.

    XLA's own update writes S rows into each of L x B x width / 128 tiles
    at an offset it sees as dynamic, a sublane at a time (47 ns a tile:
    PERF.md section 6, PR 47); a copy may not take less than a tile of the
    buffer either. So the tile of rows the step falls in is what moves,
    of every layer and slot: ROWS_TILE / W of the buffer through VMEM and
    back, ROWS_BLOCK_BYTES of it a grid program, the buffer aliased to the
    result. A buffer of fewer rows than a tile, or an S that does not
    divide one, moves whole."""
    L, B, W, width = buf.shape
    S = new.shape[2]
    rows = ROWS_TILE if W % ROWS_TILE == 0 and ROWS_TILE % S == 0 else W
    per_layer = B * rows * width * buf.dtype.itemsize
    # Layers a block: the most that divide L and fit the block's bytes.
    lc = max(d for d in range(1, max(1, ROWS_BLOCK_BYTES // per_layer) + 1)
             if L % d == 0)
    tile = pl.BlockSpec((lc, B, rows, width),
                        lambda i, s: (i, 0, s[0] // rows, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L // lc,),
        in_specs=[pl.BlockSpec((lc, B, S, width), lambda i, s: (i, 0, 0, 0)),
                  tile],
        out_specs=tile,
    )
    return pl.pallas_call(
        _rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32).reshape(1), new, buf)
