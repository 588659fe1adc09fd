"""ModelRunner: compiled, sharded prefill/decode steps over a device mesh.

Owns the mesh (("dp","tp"), reference §2.7 TP delegated-to-engine -> here
native via jax.sharding), the sharded parameters, the paged KV device arrays,
and the jit-compiled step functions:

- ``prefill_batch``: length-bucketed, batch-bucketed prefill of whole
  prompts (one compiled program per (bucket, batch, with_history)); supports
  history pages so long prompts prefill in chunks (chunked prefill, SURVEY.md
  §5.7 parity) and cached prefixes are skipped, attending to prior pages via
  the same paged read path as decode. First-token sampling is fused into the
  program (no separate sampler dispatch).
- ``decode_window``: M decode steps for the whole slot batch in ONE device
  program (lax.scan over steps): tokens chain on-device, positions/lengths
  advance in-graph, sampling per step. The host uploads a single packed
  int32 control array per window and reads back the [M,B] sampled tokens
  asynchronously — the design keeps host<->device round-trips OFF the
  per-token path (the reference's GPU engines rely on CUDA-graph replay for
  the same reason; XLA's equivalent is one big compiled window).
- page-table width bucketing: the decode window is compiled per power-of-2
  page-table width, so the XLA gather attention reads ~live pages instead of
  max_pages_per_seq for every sequence.

KV arrays are donated through every call so XLA updates them in place.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine import perf
from dynamo_tpu.engine.backends import SSM_STATE_DTYPE, choose
from dynamo_tpu.engine.config import (EngineConfig, UnsupportedBlockError,
                                      block_refusals, window_page_bucket)
from dynamo_tpu.engine.kv_quant import (KV_SCALE_BYTES, QuantKV, pack_parcel,
                                        parcel_to_bf16, quantize_np,
                                        scatter_tokens, unpack_parcel,
                                        window_token_slots)
from dynamo_tpu.engine.model import (
    HISTORY_SCORE_BYTES,  # read here by _prefill_with_history at call time
    dense_causal_attention,
    expert_product,
    init_params,
    param_specs,
    prefill_forward,
    decode_forward,
    decode_window_step,
)
from dynamo_tpu.engine.sampler import sample_tokens, sample_tokens_per_row
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.tracing import startup_stage

log = get_logger("runner")

# Packed per-window control array columns (int32; floats bitcast).
PK_OVERRIDE = 0   # 1 -> take PK_TOKEN instead of the chained device token
PK_TOKEN = 1
PK_POS = 2        # absolute position of the token to be written this window
PK_SEQLEN = 3     # length INCLUDING that token; 0 -> slot inactive
PK_TOPK = 4
PK_TEMP = 5       # float32 bits
PK_TOPP = 6       # float32 bits
PK_CAP = 7        # position capacity = allocated pages * page_size; a slot
                  # freezes in-graph when its position reaches this
PK_LOGPROB = 8    # 1 -> this slot wants logprobs (window computes them
                  # when ANY slot asks; per-slot filtering is host-side)
PK_FREQPEN = 9    # float32 bits: OpenAI frequency_penalty (0 = off)
PK_PRESPEN = 10   # float32 bits: OpenAI presence_penalty (0 = off)
PK_SEED = 11      # int32 sampling seed (meaningful when PK_SEEDED)
PK_SEEDED = 12    # 1 -> slot uses a per-request seeded rng stream
PK_ADAPTER = 13   # resident LoRA adapter slot id (0 = base model; the
                  # gathered A/B correction reads this row's stacks —
                  # engine/lora.py)
PK_PREFIX = 14    # page table starts here

TOP_LOGPROBS = 8  # alternatives returned when logprobs are requested

SEED_MASK = 0x7FFFFFFF  # seeds ride int32 control columns: 31 usable bits


def mask_seed(seed: int) -> int:
    """The ONE place a request seed maps to its on-device value — the
    prefill and window paths must fold the identical base key or
    preemption-recompute would diverge from the original draws."""
    return int(seed) & SEED_MASK

_PF_HDR = 12      # prefill packed-array header columns (7 freq-penalty
                  # bits, 8 pres-penalty bits, 9 seed, 10 seeded flag,
                  # 11 adapter slot id)


def _logprobs_of(logits: jax.Array, sampled: jax.Array):
    """(chosen logprob [B], top values [B,K], top ids [B,K]) from raw
    logits — log-softmax via one logsumexp, no full-vocab sort."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    chosen = jnp.take_along_axis(logits, sampled[:, None], axis=1)[:, 0]
    top_v, top_i = jax.lax.top_k(logits, TOP_LOGPROBS)
    return chosen - lse, top_v - lse[:, None], top_i


@dataclasses.dataclass
class PrefillSeq:
    """One whole-prompt (or final-chunk) prefill row."""
    tokens: np.ndarray          # [n] chunk tokens
    start_pos: int              # absolute position of tokens[0]
    chunk_pages: np.ndarray     # pages covering the chunk
    hist_pages: np.ndarray | None  # pages before the chunk (None = fresh)
    sampling: tuple[float, int, float]  # (temperature, top_k, top_p)
    logprobs: bool = False      # row wants first-token logprobs
    penalties: tuple[float, float] = (0.0, 0.0)  # (frequency, presence)
    seed: int | None = None     # per-request sampling seed
    # Multimodal: encoder embeddings [n, H] + bool mask [n] (n =
    # len(tokens)): where the mask is set, the embedding row replaces the
    # token table's row (the token id there is a placeholder).
    embeds: np.ndarray | None = None
    embeds_mask: np.ndarray | None = None
    # Resident LoRA adapter slot (0 = base model; engine/lora.py).
    adapter_id: int = 0
    # A model with a prediction module (ModelSpec.mtp_layers): the prompt's
    # token after this chunk (-1: the chunk is the last, and the token the
    # program samples is the next), and the page that holds the slot after
    # the chunk's last token, where the module's entry of that token is
    # kept (0, the scratch page: no such page is allocated).
    next_token: int = -1
    next_page: int = 0
    # A block with recurrent layers (ModelSpec.recurrent): the slot whose
    # state the chunk continues (from zeros at position 0) and leaves its
    # own in; -1: none, the state goes nowhere (a warm-up's inert row).
    # prefill_batch's ``slots`` say the same where they are given.
    slot: int = -1


def _mh_put(value, sharding):
    """Place a host-resident full array onto the mesh. In multi-controller
    mode (jax.process_count() > 1, multi-host serving) a plain device_put
    of host data onto a cross-host sharding is illegal — each process
    instead contributes its addressable shards via make_array_from_callback
    (every process holds the identical full value, so shards agree)."""
    if jax.process_count() > 1:
        arr = np.asarray(value)
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx])
    return jax.device_put(value, sharding)


def _mh_zeros(shape, dtype, sharding):
    """Sharded zeros that never materialize on one device: compiled
    creation makes each shard directly on its own device. A KV pool sized
    for four chips does not fit on the first (found on four v5e, PR 21:
    ``device_put(jnp.zeros(...))`` asked one chip for 17.3 GB), and in
    multi-controller mode it is the only legal way to place one."""
    if len(sharding.device_set) > 1:
        # dtpu: ignore[jit-recompile-hazard, unregistered-jit] until=2027-08-01 -- one-shot at pool creation, never dispatched from the serving loop
        return jax.jit(lambda: jnp.zeros(shape, dtype),
                       out_shardings=sharding)()
    return jnp.zeros(shape, dtype, device=sharding)


#: Fresh K and V one prefill program may hold for all its rows ahead of
#: its commit (prefill_batch runs a larger group in parts).
PREFILL_FRESH_KV_BYTES = 1 << 30


class ModelRunner:
    #: The program store's context (perf.program_context, set in _place):
    #: none for a runner that no launcher's start builds, nor for one put
    #: together without __init__ (tests/test_tpu_compile.py).
    _store_context = None

    def __init__(self, config: EngineConfig, params=None,
                 devices: list | None = None, seed: int = 0):
        self.config = config
        spec = config.model
        # KV-pool quantization (engine/kv_quant.py): resolved ONCE here —
        # pool sizing, allocation, parcels and the HBM ledger all key off
        # this field.
        self.quant_kv = config.resolve_quant_kv()
        if self.quant_kv not in (None, "int8"):
            raise ValueError(
                f"quant_kv must be None or 'int8', got {self.quant_kv!r}")
        # TP feasibility + KV-head replication (the role of vLLM's KV-head
        # replication for tp > num_kv_heads): each canonical KV head is
        # duplicated tp/nkv times so the cache's head axis shards evenly
        # over "tp". q head j maps to effective group j // (H/tp), which
        # composes back to the canonical grouping j // (H/nkv).
        self.canonical_spec = spec
        self.canonical_nkv = spec.num_kv_heads
        for refusal in block_refusals(spec, config):
            raise refusal
        if spec.num_heads % config.tp != 0:
            raise ValueError(
                f"num_heads={spec.num_heads} not divisible by tp={config.tp}")
        if config.tp > spec.num_kv_heads:
            if config.tp % spec.num_kv_heads != 0:
                raise ValueError(
                    f"tp={config.tp} exceeds num_kv_heads="
                    f"{spec.num_kv_heads} and is not a multiple of it; "
                    f"KV-head replication needs tp % num_kv_heads == 0")
            self.kv_rep = config.tp // spec.num_kv_heads
            spec = dataclasses.replace(spec, num_kv_heads=config.tp)
            log.info("tp=%d > num_kv_heads=%d: replicating each KV head "
                     "%dx (KV cache grows %dx)", config.tp,
                     self.canonical_nkv, self.kv_rep, self.kv_rep)
        else:
            if spec.num_kv_heads % config.tp != 0:
                raise ValueError(
                    f"num_kv_heads={spec.num_kv_heads} not divisible by "
                    f"tp={config.tp}")
            self.kv_rep = 1
        if spec.num_layers % config.pp != 0:
            raise ValueError(
                f"num_layers={spec.num_layers} not divisible by "
                f"pp={config.pp}")
        if spec.num_experts and spec.num_experts % config.tp != 0:
            raise ValueError(
                f"num_experts={spec.num_experts} not divisible by "
                f"tp={config.tp} (expert parallelism shards experts "
                f"over tp)")
        if config.sp > 1 and any(b % config.sp != 0
                                 for b in config.prefill_buckets):
            raise ValueError(
                f"sp={config.sp}: every prefill bucket "
                f"({config.prefill_buckets}) must be divisible by sp")
        self.spec = spec
        with startup_stage("startup.mesh"):
            self._place(config, spec, devices)
        with startup_stage("startup.pool_sizing") as stage:
            self._sized_pages(self.device)
            stage.set(num_pages=self.num_pages)
        if spec.loop_passes > 1:
            log.info("looped stack: %d passes over %d layers, %d pool "
                     "layers, %d B of K and V a token", spec.loop_passes,
                     spec.num_layers, spec.pool_layers,
                     config.kv_token_bytes())
        with startup_stage("startup.weights",
                           source="random" if params is None else "given"
                           ) as stage:
            self._load_params(spec, params, seed)
            stage.set(bytes=sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.params)))
        with startup_stage("startup.pool_alloc") as stage:
            self._allocate(config, spec, seed)
            stage.set(bytes=self.kv_pool_bytes + self.ssm_state_bytes)

    def _place(self, config: EngineConfig, spec, devices) -> None:
        """The compile cache, the mesh and who runs what."""
        # Every entry point builds a runner before its first compile, so
        # this is the one place the persistent compile cache is set up.
        perf.configure_compile_cache()
        devices = devices if devices is not None else jax.devices()
        total = config.dp * config.pp * config.sp * config.tp
        if len(devices) < total:
            raise ValueError(f"need {total} devices, have {len(devices)}")
        dev_array = np.array(devices[:total]).reshape(
            config.dp, config.pp, config.sp, config.tp)
        self.mesh = Mesh(dev_array, ("dp", "pp", "sp", "tp"))
        # This process's first mesh device: what memory is sized from and
        # read back from, and whose platform decides everything that
        # differs between a chip and the CPU backend (never the process
        # default). In multi-controller mode devices[0] may belong to
        # another process, and memory_stats on a remote device fails.
        local = [d for d in devices[:total]
                 if d.process_index == jax.process_index()]
        self.device = local[0] if local else devices[0]
        # Who runs what, decided once (engine/backends.py) and handed to
        # every forward program whole. Before any weight is loaded: a
        # backend that cannot be had fails the start-up in milliseconds.
        self.backends = choose(config, spec, self.device.platform,
                               self.mesh.size, self.quant_kv)
        # Who reads the pool in decode, under the name the benchmark reads.
        self.attention_backend = self.backends.attention
        # (row, choice) pairs the prefill calls sent through the grouped
        # product's kernel, a layer: counted on the host from the rows of
        # each call.
        self.moe_grouped_pairs = 0
        self.page_size = config.page_size
        # What every program below closes over, for the program store's key
        # (None, and no store, for a runner no launcher's start builds).
        self._store_context = perf.program_context(
            spec, config, self.backends, self.quant_kv, mesh=self.mesh)

    def _load_params(self, spec, params, seed: int) -> None:
        """Shard the parameters handed over, or init them."""
        pspecs = param_specs(spec)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        if params is None:
            key = jax.random.key(seed)
            # local_devices, not devices: in multi-controller mode the
            # global cpu list starts with rank 0's device, and arrays
            # initialized onto a non-addressable device can't be read.
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                # Init the CANONICAL shape so tp variants of one logical
                # model share identical parameters.
                params = init_params(self.canonical_spec, key)
        if self.kv_rep > 1:
            if _already_quantized(params):
                raise ValueError(
                    "tp > num_kv_heads needs KV-head replication, which "
                    "rewrites bf16 wk/wv — pass unquantized params (the "
                    "runner quantizes after replication)")
            params = _replicate_kv_heads(params, self.canonical_spec,
                                         self.kv_rep)
        if spec.quant == "int8" and not _already_quantized(params):
            # Weight-only int8 (engine/quant.py): quantize on host AFTER
            # KV-head replication (which rewrites bf16 wk/wv), BEFORE the
            # sharded upload — HBM holds int8 + scales only.
            from dynamo_tpu.engine.quant import quantize_params
            params = quantize_params(params)
        self.params = jax.tree.map(_mh_put, params, shardings)

    def _allocate(self, config: EngineConfig, spec, seed: int) -> None:
        """The pool, the state beside it and the small arrays that chain on
        the device between programs."""
        # KV cache arrays [L, Nkv, P, page, D]: layers sharded over pp
        # (pages live with their layer's stage), kv heads over tp, and
        # [page, D] contiguous per (head, page) for clean Pallas DMAs.
        # A latent pool (spec.kv_entry) is the same two arrays under the
        # same page table, allocator and prefix hashes: k_cache holds a
        # token's latent entry, v_cache its index key, one "head" each.
        kv_spec = P("pp", "tp", None, None, None)
        self.kv_sharding = NamedSharding(self.mesh, kv_spec)
        kv_heads, (k_width, v_width) = spec.kv_entry
        kv_shape = (spec.pool_layers, kv_heads, self.num_pages,
                    config.page_size, k_width)
        if self.quant_kv == "int8":
            # int8 pages + per-token-per-head f32 scales (zero-init: an
            # unwritten page dequantizes to 0, same as the bf16 pool;
            # every real write goes through kv_quantize, whose scales
            # are never 0).
            scale_sharding = NamedSharding(self.mesh,
                                           P("pp", "tp", None, None))
            self.k_cache = QuantKV(
                _mh_zeros(kv_shape, jnp.int8, self.kv_sharding),
                _mh_zeros(kv_shape[:-1], jnp.float32, scale_sharding))
            self.v_cache = QuantKV(
                _mh_zeros(kv_shape, jnp.int8, self.kv_sharding),
                _mh_zeros(kv_shape[:-1], jnp.float32, scale_sharding))
        else:
            self.k_cache = _mh_zeros(kv_shape, jnp.bfloat16,
                                     self.kv_sharding)
            self.v_cache = _mh_zeros((*kv_shape[:-1], v_width), jnp.bfloat16,
                                     self.kv_sharding)
        # Recurrent state beside the pages (a block with ModelSpec.recurrent
        # layers): what a ROW holds, by slot, a layer: the state S in
        # float32 and the convolution's last inputs (taps-major: the slot
        # is the THIRD axis there, spec.conv_state_shape). Donated to and
        # returned by every program that writes them (prefill at a row's
        # last real token, the window once a step), never copied whole.
        # A mixer without a convolution keeps S alone. The pool's third
        # array (ModelSpec.compressed_keys: means of keys under the same
        # page table, hybrid.py) rides the same programs behind them.
        self.ssm_state = self.conv_state = self.comp_keys = None
        if spec.recurrent:
            s_shape, c_shape = spec.ssm_state_shapes
            rows = (spec.ssm_layers, config.max_num_seqs)
            whole = NamedSharding(self.mesh, P())
            self.ssm_state = _mh_zeros((*rows, *s_shape),
                                       jnp.dtype(SSM_STATE_DTYPE), whole)
            if c_shape is not None:
                self.conv_state = _mh_zeros(
                    spec.conv_state_shape(config.max_num_seqs), jnp.bfloat16,
                    whole)
            if spec.compressed_keys:
                self.comp_keys = _mh_zeros(
                    spec.comp_key_shape(self.num_pages, config.page_size),
                    jnp.bfloat16, whole)
        # Byte ledgers for the perf plane's HBM breakdown (/debug/perf):
        # this process's per-device share of params and the KV pool —
        # workspace is whatever memory_stats says is in use beyond them.
        # The KV ledger reports the ACTUAL pool dtype bytes (int8 + scale
        # vs bf16), so workspace attribution never silently absorbs the
        # quantization savings.
        per_weight = 1 if spec.quant == "int8" else 2
        shard = max(1, config.tp * config.pp)
        self.param_bytes = spec.num_params() * per_weight // shard
        self.kv_pool_bytes = (
            2 * self.num_pages * config.page_size
            * self._kv_token_head_bytes() * spec.pool_layers
            * kv_heads) // shard
        if self.comp_keys is not None:
            self.kv_pool_bytes += self.comp_keys.nbytes

        self._prefill_cache: dict = {}
        self._decode_fn = None
        self._window_cache: dict = {}
        # COMMITTED rng: an uncommitted key traces a different jit
        # signature than the committed key the program returns, so every
        # program family paid one duplicate XLA compile on its second
        # call (found by the perf plane's unexpected-recompile detector;
        # multi-controller mode keeps the host value — device_put onto a
        # cross-host sharding is illegal there, and followers replay
        # identical dispatches anyway).
        rng = jax.random.key(seed + 1)
        if jax.process_count() == 1:
            rng = jax.device_put(rng, NamedSharding(self.mesh, P()))
        self._rng = rng
        self.tokens_dev = _mh_zeros(
            (config.max_num_seqs,), jnp.int32,
            NamedSharding(self.mesh, P()))
        # Speculative decoding (config.spec_decode="ngram"): the full
        # per-slot token history rides ON DEVICE — hist_dev feeds the
        # in-graph n-gram draft lookup, positions_dev chains the
        # DATA-DEPENDENT sequence position between pipelined spec
        # windows (the host can't know how many drafts were accepted in
        # a window it hasn't processed yet, so device state is the only
        # correct source). Allocated lazily: plain serving never pays.
        self.hist_dev = None
        self.positions_dev = None
        # Drafting with the model's own prediction module ("mtp"): the
        # position chains on the device as under "ngram", and with it the
        # draft of the token after the chained one (-1: none).
        self.draft_dev = None
        self.mtp_hidden = None
        if config.spec_decode:
            hist_w = config.max_pages_per_seq * config.page_size
            if config.spec_decode == "mtp":
                self.draft_dev = _mh_put(
                    np.full((config.max_num_seqs,), -1, np.int32),
                    NamedSharding(self.mesh, P()))
                # The model's normed output at the LAST position of every
                # page, by page id: what the module's entry of that
                # position needs once the next page's first token is known,
                # where the page came from the prefix cache and nothing
                # recomputes it (model.mtp_prefill). Decided by the tokens
                # the page's hash covers, as the page is.
                self.mtp_hidden = _mh_zeros(
                    (self.num_pages, spec.hidden_size), jnp.bfloat16,
                    NamedSharding(self.mesh, P()))
            else:
                self.hist_dev = _mh_zeros(
                    (config.max_num_seqs, hist_w), jnp.int32,
                    NamedSharding(self.mesh, P()))
            self.positions_dev = _mh_zeros(
                (config.max_num_seqs,), jnp.int32,
                NamedSharding(self.mesh, P()))
        self._seed_hist_cache: dict = {}
        # Blocking prefill readbacks performed (slots=None fetch path).
        # The scheduled chunk path must never bump this: intermediate
        # chunks dispatch with no host readback at all (tests assert 0).
        self.sync_prefill_fetches = 0
        # Per-slot generated-token counts [slots, vocab] for OpenAI
        # frequency/presence penalties (vLLM semantics: output tokens
        # only). uint8 with saturation at 255; read ONLY by the penalized
        # window variant, so unpenalized serving never touches it.
        self.counts_dev = _mh_zeros(
            (config.max_num_seqs, spec.vocab_size), jnp.uint8,
            NamedSharding(self.mesh, P()))
        # Batched LoRA stacks (engine/lora.py): one pair of stacked
        # pytrees per target projection — A [L, S, d_in, r] /
        # B [L, S, r, d_out], S = max_adapters + 1 slots with slot 0 the
        # base model (all-zero, exact no-op). Layer-major so the layer
        # scan consumes them as xs alongside params["layers"]; the layer
        # axis shards over "pp" (stacks live with their stage), the rest
        # replicates — a rank-8 stack is megabytes, not gigabytes. The
        # named-parameter-overlay shape: adapter weights ride the mesh
        # beside base params and hot-swap per slot without touching them.
        self.lora = None
        if config.max_adapters > 0:
            S = config.max_adapters + 1
            r = config.lora_max_rank
            lspec = NamedSharding(self.mesh, P("pp", None, None, None))
            shapes = config.lora_target_shapes()
            if self.kv_rep > 1:
                # KV-head replication rewrote wk/wv: the B stacks' output
                # axis follows the EFFECTIVE head count (uploads
                # replicate columns in set_adapter_slot).
                dkv = spec.num_kv_heads * spec.head_dim
                shapes["wk"] = (shapes["wk"][0], dkv)
                shapes["wv"] = (shapes["wv"][0], dkv)
            L = spec.num_layers
            self.lora = {
                key: {"a": _mh_zeros((L, S, d_in, r), jnp.bfloat16, lspec),
                      "b": _mh_zeros((L, S, r, d_out), jnp.bfloat16, lspec)}
                for key, (d_in, d_out) in shapes.items()}

    # -- setup ---------------------------------------------------------------
    def _kv_token_head_bytes(self) -> int:
        """Pool bytes per (layer, kv-head, token) of ONE of the two pool
        arrays (their mean, where a latent pool's differ in width): bf16
        values, or int8 values + the f32 scale (engine/kv_quant.py)."""
        d = sum(self.spec.kv_entry[1]) // 2
        return (d + KV_SCALE_BYTES) if self.quant_kv == "int8" else 2 * d

    @property
    def ssm_state_bytes(self) -> int:
        """Bytes of the recurrent state arrays (every slot, every recurrent
        layer); 0 for a block whose whole per-request state is pages."""
        return (self.config.max_num_seqs
                * self.spec.ssm_state_bytes_per_row)

    _STATE_ARRAYS = ("ssm_state", "conv_state", "comp_keys")

    @property
    def state_arrays(self) -> tuple:
        """What a block with recurrent layers hands its programs as
        ``state`` (hybrid.split_state): the arrays that exist, in order."""
        return tuple(a for a in (getattr(self, name)
                                 for name in self._STATE_ARRAYS)
                     if a is not None)

    def _keep_state(self, arrays) -> None:
        names = [name for name in self._STATE_ARRAYS
                 if getattr(self, name) is not None]
        for name, array in zip(names, arrays, strict=True):
            setattr(self, name, array)

    def _sized_pages(self, device) -> None:
        cfg = self.config
        if cfg.num_pages is not None:
            self.num_pages = cfg.num_pages
            return
        # Size the KV pool from free HBM after params (reference: engines'
        # gpu_memory_utilization; here hbm_kv_budget_frac).
        if device.platform == "cpu":
            free = 2 << 30  # the host backend reports no memory_stats
        else:
            stats = self._memory_stats(device)
            free = stats["bytes_limit"] - stats["bytes_in_use"]
        # Params shard over tp and pp only (dp replicates them).
        per_weight = 1 if self.spec.quant == "int8" else 2
        param_bytes = (self.spec.num_params() * per_weight
                       // max(1, cfg.tp * cfg.pp))
        # The recurrent state arrays come out of what is free ahead of the
        # pool, as the parameters do (neither is on the device yet when a
        # pool is sized on the still-empty chip).
        state_bytes = cfg.max_num_seqs * self.spec.ssm_state_bytes_per_row
        budget = max(64 << 20, int((free - param_bytes - state_bytes)
                                   * cfg.hbm_kv_budget_frac))
        if device.platform != "cpu" and self.spec.head_dim < 128:
            # Under 128 lanes of head_dim the pool rests in a compact device
            # layout, and every step program copies both caches into a
            # lane-padded one (128/head_dim times their size) to gather and
            # scatter pages. Compiled for v5e (PR 21): the window program's
            # temporaries are 2x the pool at head_dim 64, bf16 or int8, and
            # a pool sized from the whole budget does not compile. Until the
            # pool is stored lane-dense (ROADMAP D3) the copies come out of
            # the same budget.
            budget //= 1 + 128 // self.spec.head_dim
        # The cache shards over tp (heads) AND pp (layers). int8 pages
        # (+ scales) cost ~half the bf16 bytes, so the same budget holds
        # ~2x pages — directly more resident sequences per chip.
        token_bytes = (2 * self.spec.pool_layers * self.spec.kv_entry[0]
                       * self._kv_token_head_bytes())
        token_bytes += self.spec.comp_key_bytes_per_token  # the third array
        page_bytes = token_bytes * cfg.page_size // max(1, cfg.tp * cfg.pp)
        self.num_pages = max(16, budget // max(1, page_bytes))
        log.info("KV pool: %d pages of %d tokens (%.1f GiB)", self.num_pages,
                 cfg.page_size, self.num_pages * page_bytes / (1 << 30))

    @staticmethod
    def _memory_stats(device) -> dict:
        """``device.memory_stats()`` of an accelerator, or an error: pool
        sizing and the HBM gauges must not guess on a chip."""
        stats = device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"{device} ({device.platform}) reports no memory_stats; "
                f"cannot size the KV pool or report HBM use")
        return stats

    # -- compiled steps -------------------------------------------------------
    def _get_prefill(self, bucket: int, batch: int, with_history: bool,
                     penalized: bool = False, seeded: bool = False,
                     with_embeds: bool = False):
        key = (bucket, batch, with_history, penalized, seeded, with_embeds)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        spec = self.spec
        page = self.config.page_size
        bucket_pages = bucket // page
        if with_history and self.config.sp > 1 \
                and self.config.ring_attention \
                and not getattr(self, "_ring_hist_warned", False):
            # History chunks (prompts longer than one prefill bucket)
            # read prior pages via the paged gather — that path still
            # uses the GSPMD all-gather, so ring attention covers
            # single-bucket prefills only. Warn at program-build time,
            # NOT inside the traced body: a trace-time branch runs once
            # per compile (impure-jit-program).
            self._ring_hist_warned = True
            log.info("ring attention: history-chunk prefill uses the "
                     "all-gather sp path (ring covers single-bucket "
                     "prefills)")

        # All host inputs travel in ONE packed int32 array (floats bitcast):
        # h2d transfers are latency-bound, so one transfer beats ten.
        # Columns: 0 start_pos, 1 n_tokens, 2 hist_len, 3 temp bits,
        # 4 top_k, 5 top_p bits, 6 logprobs flag, 7/8 penalty bits,
        # 9 seed, 10 seeded flag, 11 spare, then tokens[bucket],
        # ptab[bucket_pages], htab[maxp if with_history].
        # The penalized variant (preemption-recompute of a penalized
        # request) additionally reads prior-generation counts so even the
        # re-sampled token respects the penalties. The embeds variant
        # (multimodal prompts) takes encoder embeddings + a mask that
        # override the token table under media spans.
        # A model whose prediction module drafts: the chunk's commit waits
        # for the module's entries, which wait for the sampled token.
        mtp = self.config.spec_decode == "mtp" and bool(spec.mtp_layers)
        # A block with recurrent layers: the rows' slots ride one column
        # behind the tables, the two state arrays are donated and returned
        # behind the rng.
        recurrent = spec.recurrent

        def step(params, k_cache, v_cache, packed, rng, counts=None,
                 emb=None, emb_mask=None, lora=None, page_ends=None,
                 state=None):
            start = packed[:, 0]
            n = packed[:, 1]
            hist_lens = packed[:, 2]
            temp = jax.lax.bitcast_convert_type(packed[:, 3], jnp.float32)
            top_k = packed[:, 4]
            top_p = jax.lax.bitcast_convert_type(packed[:, 5], jnp.float32)
            adapter_ids = packed[:, 11]
            tokens = packed[:, _PF_HDR:_PF_HDR + bucket]
            page_table = packed[:, _PF_HDR + bucket:
                                _PF_HDR + bucket + bucket_pages]
            hist_table = packed[:, _PF_HDR + bucket + bucket_pages:]
            if mtp:     # two columns behind the tables (PrefillSeq)
                hist_table, next_tok, next_page = (
                    hist_table[:, :-2], packed[:, -2], packed[:, -1])
            if recurrent:
                hist_table, slots = hist_table[:, :-1], packed[:, -1]
            # positions: start..start+n-1, pads clamped to the last valid.
            positions = start[:, None] + jnp.minimum(
                jnp.arange(bucket)[None, :],
                jnp.maximum(n - 1, 0)[:, None])
            seq_lens = n
            sp_shard = self.config.sp > 1
            cfg_pp = self.config.pp
            pipelined = (not with_history and cfg_pp > 1
                         and self.config.pp_microbatch and not sp_shard
                         and not with_embeds and lora is None
                         and batch % cfg_pp == 0
                         and spec.num_layers % cfg_pp == 0)
            if recurrent:
                from dynamo_tpu.engine import hybrid
                logits, k_cache, v_cache, state = hybrid.prefill(
                    params, spec, k_cache, v_cache, state, tokens, positions,
                    page_table, seq_lens, slots,
                    hist=(hist_table, hist_lens) if with_history else None,
                    backends=self.backends)
            elif with_history:
                logits, k_cache, v_cache, *deferred = _prefill_with_history(
                    params, spec, k_cache, v_cache, tokens, positions,
                    page_table, seq_lens, hist_table, hist_lens,
                    self.backends, sp_shard=sp_shard,
                    x_embeds=emb, embeds_mask=emb_mask,
                    lora=lora, adapter_ids=adapter_ids, defer=mtp)
            elif pipelined:
                from dynamo_tpu.engine.model import (
                    prefill_forward_pipelined)
                logits, k_cache, v_cache = prefill_forward_pipelined(
                    params, spec, k_cache, v_cache, tokens, positions,
                    page_table, seq_lens, n_stages=cfg_pp)
            else:
                logits, k_cache, v_cache, *deferred = prefill_forward(
                    params, spec, k_cache, v_cache, tokens, positions,
                    page_table, seq_lens, sp_shard=sp_shard,
                    ring_mesh=(self.mesh if sp_shard
                               and self.config.ring_attention else None),
                    x_embeds=emb, embeds_mask=emb_mask,
                    lora=lora, adapter_ids=adapter_ids,
                    backends=self.backends, defer=mtp)
            with perf.scope("sample"):
                if penalized:
                    freq = jax.lax.bitcast_convert_type(packed[:, 7],
                                                        jnp.float32)
                    pres = jax.lax.bitcast_convert_type(packed[:, 8],
                                                        jnp.float32)
                    cf = counts.astype(jnp.float32)
                    logits = (logits - freq[:, None] * cf
                              - pres[:, None] * (cf > 0))
                rng, sub = jax.random.split(rng)
                if seeded:
                    # First generated token lands at position start + n.
                    seed_flag = packed[:, 10] > 0
                    base_keys = jax.vmap(jax.random.key)(packed[:, 9])
                    per_seed = jax.vmap(jax.random.fold_in)(base_keys, start + n)
                    shared = jax.random.split(sub, temp.shape[0])
                    row_keys = jax.random.wrap_key_data(jnp.where(
                        seed_flag[:, None],
                        jax.random.key_data(per_seed),
                        jax.random.key_data(shared)))
                    sampled = sample_tokens_per_row(logits, temp, top_k, top_p,
                                                    row_keys)
                else:
                    sampled = sample_tokens(logits, temp, top_k, top_p, sub)
                B = sampled.shape[0]
                lp, top_v, top_i = jax.lax.cond(
                    jnp.any(packed[:, 6] > 0),
                    lambda _: _logprobs_of(logits, sampled),
                    lambda _: (jnp.zeros((B,), jnp.float32),
                               jnp.zeros((B, TOP_LOGPROBS), jnp.float32),
                               jnp.zeros((B, TOP_LOGPROBS), jnp.int32)),
                    None)
            if mtp:
                k_cache, page_ends, draft = self._mtp_prefill_commit(
                    params, k_cache, v_cache, page_ends, deferred[0], tokens,
                    positions, seq_lens,
                    jnp.where(next_tok >= 0, next_tok, sampled),
                    page_table, next_page,
                    (hist_table, hist_lens) if with_history else None)
                return (sampled, lp, top_v, top_i, logits, k_cache, v_cache,
                        rng, draft, page_ends)
            if recurrent:
                return (sampled, lp, top_v, top_i, logits, k_cache, v_cache,
                        rng, *state)
            return sampled, lp, top_v, top_i, logits, k_cache, v_cache, rng

        fn = perf.instrumented_jit("prefill", step, key=key,
                                   context=self._store_context,
                                   donate_argnums=(1, 2),
                                   **({"donate_argnames": ("state",)}
                                      if recurrent else {}),
                                   labels=self.backends.labels(
                                       "prefill", expert_product(
                                           bucket * batch, self.backends)))
        self._prefill_cache[key] = fn
        return fn

    def _mtp_prefill_commit(self, params, k_cache, v_cache, page_ends,
                            deferred, tokens, positions, seq_lens,
                            next_token, page_table, next_page, hist):
        """Traced inside a prefill program of a model whose prediction
        module drafts: the module over the chunk (model.mtp_prefill), then
        ONE page-block commit of the model's layers and the module's, the
        module's entry of the last valid token into the slot after it (the
        chunk's pages, or ``next_page``), and the normed output at each
        full page's last position into ``page_ends`` (mtp_hidden). Returns
        (k_cache, page_ends, the draft of the token after next [B])."""
        from dynamo_tpu.engine.kv_quant import scatter_pages
        from dynamo_tpu.engine.model import mtp_prefill
        spec = self.spec
        L, page = spec.num_layers, self.config.page_size
        hidden, k_blocks, _v_blocks, flat = deferred
        b, s = tokens.shape
        if hist is not None:
            hist_table, hist_lens = hist
            before = jnp.take_along_axis(
                hist_table, jnp.maximum(hist_lens // page - 1, 0)[:, None],
                axis=1)[:, 0]
            hist = (hist_table, hist_lens, page_ends[before])
        with perf.scope("mtp"):
            blocks, e_last, draft = mtp_prefill(
                params, spec, k_cache, hidden, tokens, positions, seq_lens,
                next_token, hist, backends=self.backends)
        with perf.scope("kv.commit"):
            k_cache = scatter_pages(
                k_cache, jnp.concatenate([k_blocks, blocks], axis=0), flat)
            full = ((jnp.arange(s // page)[None, :] + 1) * page
                    <= seq_lens[:, None])
            page_ends = page_ends.at[jnp.where(full, page_table, 0)].set(
                hidden[:, page - 1::page])
            table = jnp.concatenate([page_table, next_page[:, None]], axis=1)
            valid = seq_lens > 0
            if self.backends.kv_commit == "in_place":
                from dynamo_tpu.engine.attention import commit_window_pallas
                k_cache, _ = commit_window_pallas(
                    k_cache, v_cache, e_last[None, None, :, None, :],
                    jnp.zeros((1, 1, b, 1, 0), v_cache.dtype),
                    seq_lens, jnp.where(valid, seq_lens + 1, 0),
                    valid.astype(jnp.int32), table,
                    interpret=self.backends.interpret, layers=(L, 1))
            else:
                dest = jnp.take_along_axis(
                    table, (seq_lens // page)[:, None], axis=1)[:, 0]
                k_cache = k_cache.at[
                    L, 0, jnp.where(valid, dest, 0),
                    jnp.where(valid, seq_lens % page, 0)].set(e_last)
        return k_cache, page_ends, draft

    def _get_mtp_window(self, window: int, bucket_pages: int, seeded: bool):
        """The window program of a model whose own prediction module drafts
        (``spec_decode="mtp"``; a latent block without an indexer): each of
        ``window`` scan steps is ONE verify of spec_k + 1 positions a row
        (the chained token and its draft, one read of the weights) and ONE
        run of the module over the positions emitted, which drafts for the
        next step. The accept rule is _get_spec_window's for a point-mass
        drafter: each position draws from the target; the draft is accepted
        iff the first draw equals it; every emitted token is
        target-distributed and greedy rows are token-identical to the plain
        window's. A row's position and draft chain on the device
        (positions_dev, draft_dev) as the advance is data-dependent.

        The window's columns are static (step m writes columns m * S to
        m * S + S - 1 of the buffer of all pool layers, [layers, B, W,
        width]: the order the chip holds it in whatever the program says,
        a (layer, slot)'s W rows two tiles of 8, which a layer's reader
        takes as they lie; beside the in-place commit the step's write is
        attention.write_window_rows_pallas, the tile of rows it falls in
        through VMEM, where XLA's update went a sublane at a time) and a
        mask says which hold a committed token; once, after the scan, the
        committed columns are moved to the front and go into the pool
        through the in-place writer (attention.commit_window_pallas; a
        scatter where that cannot be had), the module's layer one slot on:
        its entry of position i lies at slot i + 1 (model.mtp_prefill)."""
        from dynamo_tpu.engine.model import (decode_verify_step,
                                             latent_block_attention,
                                             mtp_block, mtp_logits,
                                             spec_rope_tables)
        spec = self.spec
        page = self.config.page_size
        S = self.config.spec_k + 1
        W = window * S
        L, LP = spec.num_layers, spec.pool_layers

        def write_rows(buf, new, start):
            # A step's rows into a buffer [layers, B, W, width].
            if self.backends.kv_commit == "in_place":
                from dynamo_tpu.engine.attention import (
                    write_window_rows_pallas)
                return write_window_rows_pallas(
                    buf, new, start, interpret=self.backends.interpret)
            return jax.lax.dynamic_update_slice(buf, new, (0, 0, start, 0))

        def run_window(params, k_cache, v_cache, tokens_dev, positions_dev,
                       draft_dev, page_ends, packed, rng):
            override = packed[:, PK_OVERRIDE] > 0
            tokens0 = jnp.where(override, packed[:, PK_TOKEN], tokens_dev)
            pos0 = jnp.where(override, packed[:, PK_POS], positions_dev)
            # A token the host put in has no draft behind it.
            draft0 = jnp.where(override, -1, draft_dev)
            active = packed[:, PK_SEQLEN] > 0
            cap = packed[:, PK_CAP]
            top_k = packed[:, PK_TOPK]
            temp = jax.lax.bitcast_convert_type(packed[:, PK_TEMP],
                                                jnp.float32)
            top_p = jax.lax.bitcast_convert_type(packed[:, PK_TOPP],
                                                 jnp.float32)
            page_table = packed[:, PK_PREFIX:]
            B = tokens0.shape[0]
            b_idx = jnp.arange(B)
            width = spec.kv_entry[1][0]
            # Cache-resident before the window: the model's layers hold
            # positions below pos0, the module's layer slots 1 to pos0.
            hist_lens = jnp.where(active, pos0, 0)
            mod_lens = jnp.where(active, pos0 + 1, 0)
            with perf.scope("kv.commit"):
                kbuf0 = jnp.zeros((LP, B, W, width), k_cache.dtype)
            want_lp = jnp.any(packed[:, PK_LOGPROB] > 0)
            temp_s, top_k_s, top_p_s = (jnp.repeat(a, S)
                                        for a in (temp, top_k, top_p))
            if seeded:
                seed_s = jnp.repeat(packed[:, PK_SEEDED] > 0, S)
                base_s = jax.random.wrap_key_data(jnp.repeat(
                    jax.random.key_data(jax.vmap(jax.random.key)(
                        packed[:, PK_SEED])), S, axis=0))

            def step(carry, m):
                tokens, pos, draft, keep, kbuf, hbuf, rng = carry
                live = active & (pos < cap)
                # The draft's own position has to be under the row's cap.
                drafted = live & (draft >= 0) & (pos + 1 < cap)
                tok_blk = jnp.stack(
                    [tokens, jnp.where(drafted, draft, 0)], axis=1)
                pos_blk = pos[:, None] + jnp.arange(S)[None, :]
                hidden, logits, k_new, counts = decode_verify_step(
                    params, spec, k_cache, kbuf, keep, tok_blk, pos_blk,
                    page_table, hist_lens, jnp.stack([live, drafted], axis=1),
                    self.backends)
                with perf.scope("sample"):
                    flat = logits.reshape(B * S, -1)
                    rng, sub = jax.random.split(rng)
                    if seeded:
                        # Column j's token lands at pos + 1 + j: the plain
                        # seeded window's convention.
                        per_seed = jax.vmap(jax.random.fold_in)(
                            base_s, (pos_blk + 1).reshape(-1))
                        shared = jax.random.split(sub, B * S)
                        row_keys = jax.random.wrap_key_data(jnp.where(
                            seed_s[:, None], jax.random.key_data(per_seed),
                            jax.random.key_data(shared)))
                        out = sample_tokens_per_row(flat, temp_s, top_k_s,
                                                    top_p_s, row_keys)
                    else:
                        out = sample_tokens(flat, temp_s, top_k_s, top_p_s,
                                            sub)
                    lp, top_v, top_i = jax.lax.cond(
                        want_lp, lambda _: _logprobs_of(flat, out),
                        lambda _: (
                            jnp.zeros((B * S,), jnp.float32),
                            jnp.zeros((B * S, TOP_LOGPROBS), jnp.float32),
                            jnp.zeros((B * S, TOP_LOGPROBS), jnp.int32)),
                        None)
                    out = out.reshape(B, S)
                    accepted = drafted & (out[:, 0] == draft)
                    emitted = jnp.where(live, 1 + accepted, 0)
                with perf.scope("mtp"):
                    # The module over the positions emitted: their next
                    # tokens are the draws; the draft after the last.
                    cos, sin = spec_rope_tables(spec, pos_blk)
                    layer = jnp.asarray(L, jnp.int32)

                    def attend(q, k, v, kind):
                        return latent_block_attention(
                            q, k_cache, layer, page_table, mod_lens,
                            kbuf[L], keep, k[:, :, 0], spec, live,
                            self.backends, lo=1, scoped=False)

                    y, k_mod, mcounts = mtp_block(
                        params, spec, hidden, out, cos, sin, attend,
                        live=jnp.stack([live, accepted], axis=1),
                        backends=self.backends)
                    last = accepted.astype(jnp.int32)
                    nxt = jnp.argmax(mtp_logits(
                        params, spec, y[b_idx, last]), axis=-1)
                    draft = jnp.where(live, nxt.astype(jnp.int32), draft)
                with perf.scope("kv.commit"):
                    fresh = jnp.concatenate([k_new, k_mod[None]], axis=0)
                    kbuf = write_rows(kbuf, fresh[:, :, :, 0], m * S)
                    keep = jax.lax.dynamic_update_slice(
                        keep, jnp.arange(S)[None, :] < emitted[:, None],
                        (0, m * S))
                    hbuf = write_rows(hbuf[None], hidden[None], m * S)[0]
                tokens = jnp.where(live, out[b_idx, last], tokens)
                pos = pos + emitted
                # What the model's layers and the module's counted, by key.
                counted = {key: a.sum(0) + mcounts[key]
                           for key, a in counts.items()}
                return (tokens, pos, draft, keep, kbuf, hbuf, rng), (
                    out, lp.reshape(B, S), top_v.reshape(B, S, -1),
                    top_i.reshape(B, S, -1), emitted.astype(jnp.int32),
                    jnp.where(drafted, tok_blk[:, 1], -1), counted)

            carry0 = (tokens0, pos0, draft0, jnp.zeros((B, W), bool), kbuf0,
                      jnp.zeros((B, W, spec.hidden_size), jnp.bfloat16), rng)
            (tokens, pos, draft, keep, kbuf, hbuf, rng), \
                (toks, lps, top_vs, top_is, emits, drafts, counted) = \
                jax.lax.scan(step, carry0, jnp.arange(window))
            # "emit" [M, B]: tokens a row's step emitted (0: not live);
            # "draft": the draft it verified (-1: none).
            stats = {**{key: a.sum(0) for key, a in counted.items()},
                     "emit": emits, "draft": drafts}
            with perf.scope("kv.commit"):
                # The committed columns to the front, in order: column c of
                # the result is the token at position pos0 + c.
                wlen = jnp.sum(keep, axis=-1)
                cols = jnp.arange(W)[None, :]
                order = jnp.argsort(jnp.where(keep, cols, W + cols), axis=-1)
                # As the commit takes it: [layers, 1, B, W, width].
                kbuf = jnp.take_along_axis(
                    kbuf, order[None, :, :, None], axis=2)[:, None]
                hbuf = jnp.take_along_axis(hbuf, order[:, :, None], axis=1)
                # The model's output at each page's last position that the
                # window committed, by page id (mtp_hidden).
                for j in range(-(-W // page)):
                    col = page - 1 - pos0 % page + j * page
                    at = jnp.take_along_axis(page_table, jnp.clip(
                        (pos0 + col) // page, 0,
                        page_table.shape[1] - 1)[:, None], axis=1)[:, 0]
                    done = active & (col < wlen) & (pos0 + col < cap)
                    page_ends = page_ends.at[jnp.where(done, at, 0)].set(
                        hbuf[b_idx, jnp.clip(col, 0, W - 1)])
                seq = active.astype(jnp.int32)
                ends = [jnp.minimum(cap, first + wlen)
                        for first in (pos0, pos0 + 1)]
                if self.backends.kv_commit == "in_place":
                    from dynamo_tpu.engine.attention import (
                        commit_window_pallas)
                    interpret = self.backends.interpret
                    vwin = jnp.zeros((LP, 1, B, W, 0), v_cache.dtype)
                    k_cache, _ = commit_window_pallas(
                        k_cache, v_cache, kbuf[:L], vwin[:L], pos0, ends[0],
                        seq, page_table, interpret=interpret,
                        layers=(0, L))
                    k_cache, _ = commit_window_pallas(
                        k_cache, v_cache, kbuf[L:], vwin[L:], pos0 + 1,
                        ends[1], seq, page_table, interpret=interpret,
                        layers=(L, LP - L))
                else:
                    for part, first, end in (
                            (slice(0, L), pos0, ends[0]),
                            (slice(L, LP), pos0 + 1, ends[1])):
                        dest, off = window_token_slots(
                            first, end, seq, page_table, W, page)
                        k_cache = k_cache.at[part, :, dest, off].set(
                            kbuf[part].transpose(0, 1, 3, 2, 4))
            return (toks, lps, top_vs, top_is, tokens, pos, draft, page_ends,
                    k_cache, v_cache, rng, stats)

        return run_window

    def _get_decode(self):
        if self._decode_fn is not None:
            return self._decode_fn
        spec = self.spec
        if spec.recurrent:
            raise UnsupportedBlockError(
                "the single decode step (runner.decode)", "it carries no "
                "recurrent state; a block with recurrent layers decodes "
                "through the window program")

        def step(params, k_cache, v_cache, tokens, positions, page_table,
                 seq_lens, temperature, top_k, top_p, rng):
            logits, k_cache, v_cache = decode_forward(
                params, spec, k_cache, v_cache, tokens, positions,
                page_table, seq_lens, backends=self.backends)
            rng, sub = jax.random.split(rng)
            sampled = sample_tokens(logits, temperature, top_k, top_p, sub)
            return sampled, k_cache, v_cache, rng

        self._decode_fn = perf.instrumented_jit(
            "decode_step", step, key="decode_step", donate_argnums=(1, 2),
            context=self._store_context)
        return self._decode_fn

    def _get_window(self, window: int, bucket_pages: int,
                    penalized: bool = False, seeded: bool = False):
        """Window program, specialized on ``penalized`` and ``seeded``:
        the penalty variant threads the [B, V] counts state through the
        scan; the seeded variant derives each slot's PRNG key from
        (seed, token position), making a seeded request's draws
        batch-invariant and preemption-stable. The common variant is the
        exact plain program, so default serving costs nothing extra."""
        key = (window, bucket_pages, penalized, seeded)
        fn = self._window_cache.get(key)
        if fn is not None:
            return fn
        spec = self.spec
        page = self.config.page_size
        drafting = self.config.spec_decode == "mtp"
        # A step's rows: every slot, and each verified position.
        labels = self.backends.labels("decode_window", expert_product(
            self.config.max_num_seqs * (
                self.config.spec_k + 1 if drafting else 1), self.backends))
        if drafting:
            # The same program under the same name and key: each of its
            # ``window`` steps is a draft and a verify (_get_mtp_window).
            fn = perf.instrumented_jit(
                "decode_window",
                self._get_mtp_window(window, bucket_pages, seeded), key=key,
                donate_argnums=(1, 2), labels=labels,
                context=self._store_context)
            self._window_cache[key] = fn
            return fn

        # A block with recurrent layers: its two state arrays ride the
        # steps' carry behind the counts and are returned behind the rest.
        recurrent = spec.recurrent

        def run_window(params, k_cache, v_cache, tokens_dev, packed, rng,
                       counts=None, lora=None, state=()):
            adapter_ids = packed[:, PK_ADAPTER]
            mask = packed[:, PK_OVERRIDE] > 0
            tokens0 = jnp.where(mask, packed[:, PK_TOKEN], tokens_dev)
            positions0 = packed[:, PK_POS]
            seq_lens0 = packed[:, PK_SEQLEN]
            top_k = packed[:, PK_TOPK]
            temp = jax.lax.bitcast_convert_type(packed[:, PK_TEMP],
                                                jnp.float32)
            top_p = jax.lax.bitcast_convert_type(packed[:, PK_TOPP],
                                                 jnp.float32)
            cap = packed[:, PK_CAP]
            freq_pen = jax.lax.bitcast_convert_type(packed[:, PK_FREQPEN],
                                                    jnp.float32)
            pres_pen = jax.lax.bitcast_convert_type(packed[:, PK_PRESPEN],
                                                    jnp.float32)
            if seeded:
                seed_flag = packed[:, PK_SEEDED] > 0
                base_keys = jax.vmap(jax.random.key)(packed[:, PK_SEED])
            page_table = packed[:, PK_PREFIX:]
            B = tokens0.shape[0]
            # The window's buffers hold the layers that leave K and V.
            L = spec.pool_layers - spec.mtp_layers
            nkv, (dk, dv) = spec.kv_entry
            # Cache-resident history length is FIXED across the window: the
            # window's own tokens live in a small in-window buffer and are
            # committed to the pool ONCE, at the end. The caches are
            # read-only inside the scan — carrying a multi-GB pool through
            # scan ys/carries makes XLA copy it per step (measured: 50
            # ms/step at a 3 GB pool, vs flat ~1.5 ms this way).
            hist_lens = jnp.maximum(seq_lens0 - 1, 0)
            with perf.scope("kv.commit"):
                kbuf0 = jnp.zeros((L, nkv, B, window, dk), k_cache.dtype)
                vbuf0 = jnp.zeros((L, nkv, B, window, dv), v_cache.dtype)

            want_lp = jnp.any(packed[:, PK_LOGPROB] > 0)
            # A routed block's window also counts what its routing did to
            # the live rows (model.moe_load_stats), summed over steps and
            # layers on the device: one [3] vector more in the readback
            # ("moe"). A latent block's counts the keys its rows attended
            # and had in context (model.latent_window_attention): a [2]
            # vector of its own ("attn").
            routed = bool(spec.num_experts)
            # A looped stack's counts the passes its live rows took
            # (model.scan_passes): a [2] vector ("loop").
            looped = spec.loop_passes > 1

            # The compressed-key array is read where it lies, as the pool
            # is, and written at the window's commit.
            comp = None
            if recurrent:
                from dynamo_tpu.engine import hybrid
                state, comp = hybrid.split_state(spec, state)

            def step(carry, m):
                tokens, positions, kbuf, vbuf, rng, cnts, *state = carry
                # A slot advances only while live AND within its allocated
                # pages; at capacity it freezes in-graph (the host emits
                # LENGTH when it sees the cap).
                live = (seq_lens0 > 0) & (positions < cap)
                if recurrent:
                    logits, k_new, v_new, state, counted = hybrid.window_step(
                        params, spec, k_cache, v_cache, kbuf, vbuf, m, tokens,
                        page_table, hist_lens, tuple(state), live,
                        positions=positions, comp=comp,
                        backends=self.backends)
                else:
                    logits, k_new, v_new, counted = decode_window_step(
                        params, spec, k_cache, v_cache, kbuf, vbuf, m, tokens,
                        positions, page_table, hist_lens,
                        backends=self.backends, lora=lora,
                        adapter_ids=adapter_ids,
                        live=live if routed or looped else None)
                # Append this step's K/V ([L,B,Nkv,D] -> window col m).
                with perf.scope("kv.commit"):
                    kbuf = jax.lax.dynamic_update_slice(
                        kbuf, k_new.transpose(0, 2, 1, 3)[:, :, :, None],
                        (0, 0, 0, m, 0))
                    vbuf = jax.lax.dynamic_update_slice(
                        vbuf, v_new.transpose(0, 2, 1, 3)[:, :, :, None],
                        (0, 0, 0, m, 0))
                with perf.scope("sample"):
                    if penalized:
                        # OpenAI penalties over generated tokens (vLLM
                        # semantics): subtract before temperature/top-k.
                        cf = cnts.astype(jnp.float32)
                        logits = (logits - freq_pen[:, None] * cf
                                  - pres_pen[:, None] * (cf > 0))
                    rng, sub = jax.random.split(rng)
                    if seeded:
                        # The token being sampled lands at positions + 1: fold
                        # the request seed with that absolute position, so the
                        # draw depends only on (seed, position, logits).
                        per_seed = jax.vmap(jax.random.fold_in)(
                            base_keys, positions + 1)
                        shared = jax.random.split(sub, temp.shape[0])
                        row_keys = jax.random.wrap_key_data(jnp.where(
                            seed_flag[:, None],
                            jax.random.key_data(per_seed),
                            jax.random.key_data(shared)))
                        sampled = sample_tokens_per_row(logits, temp, top_k,
                                                        top_p, row_keys)
                    else:
                        sampled = sample_tokens(logits, temp, top_k, top_p, sub)
                    B = sampled.shape[0]
                    if penalized:
                        # Saturating per-row count bump for this step's token.
                        b_idx = jnp.arange(B)
                        cur = cnts[b_idx, sampled]
                        inc = (live & (cur < 255)).astype(jnp.uint8)
                        cnts = cnts.at[b_idx, sampled].add(inc)
                    # Logprobs only when some slot asked (lax.cond executes one
                    # branch on TPU: zero cost otherwise).
                    lp, top_v, top_i = jax.lax.cond(
                        want_lp,
                        lambda _: _logprobs_of(logits, sampled),
                        lambda _: (jnp.zeros((B,), jnp.float32),
                                   jnp.zeros((B, TOP_LOGPROBS), jnp.float32),
                                   jnp.zeros((B, TOP_LOGPROBS), jnp.int32)),
                        None)
                    tokens = jnp.where(live, sampled, tokens)
                    positions = positions + live.astype(jnp.int32)
                return (tokens, positions, kbuf, vbuf, rng, cnts, *state), (
                    sampled, lp, top_v, top_i, counted)

            carry0 = (tokens0, positions0, kbuf0, vbuf0, rng,
                      counts if penalized else jnp.zeros((), jnp.uint8),
                      *state)
            (tokens, positions_end, kbuf, vbuf, rng, counts_out, *state), \
                (toks, lps, top_vs, top_is, counted) = \
                jax.lax.scan(step, carry0, jnp.arange(window))
            if comp is not None:
                with perf.scope("attn.compress"):
                    state.append(hybrid.commit_stripes(
                        comp, k_cache, kbuf, page_table, hist_lens,
                        hist_lens + positions_end - positions0, spec))
            # [M, L, n] -> [n] under the key the step function counted it
            # by (runtime/flight.py COUNTS has the columns): a latent
            # block's keys ("attn": 2 sums), a routed block's load ("moe",
            # model.moe_load_stats: 3 sums, 5 for a told share), the live
            # rows of every step of a block with recurrent layers ("ssm": 1
            # sum, the rows whose state a step had to touch). A block that
            # counts nothing adds nothing to the program's outputs.
            stats = {key: jnp.sum(a, axis=(0, 1))
                     for key, a in counted.items()}

            # Commit the window: every (slot, step) entry goes to its page.
            with perf.scope("kv.commit"):
                if self.backends.kv_commit == "in_place":
                    # Only the pages a live row's window touched are
                    # rewritten, where and how they lie: XLA's scatter
                    # converts the whole pool to its own layout and back,
                    # four pool-sized copies a window (PERF.md 6, PR 29).
                    from dynamo_tpu.engine.attention import (
                        commit_window_pallas)
                    k_cache, v_cache = commit_window_pallas(
                        k_cache, v_cache, kbuf, vbuf, positions0, cap,
                        seq_lens0, page_table,
                        interpret=self.backends.interpret,
                        # A pool with a prediction module's layer behind
                        # the model's, which this window leaves alone.
                        layers=(0, L) if spec.mtp_layers else None)
                else:
                    dest, off = window_token_slots(
                        positions0, cap, seq_lens0, page_table, window, page)
                    # kbuf [L,Nkv,B,M,D] -> [L,Nkv,M,B,D] matching the index
                    # arrays; scatter_tokens quantizes int8 pools in the
                    # same commit.
                    k_cache = scatter_tokens(
                        k_cache, kbuf.transpose(0, 1, 3, 2, 4), dest, off)
                    v_cache = scatter_tokens(
                        v_cache, vbuf.transpose(0, 1, 3, 2, 4), dest, off)
            if penalized:
                return (toks, lps, top_vs, top_is, tokens, k_cache,
                        v_cache, rng, counts_out, stats, *state)
            return (toks, lps, top_vs, top_is, tokens, k_cache, v_cache,
                    rng, stats, *state)

        donate = (1, 2, 6) if penalized else (1, 2)
        fn = perf.instrumented_jit(
            "decode_window", run_window, key=key, donate_argnums=donate,
            labels=labels, context=self._store_context,
            **({"donate_argnames": ("state",)} if recurrent else {}))
        self._window_cache[key] = fn
        return fn

    def _get_spec_window(self, m_outer: int, k: int, bucket_pages: int):
        """Speculative window program: m_outer verify steps, each
        drafting up to ``k`` tokens by bigram prompt-lookup against the
        ON-DEVICE token history and verifying them in one forward
        (model.decode_window_multi_step). Sequence position is carried in
        positions_dev between windows — the advance is data-dependent
        (accepted drafts), so pipelined dispatches must chain on-device.

        Sampling is on-device rejection sampling degenerated for the
        point-mass (n-gram) drafter: accepting a draft w.p.
        min(1, p_target/q_draft) and resampling the first rejection from
        the normalized residual collapses, when q is a point mass at the
        draft token, to "sample x ~ target at each position; accept iff
        x == draft; emit x either way" — so each verify position draws
        ONE per-row sample from the target distribution and the existing
        prefix-acceptance compare is the accept rule. Every emitted
        token is exactly target-distributed; greedy rows (temp <= 0)
        degenerate to argmax, bit-identical to non-spec greedy decode.
        Temperature/top-k/top-p/seed ride in as DATA (packed columns):
        one program serves any mix, zero recompiles. Seeded rows fold
        the request seed with the token's absolute landing position —
        the same convention as the plain seeded window — so a seeded
        stream is token-identical with spec on or off."""
        key = ("spec", m_outer, k, bucket_pages)
        fn = self._window_cache.get(key)
        if fn is not None:
            return fn
        spec = self.spec
        page = self.config.page_size
        S = k + 1
        W = m_outer * S  # in-window KV columns (worst case: all accepted)

        def run_spec(params, k_cache, v_cache, tokens_dev, hist_dev,
                     positions_dev, packed, rng, lora=None):
            from dynamo_tpu.engine.model import decode_window_multi_step
            adapter_ids = packed[:, PK_ADAPTER]
            override = packed[:, PK_OVERRIDE] > 0
            tokens0 = jnp.where(override, packed[:, PK_TOKEN], tokens_dev)
            pos0 = jnp.where(override, packed[:, PK_POS], positions_dev)
            active = packed[:, PK_SEQLEN] > 0
            cap = packed[:, PK_CAP]
            top_k = packed[:, PK_TOPK]
            temp = jax.lax.bitcast_convert_type(packed[:, PK_TEMP],
                                                jnp.float32)
            top_p = jax.lax.bitcast_convert_type(packed[:, PK_TOPP],
                                                 jnp.float32)
            seed_flag = packed[:, PK_SEEDED] > 0
            base_keys = jax.vmap(jax.random.key)(packed[:, PK_SEED])
            page_table = packed[:, PK_PREFIX:]
            B = tokens0.shape[0]
            H = hist_dev.shape[1]
            L, nkv, d = spec.num_layers, spec.num_kv_heads, spec.head_dim
            b_idx = jnp.arange(B)
            kbuf0 = jnp.zeros((L, nkv, B, W, d), k_cache.dtype)
            vbuf0 = jnp.zeros((L, nkv, B, W, d), v_cache.dtype)
            # Per-(row, verify-column) sampling params: column j of a
            # row's block shares that row's temperature/top-k/top-p.
            temp_s = jnp.repeat(temp, S)
            top_k_s = jnp.repeat(top_k, S)
            top_p_s = jnp.repeat(top_p, S)
            seed_s = jnp.repeat(seed_flag, S)
            base_s = jax.random.wrap_key_data(
                jnp.repeat(jax.random.key_data(base_keys), S, axis=0))

            def step(carry, _):
                tokens, pos, wlen, hist, kbuf, vbuf, rng = carry
                live = active & (pos < cap)
                safe_pos = jnp.clip(pos, 0, H - 1)
                # Invariant: hist[pos] = the token being fed this step.
                hist = hist.at[b_idx, safe_pos].set(
                    jnp.where(live, tokens, hist[b_idx, safe_pos]))
                # Bigram prompt-lookup: most recent earlier occurrence of
                # (hist[pos-1], tokens); drafts = what followed it.
                x1 = hist[b_idx, jnp.clip(pos - 1, 0, H - 1)]
                jidx = jnp.arange(H - 1)
                match = ((hist[:, :-1] == x1[:, None])
                         & (hist[:, 1:] == tokens[:, None])
                         & (jidx[None, :] + 1 < pos[:, None]))
                jstar = jnp.max(jnp.where(match, jidx[None, :], -1), axis=1)
                found = (jstar >= 0) & (pos >= 1) & live
                didx = jstar[:, None] + 2 + jnp.arange(k)[None, :]  # [B,k]
                drafts = hist[b_idx[:, None], jnp.clip(didx, 0, H - 1)]
                dvalid = (found[:, None]
                          & (didx <= pos[:, None])
                          & (pos[:, None] + 1 + jnp.arange(k)[None, :]
                             < cap[:, None]))
                # Draft validity must be a prefix (cumulative AND).
                dvalid = jnp.cumprod(
                    dvalid.astype(jnp.int32), axis=1).astype(bool)
                ndraft = dvalid.sum(axis=1)
                tok_blk = jnp.concatenate(
                    [tokens[:, None], jnp.where(dvalid, drafts, 0)], axis=1)
                pos_blk = pos[:, None] + jnp.arange(S)[None, :]
                # Cache-resident history is FIXED across the window
                # (pos0): everything this window produced lives in
                # kbuf/vbuf cols < wlen, and the pool pages for those
                # positions hold garbage until the post-scan commit.
                logits, k_new, v_new = decode_window_multi_step(
                    params, spec, k_cache, v_cache, kbuf, vbuf, wlen,
                    tok_blk, pos_blk, page_table, hist_lens=pos0,
                    lora=lora, adapter_ids=adapter_ids)
                # One target-distributed draw per verify position ([B,S]
                # flattened to [B*S] rows — the sampler's per-row core is
                # shared with the plain decode window). Column j's token
                # LANDS at pos + 1 + j: seeded rows fold the request seed
                # with that absolute position (the plain seeded window's
                # exact convention), unseeded rows draw fresh split keys.
                rng, sub = jax.random.split(rng)
                land = (pos[:, None] + 1
                        + jnp.arange(S)[None, :]).reshape(-1)  # [B*S]
                per_seed = jax.vmap(jax.random.fold_in)(base_s, land)
                shared = jax.random.split(sub, B * S)
                row_keys = jax.random.wrap_key_data(jnp.where(
                    seed_s[:, None],
                    jax.random.key_data(per_seed),
                    jax.random.key_data(shared)))
                out = sample_tokens_per_row(
                    logits.reshape(B * S, -1), temp_s, top_k_s, top_p_s,
                    row_keys).reshape(B, S)
                # Prefix-acceptance IS the rejection-sampling accept rule
                # for a point-mass drafter: out[:, j] ~ target, accepted
                # iff it reproduced the draft; the first rejection's draw
                # is the residual resample (emitted via out[b, a]); draws
                # past it are conditioned on a dead prefix and dropped.
                eq = (drafts == out[:, :k]) & dvalid
                accflags = jnp.cumprod(
                    eq.astype(jnp.int32), axis=1).astype(bool)
                a = accflags.sum(axis=1)              # accepted drafts
                e = jnp.where(live, a + 1, 0)         # emitted / advance
                # Commit t0 + accepted drafts (block cols < e) into the
                # window buffer at cols wlen..wlen+e-1; invalid -> W
                # (dropped). k_new [L,B,S,Nkv,D] -> kbuf [L,Nkv,B,W,D].
                cols = wlen[:, None] + jnp.arange(S)[None, :]
                kvvalid = jnp.arange(S)[None, :] < e[:, None]
                cols = jnp.where(kvvalid, cols, W)
                kn = k_new.transpose(0, 3, 1, 2, 4)   # [L,Nkv,B,S,D]
                vn = v_new.transpose(0, 3, 1, 2, 4)
                kbuf = kbuf.at[:, :, b_idx[:, None], cols].set(
                    kn, mode="drop")
                vbuf = vbuf.at[:, :, b_idx[:, None], cols].set(
                    vn, mode="drop")
                # History gains every emitted token out[0..a] at pos+1+j.
                hidx = pos[:, None] + 1 + jnp.arange(S)[None, :]
                hidx = jnp.where(kvvalid & (hidx < H), hidx, H)
                hist = hist.at[b_idx[:, None], hidx].set(out, mode="drop")
                tokens = jnp.where(live, out[b_idx, a], tokens)
                pos = pos + e
                wlen = wlen + e
                # Emit e (not a): e == 0 distinguishes a frozen/inactive
                # slot from "zero drafts accepted" (e == 1) — the host
                # walk needs that to mirror the in-graph freeze.
                return (tokens, pos, wlen, hist, kbuf, vbuf, rng), (
                    out, e.astype(jnp.int32), ndraft.astype(jnp.int32))

            carry0 = (tokens0, pos0, jnp.zeros((B,), jnp.int32), hist_dev,
                      kbuf0, vbuf0, rng)
            (tokens, pos, wlen, hist, kbuf, vbuf, rng), \
                (outs, emits, ndrafts) = \
                jax.lax.scan(step, carry0, jnp.arange(m_outer))
            # Commit the window buffer: col c holds the token at absolute
            # position pos0 + c; cols >= wlen land on scratch page 0.
            c_idx = jnp.broadcast_to(jnp.arange(W)[None, :], (B, W))
            abspos = pos0[:, None] + c_idx
            valid = c_idx < wlen[:, None]
            pidx = jnp.clip(abspos // page, 0, page_table.shape[1] - 1)
            dest = jnp.take_along_axis(page_table, pidx, axis=1)
            dest = jnp.where(valid, dest, 0)
            off = jnp.where(valid, abspos % page, 0)
            k_cache = scatter_tokens(k_cache, kbuf, dest, off)
            v_cache = scatter_tokens(v_cache, vbuf, dest, off)
            return (outs, emits, ndrafts, tokens, pos, hist,
                    k_cache, v_cache, rng)

        fn = perf.instrumented_jit("spec_window", run_spec, key=key,
                                   donate_argnums=(1, 2, 4),
                                   context=self._store_context)
        self._window_cache[key] = fn
        return fn

    def decode_spec_window(self, packed: np.ndarray, m_outer: int, k: int):
        """Dispatch one speculative window (m_outer verify steps x up to
        k drafts each). Returns (toks [m_outer,B,k+1], accs [m_outer,B],
        ndrafts [m_outer,B]) device arrays; positions/tokens/history
        chain on-device (see _get_spec_window)."""
        bucket_pages = packed.shape[1] - PK_PREFIX
        fn = self._get_spec_window(m_outer, k, bucket_pages)
        kw = {} if self.lora is None else {"lora": self.lora}
        with self.mesh:
            (outs, accs, ndrafts, self.tokens_dev, self.positions_dev,
             self.hist_dev, self.k_cache, self.v_cache, self._rng) = fn(
                self.params, self.k_cache, self.v_cache, self.tokens_dev,
                self.hist_dev, self.positions_dev, jnp.asarray(packed),
                self._rng, **kw)
        return outs, accs, ndrafts

    def seed_history(self, entries: list[tuple]) -> None:
        """Scatter prefill-chunk tokens into the on-device history +
        position buffers (spec decode only; no-op otherwise). Entries:
        (slot, tokens_np, start_pos, final, first_token) — ``final``
        rows also record the chained sampled token (from tokens_dev,
        or ``first_token`` >= 0 for paths that know it host-side, e.g.
        KV-injected disagg decode) and set positions_dev."""
        if self.hist_dev is None or not entries:
            return
        n_max = max(len(t) for _, t, _, _, _ in entries)
        bucket = 64  # pow2 buckets; full prompts can exceed prefill buckets
        while bucket < n_max:
            bucket *= 2
        bp = 1
        while bp < len(entries):
            bp *= 2
        toks = np.zeros((bp, bucket), np.int32)
        meta = np.zeros((bp, 4), np.int32)  # slot, start, len, final_tok
        meta[:, 3] = -2  # inactive rows
        for i, (slot, t, start, final, first_tok) in enumerate(entries):
            toks[i, :len(t)] = t
            meta[i] = (slot, start, len(t),
                       (first_tok if final and first_tok is not None
                        else (-1 if final else -2)))
        key = ("seedh", bucket, bp)
        fn = self._seed_hist_cache.get(key)
        if fn is None:
            H = self.hist_dev.shape[1]

            def scatter(hist, pos_dev, tokens_dev, toks, meta):
                slots = meta[:, 0]
                starts = meta[:, 1]
                lens = meta[:, 2]
                ftok = meta[:, 3]
                idx = starts[:, None] + jnp.arange(bucket)[None, :]
                ok = ((jnp.arange(bucket)[None, :] < lens[:, None])
                      & (idx < H))  # padding rows have lens == 0
                idx = jnp.where(ok, idx, H)
                hist = hist.at[slots[:, None], idx].set(toks, mode="drop")
                # Final rows: the sampled token sits at start+len and
                # becomes the slot's next fed position. Non-final and
                # inactive rows scatter to dropped (out-of-range)
                # indices — duplicate in-range indices across rows would
                # have unspecified write order.
                final = ftok >= -1
                fpos = jnp.where(final, starts + lens, H)
                fval = jnp.where(ftok >= 0, ftok, tokens_dev[slots])
                hist = hist.at[slots, fpos].set(fval, mode="drop")
                pslot = jnp.where(final, slots, pos_dev.shape[0])
                pos_dev = pos_dev.at[pslot].set(starts + lens, mode="drop")
                return hist, pos_dev

            fn = perf.instrumented_jit("seed_history", scatter, key=key,
                                       donate_argnums=(0, 1),
                                       context=self._store_context)
            self._seed_hist_cache[key] = fn
        with self.mesh:
            self.hist_dev, self.positions_dev = fn(
                self.hist_dev, self.positions_dev, self.tokens_dev,
                jnp.asarray(toks), jnp.asarray(meta))

    # -- batched LoRA (engine/lora.py) ----------------------------------------
    def set_adapter_slot(self, slot: int, host: dict) -> None:
        """Upload one adapter's host weights into device slot ``slot``
        (ENGINE THREAD; the AdapterStore's hot-load path). ``host`` is
        the COMPLETE target set {key: (A [L, d_in, r], B [L, r, d_out])}
        at canonical shapes — untargeted projections are zeros, so a
        slot overwrite can never leave a previous tenant's deltas
        behind. One compiled scatter program for every slot (the slot
        index is data), registered through perf.instrumented_jit."""
        if self.lora is None:
            raise RuntimeError("runner built without max_adapters")
        if not 1 <= slot <= self.config.max_adapters:
            raise ValueError(f"adapter slot {slot} outside "
                             f"[1, {self.config.max_adapters}]")
        key = ("lora_load",)
        fn = self._window_cache.get(key)
        if fn is None:
            def scatter(lora, host, s):
                return jax.tree.map(
                    lambda dst, src: dst.at[:, s].set(src), lora, host)
            fn = perf.instrumented_jit("lora_load", scatter, key=key,
                                       donate_argnums=(0,),
                                       context=self._store_context)
            self._window_cache[key] = fn
        dev = {}
        for k, (a, b) in host.items():
            a = np.asarray(a)
            b = np.asarray(b)
            if self.kv_rep > 1 and k in ("wk", "wv"):
                # Match the replicated wk/wv columns: canonical head g's
                # B columns land at effective heads [g*rep, (g+1)*rep).
                L, r, _ = b.shape
                d = self.spec.head_dim
                b = (b.reshape(L, r, self.canonical_nkv, d)
                     .repeat(self.kv_rep, axis=2)
                     .reshape(L, r, self.spec.num_kv_heads * d))
            dev[k] = {"a": jnp.asarray(a), "b": jnp.asarray(b)}
        with self.mesh:
            self.lora = fn(self.lora, dev, jnp.asarray(slot, jnp.int32))

    # -- public API (blocking; called from the engine thread) -----------------
    def prefill_batch(self, seqs: list[PrefillSeq],
                      slots: list[int] | None = None,
                      count_rows: np.ndarray | None = None,
                      fetch: bool = True):
        """Prefill a batch of chunks (same compiled program per
        (bucket, padded-batch, with_history) key).

        With ``slots=None`` (tests, disagg prefill): blocks and returns the
        sampled first tokens [len(seqs)] as numpy. With ``slots`` given
        (the serving engine): the sampled tokens are ALSO scattered into
        ``tokens_dev[slots]`` on-device — the decode windows chain from
        them with no override upload — and the DEVICE array is returned so
        the caller can fetch the values asynchronously (first-token
        emission never blocks the dispatch pipeline on a host<->device
        round trip).

        All rows must agree on with-history-ness; rows are padded to the next
        batch bucket (1,2,4,8) with inactive rows.
        """
        cfg = self.config
        page = cfg.page_size
        n_max = max(len(s.tokens) for s in seqs)
        bucket = cfg.bucket_for(n_max)
        bucket_pages = bucket // page
        # One program holds at most max_prefill_tokens rows x bucket: a
        # group over that (the engine's groups are 8 prompts of ANY length:
        # 8 x 8,192 rows are 4.3 GB of a 16,384-wide feed-forward's
        # intermediates alone) runs in parts, in turn, each a program that
        # a smaller group of the same bucket also draws.
        rows = max(1, cfg.max_prefill_tokens // bucket)
        # And at most PREFILL_FRESH_KV_BYTES of fresh K and V, which a
        # program holds for all its rows ahead of the commit (a looped
        # stack's token leaves K and V once a PASS and layer: 1.5 MiB a
        # token at 192 pairs of 16 heads, 6 GiB for 8 rows x 512): the
        # largest power of two of rows under it, since a batch is padded
        # to one. No preset's bytes reach the bound at max_prefill_tokens.
        fresh = bucket * cfg.kv_token_bytes() // max(1, cfg.tp * cfg.pp)
        while rows > 1 and rows * fresh > PREFILL_FRESH_KV_BYTES:
            rows = 1 << ((rows - 1).bit_length() - 1)
        if len(seqs) > rows:
            return self._prefill_parts(seqs, slots, count_rows, fetch, rows)
        with_history = any(s.hist_pages is not None and len(s.hist_pages)
                           for s in seqs)
        bp = 1
        while bp < len(seqs):
            bp *= 2
        maxp = cfg.max_pages_per_seq
        mtp = cfg.spec_decode == "mtp" and bool(self.spec.mtp_layers)
        recurrent = self.spec.recurrent
        width = (_PF_HDR + bucket + bucket_pages
                 + (maxp if with_history else 0) + (2 if mtp else 0)
                 + int(recurrent))
        packed = np.zeros((bp, width), np.int32)
        if recurrent:
            packed[:, -1] = -1      # a padding row's state goes nowhere
        for i, s in enumerate(seqs):
            n = len(s.tokens)
            if recurrent:
                packed[i, -1] = s.slot if slots is None else slots[i]
            packed[i, 0] = s.start_pos
            packed[i, 1] = n
            temp, top_k, top_p = s.sampling
            packed[i, 3] = np.float32(temp).view(np.int32)
            packed[i, 4] = top_k
            packed[i, 5] = np.float32(top_p).view(np.int32)
            packed[i, 6] = int(s.logprobs)
            fp, pp = s.penalties
            packed[i, 7] = np.float32(fp).view(np.int32)
            packed[i, 8] = np.float32(pp).view(np.int32)
            if s.seed is not None:
                packed[i, 9] = mask_seed(s.seed)
                packed[i, 10] = 1
            packed[i, 11] = s.adapter_id
            if mtp:
                packed[i, -2:] = (s.next_token, s.next_page)
            packed[i, _PF_HDR:_PF_HDR + n] = s.tokens
            # Pad page-table rows stay 0 = the allocator's RESERVED scratch
            # page, so padded block scatters land there — padding with a
            # live page would create duplicate scatter indices whose XLA
            # write order is unspecified.
            packed[i, _PF_HDR + bucket:
                   _PF_HDR + bucket + len(s.chunk_pages)] = s.chunk_pages
            if with_history and s.hist_pages is not None and len(s.hist_pages):
                off = _PF_HDR + bucket + bucket_pages
                packed[i, off:off + len(s.hist_pages)] = s.hist_pages
                packed[i, 2] = s.start_pos
        penalized = count_rows is not None
        seeded = any(s.seed is not None for s in seqs)
        with_embeds = any(s.embeds is not None for s in seqs)
        kw = {}
        if with_embeds:
            import ml_dtypes
            emb = np.zeros((bp, bucket, self.spec.hidden_size),
                           ml_dtypes.bfloat16)
            emb_mask = np.zeros((bp, bucket), bool)
            for i, s in enumerate(seqs):
                if s.embeds is None:
                    continue
                n_row = len(s.tokens)
                emb[i, :n_row] = s.embeds.astype(ml_dtypes.bfloat16)
                emb_mask[i, :n_row] = s.embeds_mask
            kw = {"emb": jnp.asarray(emb), "emb_mask": jnp.asarray(emb_mask)}
        if self.lora is not None:
            # Adapter stacks ride every prefill when LoRA serving is on:
            # row ids are data (col 11), so one program covers every mix.
            kw["lora"] = self.lora
        if mtp:
            kw["page_ends"] = self.mtp_hidden
        if recurrent:
            kw["state"] = self.state_arrays
        fn = self._get_prefill(bucket, bp, with_history, penalized, seeded,
                               with_embeds)
        if self.spec.num_experts and expert_product(
                bucket * bp, self.backends) == "grouped":
            self.moe_grouped_pairs += (bucket * bp
                                       * self.spec.num_experts_per_tok)
        # rest: a prediction module's (draft, page ends), or the two state
        # arrays of a block with recurrent layers.
        with self.mesh:
            if penalized:
                rows = np.asarray(count_rows, np.uint8)
                if rows.shape[0] < bp:  # pad to the batch bucket
                    rows = np.concatenate(
                        [rows, np.zeros((bp - rows.shape[0], rows.shape[1]),
                                        np.uint8)])
                (sampled, lp, top_v, top_i, logits, self.k_cache,
                 self.v_cache, self._rng, *rest) = fn(
                    self.params, self.k_cache, self.v_cache,
                    jnp.asarray(packed), self._rng, jnp.asarray(rows), **kw)
            else:
                (sampled, lp, top_v, top_i, logits, self.k_cache,
                 self.v_cache, self._rng, *rest) = fn(
                    self.params, self.k_cache, self.v_cache,
                    jnp.asarray(packed), self._rng, **kw)
                if mtp:
                    self.mtp_hidden = rest[1]
            if recurrent:
                self._keep_state(rest)
        # Device handle (no transfer unless a caller converts it).
        self.last_prefill_logits = logits
        if slots is not None:
            idx = jnp.asarray(np.asarray(slots, np.int32))
            with self.mesh:
                self.tokens_dev = self.tokens_dev.at[idx].set(
                    sampled[:len(seqs)])
                if mtp:
                    # The drafting window chains from these as from the
                    # token: where the row stands, and the module's draft
                    # of the token after it.
                    self.positions_dev = self.positions_dev.at[idx].set(
                        jnp.asarray(np.asarray(
                            [s.start_pos + len(s.tokens) for s in seqs],
                            np.int32)))
                    self.draft_dev = self.draft_dev.at[idx].set(
                        rest[0][:len(seqs)])
                if count_rows is not None:
                    # Penalty state for these slots: prior generated-token
                    # counts (zeros for fresh requests; rebuilt rows after
                    # preemption-recompute) plus this prefill's sampled
                    # token, which stays on device.
                    cnt = jnp.asarray(count_rows, jnp.uint8)
                    sel = sampled[:len(seqs)]
                    n = jnp.arange(len(seqs))
                    bumped = cnt.at[n, sel].add(
                        (cnt[n, sel] < 255).astype(jnp.uint8))
                    self.counts_dev = self.counts_dev.at[idx].set(bumped)
            for arr in (sampled, lp, top_v, top_i):
                try:
                    arr.copy_to_host_async()
                except Exception:  # noqa: BLE001
                    pass
            return {"tokens": sampled, "lp": lp, "top_v": top_v,
                    "top_i": top_i}
        if not fetch:
            # Dispatch-only (intermediate prefill chunks): the KV pages
            # are written on device and the sampled token is discarded.
            # Return the device array purely as a completion handle
            # (is_ready pacing) — no host copy is even started.
            return sampled
        self.sync_prefill_fetches += 1
        # dtpu: ignore[host-sync-in-hot-path] -- fetch=True branch only: prefill_chunk_async passes fetch=False and returns at the dispatch-only branch above (runtime twin: sync_prefill_fetches counter)
        return np.asarray(jax.device_get(sampled))[:len(seqs)]

    def _prefill_parts(self, seqs, slots, count_rows, fetch: bool, rows: int):
        """``prefill_batch`` of a group over the bound, ``rows`` prompts at
        a time: what it returns for the whole group, joined."""
        cuts = [slice(at, at + rows) for at in range(0, len(seqs), rows)]
        parts, logits = [], []
        for cut in cuts:
            parts.append(self.prefill_batch(
                seqs[cut], None if slots is None else slots[cut],
                None if count_rows is None else count_rows[cut], fetch))
            logits.append(self.last_prefill_logits[:len(seqs[cut])])
        self.last_prefill_logits = jnp.concatenate(logits)
        if slots is not None:
            return {key: jnp.concatenate(
                [part[key][:len(seqs[cut])]
                 for part, cut in zip(parts, cuts)])
                for key in parts[0]}
        if not fetch:
            return parts[-1]     # a completion handle: the last dispatched
        return np.concatenate(parts)

    # dtpu: hotpath -- PR 5 zero-readback invariant, now static: no device->host fetch anywhere below this entry
    def prefill_chunk_async(self, seq: PrefillSeq):
        """Dispatch ONE intermediate prefill chunk with NO host readback
        (the stall-free chunked-prefill path): device-stream order
        guarantees the chunk's KV writes land before any later program
        reads them as history, so nothing about the chunk needs to come
        back to the host. Returns the sampled-token device array as a
        completion handle only."""
        return self.prefill_batch([seq], fetch=False)

    def prefill(self, tokens: np.ndarray, start_pos: int,
                chunk_pages: np.ndarray, hist_pages: np.ndarray | None,
                sampling: tuple[float, int, float],
                penalties: tuple[float, float] = (0.0, 0.0),
                count_row: np.ndarray | None = None,
                seed: int | None = None,
                embeds: np.ndarray | None = None,
                embeds_mask: np.ndarray | None = None
                ) -> tuple[int, jax.Array]:
        """Single-sequence prefill chunk; returns (sampled_token,
        last-position logits [1,V])."""
        seq = PrefillSeq(tokens=np.asarray(tokens, np.int32),
                         start_pos=start_pos,
                         chunk_pages=np.asarray(chunk_pages, np.int32),
                         hist_pages=hist_pages, sampling=sampling,
                         penalties=penalties, seed=seed,
                         embeds=embeds, embeds_mask=embeds_mask)
        token = int(self.prefill_batch(
            [seq], count_rows=None if count_row is None
            else count_row[None])[0])
        return token, self.last_prefill_logits[:1]

    def set_count_rows(self, slots: list[int], rows: np.ndarray) -> None:
        """Install penalty-count rows for slots whose first token is
        already known host-side (chunked-prefill finish, KV-injected
        admission): the engine builds the row including that token."""
        with self.mesh:
            self.counts_dev = self.counts_dev.at[
                jnp.asarray(np.asarray(slots, np.int32))].set(
                jnp.asarray(rows, jnp.uint8))

    def bucket_pages_for(self, needed: int) -> int:
        """Page-table width bucket for the decode window
        (config.window_page_bucket, by the reader this runner resolved)."""
        return window_page_bucket(needed, self.attention_backend,
                                  self.config.page_size,
                                  self.config.max_pages_per_seq)

    def decode_window(self, packed: np.ndarray, window: int):
        """Dispatch one M-step decode window.

        packed [B, PK_PREFIX + bucket_pages] int32 (see PK_* columns).
        Returns (toks [M,B], lp [M,B], top_v [M,B,K], top_i [M,B,K])
        device arrays (fetch with np.asarray when needed; start async
        copies early via .copy_to_host_async()). The logprob arrays are
        zeros unless some slot set PK_LOGPROB. The fifth is a dict of what
        the block counted on the device, empty for most: "moe" (a routed
        block) float32 [3], over the window's steps and expert layers the
        sum of distinct experts the live rows chose, the sum of the fullest
        expert's tokens over the mean, and the layer-steps counted ([5] for
        a told share: picks on held experts, all picks); "attn" (a latent
        block) float32 [2], keys the live rows attended and keys they had
        in context, over steps and layers; "ssm" (a block with recurrent
        layers) float32 [1], the live rows summed over the window's steps.
        """
        bucket_pages = packed.shape[1] - PK_PREFIX
        # Specialize on whether any slot carries penalties THIS window —
        # derived from the packed array, so multihost followers replaying
        # the same control data pick the same program.
        penalized = bool(packed[:, PK_FREQPEN].any()
                         or packed[:, PK_PRESPEN].any())
        seeded = bool(packed[:, PK_SEEDED].any())
        fn = self._get_window(window, bucket_pages, penalized, seeded)
        kw = {} if self.lora is None else {"lora": self.lora}
        if self.spec.recurrent:
            kw["state"] = self.state_arrays
        with self.mesh:
            if self.draft_dev is not None:
                # The drafting window (_get_mtp_window): toks, lps and tops
                # have an axis of spec_k + 1 positions behind the rows'.
                (toks, lps, top_vs, top_is, self.tokens_dev,
                 self.positions_dev, self.draft_dev, self.mtp_hidden,
                 self.k_cache, self.v_cache, self._rng, stats) = fn(
                    self.params, self.k_cache, self.v_cache,
                    self.tokens_dev, self.positions_dev, self.draft_dev,
                    self.mtp_hidden, jnp.asarray(packed), self._rng)
            elif penalized:
                (toks, lps, top_vs, top_is, self.tokens_dev, self.k_cache,
                 self.v_cache, self._rng, self.counts_dev, stats,
                 *state) = fn(
                    self.params, self.k_cache, self.v_cache,
                    self.tokens_dev, jnp.asarray(packed), self._rng,
                    self.counts_dev, **kw)
            else:
                (toks, lps, top_vs, top_is, self.tokens_dev, self.k_cache,
                 self.v_cache, self._rng, stats, *state) = fn(
                    self.params, self.k_cache, self.v_cache,
                    self.tokens_dev, jnp.asarray(packed), self._rng, **kw)
            if self.spec.recurrent:
                self._keep_state(state)
        return (toks, lps, top_vs, top_is, stats)

    def embed(self, token_lists: list[list[int]],
              pooling: str = "last") -> np.ndarray:
        """Pooled, L2-normalized embeddings [n, H] for a batch of prompts
        (compiled per (bucket, batch-bucket, pooling))."""
        from dynamo_tpu.engine.model import embed_forward
        cfg = self.config
        spec = self.spec
        if spec.recurrent:
            raise UnsupportedBlockError(
                "pooled embeddings (runner.embed)", "embed_forward scans "
                "one stack of attention and feed-forward layers, and this "
                "block's layers are one mixer each")
        if spec.loop_passes > 1:
            raise UnsupportedBlockError(
                "pooled embeddings (runner.embed)", "embed_forward scans "
                "the layers once and has no loop over passes")
        if not token_lists or any(not t for t in token_lists):
            raise ValueError("embeddings need at least one non-empty input")
        n_max = max(len(t) for t in token_lists)
        if n_max > cfg.prefill_buckets[-1]:
            raise ValueError(
                f"embedding input of {n_max} tokens exceeds the largest "
                f"prefill bucket ({cfg.prefill_buckets[-1]})")
        bucket = cfg.bucket_for(n_max)
        bp = 1
        while bp < len(token_lists):
            bp *= 2
        key = ("embed", bucket, bp, pooling)
        fn = self._window_cache.get(key)
        if fn is None:
            fn = perf.instrumented_jit(
                "embed", lambda p, t, sl: embed_forward(
                    p, spec, t, sl, pooling=pooling), key=key,
                context=self._store_context)
            self._window_cache[key] = fn
        toks = np.zeros((bp, bucket), np.int32)
        lens = np.ones((bp,), np.int32)
        for i, t in enumerate(token_lists):
            toks[i, :len(t)] = t
            lens[i] = len(t)
        with self.mesh:
            out = fn(self.params, jnp.asarray(toks), jnp.asarray(lens))
        return np.asarray(jax.device_get(out))[:len(token_lists)]

    # -- KV page transfer (disaggregation data plane) -------------------------
    def _get_extract(self, n: int):
        key = ("extract", n)
        fn = self._window_cache.get(key)
        if fn is None:
            def gather(k_cache, v_cache, pages):
                if isinstance(k_cache, QuantKV):
                    # Compressed extract: (data int8, scale f32) — packed
                    # into the uint8 wire parcel host-side.
                    return (jnp.stack([k_cache.data[:, :, pages],
                                       v_cache.data[:, :, pages]]),
                            jnp.stack([k_cache.scale[:, :, pages],
                                       v_cache.scale[:, :, pages]]))
                return jnp.stack([k_cache[:, :, pages], v_cache[:, :, pages]])
            if jax.process_count() > 1:
                # Multi-controller: the pool shards over (pp, tp) across
                # HOSTS, so replicate the gathered pages (XLA all-gathers
                # over ICI/DCN) — every host then holds the full parcel
                # and the leader's host fetch is purely local. This is the
                # cross-host gather that unblocks disagg + tiering in
                # multi-host mode (round-3 VERDICT missing #2).
                fn = perf.instrumented_jit(
                    "extract", gather, key=key,
                    context=self._store_context,
                    out_shardings=NamedSharding(self.mesh, P()))
            else:
                fn = perf.instrumented_jit("extract", gather, key=key,
                                           context=self._store_context)
            self._window_cache[key] = fn
        return fn

    def _get_insert(self, n: int):
        key = ("insert", n)
        fn = self._window_cache.get(key)
        if fn is None:
            if self.quant_kv == "int8":
                def scatter(k_cache, v_cache, kvq, kvs, pages):
                    k_cache = QuantKV(
                        k_cache.data.at[:, :, pages].set(kvq[0]),
                        k_cache.scale.at[:, :, pages].set(kvs[0]))
                    v_cache = QuantKV(
                        v_cache.data.at[:, :, pages].set(kvq[1]),
                        v_cache.scale.at[:, :, pages].set(kvs[1]))
                    return k_cache, v_cache
            else:
                def scatter(k_cache, v_cache, kv, pages):
                    k_cache = k_cache.at[:, :, pages].set(kv[0])
                    v_cache = v_cache.at[:, :, pages].set(kv[1])
                    return k_cache, v_cache
            fn = perf.instrumented_jit("insert", scatter, key=key,
                                       donate_argnums=(0, 1),
                                       context=self._store_context)
            self._window_cache[key] = fn
        return fn

    @staticmethod
    def _page_bucket(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    # -- perf plane (engine/perf.py; docs/OBSERVABILITY.md) -------------------
    def hbm_stats(self) -> dict:
        """``device.memory_stats()`` of this process's first addressable
        mesh device, normalized to the three gauge fields. Empty dict on
        the CPU backend (it has no such API); on an accelerator a missing
        report raises."""
        if self.device.platform == "cpu":
            return {}
        stats = self._memory_stats(self.device)
        return {"bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0))}

    def memory_breakdown(self) -> dict:
        """Params / KV-pool / workspace attribution of device memory from
        this runner's own ledgers (the breakdown memory_stats can't
        give): workspace = measured in-use minus the known pools (the
        parameters, the KV pool, the recurrent state), None when the
        backend has no memory_stats."""
        hbm = self.hbm_stats()
        in_use = hbm.get("bytes_in_use")
        return {
            "params_bytes": self.param_bytes,
            "kv_pool_bytes": self.kv_pool_bytes,
            # The recurrent state arrays (0 without recurrent layers).
            "ssm_state_bytes": self.ssm_state_bytes,
            "workspace_bytes": (max(0, in_use - self.param_bytes
                                    - self.kv_pool_bytes
                                    - self.ssm_state_bytes)
                                if in_use is not None else None),
        }

    def d2h_fetch_floor_ms(self) -> float:
        """Measured per-fetch device->host latency floor of this
        process's first mesh device (cached probe). Splitting an extract
        into pipelined page groups pays this once per group, so extract
        grouping gates on it (engine.prefill_extract_staged)."""
        if getattr(self, "_d2h_floor_ms", None) is None:
            np.asarray(jax.device_put(np.arange(256, dtype=np.int32),
                                      self.device))  # warm any lazy init
            best = float("inf")
            for i in range(3):
                a = jax.device_put(np.full((256,), i, np.int32), self.device)
                a.block_until_ready()
                t0 = time.monotonic()
                np.asarray(a)
                best = min(best, (time.monotonic() - t0) * 1e3)
            self._d2h_floor_ms = best
        return self._d2h_floor_ms

    def extract_pages_async(self, pages: list[int]):
        """Dispatch the page gather and start the device->host copy WITHOUT
        blocking (offload path: the extract is stream-ordered before any
        later program that reuses the pages, and the host fetch overlaps
        subsequent windows). Finalize with ``finalize_extract``."""
        for refusal in block_refusals(self.spec, kv_transfer=True):
            raise refusal
        n = len(pages)
        nb = self._page_bucket(n)
        idx = np.zeros(nb, np.int32)
        idx[:n] = pages
        with self.mesh:
            out = self._get_extract(nb)(self.k_cache, self.v_cache,
                                        jnp.asarray(idx))
        # Multihost followers replay this dispatch for the collectives
        # only — never fetch: the result is leader-read, and N-1 wasted
        # full-parcel D2H copies would fight the offload path for host
        # bandwidth.
        if jax.process_index() == 0:
            for leaf in (out if isinstance(out, tuple) else (out,)):
                try:
                    leaf.copy_to_host_async()
                except Exception:  # noqa: BLE001
                    pass
        return out, n

    def finalize_extract(self, handle) -> np.ndarray:
        out, n = handle
        if isinstance(out, tuple):
            # Quantized pool: pack (data, scale) into the uint8 parcel
            # (engine/kv_quant.py wire format) — ~half the bf16 bytes on
            # every tier/wire path downstream.
            data = np.asarray(jax.device_get(out[0]))[:, :, :, :n]
            scale = np.asarray(jax.device_get(out[1]))[:, :, :, :n]
            if self.kv_rep > 1:
                data = data[:, :, ::self.kv_rep]
                scale = scale[:, :, ::self.kv_rep]
            return pack_parcel(data, scale)
        out = np.asarray(jax.device_get(out))[:, :, :, :n]
        if self.kv_rep > 1:
            out = out[:, :, ::self.kv_rep]
        return out

    def extract_pages(self, pages: list[int]) -> np.ndarray:
        """Gather the given pages' K/V to host: [2, L, Nkv, n, page, D]
        bf16, or with --quant-kv the PACKED int8+scales parcel
        [2, L, Nkv, n, page, D+4] uint8 at ~half the bytes (canonical
        heads either way — replicas deduplicated so parcels are portable
        across tp configurations). The disaggregation data plane's
        source side (role of the reference's NIXL reads, host-staged v0
        — SURVEY.md §5.8)."""
        return self.finalize_extract(self.extract_pages_async(pages))

    def insert_pages(self, kv: np.ndarray, pages: list[int]) -> None:
        """Write transferred K/V pages into this runner's cache. kv is a
        bf16 parcel [2, L, Nkv, n, page, D] or a PACKED int8+scales
        parcel [2, L, Nkv, n, page, D+4] uint8 (engine/kv_quant.py);
        either form converts to this runner's pool dtype on upload, so
        mixed bf16/int8 fleets interoperate. The mesh re-shards on
        upload, so TP-mismatched prefill->decode transfers work without
        a transpose kernel (the role of block_copy.cu). Pages of another
        page size are refused (kv_transfer.foreign_pages)."""
        from dynamo_tpu.llm.kv_transfer import foreign_pages
        for refusal in block_refusals(self.spec, kv_transfer=True):
            raise refusal
        refusal = foreign_pages(kv.shape, self.config.page_size)
        if refusal:
            raise ValueError(refusal)
        n = len(pages)
        assert kv.shape[3] == n, (kv.shape, n)
        if kv.shape[2] == self.canonical_nkv and self.kv_rep > 1:
            kv = np.repeat(kv, self.kv_rep, axis=2)
        assert kv.shape[2] == self.spec.num_kv_heads, (
            kv.shape, self.spec.num_kv_heads)
        nb = self._page_bucket(n)
        idx = np.zeros(nb, np.int32)
        idx[:n] = pages
        if self.quant_kv == "int8":
            if kv.dtype == np.uint8:
                data, scale = unpack_parcel(kv)
            else:
                # bf16 parcel from an unquantized peer: quantize host-side
                # (numpy twin of the in-graph kv_quantize — same rounding).
                data, scale = quantize_np(kv)
            if nb != n:
                # Pad toward the scratch page target (duplicate scatters
                # to page 0 are unordered but all-garbage).
                data = np.concatenate([data, np.zeros(
                    (*data.shape[:3], nb - n, *data.shape[4:]), np.int8)],
                    axis=3)
                scale = np.concatenate([scale, np.zeros(
                    (*scale.shape[:3], nb - n, scale.shape[4]),
                    np.float32)], axis=3)
            with self.mesh:
                self.k_cache, self.v_cache = self._get_insert(nb)(
                    self.k_cache, self.v_cache, jnp.asarray(data),
                    jnp.asarray(scale), jnp.asarray(idx))
            return
        kv = parcel_to_bf16(kv)  # packed parcels from int8 peers dequant
        if nb != n:
            # Pad with copies of the scratch page target (duplicate scatters
            # to page 0 are unordered but all-garbage).
            pad_kv = np.zeros(
                (*kv.shape[:3], nb - n, *kv.shape[4:]), kv.dtype)
            kv = np.concatenate([kv, pad_kv], axis=3)
        with self.mesh:
            self.k_cache, self.v_cache = self._get_insert(nb)(
                self.k_cache, self.v_cache, jnp.asarray(kv),
                jnp.asarray(idx))

    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               page_table: np.ndarray, seq_lens: np.ndarray,
               temperature: np.ndarray, top_k: np.ndarray,
               top_p: np.ndarray) -> np.ndarray:
        """One decode step over the slot batch; returns sampled tokens [B].
        (Kept for tests/dryrun; the serving engine uses decode_window.)"""
        fn = self._get_decode()
        with self.mesh:
            sampled, self.k_cache, self.v_cache, self._rng = fn(
                self.params, self.k_cache, self.v_cache,
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(page_table), jnp.asarray(seq_lens),
                jnp.asarray(temperature), jnp.asarray(top_k),
                jnp.asarray(top_p), self._rng)
        return np.asarray(jax.device_get(sampled))


def _already_quantized(params) -> bool:
    from dynamo_tpu.engine.quant import QTensor
    return isinstance(params.get("embed"), QTensor)


def _replicate_kv_heads(params, spec, rep: int):
    """Duplicate each canonical KV head ``rep`` times in wk/wv (+ biases) so
    the effective head axis equals tp. Canonical head g lands at effective
    heads [g*rep, (g+1)*rep)."""
    d = spec.head_dim
    nkv = spec.num_kv_heads

    def rep_w(w):  # [L, h, nkv*d] -> [L, h, nkv*rep*d]
        L, h, _ = w.shape
        return np.asarray(w).reshape(L, h, nkv, d).repeat(rep, axis=2) \
            .reshape(L, h, nkv * rep * d)

    def rep_b(b):  # [L, nkv*d] -> [L, nkv*rep*d]
        L, _ = b.shape
        return np.asarray(b).reshape(L, nkv, d).repeat(rep, axis=1) \
            .reshape(L, nkv * rep * d)

    layers = dict(params["layers"])
    layers["wk"] = rep_w(layers["wk"])
    layers["wv"] = rep_w(layers["wv"])
    if "bk" in layers:
        layers["bk"] = rep_b(layers["bk"])
        layers["bv"] = rep_b(layers["bv"])
    out = dict(params)
    out["layers"] = layers
    return out


def _prefill_with_history(params, spec, k_cache, v_cache, tokens, positions,
                          page_table, seq_lens, hist_table, hist_lens,
                          backends, sp_shard: bool = False,
                          x_embeds=None, embeds_mask=None,
                          lora=None, adapter_ids=None, defer: bool = False):
    """Chunked prefill: like prefill_forward but queries also attend to the
    sequence's earlier pages (read via the paged path). x_embeds/embeds_mask
    override token embeddings under multimodal media spans (rows are
    chunk-relative), so media anywhere in a long prompt — not just the
    first chunk — injects correctly."""
    import jax
    import jax.numpy as jnp
    from dynamo_tpu.engine.kv_quant import gather_pages_folded
    from dynamo_tpu.engine.model import (
        embed_lookup, history_attention, latent_prefill_attention, layer_kind,
        lm_logits, norm, scan_passes, spec_rope_tables, transformer_block,
        window_reach)

    b, s = tokens.shape
    d = spec.head_dim
    nkv = spec.num_kv_heads
    page = k_cache.shape[3]
    L = spec.layer_visits
    with perf.scope("embed"):
        x = embed_lookup(params["embed"], tokens)
        if x_embeds is not None:
            x = jnp.where(embeds_mask[..., None], x_embeds.astype(x.dtype), x)
        if sp_shard:
            x = jax.lax.with_sharding_constraint(x, P(None, "sp", None))
    with perf.scope("attn.qkv"):
        cos, sin = spec_rope_tables(spec, positions)
    valid = jnp.arange(s)[None, :] < seq_lens[:, None]
    maxp = hist_table.shape[1]

    def layer_fn(x, scan_in):
        if lora is not None:
            lp, layer, ll = scan_in
        else:
            (lp, layer), ll = scan_in, None

        def attend(q, k, v, kind):
            reach = window_reach(spec, kind)
            # History over prior pages: layer+head-folded gather from the
            # stacked cache straight into the dot's [Nkv,B,L,D] layout
            # (hist pages are disjoint from this chunk's pages, whose
            # writes are deferred out of the scan).
            with perf.scope("attn.kv_gather"):
                k_hist = gather_pages_folded(k_cache, layer, hist_table)
                v_hist = gather_pages_folded(v_cache, layer, hist_table)
            if spec.latent:
                # Entries and index keys of the earlier pages beside the
                # chunk's own: the indexer chooses among both.
                return latent_prefill_attention(
                    q, k, v, positions, valid, spec,
                    hist=(k_hist[0], v_hist[0], hist_lens))

            with perf.scope("attn.core"):
                return history_attention(q, k, v, k_hist, v_hist, positions,
                                         valid, hist_lens, spec, reach,
                                         limit=HISTORY_SCORE_BYTES)

        x, k, v, _ = transformer_block(
            x, lp, spec, cos, sin, attend, layer_kind(spec, layer), ll,
            adapter_ids, backends=backends)
        return x, (k, v)

    xs = ((params["layers"], jnp.arange(L), lora) if lora is not None
          else (params["layers"], jnp.arange(L)))
    x, (k_new, v_new) = scan_passes(
        layer_fn, x, xs, spec, params["final_norm"],
        whole_experts=expert_product(b * s, backends) != "masked")
    with perf.scope("kv.commit"):
        heads, (dk, dv) = spec.kv_entry
        k_blocks = (k_new.reshape(L, b * (s // page), page, heads, dk)
                    .transpose(0, 3, 1, 2, 4))
        v_blocks = (v_new.reshape(L, b * (s // page), page, heads, dv)
                    .transpose(0, 3, 1, 2, 4))
        flat = page_table.reshape(-1)
        from dynamo_tpu.engine.kv_quant import scatter_pages
        if not defer:
            k_cache = scatter_pages(k_cache, k_blocks, flat)
            v_cache = scatter_pages(v_cache, v_blocks, flat)
    with perf.scope("lm_head"):
        x = norm(x, params["final_norm"], spec)
        last_idx = jnp.maximum(seq_lens - 1, 0)
        x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        logits = lm_logits(x_last, params, spec)
    if defer:   # prefill_forward's: the caller commits with the module's
        return logits, k_cache, v_cache, (x, k_blocks, v_blocks, flat)
    return logits, k_cache, v_cache
