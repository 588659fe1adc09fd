"""Model and engine configuration.

ModelSpec states six block kinds: the dense Llama / Qwen2 block (QKV bias
by ``qkv_bias``), the Mixtral-style block (``num_experts`` SwiGLU experts of
the dense width, top-k then softmax, routed on the post-attention norm), the
SmallThinker block (a router that reads the layer's INPUT, softmax over
all experts then top-k renormalised, ReGLU experts of their own width, and a
per-layer pattern of RoPE / NoPE and sliding-window / full attention), and
the Cohere2-MoE block (Command A+: attention and feed-forward read ONE
mean-centred LayerNorm of the layer's input and both add to the residual, a
sigmoid router whose width is the deployment's experts while this device
holds ``num_experts`` of them from ``first_expert`` on, shared experts
averaged, interleaved RoPE on window layers and none on full ones, a tied
head), and the DeepSeek-V3.2 block (``deepseek_v32``: latent attention whose
cache holds ONE latent entry and one index key a token, a learned indexer
that keeps ``index_topk`` keys a query, leading dense layers ahead of the
expert layers, a grouped sigmoid router with a selection bias and a scaling
factor, YaRN frequencies on part of a head), and the GLM-4.7-Flash block
(``glm4_moe_lite``: the same latent block with NO indexer, every query
attends every key and the cache holds one array, plus ``mtp_layers``
prediction modules: a whole block of the same kind behind a projection of
[embedding ; hidden], which drafts the token after next).
``from_hf_config`` reads each from its public ``config.json`` keys as they
are spelled there.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re


#: What a leading dense layer's leaves are called in ``params["layers"]``
#: (``ModelSpec.first_k_dense``): the layer's own names behind this prefix,
#: stacked over the dense layers alone (model.scan_layers).
DENSE_PREFIX = "dense_"
#: What a prediction module's leaves are called there (``ModelSpec.
#: mtp_layers``): its block's own names behind this prefix, a stack of one,
#: and ``w_eh`` (the projection of [embedding ; hidden]), ``e_norm``,
#: ``h_norm`` and ``head_norm`` (model.mtp_leaves).
MTP_PREFIX = "mtp_"


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    hbm_gbps: float
    bf16_tflops: float
    int8_tops: float


# Keyed by ``jax.Device.device_kind``. v5e: Google Cloud documentation,
# "TPU v5e" system architecture page (819 GB/s HBM2e, 197 TFLOP/s bf16,
# 393 TOP/s int8 per chip). A kind that is not here is an error, never a
# default: a roofline against the wrong chip's peak is a wrong number.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(hbm_gbps=819.0, bf16_tflops=197.0,
                               int8_tops=393.0),
}


def device_peaks(device) -> DevicePeaks | None:
    """Peaks of ``device`` (a jax.Device). The CPU backend has no published
    peak: it returns None and every caller handles that explicitly (the
    bandwidth model is off, nothing is reported as a roofline share). Any
    other kind missing from DEVICE_PEAKS raises."""
    if device.platform == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add its row, with the "
            f"source, to engine/config.py DEVICE_PEAKS") from None


@dataclasses.dataclass
class ModelSpec:
    name: str = "tiny-test"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int | None = None  # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    qkv_bias: bool = False  # Qwen2 style
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 8192
    # MoE (Mixtral family): num_experts == 0 means dense FFN.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # What the dense and the Mixtral-style block have as constants and
    # another block kind states as FIELDS of its subclass
    # (SmallThinkerSpec): class attributes here, so a dense spec stays
    # equal, field by field, to the one every cached program and the
    # benchmark's tests were built from.
    moe_intermediate_size = None        # an expert's width: intermediate_size
    moe_router = "topk_softmax"         # the k largest logits, softmax over them
    norm_topk_prob = True
    moe_router_input = "post_attn_norm"  # the state the experts read
    ffn_act = "silu"                    # SwiGLU
    sliding_window = None               # every layer sees every earlier key
    sliding_window_layout = None
    rope_layout = None                  # RoPE on every layer
    rope_interleaved = False            # rotate-half pairs (i, i + half)
    norm_kind = "rms"                   # RMSNorm; rms_norm_eps is its epsilon
    parallel_block = False              # attention, then feed-forward
    # An expert layer that is told its share (Cohere2MoeSpec): the router's
    # width where it is not num_experts, the first expert held, and the
    # experts every token passes through.
    num_routed_experts = None
    first_expert = 0
    num_shared_experts = 0
    # What the DeepSeek-V3.2 block states (DeepseekV32Spec). kv_lora_rank 0:
    # a token leaves K and V of num_kv_heads x head_dim in the cache.
    kv_lora_rank = 0
    q_lora_rank = 0
    qk_nope_head_dim = 0
    qk_rope_head_dim = 0
    v_head_dim = 0
    index_n_heads = 0
    index_head_dim = 0
    index_topk = 0
    first_k_dense = 0                   # every layer has the same feed-forward
    n_group = 1                         # the router chooses among all experts
    topk_group = 1
    routed_scaling_factor = 1.0
    moe_select_bias = False
    rope_yarn = None                    # plain frequencies theta ** (-2i / d)
    mtp_layers = 0                      # no prediction module to draft with
    # What the Nemotron-H block states (NemotronHSpec). layer_pattern None:
    # every layer is attention, then a feed-forward.
    layer_pattern = None
    ssm_heads = 0
    ssm_head_dim = 0
    ssm_groups = 0
    ssm_state = 0
    ssm_conv = 0
    ssm_chunk = 0
    shared_intermediate_size = None     # a shared expert is expert_size wide
    # What the Solar-Open2 block states (SolarOpen2Spec): the rank of the
    # low-rank pairs behind a delta-rule mixer's decay and output gate, what
    # its beta is multiplied by (2: I - beta k k^T may reflect), and whether
    # a * layer's output is gated by sigmoid(u W_z).
    ssm_low_rank = 0
    ssm_beta_scale = 1.0
    attn_gate = False
    # What the MiniCPM-SALA block states (MiniCPMSALASpec): the muP scalars
    # (the embedding's factor, what a sublayer's output is multiplied by
    # ahead of the residual, what divides the final norm's output ahead of
    # the head) and the constants of attention over chosen BLOCKS of keys
    # (sparse_block 0: every layer attends every earlier key).
    scale_emb = 1.0
    residual_scale = 1.0
    logit_divisor = 1.0
    qk_norm = False
    sparse_kernel = 0
    sparse_stride = 0
    sparse_block = 0
    sparse_topk = 0
    sparse_init_blocks = 0
    sparse_window = 0
    # What a looped stack states (OuroSpec): how many times a token passes
    # the SAME layers, each pass leaving its own K and V; whether a
    # sublayer's OUTPUT is normed ahead of the residual sum; the cumulative
    # exit probability at which a token would leave the loop.
    loop_passes = 1
    sandwich_norm = False
    early_exit_threshold = 1.0
    # What the Falcon-H1 block states (FalconH1Spec): that a group's
    # recurrent mixer and its attention layer run SIDE BY SIDE on one normed
    # input and are summed into one residual; that a * layer rotates q and
    # k; and the muP constants: on k, on attention's input and output, on
    # the Mamba-2 mixer's input, on the five segments of its in-projection's
    # output (z, x, B, C, dt) and on its output, on the SwiGLU's gate
    # pre-activation and down product.
    parallel_mixers = False
    attn_rope = False
    key_multiplier = 1.0
    attn_in_multiplier = 1.0
    attn_out_multiplier = 1.0
    ssm_in_multiplier = 1.0
    ssm_multipliers = None
    ssm_out_multiplier = 1.0
    mlp_multipliers = None
    # Weight-only quantization: None (bf16) or "int8" (engine/quant.py —
    # int8 storage, bf16 MXU compute; halves the weight-read roofline and
    # fits full llama-3-8b on one 16 GB v5e).
    quant: str | None = None

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads

    @property
    def expert_size(self) -> int:
        """Width of one expert's gate / up / down matrices."""
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def router_width(self) -> int:
        """Outputs of the router: every expert of the deployment, of which
        this device holds ``num_experts``."""
        return self.num_routed_experts or self.num_experts

    @property
    def holds_share(self) -> bool:
        """Some of the router's experts are held elsewhere."""
        return self.router_width != self.num_experts

    @property
    def has_layer_pattern(self) -> bool:
        """Layers differ in kind (RoPE or not, window or full)."""
        return bool((self.rope_layout and not all(self.rope_layout))
                    or (self.sliding_window_layout
                        and any(self.sliding_window_layout)))

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def latent(self) -> bool:
        """A token leaves a latent entry and an index key in the cache, not
        K and V (kv_lora_rank > 0)."""
        return self.kv_lora_rank > 0

    @property
    def recurrent(self) -> bool:
        """Some layers keep a state a ROW (``ssm_layers`` of them), beside
        what the attention layers leave a TOKEN in the pool."""
        return self.ssm_layers > 0

    @property
    def ssm_layers(self) -> int:
        """Layers that keep a state a row: a pattern's M (Mamba-2), L
        (lightning linear attention) and K (the gated delta rule)."""
        pattern = self.layer_pattern or ""
        return sum(pattern.count(kind) for kind in RECURRENT_KINDS)

    @property
    def ssm_kind(self) -> str | None:
        """The letter of the pattern's recurrent mixer (one kind a model:
        their leaves share the ``ssm_`` names); None without one."""
        pattern = self.layer_pattern or ""
        return next((k for k in RECURRENT_KINDS if k in pattern), None)

    @property
    def compressed_keys(self) -> bool:
        """The attention layers choose BLOCKS of keys by scores over
        mean-pooled keys, which the pool holds in a third array beside K
        and V under the same page table (``comp_key_shape``)."""
        return self.sparse_block > 0

    @property
    def expert_layers(self) -> int:
        """Layers with a routed feed-forward: every one, or a pattern's E."""
        if self.layer_pattern:
            return self.layer_pattern.count("E")
        return self.num_layers - self.first_k_dense if self.num_experts else 0

    @property
    def ssm_channels(self) -> int:
        """Inputs of the recurrent layer's convolution: x | B | C (Mamba-2),
        q | k | v (the delta rule: q and k of ``ssm_state`` a head, v of
        ``ssm_head_dim``; the same sum with a group a head)."""
        return (self.ssm_heads * self.ssm_head_dim
                + 2 * self.ssm_groups * self.ssm_state)

    @property
    def ssm_state_shapes(self) -> tuple[tuple, tuple | None]:
        """What ONE row keeps in ONE recurrent layer: the state S [heads,
        head_dim, state] (float32) and the convolution's last inputs
        [ssm_conv - 1, channels] (bfloat16; None for a mixer without a
        convolution, ``ssm_conv`` 0: there is no second array; the
        runner's array of them over all slots: ``conv_state_shape``)."""
        return ((self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                (self.ssm_conv - 1, self.ssm_channels) if self.ssm_conv
                else None)

    def conv_state_shape(self, slots: int) -> tuple:
        """The convolution's carried inputs of ``slots`` rows over every
        recurrent layer: [layers, ssm_conv - 1, slots, channels] bfloat16,
        TAPS-MAJOR: a tap is a whole plane [slots, channels] whose tiles
        are full (row-major a tile of 16 rows held a row's 3), and a
        decode step shifts planes (``hybrid.conv_token``)."""
        taps, channels = self.ssm_state_shapes[1]
        return (self.ssm_layers, taps, slots, channels)

    @property
    def ssm_state_bytes_per_row(self) -> int:
        """Bytes of recurrent state a row (a slot) holds over all layers."""
        s, c = self.ssm_state_shapes
        return self.ssm_layers * (4 * math.prod(s)
                                  + (2 * math.prod(c) if c else 0))

    @property
    def comp_key_bytes_per_token(self) -> int:
        """What a token adds to the compressed-key array over all attention
        layers (bfloat16: a stripe's share a KV head); 0 without one."""
        if not self.compressed_keys:
            return 0
        return (self.pool_layers * self.num_kv_heads
                * 2 * self.head_dim // self.sparse_stride)

    def comp_key_shape(self, num_pages: int, page_size: int) -> tuple:
        """The compressed-key array of a pool of ``num_pages`` pages:
        [attention layers, KV heads, pages, stripes a page, head_dim]
        bfloat16. A STRIPE is the mean of ``sparse_stride`` keys in a row
        of the page; a compressed key (the mean of ``sparse_kernel`` = two
        strides of keys) is the mean of two stripes that follow each other,
        taken where the scores are (hybrid.choose_blocks), so that a page's
        stripes are the page's own keys' and nothing is written across a
        page's border. A page's stripes are whole tiles of the array as the
        chip holds it (8 x 128 bfloat16 under T(8,128)(2,1): 2 KB a page and
        head, no padding; compiled for a described v5e, PR 46), so that the
        kernel that scores a row's stripes in decode
        (attention.stripe_scores_pallas) copies a page's, both heads in one
        strided copy: behind one flat axis [pages, stripes x head_dim] a
        page is one ROW of a tile of 8 pages, and Mosaic slices no tile.
        Written by prefill (hybrid.prefill) and the window's commit
        (hybrid.commit_stripes); read by that kernel where the Pallas
        reader runs and by XLA's gather (hybrid.pool_stripes) everywhere
        else and in a prefill chunk over its history."""
        return (self.pool_layers, self.num_kv_heads, num_pages,
                page_size // self.sparse_stride, self.head_dim)

    @property
    def pool_layers(self) -> int:
        """Layers of the two pool arrays: the model's, once a pass of a looped
        stack (``layer_visits``), then one a prediction module
        (``mtp_layers``), whose block leaves entries of the same width
        under the same page table; under a ``layer_pattern`` the
        attention layers alone (the others leave nothing a token)."""
        if self.layer_pattern:
            return (self.layer_pattern.count("*")
                    + self.layer_pattern.count("S"))
        return self.layer_visits + self.mtp_layers

    @property
    def layer_visits(self) -> int:
        """(pass, layer) pairs a token goes through, each leaving K and V
        of its own: pool layer ``t * num_layers + l`` is pass t of layer l
        (``loop_passes`` 1: the layers)."""
        return self.loop_passes * self.num_layers

    @property
    def kv_entry(self) -> tuple[int, tuple[int, int]]:
        """(heads, (width of the first pool's row, of the second's)): what
        ONE token leaves in ONE layer of the two pool arrays
        [L, heads, P, page, width] that share a page table. K and V of
        num_kv_heads x head_dim; or (latent) one row of the latent
        kv_lora_rank, then the shared rope key, then zeros up to the next
        multiple of 128 lanes (512 + 64 + 64 = 640: a last dimension that
        is no multiple of 128 costs a relayout of the pool a layer, PERF.md
        section 6, PR 26), and one row of the index key."""
        if not self.latent:
            return self.num_kv_heads, (self.head_dim, self.head_dim)
        used = self.kv_lora_rank + self.qk_rope_head_dim
        return 1, (-(-used // 128) * 128, self.index_head_dim)

    def num_params(self) -> int:
        """Parameters resident here, a looped stack's layers ONCE: the sum
        of model.param_shapes (the experts HELD, shared experts, QKV biases, one norm a layer in a
        parallel block; the latent projections, the indexer, the selection
        bias and the leading dense layers of the DeepSeek-V3.2 block)."""
        if self.layer_pattern:
            from dynamo_tpu.engine.model import param_shapes
            shapes = param_shapes(self)
            return sum(math.prod(s) for s in (
                *shapes.pop("layers").values(), *shapes.values()))
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        d = self.head_dim
        if self.latent:
            nh, r, qr = self.num_heads, self.kv_lora_rank, self.q_lora_rank
            rope, nope = self.qk_rope_head_dim, self.qk_nope_head_dim
            attn = (h * qr + qr + qr * nh * (nope + rope) + h * (r + rope) + r
                    + r * nh * (nope + self.v_head_dim)
                    + nh * self.v_head_dim * h
                    # the indexer: query, key, its LayerNorm, head weights
                    + qr * self.index_n_heads * self.index_head_dim
                    + h * self.index_head_dim + 2 * self.index_head_dim
                    + h * self.index_n_heads)
        else:
            attn = h * (self.num_heads * d) + 2 * h * (self.num_kv_heads * d) \
                + (self.num_heads * d) * h
        if self.qkv_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * d
        if self.num_experts:
            mlp = ((self.num_experts + self.num_shared_experts)
                   * 3 * h * self.expert_size + h * self.router_width)
            if self.moe_select_bias:
                mlp += self.router_width
        else:
            mlp = 3 * h * i
        norms = (1 if self.parallel_block else 2) * h
        if self.sandwich_norm:      # a norm of each sublayer's output too
            norms += 2 * h
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        dense = self.first_k_dense
        # A prediction module: a whole expert layer of the block's kind,
        # the projection of [embedding ; hidden] and three norms; the
        # embedding and the head are the model's own.
        module = self.mtp_layers * (attn + mlp + norms + 2 * h * h + 3 * h)
        return ((self.num_layers - dense) * (attn + mlp + norms)
                + dense * (attn + 3 * h * i + norms) + embed + h + module)

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """bf16-pool bytes per token (both pool arrays, all layers/heads).
        Quantized KV pools add per-token scales — use
        EngineConfig.kv_token_bytes() for pool sizing so the int8
        accounting stays honest."""
        heads, widths = self.kv_entry
        return (self.pool_layers * heads * sum(widths) * dtype_bytes
                + self.comp_key_bytes_per_token)

    def step_read_params(self) -> int:
        """Parameters a decode step reads: the resident ones, and the
        layers once more for every further PASS of a looped stack (the
        passes are sequential: pass t + 1 of the first layer needs pass t
        of the last, and no layer stays on the chip between them). The ONE
        place a block that re-reads or skips weights states it:
        ``weight_read_step_ms`` and through it the "auto" window, the
        prefill chunk and the perf plane's roofline fraction. (The
        embedding table counts whole, as it always has, though a step
        gathers a row a sequence: an estimate's term, under a tenth of any
        preset.)"""
        resident = self.num_params()
        if self.loop_passes == 1:
            return resident
        tables = (self.vocab_size * self.hidden_size
                  * (1 if self.tie_word_embeddings else 2))
        layers = resident - tables - self.hidden_size   # less the final norm
        return resident + (self.loop_passes - 1) * layers

    def weight_read_step_ms(self, hbm_gbps: float, tp: int = 1,
                            pp: int = 1) -> float:
        """Lower bound on a decode step for this spec's shard: the bytes a
        step reads of the shard's weights (``step_read_params``, as
        stored) from HBM at ``hbm_gbps`` — the
        serving device's DevicePeaks.hbm_gbps (bench roofline, auto window
        sizing, profiling all pass the same table row)."""
        per_weight = 1.0 if self.quant == "int8" else 2.0
        shard_bytes = self.step_read_params() * per_weight / max(1, tp * pp)
        return shard_bytes / (hbm_gbps * 1e9) * 1e3

    @classmethod
    def from_hf_config(cls, path: str) -> "ModelSpec":
        """Build from a HF config.json (local dir or file)."""
        if os.path.isdir(path):
            path = os.path.join(path, "config.json")
        # dtpu: ignore[blocking-call-in-async] -- model-load startup I/O (HF config.json), never on the serving path
        with open(path) as fh:
            cfg = json.load(fh)
        if "moe_num_primary_experts" in cfg:
            return cls._from_smallthinker(cfg, path)
        if cfg.get("model_type") == "cohere2_moe":
            return cls._from_cohere2_moe(cfg, path)
        if cfg.get("model_type") == "deepseek_v32":
            return cls._from_deepseek_v32(cfg, path)
        if cfg.get("model_type") == "glm4_moe_lite":
            return cls._from_glm4_moe_lite(cfg, path)
        if cfg.get("model_type") == "nemotron_h":
            return cls._from_nemotron_h(cfg, path)
        if cfg.get("model_type") == "minicpm_sala":
            return cls._from_minicpm_sala(cfg, path)
        if cfg.get("model_type") == "ouro":
            return cls._from_ouro(cfg, path)
        if cfg.get("model_type") == "solar_open2":
            return cls._from_solar_open2(cfg, path)
        if cfg.get("model_type") == "falcon_h1":
            return cls._from_falcon_h1(cfg, path)
        return cls(
            name=cfg.get("_name_or_path", os.path.basename(os.path.dirname(path))),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            qkv_bias=cfg.get("model_type") == "qwen2",
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            num_experts=cfg.get("num_local_experts", 0),
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
        )

    @classmethod
    def _from_smallthinker(cls, cfg: dict, path: str) -> "ModelSpec":
        """SmallThinker's keys (PowerInfer/SmallThinker-21BA3B-Instruct
        ``config.json``): experts of ``moe_ffn_hidden_size`` and no dense
        width, a router ahead of attention, ReGLU, the two layouts."""
        if not cfg.get("moe_primary_router_apply_softmax", False):
            raise UnsupportedBlockError(
                "the config reader", "a SmallThinker router without softmax "
                "(moe_primary_router_apply_softmax false): its equations "
                "are not written down in this repository")
        if cfg.get("rope_scaling"):
            raise UnsupportedBlockError(
                "the config reader", "SmallThinker with rope_scaling: no "
                "path scales its rotation")
        width = cfg["moe_ffn_hidden_size"]
        return SmallThinkerSpec(
            name=cfg.get("_name_or_path") or cfg.get("model_name")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=width,
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            num_experts=cfg["moe_num_primary_experts"],
            num_experts_per_tok=cfg["moe_num_active_primary_experts"],
            moe_intermediate_size=width,
            norm_topk_prob=bool(cfg.get("norm_topk_prob", False)),
            sliding_window=cfg.get("sliding_window_size"),
            sliding_window_layout=cfg.get("sliding_window_layout"),
            rope_layout=cfg.get("rope_layout"),
        )


    @classmethod
    def _from_cohere2_moe(cls, cfg: dict, path: str) -> "ModelSpec":
        """Command A+'s keys (CohereLabs/command-a-plus-05-2026
        ``config.json``). ``num_experts`` counts the experts HELD here; a
        file that cuts a deployment's share states the router's width and
        the share's first expert under ``expert_parallel``
        (``{"routed_experts": 128, "first_expert": 0}``), the public file
        has neither and holds them all."""
        reader = "the config reader"
        for key, want, why in (
                ("use_qk_norm", False, "no path normalises q and k"),
                ("attention_bias", False, "its projections have no bias "
                 "leaves under a LayerNorm block"),
                ("use_parallel_block", True, "the sequential Cohere block "
                 "is not written down in this repository"),
                ("use_gated_activation", True, "an ungated expert is not "
                 "written down in this repository"),
                ("hidden_act", "silu", "the experts are SwiGLU"),
                ("expert_selection_fn", "sigmoid", "the router kinds are "
                 "sigmoid_topk, softmax_topk and topk_softmax"),
                ("shared_expert_combination_strategy", "average", "shared "
                 "experts are averaged and added to the routed sum"),
                ("position_embedding_type", "rope_gptj", "this block's "
                 "rotation is the interleaved one"),
                ("rotary_pct", 1, "no path rotates part of a head"),
                ("logit_scale", 1, "no path scales the logits")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"cohere2_moe with {key} {got!r}: {why}")
        if cfg.get("first_k_dense_replace", 0) > 0:
            # A leading dense layer is served (first_k_dense, the
            # DeepSeek-V3.2 block); this family's has a width and a window
            # pattern of its own.
            raise UnsupportedBlockError(
                reader, "cohere2_moe with first_k_dense_replace > 0: its "
                "leading layers take prefix_dense_intermediate_size and "
                "prefix_dense_sliding_window_pattern, whose equations are "
                "not written down in this repository")
        for key in ("n_group", "topk_group", "routed_scaling_factor",
                    "e_score_correction_bias", "topk_method"):
            if key in cfg:
                raise UnsupportedBlockError(
                    reader, f"cohere2_moe with {key}: the router serves "
                    "groups, a selection bias and a scaling factor "
                    "(moe_route), but how this family would state and "
                    "combine them is not written down in this repository")
        scaling = (cfg.get("rope_parameters") or {}).get("rope_type",
                                                         "default")
        if cfg.get("rope_scaling") or scaling != "default":
            raise UnsupportedBlockError(
                reader, "cohere2_moe with scaled RoPE: no path scales its "
                "rotation")
        kinds = {"sliding_attention": 1, "full_attention": 0}
        try:
            windowed = tuple(kinds[t] for t in cfg["layer_types"])
        except KeyError as exc:
            raise UnsupportedBlockError(
                reader, f"cohere2_moe layer type {exc.args[0]!r}: the layer "
                "kinds are sliding_attention and full_attention") from None
        share = cfg.get("expert_parallel") or {}
        width = cfg["intermediate_size"]
        return Cohere2MoeSpec(
            name=cfg.get("_name_or_path")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=width,
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=width,
            norm_topk_prob=bool(cfg.get("norm_topk_prob", False)),
            sliding_window=cfg.get("sliding_window"),
            sliding_window_layout=windowed,
            # Window layers rotate, full layers carry no position.
            rope_layout=windowed,
            num_routed_experts=share.get("routed_experts",
                                         cfg["num_experts"]),
            first_expert=share.get("first_expert", 0),
            num_shared_experts=cfg.get("num_shared_experts", 0),
        )

    @classmethod
    def _from_deepseek_v32(cls, cfg: dict, path: str) -> "ModelSpec":
        """DeepSeek-V3.2-Exp's keys (deepseek-ai/DeepSeek-V3.2-Exp
        ``config.json``). ``n_routed_experts`` counts the experts HELD
        here; a file that cuts a deployment's share states the router's
        width and the share's first expert under ``expert_parallel`` as
        Command A+'s does, the public file has neither and holds them
        all."""
        reader = "the config reader"
        for key, want, why in (
                ("attention_bias", False, "the latent projections have no "
                 "bias leaves"),
                ("hidden_act", "silu", "the feed-forward is SwiGLU"),
                ("scoring_func", "sigmoid", "the grouped router scores "
                 "with a sigmoid"),
                ("topk_method", "noaux_tc", "the router's choice is the "
                 "grouped one with a selection bias"),
                ("moe_layer_freq", 1, "every layer after the leading "
                 "dense ones is an expert layer"),
                ("num_nextn_predict_layers", 0, "a prediction module drafts "
                 "inside the window program (spec_decode mtp), whose verify "
                 "step attends every key and has no indexer's selection "
                 "(ROADMAP R10)"),
                ("num_key_value_heads", cfg["num_attention_heads"],
                 "latent attention expands one latent to every head"),
                ("n_shared_experts", 1, "the shared experts' outputs are "
                 "averaged (ffn_block), which is this block's sum only "
                 "for one")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"deepseek_v32 with {key} {got!r}: {why}")
        if not cfg.get("q_lora_rank"):
            raise UnsupportedBlockError(
                reader, "deepseek_v32 without q_lora_rank: the indexer "
                "reads the low-rank query")
        scaling = cfg.get("rope_scaling")
        yarn = None
        if scaling:
            if scaling.get("type", scaling.get("rope_type")) != "yarn":
                raise UnsupportedBlockError(
                    reader, f"deepseek_v32 with rope_scaling {scaling!r}: "
                    "the scaled rotation written down is YaRN")
            if scaling.get("mscale", 1) != scaling.get("mscale_all_dim", 1):
                raise UnsupportedBlockError(
                    reader, "deepseek_v32 with mscale != mscale_all_dim: "
                    "no path scales cos and sin")
            yarn = (float(scaling["factor"]),
                    int(scaling["original_max_position_embeddings"]),
                    float(scaling.get("beta_fast", 32)),
                    float(scaling.get("beta_slow", 1)),
                    float(scaling.get("mscale_all_dim", 0)))
        return DeepseekV32Spec(
            **cls._latent_fields(cfg, path),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            index_n_heads=cfg["index_n_heads"],
            index_head_dim=cfg["index_head_dim"],
            index_topk=cfg["index_topk"],
            rope_yarn=yarn,
        )

    @staticmethod
    def _latent_fields(cfg: dict, path: str) -> dict:
        """What the two latent families' files state under the same keys
        (``_from_deepseek_v32``, ``_from_glm4_moe_lite``): the widths, the
        low ranks, a head's parts, the router and the share."""
        share = cfg.get("expert_parallel") or {}
        return dict(
            name=cfg.get("_name_or_path")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_attention_heads"],
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            num_experts=cfg["n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            num_routed_experts=share.get("routed_experts",
                                         cfg["n_routed_experts"]),
            first_expert=share.get("first_expert", 0),
            num_shared_experts=cfg.get("n_shared_experts", 0),
            kv_lora_rank=cfg["kv_lora_rank"],
            q_lora_rank=cfg["q_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            first_k_dense=cfg.get("first_k_dense_replace", 0),
            n_group=cfg.get("n_group", 1),
            topk_group=cfg.get("topk_group", 1),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        )

    @classmethod
    def _from_glm4_moe_lite(cls, cfg: dict, path: str) -> "ModelSpec":
        """GLM-4.7-Flash's keys (zai-org/GLM-4.7-Flash ``config.json``,
        ``glm4_moe_lite``): the DeepSeek-V3.2 block's latent attention and
        router WITHOUT an indexer (no ``index_*`` key: every query attends
        every key, the pool is one array), plain rope frequencies, and
        ``num_nextn_predict_layers`` prediction modules that the window
        program drafts with (``spec_decode="mtp"``). ``n_routed_experts``
        and ``expert_parallel`` as ``_from_deepseek_v32`` reads them."""
        reader = "the config reader"
        for key, want, why in (
                ("attention_bias", False, "the latent projections have no "
                 "bias leaves"),
                ("hidden_act", "silu", "the feed-forward is SwiGLU"),
                ("scoring_func", "sigmoid", "the router scores with a "
                 "sigmoid"),
                ("topk_method", "noaux_tc", "the router's choice is the "
                 "one with a selection bias"),
                ("moe_layer_freq", 1, "every layer after the leading "
                 "dense ones is an expert layer"),
                ("partial_rotary_factor", 1, "the rope part of a head is "
                 "qk_rope_head_dim, all of it rotated"),
                ("num_key_value_heads", cfg["num_attention_heads"],
                 "latent attention expands one latent to every head"),
                ("n_shared_experts", 1, "the shared experts' outputs are "
                 "averaged (ffn_block), which is this block's sum only "
                 "for one")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"glm4_moe_lite with {key} {got!r}: {why}")
        if not cfg.get("q_lora_rank"):
            raise UnsupportedBlockError(
                reader, "glm4_moe_lite without q_lora_rank: the query is "
                "written down as the low-rank pair alone")
        if cfg.get("rope_scaling"):
            raise UnsupportedBlockError(
                reader, f"glm4_moe_lite with rope_scaling "
                f"{cfg['rope_scaling']!r}: this family's scaled rotation is "
                "not written down in this repository")
        for key in ("index_topk", "index_n_heads", "index_head_dim"):
            if cfg.get(key):
                raise UnsupportedBlockError(
                    reader, f"glm4_moe_lite with {key}: the block with an "
                    "indexer is read as deepseek_v32")
        modules = int(cfg.get("num_nextn_predict_layers", 0))
        if modules > 1:
            raise UnsupportedBlockError(
                reader, f"glm4_moe_lite with num_nextn_predict_layers "
                f"{modules}: the window program chains ONE prediction "
                "module (a second would draft from the first's output, "
                "which no path carries)")
        return DeepseekV32Spec(
            **cls._latent_fields(cfg, path),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            index_n_heads=0, index_head_dim=0, index_topk=0,
            mtp_layers=modules,
        )

    @classmethod
    def _from_nemotron_h(cls, cfg: dict, path: str) -> "ModelSpec":
        """Nemotron-3-Nano's keys (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
        ``config.json``, ``nemotron_h``): ``hybrid_override_pattern`` gives
        every layer ONE mixer (M a Mamba-2 layer, E an expert layer, * an
        attention layer); ``n_routed_experts`` and ``expert_parallel`` as
        ``_from_deepseek_v32`` reads them. ``expand`` is not read (the inner
        width is mamba_num_heads x mamba_head_dim), nor the rope keys (the
        attention layers rotate nothing) nor the ``time_step_*`` keys (they
        shape the initialisation of dt_bias; the row has no
        ``time_step_limit``, so dt is not clamped)."""
        reader = "the config reader"
        for key, want, why in (
                ("attention_bias", False, "the attention layers have no "
                 "bias leaves"),
                ("mamba_proj_bias", False, "the recurrent layer's "
                 "projections have no bias leaves"),
                ("mlp_bias", False, "the experts have no bias leaves"),
                ("use_bias", False, "no projection of this block has a bias "
                 "leaf"),
                ("use_conv_bias", True, "the convolution is written down "
                 "with its bias"),
                ("mamba_hidden_act", "silu", "the convolution's activation "
                 "and the gate are SiLU"),
                ("mlp_hidden_act", "relu2", "the experts are two matrices "
                 "around a squared ReLU (relu2)"),
                ("scoring_func", "sigmoid", "the router scores with a "
                 "sigmoid"),
                ("n_shared_experts", 1, "ONE shared expert of its own width "
                 "is added to the routed sum"),
                ("sliding_window", None, "the attention layers see every "
                 "earlier key"),
                ("residual_in_fp32", False, "the residual stream is "
                 "bfloat16")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"nemotron_h with {key} {got!r}: {why}")
        if cfg.get("time_step_limit"):
            raise UnsupportedBlockError(
                reader, "nemotron_h with time_step_limit: the recurrent "
                "layer is written down without a clamp on dt")
        pattern = cfg["hybrid_override_pattern"]
        if len(pattern) != cfg["num_hidden_layers"]:
            raise UnsupportedBlockError(
                reader, f"nemotron_h whose hybrid_override_pattern has "
                f"{len(pattern)} layers for num_hidden_layers "
                f"{cfg['num_hidden_layers']}")
        share = cfg.get("expert_parallel") or {}
        return NemotronHSpec(
            name=cfg.get("_name_or_path")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rms_norm_eps=cfg.get("layer_norm_epsilon",
                                 cfg.get("norm_eps", 1e-5)),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            num_experts=cfg["n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            num_routed_experts=share.get("routed_experts",
                                         cfg["n_routed_experts"]),
            first_expert=share.get("first_expert", 0),
            num_shared_experts=1,
            n_group=cfg.get("n_group", 1),
            topk_group=cfg.get("topk_group", 1),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            layer_pattern=pattern,
            ssm_heads=cfg["mamba_num_heads"],
            ssm_head_dim=cfg["mamba_head_dim"],
            ssm_groups=cfg["n_groups"],
            ssm_state=cfg["ssm_state_size"],
            ssm_conv=cfg["conv_kernel"],
            ssm_chunk=cfg.get("chunk_size", 128),
            shared_intermediate_size=cfg[
                "moe_shared_expert_intermediate_size"],
        )

    @classmethod
    def _from_minicpm_sala(cls, cfg: dict, path: str) -> "ModelSpec":
        """MiniCPM-SALA's keys (openbmb/MiniCPM-SALA ``config.json``,
        ``minicpm_sala``): ``mixer_types`` gives every layer its mixer
        (``lightning-attn`` linear attention, ``minicpm4`` attention over
        chosen blocks of keys), every layer a dense SwiGLU feed-forward
        behind it. The sparse layer's six constants come from
        ``sparse_config`` where the file has it, else the family's
        (MiniCPM4.1's released ``sparse_config``). ``mup_denominator`` shapes
        training and ``lightning_scale`` ("1/sqrt(d)") is the only scale
        written down; neither is read."""
        reader = "the config reader"
        for key, want, why in (
                ("attention_bias", False, "no projection has a bias leaf"),
                ("hidden_act", "silu", "the feed-forward is SwiGLU"),
                ("attn_use_rope", False, "the attention layers over chosen "
                 "blocks rotate nothing (compressed keys are means of "
                 "unrotated keys)"),
                ("lightning_use_rope", True, "the linear-attention layers "
                 "rotate q and k"),
                ("qk_norm", True, "q and k are RMS-normalised a head"),
                ("use_output_norm", True, "the linear-attention output is "
                 "RMS-normalised a head ahead of its gate"),
                ("use_output_gate", True, "the linear-attention output is "
                 "gated by sigmoid(u W_z)"),
                ("attn_use_output_gate", True, "the attention output is "
                 "gated by sigmoid(u W_z)"),
                ("lightning_scale", "1/sqrt(d)", "the state is read by "
                 "q / sqrt(head_dim)")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"minicpm_sala with {key} {got!r}: {why}")
        kinds = {"lightning-attn": "L", "minicpm4": "S"}
        mixers = cfg["mixer_types"]
        unknown = sorted(set(mixers) - set(kinds))
        if unknown or len(mixers) != cfg["num_hidden_layers"]:
            raise UnsupportedBlockError(
                reader, f"minicpm_sala whose mixer_types names {unknown} or "
                f"has {len(mixers)} entries for num_hidden_layers "
                f"{cfg['num_hidden_layers']}")
        if (cfg["lightning_nkv"] != cfg["lightning_nh"]
                or cfg["lightning_head_dim"] != cfg["head_dim"]):
            raise UnsupportedBlockError(
                reader, "minicpm_sala whose linear-attention layers share "
                "keys between heads or have another head width than the "
                "attention layers: one state a head of head_dim x head_dim "
                "is what is written down")
        sparse = cfg.get("sparse_config") or {}
        layers = cfg["num_hidden_layers"]
        return MiniCPMSALASpec(
            name=cfg.get("_name_or_path")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            layer_pattern="".join(kinds[m] + "D" for m in mixers),
            ssm_heads=cfg["lightning_nh"],
            ssm_head_dim=cfg["lightning_head_dim"],
            ssm_groups=cfg["lightning_nh"],
            ssm_state=cfg["lightning_head_dim"],
            ssm_chunk=cfg.get("chunk_size", 128),
            scale_emb=float(cfg.get("scale_emb", 1.0)),
            residual_scale=float(cfg.get("scale_depth", 1.0))
            / math.sqrt(layers) if "scale_depth" in cfg else 1.0,
            logit_divisor=cfg["hidden_size"] / cfg["dim_model_base"]
            if "dim_model_base" in cfg else 1.0,
            sparse_kernel=sparse.get("kernel_size", 32),
            sparse_stride=sparse.get("kernel_stride", 16),
            sparse_block=sparse.get("block_size", 64),
            sparse_topk=sparse.get("topk", 64),
            sparse_init_blocks=sparse.get("init_blocks", 1),
            sparse_window=sparse.get("window_size", 2048),
        )

    @classmethod
    def _from_ouro(cls, cfg: dict, path: str) -> "ModelSpec":
        """Ouro's keys (ByteDance/Ouro-2.6B ``config.json``, ``ouro``): the
        dense block's, ``total_ut_steps`` passes over the same layers and
        ``early_exit_threshold`` (kept as stated: config.block_refusals
        refuses one under 1). The sandwich norms are the model type's, no
        key's."""
        reader = "the config reader"
        for key, want, why in (
                ("hidden_act", "silu", "the feed-forward is SwiGLU"),
                ("attention_bias", False, "no projection has a bias leaf"),
                ("rope_scaling", None, "plain frequencies theta ** (-2i / "
                 "d) are what is written down"),
                ("use_sliding_window", False, "every layer of every pass "
                 "attends every earlier key")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"ouro with {key} {got!r}: {why}")
        kinds = set(cfg.get("layer_types") or ["full_attention"])
        if kinds != {"full_attention"}:
            raise UnsupportedBlockError(
                reader, f"ouro whose layer_types names {sorted(kinds)}: "
                "every layer of every pass attends every earlier key")
        return OuroSpec(
            name=cfg.get("_name_or_path")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            loop_passes=cfg["total_ut_steps"],
            early_exit_threshold=float(cfg.get("early_exit_threshold", 1.0)),
        )

    @classmethod
    def _from_solar_open2(cls, cfg: dict, path: str) -> "ModelSpec":
        """Solar-Open2's keys (upstage/Solar-Open2-250B ``config.json``,
        ``solar_open2``): ``gqa_layers`` names the layers whose mixer is
        softmax attention (*, no rotary embedding, gated where
        ``use_gqa_gate``); every other layer's is a gated delta-rule
        recurrence (K: Kimi Delta Attention, ``linear_attn_config`` and the
        ``kda_*`` keys); every layer an expert layer behind its mixer
        (``first_k_dense_replace`` 0). ``n_routed_experts`` and
        ``expert_parallel`` as ``_from_deepseek_v32`` reads them.
        ``gqa_interval``, the rope keys and ``intermediate_size`` (a dense
        layer's width, which no layer has) are carried and not read."""
        reader = "the config reader"
        for key, want, why in (
                ("use_rope", False, "the attention layers rotate nothing"),
                ("kda_use_full_proj", False, "the decay's and the output "
                 "gate's projections are written down as low-rank pairs"),
                ("first_k_dense_replace", 0, "every layer's feed-forward is "
                 "an expert layer"),
                ("n_shared_experts", 1, "ONE shared expert is added to the "
                 "routed sum"),
                ("scoring_func", "sigmoid", "the router scores with a "
                 "sigmoid"),
                ("n_group", 1, "the router chooses among all experts"),
                ("hidden_act", "silu", "the experts are SwiGLU"),
                ("attention_bias", False, "no projection has a bias leaf")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"solar_open2 with {key} {got!r}: {why}")
        linear = cfg["linear_attn_config"]
        if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
            raise UnsupportedBlockError(
                reader, f"solar_open2 whose linear layers have "
                f"{linear['num_kv_heads']} key heads for "
                f"{linear['num_heads']}: one state a head with keys of its "
                "own is what is written down")
        layers = cfg["num_hidden_layers"]
        softmax = set(cfg["gqa_layers"])
        if not softmax <= set(range(layers)):
            raise UnsupportedBlockError(
                reader, f"solar_open2 whose gqa_layers {sorted(softmax)} "
                f"are not among its {layers} layers")
        share = cfg.get("expert_parallel") or {}
        return SolarOpen2Spec(
            name=cfg.get("_name_or_path")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            num_experts=cfg["n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            num_routed_experts=share.get("routed_experts",
                                         cfg["n_routed_experts"]),
            first_expert=share.get("first_expert", 0),
            num_shared_experts=1,
            routed_scaling_factor=float(cfg.get("routed_scaling_factor")
                                        or 1.0),
            layer_pattern="".join(("*" if i in softmax else "K") + "E"
                                  for i in range(layers)),
            ssm_heads=linear["num_heads"],
            ssm_head_dim=linear["head_dim"],
            ssm_groups=linear["num_heads"],
            ssm_state=linear["head_dim"],
            ssm_conv=linear["short_conv_kernel_size"],
            ssm_chunk=cfg.get("chunk_size", 32),
            ssm_low_rank=linear["head_dim"],
            ssm_beta_scale=2.0 if cfg.get("kda_allow_neg_eigval") else 1.0,
            attn_gate=bool(cfg.get("use_gqa_gate", False)),
        )

    @classmethod
    def _from_falcon_h1(cls, cfg: dict, path: str) -> "ModelSpec":
        """Falcon-H1's keys (tiiuae/Falcon-H1-34B-Instruct ``config.json``,
        ``falcon_h1``): every layer a Mamba-2 mixer (the ``mamba_*`` keys)
        AND rotary attention side by side on one normed input, summed into
        one residual, then a dense SwiGLU; the muP scalars as published
        (``embedding_multiplier`` -> ``scale_emb``, ``lm_head_multiplier``
        -> 1 / ``logit_divisor``, the others under their own names).
        ``mamba_expand`` is not read (``mamba_d_ssm`` states the inner
        width), nor ``mlp_expansion_factor`` (``intermediate_size`` does)."""
        reader = "the config reader"
        for key, want, why in (
                ("attention_bias", False, "no projection has a bias leaf"),
                ("mamba_proj_bias", False, "the recurrent layer's "
                 "projections have no bias leaves"),
                ("mlp_bias", False, "the feed-forward has no bias leaves"),
                ("projectors_bias", False, "no projection has a bias leaf"),
                ("mamba_conv_bias", True, "the convolution is written down "
                 "with its bias"),
                ("hidden_act", "silu", "the feed-forward is SwiGLU and the "
                 "mixer's activation SiLU"),
                ("mamba_rms_norm", True, "the mixer's output is RMS-"
                 "normalised within its groups"),
                ("mamba_norm_before_gate", False, "the gate is applied "
                 "BEFORE the grouped norm"),
                ("mamba_use_mlp", True, "every layer has its feed-forward"),
                ("attn_layer_indices", None, "EVERY layer attends: a pool "
                 "layer a layer"),
                ("rope_scaling", None, "the rotation has plain frequencies "
                 "theta ** (-2i / d)")):
            got = cfg.get(key, want)
            if got != want:
                raise UnsupportedBlockError(
                    reader, f"falcon_h1 with {key} {got!r}: {why}")
        heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        if cfg.get("mamba_d_ssm", heads * p) != heads * p:
            raise UnsupportedBlockError(
                reader, f"falcon_h1 whose mamba_d_ssm {cfg['mamba_d_ssm']} "
                f"is not mamba_n_heads x mamba_d_head ({heads} x {p})")
        segments = tuple(float(m) for m in cfg.get("ssm_multipliers",
                                                   (1.0,) * 5))
        mlp = tuple(float(m) for m in cfg.get("mlp_multipliers", (1.0, 1.0)))
        if len(segments) != 5 or len(mlp) != 2:
            raise UnsupportedBlockError(
                reader, f"falcon_h1 with {len(segments)} ssm_multipliers and "
                f"{len(mlp)} mlp_multipliers: five segments (z, x, B, C, dt) "
                "and the pair (gate, down) are what is written down")
        layers = cfg["num_hidden_layers"]
        return FalconH1Spec(
            name=cfg.get("_name_or_path")
            or os.path.basename(os.path.dirname(path)),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            layer_pattern="M*D" * layers,
            ssm_heads=heads,
            ssm_head_dim=p,
            ssm_groups=cfg["mamba_n_groups"],
            ssm_state=cfg["mamba_d_state"],
            ssm_conv=cfg["mamba_d_conv"],
            ssm_chunk=cfg.get("mamba_chunk_size", 128),
            scale_emb=float(cfg.get("embedding_multiplier", 1.0)),
            logit_divisor=1.0 / float(cfg.get("lm_head_multiplier", 1.0)),
            key_multiplier=float(cfg.get("key_multiplier", 1.0)),
            attn_in_multiplier=float(cfg.get("attention_in_multiplier", 1.0)),
            attn_out_multiplier=float(cfg.get("attention_out_multiplier",
                                              1.0)),
            ssm_in_multiplier=float(cfg.get("ssm_in_multiplier", 1.0)),
            ssm_multipliers=segments,
            ssm_out_multiplier=float(cfg.get("ssm_out_multiplier", 1.0)),
            mlp_multipliers=mlp,
        )


@dataclasses.dataclass
class SmallThinkerSpec(ModelSpec):
    """The SmallThinker block (PowerInfer/SmallThinker-21BA3B-Instruct):
    what it states beyond ModelSpec's fields."""
    # An expert's width (``moe_ffn_hidden_size``); the block has no dense
    # feed-forward, so intermediate_size repeats it.
    moe_intermediate_size: int | None = None
    # "softmax_topk": softmax over all experts in float32, the k largest,
    # divided by their sum when norm_topk_prob.
    moe_router: str = "softmax_topk"
    norm_topk_prob: bool = True
    # "layer_input": the router reads the residual stream as it enters the
    # layer, ahead of input_norm (the pre-attention router).
    moe_router_input: str = "layer_input"
    ffn_act: str = "relu"               # ReGLU
    # Layer pattern, one entry a layer. In rope_layout 1 is rotate-half
    # RoPE and 0 none (NoPE); in sliding_window_layout 1 is a layer whose
    # query i sees key j iff i - sliding_window < j <= i, 0 one that sees
    # every j <= i.
    sliding_window: int | None = None
    sliding_window_layout: tuple | None = None
    rope_layout: tuple | None = None

    def __post_init__(self):
        super().__post_init__()
        for name in ("sliding_window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout is not None:
                layout = tuple(int(v) for v in layout)
                if len(layout) != self.num_layers:
                    raise ValueError(
                        f"{name} has {len(layout)} entries for "
                        f"{self.num_layers} layers")
                setattr(self, name, layout)
        if self.sliding_window_layout and any(self.sliding_window_layout) \
                and not self.sliding_window:
            raise ValueError("sliding_window_layout without sliding_window")


@dataclasses.dataclass
class Cohere2MoeSpec(SmallThinkerSpec):
    """The Cohere2-MoE block (CohereLabs/command-a-plus-05-2026): the layer
    pattern, expert width and router fields SmallThinkerSpec states, at
    this block's values, and what it states beyond them."""
    # "sigmoid_topk": sigmoid of every logit in float32, the k largest,
    # divided by their sum when norm_topk_prob (over all k chosen, held
    # here or not).
    moe_router: str = "sigmoid_topk"
    # The router reads what the experts read: the layer's one norm.
    moe_router_input: str = "post_attn_norm"
    ffn_act: str = "silu"               # SwiGLU
    # RoPE in interleaved pairs (2i, 2i + 1) ("rope_gptj"), where
    # rope_layout has a 1.
    rope_interleaved: bool = True
    # "layer": (x - mean) / sqrt(var + rms_norm_eps) * weight, no bias.
    norm_kind: str = "layer"
    # x + attention(norm(x)) + feed_forward(norm(x)): one norm a layer.
    parallel_block: bool = True
    # The router's width; this device holds experts first_expert to
    # first_expert + num_experts - 1 and computes their part of the routed
    # sum. What the others would add is left out: no exchange.
    num_routed_experts: int | None = None
    first_expert: int = 0
    # Experts every token passes through, of the routed experts' width;
    # the mean of their outputs is added to the routed sum.
    num_shared_experts: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.num_routed_experts is None:
            self.num_routed_experts = self.num_experts
        if not (0 <= self.first_expert
                and self.first_expert + self.num_experts
                <= self.num_routed_experts):
            raise ValueError(
                f"experts {self.first_expert} to "
                f"{self.first_expert + self.num_experts - 1} are not among "
                f"the router's {self.num_routed_experts}")


@dataclasses.dataclass
class DeepseekV32Spec(Cohere2MoeSpec):
    """The DeepSeek-V3.2 block (deepseek-ai/DeepSeek-V3.2-Exp,
    ``deepseek_v32``): the expert width, the sigmoid router, the share of a
    wider router and the shared experts that Cohere2MoeSpec states, at this
    block's values, and what it states beyond them. ``head_dim`` is a
    query head's width, qk_nope_head_dim + qk_rope_head_dim;
    ``num_kv_heads`` equals ``num_heads`` (the latent expands to every
    head) and sizes no pool: ``kv_entry`` does."""
    norm_kind: str = "rms"
    parallel_block: bool = False        # attention, then feed-forward
    # The rope part of q and the shared rope key turn in interleaved pairs
    # (2i, 2i + 1); the indexer's query and key in rotate-half pairs.
    rope_interleaved: bool = True
    # Latent attention: c = RMS(h Wkv_a[:kv_lora_rank]) and ONE rope key
    # of qk_rope_head_dim a token; a head's key is (c Wkv_b[K] | rope key),
    # its value c Wkv_b[V] of v_head_dim; q = RMS(h Wq_a) Wq_b, a head
    # (nope | rope).
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # The indexer: I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]) over
    # index_n_heads heads of index_head_dim; query t attends the
    # index_topk keys s <= t of largest I (all of them up to that many).
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # Layers 0 to first_k_dense - 1 have a dense feed-forward of
    # intermediate_size in place of the expert layer.
    first_k_dense: int = 0
    # The router's choice: z = sigmoid + bias; n_group groups of equal
    # size, a group's score the sum of its 2 largest z, the topk_group best
    # groups kept, the k largest z among their experts; gates are the
    # chosen sigmoids (no bias) over their sum, times
    # routed_scaling_factor.
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    moe_select_bias: bool = True
    # YaRN: (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale_all_dim); None: plain frequencies. The softmax
    # scale is head_dim ** -0.5 times (0.1 mscale_all_dim ln(factor) + 1)
    # squared.
    rope_yarn: tuple | None = None
    # Prediction modules (``num_nextn_predict_layers``): each a whole block
    # of this kind over x_i = [RMS(Emb(t_{i+1})) ; RMS(h_i)] W_eh, h_i the
    # model's output at position i, whose output through the model's own
    # head drafts t_{i+2}; its entries are one more layer of the pool.
    # index_topk 0 (with index_n_heads and index_head_dim 0) is this block
    # WITHOUT the indexer: every query attends every key.
    mtp_layers: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.router_width % self.n_group:
            raise ValueError(f"{self.router_width} experts do not divide "
                             f"into {self.n_group} groups")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError(f"first_k_dense {self.first_k_dense} of "
                             f"{self.num_layers} layers")

    @property
    def attn_scale(self) -> float:
        """What multiplies q . k ahead of the softmax."""
        scale = self.head_dim ** -0.5
        if self.rope_yarn is not None:
            factor, _, _, _, mscale_all = self.rope_yarn
            if factor > 1:
                scale *= (0.1 * mscale_all * math.log(factor) + 1.0) ** 2
        return scale

@dataclasses.dataclass
class NemotronHSpec(Cohere2MoeSpec):
    """The Nemotron-H block (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
    ``nemotron_h``): every layer is h + Mixer(RMS(h)) with ONE mixer, its
    kind by ``layer_pattern``; the sigmoid router, the share of a wider
    router and the shared expert that Cohere2MoeSpec states, at this
    block's values, and what it states beyond them. The programs are
    engine/hybrid.py's."""
    norm_kind: str = "rms"
    parallel_block: bool = False
    rope_interleaved: bool = False      # nothing rotates: no rope at all
    # "relu2": an expert is TWO matrices, down(relu(up x) ** 2): no gate
    # leaf. The shared expert is one of the same form, of its own width,
    # added unscaled.
    ffn_act: str = "relu2"
    shared_intermediate_size: int | None = None
    # The router's choice as DeepseekV32Spec states it.
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    moe_select_bias: bool = True
    # One letter a layer: M a Mamba-2 mixer, E an expert layer, * an
    # attention layer (no rotary embedding, every earlier key). The pattern
    # is pairs of M and E, a pair at a time, some with a * between the two:
    # the programs scan the stacked pairs (hybrid.pairs_of).
    layer_pattern: str | None = None
    # The Mamba-2 mixer: ssm_heads heads of ssm_head_dim, B and C in
    # ssm_groups groups of ssm_state, a causal depthwise convolution of
    # ssm_conv taps over x | B | C; prefill computes the recurrence in
    # chunks of ssm_chunk tokens. A row keeps the state S [heads, head_dim,
    # state] in float32 and the convolution's last ssm_conv - 1 inputs.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 128

    def __post_init__(self):
        super().__post_init__()
        pattern = self.layer_pattern or ""
        if len(pattern) != self.num_layers or set(pattern) - set("ME*"):
            raise ValueError(f"layer_pattern {pattern!r} does not give "
                             f"{self.num_layers} layers of M, E and *")
        _check_groups(pattern)
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(f"{self.ssm_heads} heads do not divide into "
                             f"{self.ssm_groups} groups")


#: The letters of a recurrent mixer: M Mamba-2, L lightning linear attention,
#: K the gated delta rule (Kimi Delta Attention). One kind a model.
RECURRENT_KINDS = "MLK"
#: What a start-up fact calls each (engine.perf_status ``ssm.kind``).
RECURRENT_NAMES = {"M": "mamba2", "L": "lightning", "K": "delta_rule"}
#: A GROUP of a ``layer_pattern``: at most one recurrent mixer
#: (RECURRENT_KINDS), at most one attention layer behind it (* over
#: every earlier key, S over chosen blocks of keys), then ONE feed-forward (E
#: an expert layer, D a dense one). The programs scan the stacked groups
#: (hybrid.groups_of): Nemotron-H's pairs of M and E with a * between some
#: are one instance, a layer of a mixer and its feed-forward another.
#: Whether a group's mixer and attention layer run one after the other, each
#: behind its own norm and residual sum, or SIDE BY SIDE on one normed input
#: (``ModelSpec.parallel_mixers``) is the block's and not a letter's.
GROUP = rf"[{RECURRENT_KINDS}]?[*S]?[ED]"


def _check_groups(pattern: str) -> None:
    if len(set(pattern) & set(RECURRENT_KINDS)) > 1:
        raise UnsupportedBlockError(
            "the layer scan", f"layer_pattern {pattern!r} has recurrent "
            "mixers of more than one kind: a model's recurrent layers share "
            "ONE set of leaves and ONE pair of state arrays")
    if not re.fullmatch(f"({GROUP})+", pattern):
        raise UnsupportedBlockError(
            "the layer scan", f"layer_pattern {pattern!r} is not groups of "
            "at most one recurrent mixer, at most one attention layer and "
            "one feed-forward (Nemotron-H's pairs of M and E with a * "
            "between some are such groups): the scan over stacked groups is "
            "written for that form alone")


@dataclasses.dataclass
class MiniCPMSALASpec(ModelSpec):
    """The MiniCPM-SALA block (openbmb/MiniCPM-SALA, ``minicpm_sala``):
    every layer is ``h + a Mixer(RMS(h))`` then ``h + a MLP(RMS(h))`` with
    ``a = residual_scale``, the mixer one of two kinds by the layer; what it
    states beyond ModelSpec's fields. The programs are engine/hybrid.py's,
    which has the equations."""
    # Two letters a layer, one a SUBLAYER: L a lightning linear-attention
    # mixer, S attention over chosen blocks of keys, each followed by D, the
    # dense SwiGLU feed-forward (config.GROUP).
    layer_pattern: str | None = None
    # The lightning mixer: ssm_heads heads, a state S [ssm_head_dim (v),
    # ssm_state (k)] float32 a head and NO convolution (ssm_conv 0: a row
    # keeps the state alone); a head's keys are its own (ssm_groups =
    # ssm_heads). Prefill computes the recurrence in chunks of ssm_chunk.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_chunk: int = 128
    # q and k are RMS-normalised a head, times a weight [head_dim].
    qk_norm: bool = True
    # muP: x0 = scale_emb * E[token]; every sublayer's output times
    # residual_scale (scale_depth / sqrt(num_hidden_layers)); the final
    # norm's output over logit_divisor (hidden_size / dim_model_base).
    scale_emb: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # Attention over chosen blocks (InfLLM-V2): compressed keys are means of
    # sparse_kernel keys every sparse_stride; a query keeps sparse_topk
    # blocks of sparse_block keys a KV group, among them always the first
    # sparse_init_blocks and those of the last sparse_window keys.
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048

    def __post_init__(self):
        super().__post_init__()
        pattern = self.layer_pattern or ""
        if len(pattern) != 2 * self.num_layers or set(pattern) - set("LSD"):
            raise ValueError(f"layer_pattern {pattern!r} does not give "
                             f"{self.num_layers} layers of L or S, then D")
        _check_groups(pattern)
        if self.sparse_kernel != 2 * self.sparse_stride:
            raise UnsupportedBlockError(
                "the compressed-key array", f"a compressed key of "
                f"{self.sparse_kernel} keys every {self.sparse_stride} is "
                "not two strides: the array holds a mean a stride and a "
                "compressed key is the mean of two of them")
        if (self.sparse_block % self.sparse_stride
                or self.sparse_window < 2 * self.sparse_block):
            raise UnsupportedBlockError(
                "attention over chosen blocks", f"blocks of "
                f"{self.sparse_block} keys are not whole strides of "
                f"{self.sparse_stride}, or the window of "
                f"{self.sparse_window} keys every query keeps is under two "
                "blocks (the block a window's tokens are written in is kept "
                "by the window alone)")


@dataclasses.dataclass
class SolarOpen2Spec(Cohere2MoeSpec):
    """The Solar-Open2 block (upstage/Solar-Open2-250B, ``solar_open2``):
    every layer is ``h + Mixer(RMS(h))`` then ``h + MoE(RMS(h))``, the mixer
    a gated delta-rule recurrence (K) or softmax attention without a rotary
    embedding (*) by the layer; the sigmoid router with a selection bias,
    the share of a wider router and the shared expert that Cohere2MoeSpec
    and NemotronHSpec state, at this block's values (SwiGLU experts), and
    what it states beyond them. The programs are engine/hybrid.py's, which
    has the equations."""
    norm_kind: str = "rms"
    parallel_block: bool = False
    rope_interleaved: bool = False      # nothing rotates
    ffn_act: str = "silu"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    moe_select_bias: bool = True
    # Two letters a layer, one a SUBLAYER: K or *, then E (config.GROUP).
    layer_pattern: str | None = None
    # The delta-rule mixer: ssm_heads heads, q and k of ssm_state and v of
    # ssm_head_dim a head, a causal depthwise convolution of ssm_conv taps
    # over q | k | v. A row keeps S [heads, head_dim (v), state (k)] in
    # float32 and the convolution's last ssm_conv - 1 inputs; prefill solves
    # the delta rule in chunks of ssm_chunk tokens (hybrid.delta_chunked:
    # 32, the float32 decays between every pair of a chunk's tokens are 8 KB
    # a pair and head).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 32
    # The decay's and the output gate's projections go through this rank.
    ssm_low_rank: int = 0
    # beta = ssm_beta_scale * sigmoid(u W_b): 2 where kda_allow_neg_eigval.
    ssm_beta_scale: float = 2.0
    # A * layer's output times sigmoid(u W_z) (use_gqa_gate).
    attn_gate: bool = True

    def __post_init__(self):
        super().__post_init__()
        pattern = self.layer_pattern or ""
        if len(pattern) != 2 * self.num_layers or set(pattern) - set("K*E"):
            raise ValueError(f"layer_pattern {pattern!r} does not give "
                             f"{self.num_layers} layers of K or *, then E")
        _check_groups(pattern)
        if self.ssm_groups != self.ssm_heads:
            raise ValueError("a delta-rule head's keys are its own: "
                             f"{self.ssm_groups} groups for "
                             f"{self.ssm_heads} heads")


@dataclasses.dataclass
class FalconH1Spec(ModelSpec):
    """The Falcon-H1 block (tiiuae/Falcon-H1-34B-Instruct, ``falcon_h1``):
    every layer ``u = RMS(h)``, ``h <- h + a_s SSM(b_s u) + a_a Attn(b_a
    u)`` (a Mamba-2 mixer and rotary attention SIDE BY SIDE on ONE normed
    input, summed into ONE residual), then ``h <- h + MLP(RMS(h))``, under
    the model's muP constants; what it states beyond ModelSpec's fields.
    The programs are engine/hybrid.py's, which has the equations."""
    # Three letters a layer, one a SUBLAYER (config.GROUP): M, * and D. The
    # letters name each sublayer's KIND (its leaves, its state a row, its
    # pool layer); that M and * of a group are wired in parallel is this
    # block's, stated once here and not by the letters.
    layer_pattern: str | None = None
    parallel_mixers: bool = True
    # A * layer rotates q and k (rotate-half, every lane, rope_theta).
    attn_rope: bool = True
    # The Mamba-2 mixer, as NemotronHSpec states it.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 128
    # muP: x0 = scale_emb * E[token]; logits = RMS(h) W_head / logit_divisor;
    # k = key_multiplier * (u W_k) ahead of the rotation and of the pool;
    # attention reads attn_in_multiplier * u and its output is multiplied by
    # attn_out_multiplier; the mixer reads ssm_in_multiplier * u, the
    # segments z, x, B, C, dt of its in-projection's output are multiplied
    # by ssm_multipliers, its output by ssm_out_multiplier; the SwiGLU's
    # gate pre-activation and its down product by mlp_multipliers.
    scale_emb: float = 1.0
    logit_divisor: float = 1.0
    key_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)

    def __post_init__(self):
        super().__post_init__()
        pattern = self.layer_pattern or ""
        if pattern != "M*D" * self.num_layers:
            raise ValueError(f"layer_pattern {pattern!r} does not give "
                             f"{self.num_layers} layers of M and * side by "
                             "side, then D")
        _check_groups(pattern)
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(f"{self.ssm_heads} heads do not divide into "
                             f"{self.ssm_groups} groups")
        self.ssm_multipliers = tuple(self.ssm_multipliers)
        self.mlp_multipliers = tuple(self.mlp_multipliers)


@dataclasses.dataclass
class OuroSpec(ModelSpec):
    """A looped stack (ByteDance/Ouro-2.6B, ``ouro``; arXiv:2510.25741):
    the dense block's layers run ``loop_passes`` times a token, the final
    norm after every pass, its output the next pass's input and the last
    one's the head's. A pass attends the K and V of its OWN earlier visits,
    so a token leaves K and V ``layer_visits`` times (ModelSpec.pool_layers:
    pool layer ``t * num_layers + l``). What it states beyond ModelSpec's
    fields; the programs are engine/model.py's (``scan_passes`` around the
    one ``transformer_block``)."""
    loop_passes: int = 4
    # x + RMS(Sublayer(RMS(x))): a norm of each sublayer's OUTPUT, with a
    # gain of its own (``attn_out_gain``, ``mlp_out_gain``), beside the
    # one of its input.
    sandwich_norm: bool = True
    # The cumulative exit probability at which a token would leave the
    # loop. At 1 only the last pass reaches it: every token takes every
    # pass and the gate is not evaluated (it has no leaf here).
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if self.loop_passes < 1:
            raise ValueError(f"loop_passes must be >= 1, got "
                             f"{self.loop_passes}")


class UnsupportedBlockError(NotImplementedError):
    """A path that lacks a mechanism a model's block needs refuses the
    model at start-up and names what it lacks; it never runs the block
    under another block's rules."""

    def __init__(self, what: str, why: str):
        super().__init__(f"{what} cannot take this model: {why}")


def block_refusals(spec: ModelSpec, config: "EngineConfig | None" = None,
                   checkpoint: bool = False, embeddings: bool = False,
                   kv_transfer: bool = False
                   ) -> list[UnsupportedBlockError]:
    """Every reason the block of ``spec`` cannot run the way ``config``
    asks (None: nothing is asked of an engine), with ``checkpoint`` take
    its weights from safetensors, with ``embeddings`` take an encoder's
    embeddings in place of token rows, or with ``kv_transfer`` hand pages
    of its pool to another holder (a KV-plane parcel, a disaggregated
    insert, a host tier). ModelRunner raises the first at
    start-up, the loader before it opens a file and the engine as it
    validates such a request; the forward functions hold no refusal, and
    nothing else in the package asks what kind of block a model has.

    Where a path lacks a mechanism, the test is on the field that carries
    it. Where a combination was only never compared with its reference,
    the test is ``other``: a block that is not the Llama / Qwen2 / Mixtral
    one (a router of another kind or ahead of attention, ReGLU, layers
    that differ in kind, one norm for both branches, shared experts, a
    share of the experts)."""
    windowed = bool(spec.sliding_window_layout
                    and any(spec.sliding_window_layout))
    share = spec.holds_share
    other = (spec.has_layer_pattern or spec.moe_router != "topk_softmax"
             or spec.moe_router_input != "post_attn_norm"
             or spec.ffn_act != "silu" or spec.parallel_block
             or spec.norm_kind != "rms" or spec.num_shared_experts > 0
             or share)
    unlike = "a block other than Llama's, Qwen2's or Mixtral's"
    out = []
    if checkpoint and other:
        out.append(UnsupportedBlockError(
            "the safetensors loader", "it has a tensor-name map for the "
            f"Llama, Qwen2 and Mixtral checkpoints only, and this is {unlike} "
            "(random weights only)"))
    if embeddings and (spec.num_shared_experts or share):
        out.append(UnsupportedBlockError(
            "encoder embeddings in a prompt (mm_embeds)", "no vision or "
            "audio tower is written down for a block with shared experts "
            "or a share of its routed experts, and another encoder's rows "
            "under it were never compared with its reference"))
    latent = spec.latent
    if kv_transfer and latent:
        out.append(UnsupportedBlockError(
            "a KV parcel (KV-plane tickets, disaggregated insert, host and "
            "disk tiers)", "a parcel is K and V pages of one shape stacked, "
            "and a latent pool's page is a latent entry and an index key "
            "of different widths"))
    recurrent = spec.recurrent
    if kv_transfer and recurrent:
        out.append(UnsupportedBlockError(
            "a KV parcel (KV-plane tickets, disaggregated insert, host and "
            "disk tiers)", "a parcel is pages, and a row of this model also "
            "holds a recurrent state a layer, which has no parcel: pages "
            "without the state at their border continue nothing"))
    if kv_transfer and spec.compressed_keys:
        out.append(UnsupportedBlockError(
            "a KV parcel (KV-plane tickets, disaggregated insert, host and "
            "disk tiers)", "a parcel is K and V pages, and a page of this "
            "model also has a row of the compressed-key array, which no "
            "parcel carries: pages inserted without it are never chosen"))
    if checkpoint and recurrent:
        out.append(UnsupportedBlockError(
            "the safetensors loader", "it has no tensor-name map for the "
            "recurrent layer's leaves (random weights only)"))
    if embeddings and recurrent:
        out.append(UnsupportedBlockError(
            "encoder embeddings in a prompt (mm_embeds)", "the programs of "
            "a block with recurrent layers (engine/hybrid.py) take token "
            "rows alone"))
    looped = spec.loop_passes > 1
    if spec.early_exit_threshold < 1.0:
        out.append(UnsupportedBlockError(
            "every engine path", f"an exit threshold of "
            f"{spec.early_exit_threshold} lets rows of one batch leave the "
            "loop at different passes: the scheduler and the window program "
            "run every row of a step through every pass, the exit gate has "
            "no leaf and a row that left would have no K and V in the "
            "passes it skipped"))
    if checkpoint and (looped or spec.sandwich_norm):
        out.append(UnsupportedBlockError(
            "the safetensors loader", "it has no tensor-name map for the "
            "norms of a sublayer's output nor for a looped stack's "
            "checkpoint (random weights only)"))
    if embeddings and looped:
        out.append(UnsupportedBlockError(
            "encoder embeddings in a prompt (mm_embeds)", "another "
            "encoder's rows entering the first of several passes were "
            "never compared with a reference"))
    if kv_transfer and looped:
        out.append(UnsupportedBlockError(
            "a KV parcel (KV-plane tickets, disaggregated insert, host and "
            "disk tiers)", f"a page holds K and V of {spec.layer_visits} "
            "(pass, layer) pairs, and a parcel of such pages was never "
            "moved nor compared with its source (its holders size their "
            "buffers by the model's layers)"))
    if config is None:
        return out
    if looped:
        out += _looped_refusals(spec, config)
    if latent:
        out += _latent_refusals(spec, config)
    if recurrent:
        out += _recurrent_refusals(spec, config)
    if spec.compressed_keys:
        out += _compressed_refusals(spec, config)
    if share and config.tp * config.pp * config.dp * config.sp > 1:
        out.append(UnsupportedBlockError(
            "a tp/pp/dp/sp mesh", f"the expert layer is told ONE share "
            f"(experts {spec.first_expert} to "
            f"{spec.first_expert + spec.num_experts - 1} of "
            f"{spec.router_width}) and there is no exchange of rows "
            "between devices that hold different experts"))
    if config.spec_decode and windowed:
        out.append(UnsupportedBlockError(
            "speculative decoding (spec_decode)", "the verify step's scores "
            "have no window mask, and sliding_window_layout has a window "
            "layer"))
    if config.spec_decode == "mtp":
        if not spec.mtp_layers:
            out.append(UnsupportedBlockError(
                "drafting with the model's own prediction module "
                "(spec_decode mtp)", "the model has no such module "
                "(num_nextn_predict_layers 0: no mtp_ leaves to draft "
                "with)"))
        elif config.spec_k > spec.mtp_layers:
            out.append(UnsupportedBlockError(
                "drafting with the model's own prediction module "
                "(spec_decode mtp)", f"spec_k {config.spec_k} asks for more "
                f"drafts a step than the model has modules "
                f"({spec.mtp_layers}): a module drafts one token and "
                "nothing chains a module on itself"))
        if config.tp * config.pp * config.dp * config.sp > 1:
            out.append(UnsupportedBlockError(
                "drafting with the model's own prediction module "
                "(spec_decode mtp)", "the drafting window carries a row's "
                "position and draft on ONE device and was never compared "
                "with its reference on a mesh"))
    elif config.spec_decode and spec.mtp_layers:
        out.append(UnsupportedBlockError(
            "speculative decoding by n-gram drafting (spec_decode ngram)",
            "the model's pool has a "
            "layer of its prediction module's entries, which the n-gram "
            "program's verify step neither reads nor writes"))
    if config.ring_attention and windowed:
        out.append(UnsupportedBlockError(
            "ring attention", "its blockwise scores have no window mask, "
            "and sliding_window_layout has a window layer"))
    if config.pp_microbatch and spec.has_layer_pattern:
        out.append(UnsupportedBlockError(
            "the pipelined prefill (pp_microbatch)", "a stage's scan has no "
            "global layer index, and rope_layout / sliding_window_layout "
            "give layers that differ in kind"))
    if config.max_adapters > 0 and other:
        out.append(UnsupportedBlockError(
            "LoRA adapters (max_adapters)", f"LoRA on the attention of "
            f"{unlike} was never compared with its reference"))
    if config.tp * config.pp * config.dp * config.sp > 1 and other:
        out.append(UnsupportedBlockError(
            "a tp/pp/dp/sp mesh", f"{unlike} was never compared with its "
            "reference on more than one device (its grouped expert product "
            "has no partitioning rule)"))
    return out


def _looped_refusals(spec: ModelSpec, config: "EngineConfig"
                     ) -> list[UnsupportedBlockError]:
    """block_refusals' part for a looped stack (``spec.loop_passes`` > 1):
    every engine path whose layer axis is the model's layers and not the
    (pass, layer) pairs the pool holds, by what it lacks."""
    out = []
    if config.spec_decode:
        out.append(UnsupportedBlockError(
            f"speculative decoding (spec_decode {config.spec_decode})",
            "the verify step (model.decode_window_multi_step) scans the "
            "layers once over a window buffer of one entry a layer: it has "
            "no loop over passes and no buffer a (pass, layer) pair"))
    if config.max_adapters > 0:
        out.append(UnsupportedBlockError(
            "LoRA adapters (max_adapters)", "the adapter stacks ride the "
            "layer scan as one entry a layer, and nothing says whether an "
            "adapter is the same in every pass"))
    if config.pp_microbatch or config.ring_attention:
        out.append(UnsupportedBlockError(
            "the pipelined and the ring prefill (pp_microbatch, "
            "ring_attention)", "a stage's scan and the ring's blocks run "
            "the layers once and have no loop over passes"))
    if config.resolve_quant_kv() is not None:
        out.append(UnsupportedBlockError(
            "int8 KV pages (quant_kv)", "the programs of a looped stack "
            "were compared with their reference over a bfloat16 pool alone "
            f"(the rounding of {spec.layer_visits} quantised layer visits "
            "was never measured)"))
    if config.host_cache_pages > 0 or config.kv_disk_cache_dir:
        out.append(UnsupportedBlockError(
            "the host and disk KV tiers (kvbm)", "they move parcels of "
            f"pages, and a page of {spec.layer_visits} (pass, layer) pairs "
            "was never moved nor compared with its source"))
    if config.tp * config.pp * config.dp * config.sp > 1:
        out.append(UnsupportedBlockError(
            "a tp/pp/dp/sp mesh", "the pool's layer axis is (pass, layer) "
            "pairs and the parameters' is layers: a pp stage would hold "
            "other pool layers than its own, and no mesh was compared with "
            "the reference"))
    return out


def _recurrent_refusals(spec: ModelSpec, config: "EngineConfig"
                        ) -> list[UnsupportedBlockError]:
    """block_refusals' part for a block with recurrent layers
    (``spec.recurrent``): every engine path that assumes a row's whole
    state is pages, by what it lacks."""
    out = []
    if config.spec_decode:
        out.append(UnsupportedBlockError(
            f"speculative decoding (spec_decode {config.spec_decode})",
            "a rejected draft has to be undone, and the recurrent state a "
            "verify step leaves is the state AFTER every drafted token: "
            "there is no copy of the state before them to go back to"))
    if config.host_cache_pages > 0 or config.kv_disk_cache_dir:
        out.append(UnsupportedBlockError(
            "the host and disk KV tiers (kvbm)", "they move parcels of "
            "pages, and pages onboarded without the recurrent state at "
            "their border continue nothing"))
    if config.tp * config.pp * config.dp * config.sp > 1:
        out.append(UnsupportedBlockError(
            "a tp/pp/dp/sp mesh", "the recurrent state arrays and the "
            "programs that carry them (engine/hybrid.py) have no "
            "partitioning rule: they were written and compared with their "
            "reference on one device"))
    if config.ring_attention:
        out.append(UnsupportedBlockError(
            "ring attention", "its blocks of a prompt rotate K and V "
            "between devices, and the recurrence over the prompt has no "
            "hand-over of its state from one block's device to the next"))
    if config.pp_microbatch:
        out.append(UnsupportedBlockError(
            "the pipelined prefill (pp_microbatch)", "a stage's scan takes "
            "one stack of alike layers and carries no recurrent state from "
            "stage to stage"))
    if config.max_adapters > 0:
        out.append(UnsupportedBlockError(
            "LoRA adapters (max_adapters)", "the adapter targets are wq, "
            "wk, wv and wo of every layer, and most of this block's layers "
            "(the recurrent and the expert layers) have none of them"))
    if config.resolve_quant_kv() is not None:
        out.append(UnsupportedBlockError(
            "int8 KV pages (quant_kv)", "the programs of a block with "
            "recurrent layers (engine/hybrid.py) were compared with their "
            "reference over a bfloat16 pool alone"))
    return out


def _compressed_refusals(spec: ModelSpec, config: "EngineConfig"
                         ) -> list[UnsupportedBlockError]:
    """block_refusals' part for attention over chosen blocks of keys
    (``spec.compressed_keys``): every engine path that knows two pool
    arrays and a row's whole context, by what it lacks."""
    out = []
    if config.page_size % spec.sparse_block:
        out.append(UnsupportedBlockError(
            f"a pool of pages of {config.page_size} tokens", "the chosen "
            f"blocks of {spec.sparse_block} keys are read as parts of a "
            "page and a page's stripes are the page's own keys': a page "
            "has to be whole blocks (page_size \"auto\" resolves to one)"))
    if config.spec_decode:
        out.append(UnsupportedBlockError(
            f"speculative decoding (spec_decode {config.spec_decode})",
            "the verify step scores every key in context for several "
            "query positions and has no choice of blocks a position, nor "
            "the compressed keys of the drafted tokens"))
    if config.host_cache_pages > 0 or config.kv_disk_cache_dir:
        out.append(UnsupportedBlockError(
            "the host and disk KV tiers (kvbm)", "they move parcels of K "
            "and V pages, and a page of this model also has a row of the "
            "compressed-key array, which no tier holds"))
    if config.tp * config.pp * config.dp * config.sp > 1:
        out.append(UnsupportedBlockError(
            "a tp/pp/dp/sp mesh", "the chosen blocks are read through a "
            "table over the pool seen as blocks of ONE KV head, and the "
            "compressed-key array has no partitioning rule"))
    if config.resolve_quant_kv() is not None:
        out.append(UnsupportedBlockError(
            "int8 KV pages (quant_kv)", "the compressed keys are means of "
            "bfloat16 keys read back from the pool's last rows, and no "
            "scale is written down for a mean of int8 rows"))
    return out


def _latent_refusals(spec: ModelSpec, config: "EngineConfig"
                     ) -> list[UnsupportedBlockError]:
    """block_refusals' part for a latent pool (``spec.latent``): every
    engine path that still assumes a K and V pair, by what it lacks."""
    out = []
    if config.resolve_quant_kv() is not None:
        out.append(UnsupportedBlockError(
            "int8 KV pages (quant_kv)", "QuantKV scales a K or V row a "
            "head; a latent entry's latent and rope key differ in range "
            "and the index key decides a choice: no scale is written down "
            "for either"))
    if config.host_cache_pages > 0 or config.kv_disk_cache_dir:
        out.append(UnsupportedBlockError(
            "the host and disk KV tiers (kvbm)", "they move parcels of K "
            "and V pages of one shape, and a latent pool's two arrays "
            "differ in width"))
    if config.spec_decode == "mtp" and spec.index_topk:
        out.append(UnsupportedBlockError(
            "drafting with the model's own prediction module (spec_decode "
            "mtp)", "the drafting window's verify step attends every key "
            "in context and has no indexer's selection over two query "
            "positions (index_topk > 0)"))
    elif config.spec_decode and config.spec_decode != "mtp":
        out.append(UnsupportedBlockError(
            "speculative decoding by n-gram drafting (spec_decode ngram)",
            "its verify step "
            "(model.decode_window_multi_step) scores K and V heads and has "
            "no absorbed latent product over a pool of latent entries"))
    if config.ring_attention or config.sp > 1:
        out.append(UnsupportedBlockError(
            "ring and sequence-parallel prefill", "their blockwise scores "
            "rotate K and V blocks and have no index scores to select by"))
    if config.pp_microbatch or config.pp > 1:
        out.append(UnsupportedBlockError(
            "a pipeline of layer stages (pp)", "a stage's scan takes one "
            "stack of layers, and the leading dense layers are a stack of "
            "their own"))
    if config.max_adapters > 0:
        out.append(UnsupportedBlockError(
            "LoRA adapters (max_adapters)", "the adapter targets are wq, "
            "wk, wv and wo, and the latent block's queries and keys come "
            "from low-rank pairs"))
    return out


# Presets (shapes from the public model cards).
PRESETS: dict[str, ModelSpec] = {
    "tiny-test": ModelSpec(name="tiny-test", vocab_size=512, hidden_size=128,
                           intermediate_size=352, num_layers=2, num_heads=4,
                           num_kv_heads=2, max_position_embeddings=2048),
    "qwen2.5-0.5b": ModelSpec(name="qwen2.5-0.5b", vocab_size=151936,
                              hidden_size=896, intermediate_size=4864,
                              num_layers=24, num_heads=14, num_kv_heads=2,
                              rope_theta=1000000.0, qkv_bias=True,
                              tie_word_embeddings=True),
    # Llama-3-8B per-layer shapes with 8 of 32 layers: fits one v5e chip in
    # bf16 (~5.6 GiB) for single-chip benchmarking; full-model per-chip
    # numbers extrapolate by layer count.
    "llama-3-8b-L8": ModelSpec(name="llama-3-8b-L8", vocab_size=128256,
                               hidden_size=4096, intermediate_size=14336,
                               num_layers=8, num_heads=32, num_kv_heads=8,
                               rope_theta=500000.0),
    "llama-3-8b": ModelSpec(name="llama-3-8b", vocab_size=128256,
                            hidden_size=4096, intermediate_size=14336,
                            num_layers=32, num_heads=32, num_kv_heads=8,
                            rope_theta=500000.0),
    "llama-3-70b": ModelSpec(name="llama-3-70b", vocab_size=128256,
                             hidden_size=8192, intermediate_size=28672,
                             num_layers=80, num_heads=64, num_kv_heads=8,
                             rope_theta=500000.0),
}


#: The page where nothing on the device chose one: the reference's GPU
#: engines' block, and the floor of a derived page.
DEFAULT_PAGE_SIZE = 16
MAX_PAGE_SIZE = 128
#: What one page copy of the Pallas decode kernel should move, K or V across
#: the KV heads of one page of one layer: measured on one v5e (PERF.md
#: section 6, PR 31).
PAGE_COPY_BYTES = 64 * 1024
#: The context limit in tokens where max_pages_per_seq is not given.
DEFAULT_MAX_MODEL_LEN = 8192


def pool_access(attention_backend: str, platform: str, mesh_size: int,
                head_dim: int, quant_kv: str | None, latent: bool = False
                ) -> tuple[str, str]:
    """(attention backend, KV commit): who reads the KV pool in decode and
    how the decode window writes it, from what a runner observes and
    nothing else. The ONE statement of that choice, the inner rule of
    backends.choose: that asks it with the runner's device's platform and
    its mesh (and decides beside it what follows the reader),
    EngineConfig.resolve_page_size with the configuration's.

    Reader, under "auto": the Pallas kernel on one TPU device at head_dim
    128, the XLA gather everywhere else. Timed on one v5e (PERF.md section
    6, PR 26). head_dim 128: attention of a Qwen2.5-7B decode step, 17
    live rows of 32 at about 950 tokens, costs 21.4 ms gathered and 3 ms
    in the kernel, a row past 2048 tokens moves every slot of the gather
    to the next bucket and costs the kernel its own pages, and at 8- and
    16-page buckets the two are level (llama-3-8b-L8: 12.4 against 12.4
    and 12.9 against 12.5 ms a step). head_dim 64: the kernel's [page, D]
    -> [rows, 128] view of the pool is a relayout on the device, a copy of
    the pool per layer (qwen2.5-0.5b: 247 ms a step against 8.5), so a
    packed head stays on XLA until the pool is stored lane-dense (ROADMAP
    D3). The CPU would interpret the kernel; a mesh would gather the pool
    around it. A requested backend is returned as asked: whether it can be
    had is the runner's to refuse.

    Writer: "in_place" (attention.commit_window_pallas) where that kernel
    reads a plain bf16 pool row-major at head_dim 128 on one device, so
    the touched rows are rewritten where they lie; "scatter"
    (kv_quant.scatter_tokens) everywhere else: the XLA reader (a mesh and
    the CPU under "auto"), a packed head (head_dim 64), int8 pages
    (QuantKV: tiles of 32 rows, and the scales are a second array).

    A ``latent`` pool (ModelSpec.kv_entry: one latent entry of 640 lanes
    and one index key of 128 a token a layer, under one page table), under
    "auto": the Pallas kernel (attention.latent_history_pallas) on one TPU
    device over bfloat16 entries, XLA's walk everywhere else (the CPU, any
    mesh). XLA's walk gathers the page-table bucket of the LONGEST row for
    all slots (32 x 5,120 tokens x 1,280 B a layer) and reads the copy
    twice more: 8.0 ms of a 21.6 ms decode step on one v5e at 17 live rows
    of 32 (PERF.md section 5, PR 34); the kernel reads a row's live pages
    once, one copy a page, with the indexer's choice as its mask (PERF.md
    section 6, PR 35). The indexer's scores follow the reader: whoever
    walks a row's entries walks its index keys under the same page table
    (Backends.index is the reader's name for a latent pool, None for
    a block without an indexer). XLA's gathers the index keys of every
    slot's bucket and scores the copy; attention.latent_index_pallas reads
    a row's live pages of index keys once and returns a float32 score a
    key: the indexer of a decode step 1.36 -> 0.52 ms on one v5e at 17 live
    rows of 32 (PERF.md section 6, PR 37). The choice over the scores
    (model.select_topk) is XLA's under either. A requested
    backend is returned as asked. The writer on one TPU device is
    "in_place" under either reader: both read the row-major pool as it
    lies (XLA's gather takes whole [page, width] blocks by their leading
    indices), so nothing converts the pool, and the commit kernel moves
    rows of any lane-dense width."""
    # What the kernel of this pool can walk: bfloat16 entries, or K and V
    # heads of 128; on one device either way.
    plain = mesh_size == 1 and (quant_kv is None if latent
                                else head_dim == 128)
    reader = attention_backend
    if reader == "auto":
        reader = "pallas" if platform == "tpu" and plain else "xla"
    if latent:
        in_place = platform == "tpu" and plain
    else:
        in_place = reader == "pallas" and plain and quant_kv is None
    return reader, "in_place" if in_place else "scatter"


#: Tokens by which a page-table bucket that XLA gathers whole grows past its
#: first two steps (window_page_bucket).
XLA_BUCKET_TOKENS = 1024


def window_page_bucket(needed: int, reader: str, page_size: int,
                       max_pages: int) -> int:
    """Page-table width of the decode window whose longest row holds
    ``needed`` pages, by who still pays for the bucket: a power of two from
    8 up to ``max_pages``. An XLA gather reads the bucket of EVERY slot
    whatever the rows hold, so its time follows the bucket and not the
    rows: past two steps of XLA_BUCKET_TOKENS such a bucket is a multiple
    of that step, and a step's time follows the longest row within 1,024
    tokens. That is the XLA ``reader`` (``pool_access``'s) of any pool, K
    and V pages or latent entries and their index keys. A Pallas reader
    walks a row's live pages alone and pays nothing for a wide table: its
    buckets stay powers of two (fewer programs). For a latent pool that
    holds since its indexer's scores are the kernel's too: what still
    follows the bucket there is XLA's ``select_topk`` (32 counts over
    [slots, bucket]) and the reader's mask, 0.1 ms of a 14.4 ms step at
    5,120 tokens on one v5e, and ``tpot_p50_ms`` of the latent cell read
    within 0.11 % at steps of 1,024 tokens and at powers of two, with four
    window programs fewer to compile (PERF.md section 6, PR 37 (5)). Where
    XLA's walk served that pool a step was a third longer at powers of two
    from the moment one row passed 4,096 tokens (PERF.md section 6, PR 34).
    Not measured over K and V pages (no cell is on the XLA side there): the
    gather's cost by bucket is PR 26's."""
    b = 8
    while b < needed and b < max_pages:
        b *= 2
    step = max(8, XLA_BUCKET_TOKENS // page_size)
    if reader == "xla" and b > 2 * step:
        b = -(-needed // step) * step
    return min(b, max_pages)


@dataclasses.dataclass
class EngineConfig:
    model: ModelSpec = dataclasses.field(
        default_factory=lambda: PRESETS["tiny-test"])
    # KV paging. page_size: tokens per page (= kv_cache_block_size: the
    # allocator's unit, the prefix cache's hash block, the model card's
    # and the KV router's block, a KV parcel's page). "auto" is resolved
    # to an integer as this object is built (resolve_page_size, from the
    # platform of the process's first device), so every reader sees a
    # number; an explicit integer is kept.
    page_size: int | str = "auto"
    num_pages: int | None = None  # None => size from HBM budget
    hbm_kv_budget_frac: float = 0.6  # fraction of free HBM for KV after params
    # Page-table width of a sequence. None: what holds DEFAULT_MAX_MODEL_LEN
    # tokens at the resolved page, so the context limit (max_model_len)
    # stays in tokens whatever the page (512 pages of 16, 128 of 64).
    max_pages_per_seq: int | None = None
    # Batching
    max_num_seqs: int = 32
    max_prefill_tokens: int = 8192
    prefill_buckets: tuple = (128, 256, 512, 1024, 2048, 4096, 8192)
    # Decode steps per dispatched device program (tokens chain on-device;
    # the host sees sampled tokens once per window). Larger windows amortize
    # dispatch + readback latency at the cost of coarser stop-condition
    # granularity (up to window-1 wasted speculative tokens per finish).
    # "auto" sizes M from the model's weight-read step estimate so the
    # window PERIOD (M x step) lands near DTPU_WINDOW_TARGET_MS (default
    # 75 ms — keeps prefill admission gaps SLA-friendly): a 0.5B model
    # resolves to M=32, an unsharded 8B to M=4, an 8B shard at tp=4 to
    # M=12. The target was swept on other hardware: not measured on this
    # chip (ROADMAP D6; both cells' periods are in PERF.md section 5).
    decode_window: int | str = 8
    # Microbatched pipeline-parallel PREFILL (model.prefill_forward_
    # pipelined): with pp > 1, whole-prompt prefill batches split into pp
    # microbatches flowing through the layer stages concurrently
    # (GPipe-style) instead of every stage idling while one batch
    # traverses the others' layers. Decode and history-chunk prefill keep
    # the layer-sharded path. Requires batch-bucket % pp == 0 to engage.
    pp_microbatch: bool = False
    # Ring attention for the sp axis (model.ring_causal_attention): K/V
    # blocks rotate around the sp ring via neighbor ppermute with an
    # online softmax instead of GSPMD's full K/V all-gather — peak
    # per-device K/V memory during a WHOLE-PROMPT (single-bucket)
    # prefill is one block. History-chunk prefills (prompts longer than
    # the largest bucket) still use the all-gather path, so size
    # prefill_buckets to the long-context target when enabling this.
    # Opt-in; the all-gather path stays the default.
    ring_attention: bool = False
    # Compile the decode-window program and the smallest prefill bucket
    # on the engine thread before serving, so a first short request
    # doesn't pay those XLA compile stalls (larger prefill buckets still
    # compile on first use). Workers enable this; tests skip it to keep
    # CPU suites fast.
    warmup_windows: bool = False
    # Extend warmup to the FULL prefill-bucket ladder including the
    # with-history (chunk) program variants. Without it the first long
    # prompt pays seconds of XLA compile per new bucket while every live
    # decode slot waits. Off by default so small-RAM CPU runs keep warmup
    # cheap; serving workers opt in (--warmup-prefill-ladder).
    warmup_prefill_ladder: bool = False
    # Stall-free chunked prefill (engine scheduler): per engine-loop
    # iteration at most this many prompt tokens are dispatched as prefill
    # chunks before the next decode window, so decode ITL interference
    # from a long prompt is bounded by ~one chunk's compute instead of
    # the whole prompt. "auto" derives the budget from the same
    # DTPU_WINDOW_TARGET_MS model as decode_window="auto" (one chunk ~
    # one window period). Env DTPU_PREFILL_CHUNK_TOKENS overrides either
    # form. On this chip: prompts of 2,049 to 8,192 tokens in chunks cost
    # the Qwen configuration 6 % of time to first token and took a third
    # off the decoders' gap p95 (PERF.md section 6, PR 28 f).
    prefill_chunk_tokens: int | str = "auto"
    # Windows in flight before the host blocks on the oldest readback.
    # Each dispatch/readback pays a host<->device round trip (about 0.45 ms
    # fetch floor on a local v5e, PR 21 chip run); depth D overlaps D of
    # them, so the steady-state window period approaches pure compute.
    # The default predates the local chip and has not been re-derived
    # (ROADMAP D6).
    pipeline_depth: int = 8
    # Parallelism: tp shards heads/FFN (and MoE experts), pp shards the
    # stacked LAYER axis of parameters + KV cache across a "pp" mesh axis
    # (layer-sharded memory distribution; XLA streams each layer's weights
    # to where the activations are — microbatched true pipelining is a
    # future optimization), dp replicates.
    # sp shards the SEQUENCE axis of prefill activations/attention over a
    # mesh axis (all-to-all context parallelism via GSPMD: Q stays
    # sequence-sharded, XLA gathers K/V — the quadratic score term is
    # sp-sharded, which is what makes long-context prefill fit; a ring
    # attention kernel is the bandwidth optimization path). Decode is
    # unaffected (one token per step).
    tp: int = 1
    dp: int = 1
    pp: int = 1
    sp: int = 1
    # Numerics
    dtype: str = "bfloat16"
    # KV-cache quantization (engine/kv_quant.py): None (bf16 pages) or
    # "int8" — paged K/V stored int8 with per-token-per-head f32 scales,
    # dequant fused into the attention reads and quantize fused into the
    # page/window commit scatters. ~1.9x pool compression at head_dim
    # 64–128 => ~2x resident pages per HBM GB, and attention HBM traffic
    # at long context roughly halves. Composes with weight-only
    # ModelSpec.quant. Env DTPU_QUANT_KV overrides ("none" disables).
    quant_kv: str | None = None
    # Decode attention over cache-resident history: "pallas" reads each
    # row's live pages in place (engine/attention.py), "xla" gathers the
    # page-table bucket of every slot first. "auto" is the kernel on one
    # TPU device at head_dim 128 and XLA everywhere else (CPU, any
    # tp/pp/dp/sp mesh, a head_dim under 128, where the kernel's packed
    # view of the pool is a copy of it); backends.choose decides, and
    # runner.attention_backend says what it resolved to.
    attention_backend: str = "auto"
    # KV tiering (reference KVBM G1..G3, block_manager.rs:72-82):
    # host_cache_pages > 0 enables the G2 host-DRAM block cache — pages
    # evicted from HBM are offloaded (async extract overlapping compute)
    # and prefix hits on spilled blocks are onboarded by upload instead of
    # recomputed. kv_disk_cache_dir adds the G3 disk tier behind it.
    host_cache_pages: int = 0
    kv_disk_cache_dir: str | None = None
    disk_cache_pages: int = 4096
    # KVBM placement policy (engine/kvbm.py): with a low watermark set,
    # the engine proactively demotes LRU inactive blocks to the host
    # tier whenever the HBM free list drops below low_watermark of the
    # pool, stopping at high_watermark (hysteresis; 0 = demote only
    # under allocation pressure, the pre-KVBM behavior). Needs
    # host_cache_pages > 0 to have somewhere to demote to. Env
    # DTPU_KV_WATERMARKS="low,high" overrides both.
    kv_demote_low_watermark: float = 0.0
    kv_demote_high_watermark: float = 0.0
    # Speculative decoding (reference SpecDecodeStats protocols.rs:32-56;
    # the reference delegates spec decode to its engines — here the
    # engine IS ours). "ngram" = prompt-lookup self-drafting: the window
    # program matches the sequence's trailing bigram against its own
    # on-device token history, proposes the spec_k tokens that followed
    # the previous occurrence, and VERIFIES them in one multi-token
    # forward — one weight read covers up to spec_k+1 positions, which
    # on an HBM-bound decode is up to a (spec_k+1)x ITL win on
    # repetitive text (summaries, code edits, RAG). "mtp" = the model's
    # own prediction module drafts inside the window program's steps (a
    # model with mtp_layers; runner._get_mtp_window). Either way the
    # verify is rejection sampling on the device for a point-mass
    # drafter: every emitted token is target-distributed, temperature,
    # top-k, top-p and seeds ride in as data, and greedy rows are
    # token-identical to plain decode (runner._get_spec_window). Still
    # refused, by name (engine.generate): penalties under either drafter,
    # logprobs under "ngram". Off by default; plain serving is untouched.
    spec_decode: str | None = None  # None | "ngram" | "mtp"
    spec_k: int = 3                 # drafts verified per step
    # SLA-aware admission (reference pre_deployment_profiling.md:36-38
    # role): with a TTFT budget set, admission projects the time to
    # prefill every already-admitted cold token plus the candidate's
    # (from the measured end-to-end prefill rate, EWMA over batched-
    # prefill readbacks) and defers the candidate in the waiting queue
    # while the projection exceeds the budget. One request is always
    # admissible when nothing else is in flight (a single over-budget
    # prompt must not starve). None disables the limiter.
    ttft_budget_ms: float | None = None
    # With a budget set, generate() additionally raises OverloadedError
    # (HTTP 503 at the frontend; the router retries elsewhere) when the
    # projected TTFT including QUEUED cold tokens exceeds budget x this
    # factor. 0 disables rejection: requests queue unboundedly instead.
    admission_reject_factor: float = 0.0
    # Engine-local brownout (runtime/overload.py has the frontend half):
    # at projected-TTFT pressure level >= this, speculative drafting is
    # suspended for decode windows until pressure drops — the verify
    # step's extra positions are overhead exactly when the engine is
    # behind. 0 disables the hook. Needs ttft_budget_ms to have a
    # pressure signal at all.
    brownout_spec_disable_level: int = 2
    # Multi-tenant batched LoRA (engine/lora.py, ROADMAP item 4): > 0
    # enables the adapter subsystem with this many RESIDENT device
    # adapter slots (slot 0 is always the base model — no delta). All
    # serving programs then add the gathered low-rank correction
    # x @ A[ids] @ B[ids] at every target projection, so HETEROGENEOUS
    # adapters batch into one decode window (the S-LoRA / Punica
    # technique, static-shaped so the jit program count stays fixed).
    # Registered adapters beyond the resident count hot-load on demand
    # with LRU eviction (host copies are always kept). 0 = disabled:
    # programs are byte-identical to the pre-LoRA engine.
    max_adapters: int = 0
    # Per-adapter rank is padded to this fixed max so A/B stacks keep
    # static shapes across heterogeneous adapters (checkpoints with a
    # larger rank are rejected at load).
    lora_max_rank: int = 8
    # Perf plane (engine/perf.py): the roofline fraction this deployment
    # is EXPECTED to achieve in steady-state decode — recorded into the
    # model card's runtime_config.extra and served on /debug/perf, so
    # doctor can WARN when the live perf_roofline_frac regresses > 20%
    # below it. None (default) disables the comparison; env
    # DTPU_EXPECTED_ROOFLINE_FRAC overrides at serving time.
    expected_roofline_frac: float | None = None

    def __post_init__(self):
        if isinstance(self.page_size, str):
            if self.page_size != "auto":
                raise ValueError(f"page_size must be an int or 'auto', "
                                 f"got {self.page_size!r}")
            self.page_size = self.resolve_page_size()
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.max_pages_per_seq is None:
            self.max_pages_per_seq = max(
                1, DEFAULT_MAX_MODEL_LEN // self.page_size)

    @property
    def mesh_size(self) -> int:
        return self.tp * self.pp * self.dp * self.sp

    def resolve_page_size(self, platform: str | None = None) -> int:
        """``page_size="auto"`` as a number of tokens. Where on a TPU the
        Pallas kernel reads the pool and the window commits in place
        (pool_access), a page is the smallest power of two of tokens whose
        ONE strided copy across the KV heads moves PAGE_COPY_BYTES, within
        [DEFAULT_PAGE_SIZE, MAX_PAGE_SIZE]: 64 tokens at 4 KV heads of 128
        in bfloat16 (Qwen2.5-7B, SmallThinker), 32 at 8 (Llama-3-8B), 64 at
        a latent entry of 640 (one copy of 80 KB a page for its reader). The
        kernel issues a copy a live page a layer from a scalar loop, and a
        chunk turn waits for the issue of the next chunk's copies (PERF.md
        section 6, PR 31). Everywhere else DEFAULT_PAGE_SIZE: the XLA
        gather pads every slot to the longest row's page bucket of at
        least 8 pages, so a larger page there is a cost nobody has
        measured. ``platform`` None: that of the process's first device,
        asked only where a TPU would change the answer."""
        m = self.model
        _, writer = pool_access(self.attention_backend, "tpu", self.mesh_size,
                                m.head_dim, self.resolve_quant_kv(),
                                m.latent)
        # Attention over chosen blocks reads a block as part of a page: a
        # page is at least one (ModelSpec.sparse_block; 0 elsewhere).
        least = max(DEFAULT_PAGE_SIZE, m.sparse_block)
        if writer != "in_place":
            return least
        if platform is None:
            import jax
            platform = jax.devices()[0].platform
        if platform != "tpu":
            return least
        heads, widths = m.kv_entry
        copy_bytes = heads * widths[0] * 2  # a token row, bf16
        page = least
        while page < MAX_PAGE_SIZE and page * copy_bytes < PAGE_COPY_BYTES:
            page *= 2
        return page

    def resolve_quant_kv(self) -> str | None:
        """The effective KV-pool quantization mode, with the DTPU_QUANT_KV
        env override applied (same layering as prefill_chunk_tokens)."""
        env = os.environ.get("DTPU_QUANT_KV")
        if env is not None:
            env = env.strip().lower()
            return None if env in ("", "none", "off", "bf16") else env
        return self.quant_kv

    def kvbm_policy(self):
        """The KVBM tier policy for this config (engine/kvbm.py), with
        the DTPU_KV_WATERMARKS="low,high" env override applied (same
        layering as the other engine knobs)."""
        from dynamo_tpu.engine.kvbm import KvbmPolicy
        low, high = (self.kv_demote_low_watermark,
                     self.kv_demote_high_watermark)
        env = os.environ.get("DTPU_KV_WATERMARKS")
        if env:
            parts = [p for p in env.replace(",", " ").split() if p]
            low = float(parts[0])
            high = float(parts[1]) if len(parts) > 1 else 0.0
        return KvbmPolicy(low_watermark=low, high_watermark=high)

    def kv_token_bytes(self) -> int:
        """Per-token bytes in the device KV pool (both arrays, all
        layers/heads, a latent entry's lane padding included: 768 values a
        layer for the 704 it uses): bf16 = 2 bytes/value; int8 = 1
        byte/value + a 4-byte f32 scale per (layer, head, token). The
        single source for pool sizing and the perf plane's HBM KV
        ledger."""
        m = self.model
        heads, widths = m.kv_entry
        if self.resolve_quant_kv() == "int8":
            per_head = sum(w + 4 for w in widths)  # KV_SCALE_BYTES
        else:
            per_head = 2 * sum(widths)
        # (and the third array's share, where the block has one)
        return m.pool_layers * heads * per_head + m.comp_key_bytes_per_token

    def lora_target_shapes(self) -> dict[str, tuple[int, int]]:
        """(d_in, d_out) per LoRA target projection for this model —
        the attention projections always, the dense MLP projections when
        the model is dense (MoE expert weights are not adapter targets:
        PEFT Mixtral checkpoints conventionally target attention only).
        The single source for stack shapes in the runner, the loader's
        padding, and the store's host-side validation."""
        m = self.model
        d = m.head_dim
        shapes = {
            "wq": (m.hidden_size, m.num_heads * d),
            "wk": (m.hidden_size, m.num_kv_heads * d),
            "wv": (m.hidden_size, m.num_kv_heads * d),
            "wo": (m.num_heads * d, m.hidden_size),
        }
        if not m.num_experts:
            shapes["w_gate"] = (m.hidden_size, m.intermediate_size)
            shapes["w_up"] = (m.hidden_size, m.intermediate_size)
            shapes["w_down"] = (m.intermediate_size, m.hidden_size)
        return shapes

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        return self.prefill_buckets[-1]

    def weight_read_ms(self, peaks: DevicePeaks | None) -> float:
        """The time to read the bytes a step reads of the shard's weights
        (ModelSpec.weight_read_step_ms) on the serving device;
        0 where the device has no published peak (the CPU backend), so the
        "auto" sizings below fall back to their host-overhead terms."""
        if peaks is None:
            return 0.0
        return self.model.weight_read_step_ms(peaks.hbm_gbps, self.tp,
                                              self.pp)

    def resolve_decode_window(self, peaks: DevicePeaks | None) -> int:
        """Resolve ``decode_window="auto"`` to a concrete M for the device
        whose ``peaks`` (device_peaks(); None on the CPU backend) are given.

        TPU-first sizing: a decode step is bounded below by reading from
        HBM the bytes a step reads of this shard's weights
        (ModelSpec.step_read_params); the per-dispatch host overhead is
        ~constant. Pick M so the window period M x (step estimate) hits
        DTPU_WINDOW_TARGET_MS — long enough to amortize dispatch, short
        enough that prefill admission between windows keeps p99 TTFT
        inside the SLA (the target: not measured on this chip, ROADMAP
        D6)."""
        if isinstance(self.decode_window, int):
            if self.decode_window < 1:
                raise ValueError(
                    f"decode_window must be >= 1, got {self.decode_window}")
            return self.decode_window
        if self.decode_window != "auto":
            raise ValueError(
                f"decode_window must be an int or 'auto', "
                f"got {self.decode_window!r}")
        target_ms = float(os.environ.get("DTPU_WINDOW_TARGET_MS", "75"))
        step_ms = self.weight_read_ms(peaks) + 1.0  # + host/dispatch
        raw = target_ms / step_ms
        nice = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64)
        return min(nice, key=lambda m: abs(m - raw))

    def resolve_prefill_chunk_tokens(self, peaks: DevicePeaks | None) -> int:
        """Resolve ``prefill_chunk_tokens="auto"`` to a concrete budget
        (``peaks`` as in resolve_decode_window).

        Cost model: a prefill chunk of n tokens costs ~max(1, n/knee)
        weight-read periods — below the knee the chunk is bandwidth-bound
        (one weight read regardless of n), above it compute-bound (linear
        in n). knee ~= the chip's flops/byte ratio (~240 for v5e bf16);
        DTPU_PREFILL_KNEE_TOK overrides per part. The budget is sized so
        one iteration's chunk work costs about one DTPU_WINDOW_TARGET_MS
        window period, then rounded DOWN to a prefill bucket (chunks pad
        to bucket shapes, so a between-buckets budget would pad up and
        overshoot the target)."""
        val = self.prefill_chunk_tokens
        env = os.environ.get("DTPU_PREFILL_CHUNK_TOKENS")
        if env:
            val = env if env.strip() == "auto" else int(env)
        if not isinstance(val, str):
            if val < 1:
                raise ValueError(
                    f"prefill_chunk_tokens must be >= 1, got {val}")
            return max(self.page_size, int(val))
        if val != "auto":
            raise ValueError(
                f"prefill_chunk_tokens must be an int or 'auto', "
                f"got {val!r}")
        target_ms = float(os.environ.get("DTPU_WINDOW_TARGET_MS", "75"))
        step_ms = self.weight_read_ms(peaks)
        knee = float(os.environ.get("DTPU_PREFILL_KNEE_TOK", "256"))
        raw = int(knee * max(1.0, target_ms / max(step_ms, 1e-6)))
        raw = min(raw, self.max_prefill_tokens, self.prefill_buckets[-1])
        fit = [b for b in self.prefill_buckets if b <= raw]
        return max(self.page_size, fit[-1] if fit else raw)

    @property
    def max_model_len(self) -> int:
        return self.max_pages_per_seq * self.page_size
