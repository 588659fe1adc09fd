"""Who runs what: the ONE record of a runner's choice of kernels.

A forward program (engine/model.py, engine/hybrid.py) differs by runner in a
handful of facts: who reads the pool in decode, who scores a latent pool's
index keys or a compressed-key array's stripes, how the window writes the
pool, who updates a recurrent state, whether the experts are whole on one
device and whether a kernel is interpreted. ``choose`` decides them once a
runner, from what the runner observes and nothing else; the programs take
the record as ONE parameter (``backends``), ask it for the callables it
binds and for nothing else; and ``labels`` is the one place the names a
program is published under (perf.instrumented_jit's ``labels``,
/debug/perf, the ``*_info`` gauges) are assembled. The imports run config
<- backends <- model / hybrid <- runner; the Pallas kernels
(engine/attention.py) are imported where they are bound.
"""

from __future__ import annotations

import dataclasses
import functools

from dynamo_tpu.engine.config import (DEFAULT_PAGE_SIZE, EngineConfig,
                                      ModelSpec, pool_access)

#: What /debug/perf and the programs' labels say of prefix reuse for a block
#: with recurrent layers (engine.TPUEngine._plan_prefill takes no cached page).
PREFIX_REUSE_OFF = "off (recurrent state has no snapshot)"
#: What the recurrent state S is kept in: the configuration's choice (the
#: model card asks engines for a float32 state cache), not an option.
SSM_STATE_DTYPE = "float32"


@dataclasses.dataclass(frozen=True)
class Backends:
    """Plain facts, each two-valued or None; hashable. The default is XLA's
    choice on any platform: what a caller that names no record gets (the
    references, a test of the plain forward)."""
    #: Who reads the pool in decode: "pallas" | "xla".
    attention: str = "xla"
    #: Who scores, in decode, what a query chooses its keys by: a latent
    #: pool's index keys, or the compressed-key array of a block that
    #: attends chosen blocks (the reader's name: whoever walks a row's
    #: pages walks these under the same page table); None: a block whose
    #: queries choose nothing.
    index: str | None = None
    #: The chunk turn the K-and-V reader's kernel takes where it is not a
    #: KV head at a time: "heads" (attention.reader_turn, the ONE rule, on
    #: the reader's shapes); None: that turn, or no such kernel.
    kv_reader_turn: str | None = None
    #: How the decode window writes its tokens into the pool: "in_place"
    #: (attention.commit_window_pallas) | "scatter".
    kv_commit: str = "scatter"
    #: Who updates a recurrent layer's float32 state in a decode step:
    #: "kernel" (engine/recurrence.py) | "xla" (hybrid.ssm_step); None: a
    #: block without such layers.
    ssm: str | None = None
    #: The block's experts are whole on one device: a long batch's rows may
    #: go to their own experts (the kernel of engine/experts.py, which GSPMD
    #: cannot partition). On any mesh: the masked product.
    experts_whole: bool = False
    #: Every kernel above is interpreted: the CPU backend's only way to run
    #: one. A chip compiles a kernel through Mosaic or fails.
    interpret: bool = False
    #: The latent readers' static page-table width (max_pages_per_seq).
    table: int | None = None
    #: What ``labels`` says beside the choices: the block has expert layers,
    #: tokens a KV page holds, who drafts inside the window's steps.
    routed: bool = False
    page_size: int = DEFAULT_PAGE_SIZE
    draft: str = "none"

    # -- the callables the record binds -----------------------------------
    def _bound(self, name: str, latent: bool = False):
        from dynamo_tpu.engine import attention
        kw = {"interpret": self.interpret}
        if latent:
            kw["table"] = self.table
        return functools.partial(getattr(attention, name), **kw)

    def kv_reader(self, window: bool):
        """Who attends a pool of K and V pages, in a window's step or in
        the single decode step: the bound kernel, or None for XLA's gather
        (model.kv_attention has it)."""
        if self.attention != "pallas":
            return None
        return self._bound("paged_window_attention_pallas" if window
                           else "paged_decode_attention_pallas")

    def latent_readers(self) -> tuple:
        """(reader, indexer) of a latent pool, one pair for the step and
        the window: the kernel that walks a row's live entries with the
        indexer's choice as its mask and the one that scores its index
        keys; (None, None) for XLA's walk of both arrays."""
        if self.attention != "pallas":
            return None, None
        return (self._bound("latent_history_pallas", latent=True),
                self._bound("latent_index_pallas", latent=True))

    def stripe_scorer(self):
        """Who scores the stripes a row holds in the compressed-key array
        in a window's step (hybrid.sparse_window_attention): the kernel
        that walks a live row's pages of the array, or None for XLA's
        gather of every slot's bucket (hybrid.pool_stripes)."""
        if self.attention != "pallas":
            return None
        return self._bound("stripe_scores_pallas")

    def block_reader(self):
        """A latent block without an indexer, S query positions a slot (the
        drafting window's verify step and its module): the reader without
        a mask operand, or None for XLA's gather."""
        if self.attention != "pallas":
            return None
        return self._bound("latent_block_pallas", latent=True)

    # -- the names a program is published under ---------------------------
    def labels(self, program: str, expert_product: str | None = None
               ) -> dict:
        """The labels of a ``program`` family ("prefill" |
        "decode_window") whose expert layers take ``expert_product``
        (model.expert_product of the rows they multiply at once; None: say
        nothing of it). The ONLY place they are assembled:
        runner._get_prefill and _get_window hand them to
        perf.instrumented_jit, engine.perf_status and perf's ``*_info``
        gauges read the window's."""
        out = {}
        if program == "decode_window":
            out = {"attention_backend": self.attention,
                   "kv_commit_backend": self.kv_commit,
                   "page_size": self.page_size,
                   **({"index_backend": self.index} if self.index else {}),
                   **({"kv_reader_turn": self.kv_reader_turn}
                      if self.kv_reader_turn else {}),
                   # Who drafts inside this program's steps.
                   "draft": self.draft}
        if self.routed and expert_product is not None:
            out["expert_product"] = expert_product
        if self.ssm is not None:
            # What the state is kept in, and that a prompt's pages are
            # never reused (a page's border has no state to continue from).
            out.update(ssm_state=SSM_STATE_DTYPE,
                       prefix_reuse=PREFIX_REUSE_OFF)
            if program == "decode_window":
                # Who updates a recurrent layer's state in a step.
                out["ssm_backend"] = self.ssm
        return out


#: XLA's choice on any platform: the forward programs' default.
XLA = Backends()


def pallas_refusal(spec: ModelSpec, page_size: int, mesh_size: int,
                   quant_kv: str | None) -> str | None:
    """Why no Pallas kernel can read this model's pool on this mesh, or
    None: what "pallas" raises with."""
    d = spec.head_dim
    if spec.latent:
        # attention.latent_history_pallas: an entry is key and value,
        # whatever the heads' own widths.
        if quant_kv is not None:
            return ("walks a latent pool of bfloat16 entries; no kernel "
                    "reads int8 latent pages (an entry's scales would "
                    "be a third array under the page table)")
    elif not (d == 128 or (d < 128 and 128 % d == 0
                           and (page_size * d) % 128 == 0)):
        return (f"needs head_dim 128, or a head_dim that packs into 128 "
                f"lanes (128 % head_dim == 0 and page_size*head_dim % "
                f"128 == 0); got head_dim {d}, page_size {page_size}")
    if mesh_size > 1:
        return ("runs on one device: the kernel has no partitioning "
                "rule, so a tp/pp/dp/sp mesh would gather the whole KV "
                "pool around it")
    return None


def choose(config: EngineConfig, spec: ModelSpec, platform: str,
           mesh_size: int, quant_kv: str | None) -> Backends:
    """The record of a runner, decided once from what the runner observes
    and nothing else: the requested ``attention_backend``, its device's
    platform, its mesh's size, the spec and ``quant_kv``. The ONE function
    that decides a kernel from a platform (config.pool_access is its inner
    rule for the reader and the writer, and has the measurements;
    EngineConfig.resolve_page_size asks the same rule with the
    configuration's platform).

    A requested backend is what runs: "pallas" that cannot be had is an
    error, never XLA. Beside the reader: the indexer's scores follow it,
    and so do the scores over a compressed-key array;
    the recurrence's kernel runs where the Pallas reader runs on one TPU
    device (it visits the live slots where the stack lies and reads a state
    once) and XLA's ``hybrid.ssm_step`` everywhere else: the CPU backend,
    which would interpret the kernel; a mesh, which never has the Pallas
    reader and is refused for such a block (config.block_refusals); and a
    runner asked for the XLA reader, which is XLA's throughout
    (chip_smoke.py compares the two on the chip). The K-and-V kernel's
    chunk turn is attention.reader_turn's, asked with the spec's heads. The
    experts are whole on a mesh of one device. Interpret mode exists for the CPU backend only."""
    reader, writer = pool_access(config.attention_backend, platform,
                                 mesh_size, spec.head_dim, quant_kv,
                                 spec.latent)
    if reader not in ("xla", "pallas"):
        raise ValueError(f"attention_backend must be 'auto', 'xla' or "
                         f"'pallas', got {reader!r}")
    if reader == "pallas":
        refusal = pallas_refusal(spec, config.page_size, mesh_size, quant_kv)
        if refusal is not None:
            raise ValueError(f"attention_backend='pallas' {refusal}")
    heads_turn = False
    if reader == "pallas" and not spec.latent:
        from dynamo_tpu.engine.attention import reader_turn
        heads_turn = reader_turn(
            spec.num_heads // spec.num_kv_heads, spec.num_kv_heads,
            max(1, 128 // spec.head_dim), quant_kv is not None) == "heads"
    ssm = None
    if spec.recurrent:
        ssm = "kernel" if reader == "pallas" and platform == "tpu" else "xla"
    return Backends(
        attention=reader,
        kv_reader_turn="heads" if heads_turn else None,
        index=reader if (spec.latent and spec.index_topk
                         or spec.compressed_keys) else None,
        kv_commit=writer, ssm=ssm, experts_whole=mesh_size == 1,
        interpret=platform == "cpu",
        table=config.max_pages_per_seq if spec.latent else None,
        routed=bool(spec.num_experts), page_size=config.page_size,
        draft="mtp" if config.spec_decode == "mtp" else "none")
