"""Operations and bytes of one decode step, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change what a
kernel's share of its roofline is measured against. A model here is the
configuration file's dictionary (Hugging Face key names).

One decode step of a batch must at least
  * read every weight of this chip's shard once, as stored (int8 values and
    their float32 scales, or bf16), except the embedding table, of which it
    reads one row per sequence;
  * read the K and V of every live token of every sequence (this chip's
    share of the KV heads) and write one token's worth per sequence;
  * do 2 floating-point operations per weight per sequence, and 4 per live
    token per head dimension for attention.
The least time is the larger of bytes / peak bandwidth and operations /
peak rate; ``decode_step_floor`` says which. A block with routed experts
must read an expert only if some row chose it: ``decode_step_floor`` takes
the program's own count of experts a layer-step touched and hands it to the
configuration's counting module where that takes one (``takes_touched``);
this module, the dense block's, takes none.
"""

from __future__ import annotations

import inspect
import sys

from benchmark.lib import manifest


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def matmul_weights(cfg: dict) -> dict[str, tuple[int, int]]:
    """(elements, output channels) of every matrix a decode step reads in
    full: per layer and the output head. Biases and norms are separate."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d = _head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = {"wq": (h * nh * d, nh * d), "wk": (h * nkv * d, nkv * d),
                 "wv": (h * nkv * d, nkv * d), "wo": (nh * d * h, h),
                 "w_gate": (h * i, i), "w_up": (h * i, i),
                 "w_down": (i * h, h)}
    return {"per_layer": per_layer,
            "head": (h * cfg["vocab_size"], cfg["vocab_size"])}


def weight_bytes_per_step(cfg: dict, quant: str | None, tp: int = 1,
                          rows: int = 1) -> float:
    """Bytes of weights one decode step reads on ONE chip of a tp group."""
    h = cfg["hidden_size"]
    d = _head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = cfg["num_hidden_layers"]
    per_value = 1 if quant == "int8" else 2
    mats = matmul_weights(cfg)

    def stored(elements: int, channels: int) -> float:
        scales = 4 * channels if quant == "int8" else 0
        return elements * per_value + scales

    per_layer = sum(stored(*m) for m in mats["per_layer"].values())
    small = 2 * h * 2  # two norm vectors, bf16, replicated
    if cfg.get("qkv_bias", cfg.get("model_type") == "qwen2"):
        small += (nh + 2 * nkv) * d * 2 / tp
    total = layers * (per_layer / tp + small)
    if not cfg.get("tie_word_embeddings", False):
        total += stored(*mats["head"]) / tp
    else:
        total += stored(h * cfg["vocab_size"], h) / tp
    total += h * 2  # final norm
    # Embedding gather: one row per sequence, this chip's columns.
    total += rows * h * per_value / tp
    return total


def kv_bytes_per_token(cfg: dict, tp: int = 1, kv_value_bytes: int = 2
                       ) -> float:
    """K and V bytes of one token on one chip (all layers, its KV heads)."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * _head_dim(cfg) * kv_value_bytes / tp)


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float) -> float:
    """Bytes one decode step must move on one chip: the shard's weights, the
    K and V of ``context_tokens`` live tokens (summed over the batch's
    sequences), and ``rows`` new tokens of K and V written."""
    kv = kv_bytes_per_token(cfg, tp)
    return (weight_bytes_per_step(cfg, quant, tp, max(1, round(rows)))
            + (context_tokens + rows) * kv)


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    """Floating-point operations of one decode step on one chip."""
    mats = matmul_weights(cfg)
    weights = (cfg["num_hidden_layers"]
               * sum(e for e, _ in mats["per_layer"].values())
               + mats["head"][0])
    attn = (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * _head_dim(cfg) * context_tokens)
    return (2 * weights * rows + attn) / tp


def counting(cfg: dict, root: str = manifest.ROOT) -> tuple:
    """The module that counts a configuration's decode step, and where it
    is: the one its file names (``"roofline": "<name>"`` ->
    ``benchmark/rooflines/<name>.py`` with ``decode_step_bytes`` and
    ``decode_step_flops`` of the signatures above, one file per block
    kind; a routed block's ``decode_step_bytes`` may take ``touched=None``
    besides), else this module, the dense block's. A name without its file is
    a ManifestError."""
    return manifest.config_module(
        cfg, "roofline", sys.modules[__name__],
        ("decode_step_bytes", "decode_step_flops"), root)


def takes_touched(counts) -> bool:
    """Whether a counting module's ``decode_step_bytes`` takes the count of
    experts a layer-step touched (a ``touched`` parameter): the routed
    modules do, the dense block's and the unrouted ones take none."""
    return "touched" in inspect.signature(counts.decode_step_bytes).parameters


def decode_step_floor(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float, peaks: dict,
                      root: str = manifest.ROOT,
                      touched: float | None = None) -> dict:
    """The least seconds one decode step can take on one chip, which bound
    gives it, and which module counted (``counting``). ``touched`` is the
    mean number of distinct HELD experts one expert layer's live rows chose
    in one decode step (the program's own count, ``moe_roofline``'s unit);
    it reaches the counting module only where its ``decode_step_bytes``
    takes it (``takes_touched``), and ``experts_touched`` says what was
    used: the count, or None (every held expert, or no expert layer)."""
    counts, where = counting(cfg, root)
    used = touched if touched is not None and takes_touched(counts) else None
    more = {} if used is None else {"touched": used}
    t_bytes = (counts.decode_step_bytes(cfg, quant, tp, rows, context_tokens,
                                        **more)
               / (peaks["hbm_gbps"] * 1e9))
    t_flops = (counts.decode_step_flops(cfg, tp, rows, context_tokens)
               / (peaks["bf16_tflops"] * 1e12))
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bandwidth" if t_bytes >= t_flops else "compute",
            "bytes_seconds": t_bytes, "flops_seconds": t_flops,
            "counted_by": where, "experts_touched": used}


def peaks_of(device_kind: str, table: dict) -> dict:
    """The row of peaks.json for ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table['devices'])}); "
            f"add its row with the source") from None
