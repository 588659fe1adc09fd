"""HF safetensors -> dynamo_tpu parameter loading.

Maps HF Llama/Qwen2 checkpoint names onto the stacked scan-over-layers pytree
(model.py param_shapes). Loads on host CPU; the runner shards onto the mesh.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np

from dynamo_tpu.engine.config import ModelSpec, block_refusals
from dynamo_tpu.runtime.logging import get_logger

log = get_logger("weights")


def load_hf_weights(spec: ModelSpec, model_dir: str):
    """Load *.safetensors from ``model_dir`` into our param pytree (numpy,
    bf16 via ml_dtypes)."""
    import ml_dtypes
    from safetensors import safe_open

    for refusal in block_refusals(spec, checkpoint=True):
        raise refusal
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no safetensors under {model_dir}")
    tensors: dict[str, np.ndarray] = {}
    wanted_prefixes = ("model.", "lm_head.")
    for path in files:
        with safe_open(path, framework="numpy") as fh:
            for name in fh.keys():
                if name.startswith(wanted_prefixes):
                    tensors[name] = fh.get_tensor(name)

    bf16 = ml_dtypes.bfloat16

    def get(name: str) -> np.ndarray:
        if name not in tensors:
            raise KeyError(f"missing tensor {name}")
        return tensors[name].astype(bf16)

    L = spec.num_layers
    names = ["input_norm", "post_attn_norm", "wq", "wk", "wv", "wo"]
    if spec.num_experts:
        names += ["moe_gate", "moe_w_gate", "moe_w_up", "moe_w_down"]
    else:
        names += ["w_gate", "w_up", "w_down"]
    layers: dict[str, list] = {k: [] for k in names}
    if spec.qkv_bias:
        for k in ("bq", "bk", "bv"):
            layers[k] = []
    for i in range(L):
        p = f"model.layers.{i}."
        layers["input_norm"].append(get(p + "input_layernorm.weight"))
        layers["post_attn_norm"].append(
            get(p + "post_attention_layernorm.weight"))
        # HF linear weights are [out, in]; ours are [in, out].
        layers["wq"].append(get(p + "self_attn.q_proj.weight").T)
        layers["wk"].append(get(p + "self_attn.k_proj.weight").T)
        layers["wv"].append(get(p + "self_attn.v_proj.weight").T)
        layers["wo"].append(get(p + "self_attn.o_proj.weight").T)
        if spec.num_experts:
            # Mixtral: block_sparse_moe.gate + experts.N.{w1,w3,w2} =
            # (gate_proj, up_proj, down_proj).
            m = p + "block_sparse_moe."
            layers["moe_gate"].append(get(m + "gate.weight").T)
            layers["moe_w_gate"].append(np.stack(
                [get(f"{m}experts.{e}.w1.weight").T
                 for e in range(spec.num_experts)]))
            layers["moe_w_up"].append(np.stack(
                [get(f"{m}experts.{e}.w3.weight").T
                 for e in range(spec.num_experts)]))
            layers["moe_w_down"].append(np.stack(
                [get(f"{m}experts.{e}.w2.weight").T
                 for e in range(spec.num_experts)]))
        else:
            layers["w_gate"].append(get(p + "mlp.gate_proj.weight").T)
            layers["w_up"].append(get(p + "mlp.up_proj.weight").T)
            layers["w_down"].append(get(p + "mlp.down_proj.weight").T)
        if spec.qkv_bias:
            layers["bq"].append(get(p + "self_attn.q_proj.bias"))
            layers["bk"].append(get(p + "self_attn.k_proj.bias"))
            layers["bv"].append(get(p + "self_attn.v_proj.bias"))
    params = {
        "embed": get("model.embed_tokens.weight"),
        "final_norm": get("model.norm.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items()},
    }
    if not spec.tie_word_embeddings:
        params["lm_head"] = get("lm_head.weight").T
    log.info("loaded %d tensors from %s", len(tensors), model_dir)
    return params


def load_lora_weights(spec: ModelSpec, adapter_dir: str, max_rank: int):
    """Load a HF PEFT LoRA checkpoint into stacked per-projection pairs.

    Reads ``adapter_config.json`` (r, lora_alpha, target_modules) and
    ``adapter_model.safetensors`` from ``adapter_dir`` and returns
    ``{key: (A [L, d_in, max_rank], B [L, max_rank, d_out])}`` numpy
    bf16 pytrees over the projections the checkpoint targets (subset of
    wq/wk/wv/wo + dense MLP). PEFT stores ``lora_A.weight`` as [r, in]
    and ``lora_B.weight`` as [out, r]; ours are the transposes, with the
    ``lora_alpha / r`` scale folded into B so serving pays no extra
    multiply. Ranks below ``max_rank`` zero-pad — padded columns
    contribute exact zeros, so heterogeneous-rank adapters share one
    static stack shape. Layers or projections the checkpoint does not
    cover stay zero (no delta).
    """
    import ml_dtypes
    from safetensors import safe_open

    cfg_path = os.path.join(adapter_dir, "adapter_config.json")
    # dtpu: ignore[blocking-call-in-async] -- adapter-load startup/hot-load I/O, engine-thread or CLI, never the serving loop
    with open(cfg_path) as fh:
        cfg = json.load(fh)
    rank = int(cfg.get("r", 8))
    alpha = float(cfg.get("lora_alpha", rank))
    if rank > max_rank:
        raise ValueError(
            f"adapter rank {rank} exceeds lora_max_rank {max_rank} "
            f"({adapter_dir}); raise --max-lora-rank or re-train smaller")
    scale = alpha / max(1, rank)

    files = sorted(glob.glob(os.path.join(adapter_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no safetensors under {adapter_dir}")
    tensors: dict[str, np.ndarray] = {}
    for path in files:
        with safe_open(path, framework="numpy") as fh:
            for name in fh.keys():
                tensors[name] = fh.get_tensor(name)

    # HF module suffix -> our stacked projection key.
    proj_of = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
               "o_proj": "wo", "gate_proj": "w_gate", "up_proj": "w_up",
               "down_proj": "w_down"}
    if spec.num_experts:
        for k in ("gate_proj", "up_proj", "down_proj"):
            proj_of.pop(k)
    L = spec.num_layers
    bf16 = ml_dtypes.bfloat16
    found: dict[str, dict[int, tuple[np.ndarray, np.ndarray]]] = {}
    for name, arr in tensors.items():
        # base_model.model.model.layers.{i}.self_attn.q_proj.lora_A.weight
        parts = name.split(".")
        if "layers" not in parts or "weight" != parts[-1]:
            continue
        li = int(parts[parts.index("layers") + 1])
        module = parts[-3]
        kind = parts[-2]  # lora_A | lora_B
        key = proj_of.get(module)
        if key is None or kind not in ("lora_A", "lora_B") or li >= L:
            continue
        a, b = found.setdefault(key, {}).get(li, (None, None))
        if kind == "lora_A":
            a = arr
        else:
            b = arr
        found[key][li] = (a, b)
    if not found:
        raise ValueError(
            f"{adapter_dir}: no LoRA tensors matched the target "
            f"projections {sorted(proj_of.values())}")

    out: dict[str, tuple[np.ndarray, np.ndarray]] = {}
    for key, per_layer in found.items():
        # d_in/d_out from the checkpoint itself (validated against the
        # model by the AdapterStore at registration).
        li0 = next(iter(per_layer))
        a0, b0 = per_layer[li0]
        d_in = a0.shape[1]
        d_out = b0.shape[0]
        A = np.zeros((L, d_in, max_rank), bf16)
        B = np.zeros((L, max_rank, d_out), bf16)
        for li, (a, b) in per_layer.items():
            if a is None or b is None:
                raise ValueError(
                    f"{adapter_dir}: layer {li} {key} has only one of "
                    f"lora_A/lora_B")
            r = a.shape[0]
            A[li, :, :r] = a.astype(np.float32).T.astype(bf16)
            B[li, :r, :] = (b.astype(np.float32).T * scale).astype(bf16)
        out[key] = (A, B)
    log.info("loaded LoRA adapter from %s: rank %d (padded to %d), "
             "targets %s", adapter_dir, rank, max_rank, sorted(out))
    return out
