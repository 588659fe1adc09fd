"""The served path of the looped cell against its reference over many seeds
in ONE process (PR 48; PERF.md section 6): a builder's chip run, not a run
the driver makes.

    python3 scripts/ouro_ref_seeds.py <seed,seed,...> [control,control,...]

For each seed: the harness's device-made weights (lib/weights.py), then the
check's shape through the RUNNER (4 prompts of 64 tokens; the prefill
program, then four window programs of 4 steps through the paged pool: on a
chip the Pallas reader and the in-place commit), the served logprobs
against benchmark/references/ouro.py and against each control
(``skip_layer=47``, ``skip_pass=3``, ``precision=float8_e4m3fn``, ...), one
JSON line a seed: lib/reference.py's statistics of served against the
reference (``served``), served against a control (``<control>``) and the
reference against it (``<control>|ref``). Five seconds a seed without
controls on one v5e: how the tail of a check is read before the driver
draws its own seeds.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import manifest, reference, server, weights  # noqa: E402
from dynamo_tpu.engine.config import EngineConfig  # noqa: E402
from dynamo_tpu.engine.runner import (PK_CAP, PK_LOGPROB, PK_POS,  # noqa: E402
                                      PK_PREFIX, PK_SEQLEN, PK_TOPP,
                                      ModelRunner, PrefillSeq)

CELL = "ouro-2.6b.reasoning-1k"
PROMPTS, PROMPT_TOKENS, DECODED, WINDOW = 4, 64, 16, 4


def window(runner, pos: int, pages: list[int]):
    """One window of WINDOW steps for slot 0 at ``pos``: (tokens, logprobs)."""
    page = runner.config.page_size
    packed = np.zeros((runner.config.max_num_seqs, PK_PREFIX + 8), np.int32)
    packed[:, PK_TOPP] = np.float32(1.0).view(np.int32)
    packed[0, PK_POS], packed[0, PK_SEQLEN] = pos, pos + 1
    packed[0, PK_CAP], packed[0, PK_LOGPROB] = len(pages) * page, 1
    packed[0, PK_PREFIX:PK_PREFIX + len(pages)] = pages
    toks, lps, *_ = runner.decode_window(packed, WINDOW)
    return np.asarray(toks)[:, 0].tolist(), np.asarray(lps)[:, 0].tolist()


def main(argv: list[str], cell: str = CELL, num_pages: int = 339) -> int:
    """``cell``'s check over the seeds of ``argv`` through a runner whose
    pool has ``num_pages`` pages (scripts/solar_ref_seeds.py: another
    cell's)."""
    seeds = [int(s) for s in argv[0].split(",")]
    controls = argv[1].split(",") if len(argv) > 1 else []
    files = manifest.cell_files(manifest.load_manifest(), cell)
    spec = server.model_spec(files["cell"]["config"], files["config"],
                             files["config"]["launch"]["quant"])
    ref = manifest.load_module("references", files["config"]["reference"])
    config = EngineConfig(model=spec, page_size="auto", num_pages=num_pages,
                          max_num_seqs=4, decode_window=WINDOW)
    mesh = weights.runner_mesh(config)
    per_prompt = -(-(PROMPT_TOKENS + DECODED) // config.page_size) + 1
    runner = None
    params = None
    for seed in seeds:
        t0 = time.time()
        if runner is not None:      # a share of 9.5 GB does not fit twice
            runner.params = params = None
        params = weights.make_params(spec, mesh, seed)
        if runner is None:
            runner = ModelRunner(config, params=params)
        else:
            runner.params = params
        rng = np.random.default_rng(seed)
        served, full, wrong = [], [], {c: [] for c in controls}
        for k in range(PROMPTS):
            prompt = rng.integers(2, spec.vocab_size,
                                  size=PROMPT_TOKENS).tolist()
            pages = np.arange(1 + per_prompt * k, 1 + per_prompt * (k + 1),
                              dtype=np.int32)
            runner.prefill_batch([PrefillSeq(
                tokens=np.asarray(prompt, np.int32), start_pos=0,
                chunk_pages=pages[:PROMPT_TOKENS // config.page_size],
                hist_pages=None, sampling=(0.0, 0, 1.0))], slots=[0])
            first = int(np.asarray(runner.tokens_dev)[0])
            logits = runner.last_prefill_logits[0].astype(jnp.float32)
            toks, lps = [first], [float(jax.nn.log_softmax(logits)[first])]
            for pos in range(PROMPT_TOKENS, PROMPT_TOKENS + DECODED, WINDOW):
                t, lp = window(runner, pos, pages.tolist())
                toks += t
                lps += lp
            toks, lps = toks[:DECODED], lps[:DECODED]
            served += lps
            full += ref.reference_logprobs(params, spec, prompt, toks)
            for c in controls:
                key, _, value = c.partition("=")
                wrong[c] += ref.control_logprobs(
                    params, spec, prompt, toks,
                    **{key: {"false": False}.get(value, value)})
        out = {"seed": seed, "served": reference.diff_stats(served, full),
               "ok": reference.judge(served, full)["ok"]}
        for c in controls:
            out[c] = reference.diff_stats(served, wrong[c])
            out[c + "|ref"] = reference.diff_stats(full, wrong[c])
        out["seconds"] = round(time.time() - t0, 1)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
