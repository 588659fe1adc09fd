"""The selecting check of a configuration whose attention CHOOSES BLOCKS of
keys over compressed keys (``ModelSpec.compressed_keys``): ONE prompt longer
than ``sparse_topk`` blocks served through the HTTP path, and the block sets
the served programs chose read back and held to the reference's, a layer.
benchmark/long_prompt.py tells a right choice from none (``select=false``)
by the logprobs alone; this tells WHICH blocks differ. It is to blocks what
benchmark/selection_check.py is to keys (that one hooks ``model.
select_topk``, which this block never calls). A builder's chip run, not a
run the driver makes:

    python3 benchmark/block_selection_check.py --workload <cell> --seed <n> \\
        [--prompt-tokens 6000] [--decode 16] [--rehearse-cpu]

Same server, seams and weight law as benchmark/run.py, the weights drawn
from this tool's own ``--seed`` (run.py serves a cell's ONE draw,
lib/weights.py). The served sets come
from ``engine.hybrid.choose_blocks`` itself: the name is bound, before any
program is traced, to a wrapper that hands what it returns to the host
(``jax.debug.callback``, ordered: a query's layers arrive in the model's
order), so the programs that serve are the programs a cell runs plus that
copy. A layer over chosen blocks prints, for the prompt's queries (the
prefill program) and the decoded ones (the window program) apart, over the
queries that have more blocks than they keep:

  differ_pct        blocks the served set holds and the reference's
                    (``references/<name>.py chosen_blocks``, its own float32
                    forward) does not, of all kept; the sets have one size,
                    so as many are missing;
  wrong_layer_pct   the same against the reference's sets of the NEXT such
                    layer: what a choice from other scores reads (the
                    forced blocks, the first and the window's, agree
                    whatever is scored);
  sizes_ok          every served set holds min(``sparse_topk``, the blocks
                    that exist) blocks.

The FIRST layer reads one input on both sides (the embedding's rows), so
there the sets differ by the served choice's own rounding alone: blocks that
swap sides at the boundary. Deeper layers add what the streams have drifted
apart by. The verdict: what was served inside the reference module's
``ALLOWED_NATS``, every set of the right size, no query without its layers,
and ``differ_pct`` at most ``DIFFER_LIMIT_PCT`` in every layer and under a
third of its ``wrong_layer_pct``. Exits 1 otherwise.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.lib import tokenizer as bench_tok  # noqa: E402

#: Largest share of a layer's kept blocks that may lie outside the
#: reference's sets (PERF.md section 6, PR 45, has the readings on the chip).
DIFFER_LIMIT_PCT = 8.0


class ServedBlocks:
    """Every (n_keys, blocks, kept) ``choose_blocks`` returned while ``on``."""

    def __init__(self):
        self.on = False
        self.records: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []

    def install(self) -> None:
        import jax
        from dynamo_tpu.engine import hybrid
        chooses = hybrid.choose_blocks

        def record(n_keys, blocks, kept):
            if self.on:
                self.records.append((np.asarray(n_keys), np.asarray(blocks),
                                     np.asarray(kept)))

        def choose_blocks(dots, n_keys, spec):
            blocks, kept = chooses(dots, n_keys, spec)
            jax.debug.callback(record, n_keys, blocks, kept, ordered=True)
            return blocks, kept

        hybrid.choose_blocks = choose_blocks

    def take(self) -> list:
        records, self.records = self.records, []
        return records


def served_keeps(records: list, layers: int, tokens: int, prompt_len: int,
                 block: int, nkv: int) -> tuple[np.ndarray, dict]:
    """keeps [layers, tokens, nkv, blocks] bool: the blocks the served
    programs kept for query t (position t, ``n_keys`` t + 1) in the n-th
    layer over chosen blocks. A record is one call of ``choose_blocks``: a
    prefill chunk's queries (n_keys [B, per]: counted for positions inside
    the prompt, its bucket's padding left out) or a window step's (n_keys
    [B]: counted from the prompt's end on, so dead slots are left out). The
    n-th record that holds query t is layer n's."""
    nb = -(-tokens // block)
    keeps = np.zeros((layers, tokens, nkv, nb), bool)
    seen = np.zeros(tokens, int)
    extra = 0
    for n_keys, blocks, kept in records:
        prefill = n_keys.ndim == 2
        n_keys = n_keys.reshape(-1)
        blocks = blocks.reshape(len(n_keys), nkv, -1)
        kept = kept.reshape(blocks.shape)
        placed = set()
        for i, t in enumerate(n_keys - 1):
            inside = 0 <= t < prompt_len if prefill \
                else prompt_len <= t < tokens
            if not inside or t in placed:
                continue
            placed.add(t)
            if seen[t] >= layers:
                extra += 1
                continue
            for g in range(nkv):
                ids = blocks[i, g][kept[i, g]]
                keeps[seen[t], t, g, ids[ids < nb]] = True
            seen[t] += 1
    return keeps, {"queries": int(tokens),
                   "queries_missing_a_layer": int((seen != layers).sum()),
                   "records_beyond_the_layers": extra}


def set_distance(served: np.ndarray, theirs: np.ndarray,
                 other: np.ndarray, rows: np.ndarray, topk: int,
                 block: int) -> dict:
    """One layer's lines of the module's docstring over the queries
    ``rows``: served, theirs, other [tokens, nkv, blocks] bool."""
    exist = np.minimum(rows // block + 1, topk)
    sizes = served[rows].sum(-1)
    kept = int(sizes.sum())
    return {"queries": len(rows),
            "differ_pct": 100.0 * int((served[rows] & ~theirs[rows]).sum())
            / max(kept, 1),
            "wrong_layer_pct": 100.0 * int((served[rows]
                                            & ~other[rows]).sum())
            / max(kept, 1),
            "sizes_ok": bool((sizes == exist[:, None]).all())}


async def check(args, files: dict) -> dict:
    from benchmark.lib import reference, server
    config = files["config"]
    name = files["cell"]["config"]
    judged = reference.for_config(config)
    module = manifest.load_module("references", config["reference"])
    os.makedirs(manifest.RUN_DIR, exist_ok=True)
    spec = server.model_spec(name, config, config.get("launch", {}).get(
        "quant"))
    if not getattr(spec, "compressed_keys", False):
        raise SystemExit(f"{name} chooses no blocks of keys")
    tok_path = bench_tok.write_tokenizer(os.path.join(
        manifest.RUN_DIR, f"tokenizer-{spec.vocab_size}.json"),
        spec.vocab_size)
    shapes = server.WarmShapes(
        max_prompt=args.prompt_tokens,
        max_context=args.prompt_tokens + args.decode, max_batch=1)
    seams = server.Seams(name, spec, args.seed, shapes)
    seams.install()
    sets = ServedBlocks()
    sets.install()
    block, topk, nkv = spec.sparse_block, spec.sparse_topk, spec.num_kv_heads
    layers = spec.layer_pattern.count("S")
    lines = []

    def telling(params, spec, prompt, tokens):
        # check_logprobs calls this once what was served is in.
        size = len(prompt) + len(tokens) - 1
        keeps, coverage = served_keeps(sets.take(), layers, size,
                                       len(prompt), block, nkv)
        run.emit("served_sets", seed=args.seed, **coverage)
        theirs = module.chosen_blocks(params, spec,
                                      list(prompt) + list(tokens[:-1]))
        choosing = np.arange(size)[np.arange(size) // block + 1 > topk]
        for n in range(layers):
            line = {"layer": n, **{
                path: set_distance(keeps[n], theirs[n],
                                   theirs[(n + 1) % layers], rows, topk,
                                   block)
                for path, rows in (
                    ("prefill", choosing[choosing < len(prompt)]),
                    ("window", choosing[choosing >= len(prompt)]))
                if len(rows)}}
            run.emit("layer", seed=args.seed, **line)
            lines.append(line)
        lines.append(coverage)
        return module.reference_logprobs(params, spec, prompt, tokens)

    try:
        argv = run.launch_argv(name, config, tok_path)
        async with server.Server(argv) as srv:
            eng = srv.engine
            overhead = bench_tok.template_overhead(tok_path,
                                                   srv.chat_template)
            run.emit("server", startup_s=srv.startup_s,
                     timings=seams.timings, decode_window=eng.decode_window,
                     prefill_chunk_tokens=eng.prefill_chunk_tokens,
                     sparse_topk=topk, sparse_block=block)
            sets.on = True
            checked = await run.check_logprobs(
                srv, {**judged, "logprobs": telling}, args.seed, overhead,
                spec.vocab_size, prompts=1,
                prompt_tokens=args.prompt_tokens, n_gen=args.decode)
            sets.on = False
    finally:
        seams.restore()
    *per_layer, coverage = lines
    paths = [line[path] for line in per_layer
             for path in ("prefill", "window") if path in line]
    sets_ok = bool(paths) and coverage["queries_missing_a_layer"] == 0 \
        and all(p["sizes_ok"] and p["differ_pct"] <= DIFFER_LIMIT_PCT
                and 3 * p["differ_pct"] < p["wrong_layer_pct"]
                for p in paths)
    return {"seed": args.seed, "served_ok": bool(checked["ok"]),
            "sets_ok": sets_ok,
            "differ_pct_max": max((p["differ_pct"] for p in paths),
                                  default=None),
            "wrong_layer_pct_min": min((p["wrong_layer_pct"] for p in paths),
                                       default=None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt-tokens", type=int, default=6000)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    files = manifest.cell_files(manifest.load_manifest(), args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        files = run.rehearsal_cut(files)
    os.environ.setdefault("DTPU_FLIGHT_DIR",
                          os.path.join(manifest.RUN_DIR, "flight"))
    verdict = asyncio.run(check(args, files))
    verdict["ok"] = verdict["served_ok"] and verdict["sets_ok"]
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
