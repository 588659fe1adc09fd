"""The selecting check of a configuration whose attention CHOOSES its keys
(a learned indexer): ONE prompt longer than ``index_topk`` served through
the HTTP path, and the sets the served programs chose read back and held to
the reference's. benchmark/long_prompt.py tells a right selection from none
(``select=false``) by the logprobs alone; this tells WHICH keys differ and
what they cost. A builder's chip run, not a run the driver makes:

    python3 benchmark/selection_check.py --workload <cell> --seed <n> \\
        [--seeds 2] [--prompt-tokens 5000] [--decode 16] \\
        [--control select=false] [--rehearse-cpu]

Same server, seams and weight law as benchmark/run.py, the weights drawn
from this tool's own ``--seed`` (run.py serves a cell's ONE draw,
lib/weights.py). The served sets come
from ``engine.model.select_topk`` itself: the name is bound, before any
program is traced, to a wrapper that hands the mask it returns to the host
(``jax.debug.callback``, ordered: a query's layers arrive in the model's
order), so the programs that serve are the programs a cell runs plus that
copy. A seed prints, a layer:

  differ_pct      keys the served set holds and the reference's ``top_k``
                  over ITS float32 index scores does not, of all chosen,
                  over the queries past ``index_topk`` (the two sets have
                  one size, so as many are missing);
  margin_sd       how far the keys on one side alone lie from the
                  reference's ``index_topk``-th score, by the reference's
                  scores, in standard deviations of the query's scores;

for the prompt's queries (prefill chunks over history) and the decoded ones
(the window) apart. The FIRST layer reads one input on both sides (the
embedding's rows), so there the sets differ by the served indexer's own
rounding alone: keys that swap sides AT the boundary, hundredths of a
deviation off it; a selection from wrong positions or under a wrong mask
would differ by whole deviations there. Deeper layers add what the streams
have drifted apart by (a token whose experts flipped upstream leaves another
index key);

then the distance of what was served from the reference as it chooses, and
from the reference GIVEN the served sets in every layer: what is left there
is the arithmetic alone. The verdict judges the first by the reference
module's ``ALLOWED_NATS_SELECTING`` with lib/reference.py's ``judge``; every
``--control`` must fall outside it. Exits 1 otherwise.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.lib import tokenizer as bench_tok  # noqa: E402

SEED_STRIDE = 7919


class ServedSets:
    """Every (valid, chosen) pair ``select_topk`` returned while ``on``."""

    def __init__(self):
        self.on = False
        self.records: list[tuple[np.ndarray, np.ndarray]] = []

    def install(self) -> None:
        import jax
        from dynamo_tpu.engine import model
        chooses = model.select_topk

        def record(valid, mask):
            if self.on:
                self.records.append((np.asarray(valid), np.asarray(mask)))

        def select_topk(scores, valid, k):
            mask = chooses(scores, valid, k)
            jax.debug.callback(record, valid, mask, ordered=True)
            return mask

        model.select_topk = select_topk

    def take(self) -> list:
        records, self.records = self.records, []
        return records


def served_keeps(records: list, layers: int, tokens: int, topk: int,
                 window: int) -> tuple[np.ndarray, dict]:
    """keeps [layers, tokens, tokens] bool: keep[l, t, s], the keys the
    served programs let query t attend in layer l; every s <= t where they
    chose nothing (t < topk). A record is a prefill chunk's ([B, S, Lh + S]:
    the history's slots, then the chunk's own tokens; a slot l is position
    l, the chunk starts where the history ends) or a window step's ([B, hist
    + window + 1]: the pool's slots, the window's earlier steps, the token
    itself). The n-th record that holds query t is layer n's."""
    keeps = np.broadcast_to(np.tril(np.ones((tokens, tokens), bool)),
                            (layers, tokens, tokens)).copy()
    seen_layers = np.zeros(tokens, int)
    extra = 0

    def place(t, positions, valid_row, mask_row):
        nonlocal extra
        if t >= tokens or valid_row.sum() <= topk:
            return
        if seen_layers[t] >= layers:
            extra += 1
            return
        keeps[seen_layers[t], t] = False
        keeps[seen_layers[t], t, positions[mask_row]] = True
        seen_layers[t] += 1

    for valid, mask in records:
        for b in range(valid.shape[0]):
            if valid.ndim == 3:
                s = valid.shape[1]
                lh = valid.shape[2] - s
                hist = int(valid[b, 0, :lh].sum())
                positions = np.r_[np.arange(lh), hist + np.arange(s)]
                for i in range(int(valid[b, :, lh:].any(0).sum())):
                    place(hist + i, positions, valid[b, i], mask[b, i])
            else:
                slots = valid.shape[1] - window - 1
                hist = int(valid[b, :slots].sum())
                t = hist + int(valid[b, slots:slots + window].sum())
                positions = np.r_[np.arange(slots), hist + np.arange(window),
                                  t]
                place(t, positions, valid[b], mask[b])
    missing = int((seen_layers[topk:] != layers).sum())
    return keeps, {"queries_choosing": int(tokens - topk),
                   "queries_missing_a_layer": missing,
                   "records_beyond_the_layers": extra}


def set_distance(scores: np.ndarray, keep: np.ndarray, rows: range,
                 topk: int) -> dict:
    """``differ_pct`` and the margins of the module's docstring for the
    queries ``rows`` of one layer: ``scores`` the reference's [S, S] (-inf
    where s > t), ``keep`` the served sets."""
    sc, served = scores[rows.start:rows.stop], keep[rows.start:rows.stop]
    kth = -np.partition(-sc, topk - 1, axis=1)[:, topk - 1:topk]
    finite = np.isfinite(sc)
    mean = np.sum(np.where(finite, sc, 0.0), 1, keepdims=True) \
        / finite.sum(1, keepdims=True)
    spread = np.sqrt(np.sum(np.where(finite, (sc - mean) ** 2, 0.0), 1,
                            keepdims=True) / finite.sum(1, keepdims=True))
    theirs = sc >= kth
    swapped = (np.abs(sc - kth) / spread)[served != theirs]
    out = {"queries": len(rows),
           "differ_pct": 100.0 * float((served & ~theirs).sum())
           / (len(rows) * topk),
           "served_set_sizes": [int(served.sum(1).min()),
                                int(served.sum(1).max())]}
    if swapped.size:
        p50, p99 = np.percentile(swapped, [50, 99])
        out["margin_sd"] = {"p50": float(p50), "p99": float(p99),
                            "max": float(swapped.max())}
    return out


async def check_seed(args, srv, judged, module, sets, seed, overhead) -> dict:
    """One seed's lines and its verdict."""
    from benchmark.lib import reference
    eng, runner = srv.engine, srv.engine.runner
    spec = runner.spec
    keeps = {}

    def telling(params, spec, prompt, tokens):
        # check_logprobs calls this once what was served is in: the
        # reference as it is, each layer's scores held to the served sets.
        size = len(prompt) + len(tokens) - 1
        keeps["all"], coverage = served_keeps(
            sets.take(), spec.num_layers, size, spec.index_topk,
            eng.decode_window)
        run.emit("served_sets", seed=seed, tokens=size, **coverage)

        def tell(layer, scores):
            if scores is None:
                return
            scores, keep = np.asarray(scores), keeps["all"][layer]
            first = max(len(prompt), spec.index_topk)
            run.emit("layer", seed=seed, layer=layer, **{
                path: set_distance(scores, keep, rows, spec.index_topk)
                for path, rows in (
                    ("prefill", range(spec.index_topk, first)),
                    ("window", range(first, size))) if len(rows)})

        return module.selection_logprobs(params, spec, prompt, tokens,
                                         tell=tell)

    sets.on = True
    checked = await run.check_logprobs(
        srv, {**judged, "logprobs": telling,
              "allowed": module.ALLOWED_NATS_SELECTING},
        seed, overhead, spec.vocab_size, prompts=1,
        prompt_tokens=args.prompt_tokens, n_gen=args.decode)
    sets.on = False
    (prompt, tokens), = checked["_taps"]
    given = module.selection_logprobs(runner.params, spec, prompt, tokens,
                                      keeps=keeps["all"])
    run.emit("given_served_sets", seed=seed,
             served_vs_reference=reference.diff_stats(checked["_served"],
                                                      checked["_full"]),
             served_vs_given=reference.diff_stats(checked["_served"], given),
             reference_vs_given=reference.diff_stats(checked["_full"], given))
    verdict = {"seed": seed, "served_ok": bool(checked["ok"]),
               "controls": {}}
    for item in args.control:
        key, _, value = item.partition("=")
        wrong = module.control_logprobs(
            runner.params, spec, prompt, tokens,
            **{key: {"true": True, "false": False}.get(value.lower(), value)})
        against = reference.judge(checked["_served"], wrong,
                                  module.ALLOWED_NATS_SELECTING)
        run.emit("control", seed=seed, switch=item,
                 would_pass=against["ok"],
                 served_vs_control=reference.diff_stats(checked["_served"],
                                                        wrong))
        verdict["controls"][item] = not against["ok"]
    return verdict


async def check(args, files: dict) -> list[dict]:
    from benchmark.lib import reference, server, weights
    config = files["config"]
    name = files["cell"]["config"]
    judged = reference.for_config(config)
    module = manifest.load_module("references", config["reference"])
    os.makedirs(manifest.RUN_DIR, exist_ok=True)
    spec = server.model_spec(name, config, config.get("launch", {}).get(
        "quant"))
    tok_path = bench_tok.write_tokenizer(os.path.join(
        manifest.RUN_DIR, f"tokenizer-{spec.vocab_size}.json"),
        spec.vocab_size)
    shapes = server.WarmShapes(
        max_prompt=args.prompt_tokens,
        max_context=args.prompt_tokens + args.decode, max_batch=1)
    seams = server.Seams(name, spec, args.seed, shapes)
    seams.install()
    sets = ServedSets()
    sets.install()
    verdicts = []
    try:
        argv = run.launch_argv(name, config, tok_path)
        async with server.Server(argv) as srv:
            eng = srv.engine
            overhead = bench_tok.template_overhead(tok_path,
                                                   srv.chat_template)
            run.emit("server", startup_s=srv.startup_s,
                     timings=seams.timings, decode_window=eng.decode_window,
                     prefill_chunk_tokens=eng.prefill_chunk_tokens,
                     index_topk=eng.runner.spec.index_topk)
            for k in range(args.seeds):
                seed = args.seed + SEED_STRIDE * k
                if k:
                    # Another seed's weights in the same server: the cached
                    # prefixes belong to the old ones.
                    eng.runner.params = None
                    gc.collect()
                    eng.runner.params = weights.make_params(
                        eng.runner.spec, weights.runner_mesh(eng.config),
                        seed)
                    await eng.clear_kv_blocks()
                verdicts.append(await check_seed(
                    args, srv, judged, module, sets, seed, overhead))
    finally:
        seams.restore()
    return verdicts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=1,
                    help="seeds served by one server, the weights swapped "
                         "in place")
    ap.add_argument("--prompt-tokens", type=int, default=5000)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    files = manifest.cell_files(manifest.load_manifest(), args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        files = run.rehearsal_cut(files)
    os.environ.setdefault("DTPU_FLIGHT_DIR",
                          os.path.join(manifest.RUN_DIR, "flight"))
    verdicts = asyncio.run(check(args, files))
    ok = all(v["served_ok"] and all(v["controls"].values())
             for v in verdicts)
    print(json.dumps({"seeds": verdicts, "ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
