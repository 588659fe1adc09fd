#!/usr/bin/env python3
"""One expert layer alone on the device this process holds: the masked
product (every row by every resident expert) against the two kernels of
dynamo_tpu/engine/experts.py, the grouped one over sorted pairs and the
one over the touched experts: the tables at ``model.MOE_DENSE_MAX_ROWS``.

    chiprun -- python3 scripts/expert_layer_bench.py [--rows 32,64,...]
        [--live 19] [--geometry smallthinker,glm,command,nemotron,...]
        [--tiles 128x1048576,...] [--out-tiles 640,...]

Six geometries, as the benchmark's routed cells hold them (int8 leaves):
``smallthinker`` 64 experts of 2,560 x 768 all held, 6 a row, ReGLU;
``glm`` 16 held of 64 of 2,048 x 1,536, 4 a row; ``command`` 16 held of 128
of 4,096 x 4,096, 8 a row; ``nemotron`` 32 held of 128 two-matrix relu2
experts of 2,688 x 1,856 (no whole number of lane tiles), 6 a row;
``deepseek`` 16 held of 256 of 7,168 x 2,048, 8 a row; ``solar`` 40 held of
320 of 4,096 x 1,280, 8 a row. ``--live n``: the first n of the rows are
live, as a window's step has them (the others choose nothing under any
product). Two routings: ``random`` (the router of random
weights over random rows, what the benchmark's cells route by) and
``balanced`` (row t takes experts t, t + R/k, ... mod R: every expert the
same load). One JSON line a (geometry, routing, rows) with the milliseconds
of ``model.ffn_block`` under each product, the largest difference of
their outputs from the masked one's, the experts the live rows touched, and
the grouped kernel's two calls alone; ``--tiles`` times the
grouped product under other (ROW_TILE, TILE_ELEMS), ``--out-tiles``
under other output tiles of a width that is no whole number of lane tiles
(a ragged last tile in place of the whole width); ``--layers`` adds the
time a layer of a scan over stacked layers, as a served program runs them.
The decode candidate PR 56 dropped is the grouped product at row tiles of
8 to 32: ``--products masked,grouped --tiles 32x2097152,...``. A time is the least of
three loops' mean, each loop ending in ``block_until_ready``."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from dynamo_tpu.engine import experts, model                  # noqa: E402
from dynamo_tpu.engine.backends import XLA, Backends          # noqa: E402
from dynamo_tpu.engine.config import (                        # noqa: E402
    Cohere2MoeSpec, SmallThinkerSpec)
from dynamo_tpu.engine.quant import QTensor                   # noqa: E402

_ATTN = dict(num_layers=1, num_heads=2, num_kv_heads=1, head_dim=128,
             quant="int8")
GEOMETRIES = {
    "smallthinker": SmallThinkerSpec(
        hidden_size=2560, intermediate_size=768, moe_intermediate_size=768,
        num_experts=64, num_experts_per_tok=6, **_ATTN),
    "glm": Cohere2MoeSpec(
        hidden_size=2048, intermediate_size=1536, moe_intermediate_size=1536,
        num_experts=16, num_experts_per_tok=4, num_routed_experts=64,
        first_expert=16, **_ATTN),
    "command": Cohere2MoeSpec(
        hidden_size=4096, intermediate_size=4096, moe_intermediate_size=4096,
        num_experts=16, num_experts_per_tok=8, num_routed_experts=128,
        **_ATTN),
    "nemotron": Cohere2MoeSpec(
        hidden_size=2688, intermediate_size=1856, moe_intermediate_size=1856,
        num_experts=32, num_experts_per_tok=6, num_routed_experts=128,
        ffn_act="relu2", **_ATTN),
    "deepseek": Cohere2MoeSpec(
        hidden_size=7168, intermediate_size=2048, moe_intermediate_size=2048,
        num_experts=16, num_experts_per_tok=8, num_routed_experts=256,
        **_ATTN),
    "solar": Cohere2MoeSpec(
        hidden_size=4096, intermediate_size=1280, moe_intermediate_size=1280,
        num_experts=40, num_experts_per_tok=8, num_routed_experts=320,
        **_ATTN),
}
PRODUCTS = ("masked", "grouped", "touched")
ROWS = (32, 64, 128, 256, 512, 1024, 2048, 4096)


def layer(spec, key):
    """One layer's router and int8 expert stacks, made on the device."""
    h, i, e = spec.hidden_size, spec.moe_intermediate_size, spec.num_experts
    ks = jax.random.split(key, 7)

    def q(k, shape):
        return QTensor(
            jax.random.randint(k, shape, -127, 128, jnp.int8),
            jnp.full((e, 1, shape[-1]), shape[-2] ** -0.5 / 64, jnp.float32))

    lp = {"moe_gate": jax.random.normal(ks[0], (h, spec.router_width),
                                        jnp.bfloat16) * h ** -0.5,
          "moe_w_gate": q(ks[1], (e, h, i)), "moe_w_up": q(ks[2], (e, h, i)),
          "moe_w_down": q(ks[3], (e, i, h))}
    if spec.ffn_act == "relu2":     # two matrices an expert
        del lp["moe_w_gate"]
    return lp


def balanced(spec):
    r, k = spec.router_width, spec.num_experts_per_tok

    def route(router, spec, bias=None):
        t = jnp.arange(router.shape[0])[:, None]
        top_i = (t + jnp.arange(k)[None, :] * (r // k)) % r
        return jnp.full(top_i.shape, 1.0 / k, jnp.float32), top_i
    return route


def timed(fn, *args, loops=3):
    out = fn(*args)
    jax.block_until_ready(out)
    n = 5
    best = float("inf")
    for _ in range(loops):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / n
        best = min(best, dt)
        n = max(5, min(50, int(0.2 / max(dt, 1e-5))))
    return best * 1e3, out


def forced(product):
    """``model.expert_product`` answering ``product`` at every size: the
    threshold is what is being measured."""
    model.expert_product = lambda rows, backends: product


def products(spec, lp, x, live, backends, product):
    forced(product)
    f = jax.jit(lambda x, lp: model.ffn_block(x, lp, spec, router_in=x,
                                              live=live, backends=backends))
    try:
        ms, (out, stats) = timed(f, x, lp)
        return ms, out, stats
    except Exception as e:  # noqa: BLE001 -- out of memory at a size: a hole in the table
        print(f"# {type(e).__name__}: {str(e)[:200]}", file=sys.stderr)
        return None, None, None


def scanned(spec, lps, x, live, backends, product, whole):
    """Milliseconds a layer of ``model.scan_layers`` over the stacked
    layers ``lps`` (as a served program runs them), the experts sliced a
    layer or handed whole."""
    n = lps["moe_gate"].shape[0]
    forced(product)

    def body(x, lp):
        return x + model.ffn_block(x, lp, spec, router_in=x, live=live,
                                   backends=backends)[0], None

    f = jax.jit(lambda x, lps: model.scan_layers(
        body, x, lps, spec, whole_experts=whole)[0])
    try:
        return round(timed(f, x, lps)[0] / n, 4)
    except Exception as e:  # noqa: BLE001 -- out of memory at a size: a hole in the table
        print(f"# {type(e).__name__}: {str(e)[:200]}", file=sys.stderr)
        return None


def kernel_calls(spec, lp, x, interpret):
    """The kernel's two calls alone, on the batch's own sorted pairs."""
    router = jnp.einsum("th,he->te", x, lp["moe_gate"],
                        preferred_element_type=jnp.float32)
    _, top_i = model.moe_route(router, spec)
    top_i = top_i - spec.first_expert
    e, k = spec.num_experts, spec.num_experts_per_tok
    flat = jnp.where((top_i >= 0) & (top_i < e), top_i, e).reshape(-1)
    order = jnp.argsort(flat)
    sizes = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0,
                    dtype=jnp.int32)
    rows = jnp.pad(x[order // k], ((0, -flat.shape[0] % experts.ROW_TILE),
                                   (0, 0)))
    walk = experts.visits(sizes, rows.shape[0])
    # Stacks of one layer, made once: a [None] inside the timed call would
    # copy the experts each time.
    *gu, (dq, ds) = ((lp[k].q[None], lp[k].s[None])
                     for k in model.EXPERT_LEAVES if k in lp)
    up_ms, ff = timed(lambda: experts.pairs_product(
        rows, *zip(*gu), 0, walk, act=spec.ffn_act, interpret=interpret))
    down_ms, _ = timed(lambda: experts.pairs_product(
        ff, (dq,), (ds,), 0, walk, interpret=interpret))
    return {"gate_up_ms": round(up_ms, 4), "down_ms": round(down_ms, 4),
            "held_pairs": int(jnp.sum(sizes)), "pairs": int(flat.shape[0]),
            "groups": int(jnp.sum(sizes > 0)), "visits": int(walk[3])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--geometry", default=",".join(GEOMETRIES))
    ap.add_argument("--routing", default="random,balanced")
    ap.add_argument("--tiles", default="",
                    help="ROW_TILExTILE_ELEMS variants of the grouped product")
    ap.add_argument("--out-tiles", default="",
                    help="output tiles of a width that is no whole number "
                    "of lane tiles, in place of the whole width")
    ap.add_argument("--live", type=int, default=0,
                    help="how many of the rows are live (0: every row)")
    ap.add_argument("--products", default=",".join(PRODUCTS))
    ap.add_argument("--layers", type=int, default=0,
                    help="also scan this many stacked layers: ms a layer "
                    "masked, grouped over sliced experts, grouped over "
                    "whole, touched over whole")
    ap.add_argument("--out", default="chiprun_out/expert_layer.jsonl")
    args = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    real_route = model.moe_route
    # The record of a runner on one device: its experts are whole (the
    # CPU interprets the kernels).
    local = Backends(experts_whole=True, interpret=dev.platform == "cpu")
    wanted = args.products.split(",")
    with open(args.out, "a") as sink:
        def say(line):
            text = json.dumps({**line, "device": device})
            print(text, flush=True)
            sink.write(text + "\n")
            sink.flush()

        for name in args.geometry.split(","):
            spec = GEOMETRIES[name]
            lp = layer(spec, jax.random.key(40))
            lps = args.layers and jax.tree.map(
                lambda *a: jnp.stack(a), *(layer(spec, jax.random.key(i))
                                           for i in range(args.layers)))
            for routing in args.routing.split(","):
                route = balanced(spec) if routing == "balanced" else real_route
                for rows in map(int, args.rows.split(",")):
                    n_live = min(args.live or rows, rows)
                    live = jnp.arange(rows) < n_live

                    def live_route(router, spec, bias=None, live=live):
                        """A row that is not live chooses nothing, under
                        every product."""
                        gates, top_i = route(router, spec, bias)
                        return gates, jnp.where(live[:, None], top_i, -1)

                    model.moe_route = live_route
                    x = jax.random.normal(jax.random.key(rows),
                                          (rows, spec.hidden_size),
                                          jnp.bfloat16)
                    line = {"geometry": name, "routing": routing,
                            "rows": rows, "live": n_live}
                    outs = {}
                    for product in wanted:
                        ms, out, stats = products(spec, lp, x, live, local,
                                                  product)
                        line[f"{product}_ms"] = ms and round(ms, 4)
                        if out is not None:
                            outs[product] = np.asarray(out, np.float32)
                            line["touched"] = int(stats[0])
                    if "masked" in outs:
                        line["mean_abs"] = float(np.abs(outs["masked"]).mean())
                        for product, out in outs.items():
                            if product != "masked":
                                line[f"{product}_max_diff"] = float(
                                    np.abs(out - outs["masked"]).max())
                    if (routing == "random" and n_live == rows
                            and line.get("grouped_ms") is not None):
                        model.moe_route = route
                        line.update(kernel_calls(spec, lp, x,
                                                 local.interpret))
                        model.moe_route = live_route
                    if args.layers:
                        for key, product, whole in (
                                ("scan_masked_ms", "masked", False),
                                ("scan_sliced_ms", "grouped", False),
                                ("scan_whole_ms", "grouped", True),
                                ("scan_touched_sliced_ms", "touched", False),
                                ("scan_touched_ms", "touched", True)):
                            if product in wanted:
                                line[key] = scanned(spec, lps, x, live, local,
                                                    product, whole)
                    for variant in filter(None, args.tiles.split(",")):
                        tm, elems = map(int, variant.split("x"))
                        keep = experts.ROW_TILE, experts.TILE_ELEMS
                        experts.ROW_TILE, experts.TILE_ELEMS = tm, elems
                        jax.clear_caches()
                        ms = products(spec, lp, x, live, local, "grouped")[0]
                        line[f"grouped_ms@{variant}"] = ms and round(ms, 4)
                        experts.ROW_TILE, experts.TILE_ELEMS = keep
                        jax.clear_caches()
                    for tn in map(int, filter(None,
                                              args.out_tiles.split(","))):
                        keep = experts.out_tile
                        experts.out_tile = lambda k, n, tn=tn, keep=keep: (
                            tn if n % 128 else keep(k, n))
                        jax.clear_caches()
                        ms = products(spec, lp, x, live, local, "grouped")[0]
                        line[f"grouped_ms@tn{tn}"] = ms and round(ms, 4)
                        if ms is not None and routing == "random":
                            line[f"gate_up_ms@tn{tn}"] = kernel_calls(
                                spec, lp, x, local.interpret)[
                                    "gate_up_ms"]
                        experts.out_tile = keep
                        jax.clear_caches()
                    say(line)
            model.moe_route = real_route
            del lp
    return 0


if __name__ == "__main__":
    sys.exit(main())
