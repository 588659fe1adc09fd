"""The decode-window program's share of its roofline: the least time one
step could take on this chip over the device time a step took
(decode_step_ms). The floor is what a step MUST read (lib/roofline.py
``decode_step_floor``): the weights every row needs as stored, the experts
some row chose, the live K and V or state, over the peak bandwidth; or its
operations over the peak rate, whichever is larger. "The experts some row
chose" is the program's own count over the traced seconds
(``moe_experts_touched_pct.per_layer_step``: distinct held experts a
layer-step's live rows chose, as ``moe_roofline`` takes it), handed to the
configuration's counting module where it takes one; a cell without an
expert layer, or a program without the counters, hands none and reads
every weight it holds. Rows and context are the means of the engine's
occupancy samples inside the traced window. The run's ``"line": "floor"``
gives both floors, with the count and without."""

import json

NAME = "decode_window_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def inputs(r):
    if r.trace_mono is None or r.peaks is None:
        return None
    inside = [s for s in r.samples_in(*r.trace_mono) if s["rows"] > 0]
    if not inside:
        return None
    rows = sum(s["rows"] for s in inside) / len(inside)
    context = sum(s["context"] for s in inside) / len(inside)
    return rows, context


def read(r):
    from benchmark.lib import manifest, roofline
    step = manifest.load_module("layer_metrics", "decode_step_ms").read(r)
    got = inputs(r)
    if not step or got is None:
        return None
    rows, context = got
    args = (r.model, r.engine.get("quant"), r.engine.get("tp", 1), rows,
            context, r.peaks)
    whole = roofline.decode_step_floor(*args)   # every held expert
    count = manifest.load_module(
        "layer_metrics", "moe_experts_touched_pct").per_layer_step(r)
    floor = whole if count is None else roofline.decode_step_floor(
        *args, touched=count)
    print(json.dumps({
        "line": "floor", "rows": rows, "context_tokens": context,
        "decode_step_ms": step, "experts_touched": floor["experts_touched"],
        "floor_ms": floor["seconds"] * 1e3, "bound": floor["bound"],
        "floor_every_held_expert_ms": whole["seconds"] * 1e3,
        "counted_by": floor["counted_by"]}), flush=True)
    return floor["seconds"] / (step / 1e3) * 100.0
