"""The decode-window program's share of its roofline: the least time one
step could take on this chip (lib/roofline.py: this shard's weights as
stored plus the K and V of the live context over the peak bandwidth, or its
operations over the peak rate, whichever is larger) over the device time a
step took (decode_step_ms). Rows and context are the means of the engine's
occupancy samples inside the traced window."""

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
    floor = roofline.decode_step_floor(
        r.model, r.engine.get("quant"), r.engine.get("tp", 1), got[0],
        got[1], r.peaks)
    return floor["seconds"] / (step / 1e3) * 100.0
