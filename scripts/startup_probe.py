#!/usr/bin/env python3
"""ONE start of a benchmark cell's server, timed from OUTSIDE the program.

    cd <a checkout> && python3 <path>/scripts/startup_probe.py <label> <cell> <seed>

Starts the cell's server exactly as ``benchmark/run.py`` does (the seams, the
device-made weights, ``launch.run``, the warm-up of the traffic's shapes) and
leaves as soon as it is ready: no check, no window. ``jax.monitoring``'s
duration events (trace, lowering, backend build, cache retrieval) are summed
by thread and name by a listener of this script's own, so a tree WITHOUT the
program's first-call records (the parent of PR 50) reads the same way as one
with them: run it from the parent's and the change's checkout in turn, in one
chip call, to see where a start's seconds differ. Beside jax's events it
prints what the program store answered a family (``program_store``: hits,
misses, rejects with the last reason, fallbacks; run it twice from one
checkout for a cold and a warm start) and the bytes the start left in the
compile cache's directory, jax's entries and the store's apart
(``cache_dir_bytes``). ``VARIANT=nospans`` clears
jax's time-span listeners after the program registered its own (what the
records' self times cost). ``REHEARSE=1`` runs the cell's toy on the CPU.
One line, ``PROBE {json}``, also appended to ``chiprun_out/startup_probe.jsonl``
of the directory this is run from. About 80 s a start in the Qwen cell.
"""
import asyncio
import collections
import json
import os
import sys
import threading

sys.path.insert(0, os.getcwd())


def directory_bytes(path: str) -> dict:
    """Bytes under the compile cache's directory: jax's own entries, and
    the program store's (the sub-directory ``programs``)."""
    out = {"jax_cache": 0, "program_store": 0, "program_store_entries": 0}
    for root, _dirs, files in os.walk(path):
        store = os.path.basename(root) == "programs"
        for name in files:
            size = os.path.getsize(os.path.join(root, name))
            out["program_store" if store else "jax_cache"] += size
            out["program_store_entries"] += store
    return out


def main() -> int:
    if len(sys.argv) != 4:
        print(__doc__, file=sys.stderr)
        return 2
    label, cell, seed = sys.argv[1:]
    import benchmark.run as run
    from benchmark.lib import manifest
    sums: dict = collections.defaultdict(float)
    counts: dict = collections.defaultdict(int)

    def on_duration(event, duration, **_kw):
        key = (threading.current_thread().name, event.rsplit("/", 1)[-1])
        sums[key] += duration
        counts[key] += 1

    async def leave_at_ready(args, files, man, jax, srv, seams, *_rest):
        out = {"label": label, "tree": os.getcwd(),
               "variant": os.environ.get("VARIANT", ""),
               "startup_s": srv.startup_s,
               "timings": {k: v for k, v in seams.timings.items()
                           if k.endswith("_s")},
               "events": {f"{t}:{e}": [counts[(t, e)], round(sums[(t, e)], 3)]
                          for (t, e) in sorted(sums)}}
        from dynamo_tpu.engine import perf
        status = getattr(perf, "startup_status", None)   # None on the parent
        if status is not None:
            calls = status()["first_calls"]
            out["first_calls"] = {k: v for k, v in calls.items()
                                  if k != "longest"}
            # The program store's counters a family (none before PR 51),
            # and what the start left in the compile cache's directory.
            out["program_store"] = {
                name: {k: v for k, v in family.items()
                       if k.startswith("store_")}
                for name, family in
                perf.get_registry().snapshot()["programs"].items()
                if family.get("store_hits") is not None}
            out["cache_dir_bytes"] = directory_bytes(perf.compile_cache_dir())
        line = "PROBE " + json.dumps(out)
        print(line, flush=True)
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "startup_probe.jsonl"), "a",
                  encoding="utf-8") as fh:
            fh.write(line + "\n")
        return {"metrics": {}}

    run._serve_and_measure = leave_at_ready
    args = run.parse_args(["--workload", cell, "--seed", seed])
    man = manifest.load_manifest()
    files = manifest.cell_files(man, cell)
    args.seconds = int(man["run_seconds"])
    if os.environ.get("REHEARSE"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        files = run.rehearsal_cut(files)
    os.environ.setdefault("DTPU_FLIGHT_DIR",
                          os.path.join(manifest.RUN_DIR, "flight"))
    import jax
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.devices()
    if os.environ.get("VARIANT") == "nospans":
        import dynamo_tpu.engine.perf  # noqa: F401 — registers its listeners
        from jax._src import monitoring
        monitoring._event_time_span_listeners.clear()
    asyncio.run(run.run_cell(args, files, man, jax))
    return 0


if __name__ == "__main__":
    sys.exit(main())
