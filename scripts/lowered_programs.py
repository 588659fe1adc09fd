#!/usr/bin/env python3
"""Did a refactor change a program? The lowered text of every ``prefill``
and ``decode_window`` program a benchmark cell's CPU rehearsal serves, in
one tree, and the comparison of two trees (PR 44; PR 30 did the same by
hand).

    python scripts/lowered_programs.py run <tree> <out_dir> <cell> [pallas]
    python scripts/lowered_programs.py compare <out_dir_a> <out_dir_b>

``run`` runs ``<tree>/benchmark/run.py --workload <cell> --seed 1 --seconds
4 --rehearse-cpu`` as it stands (``pallas``: with every EngineConfig asked
for the Pallas reader, which the CPU interprets), the compile observatory
keeping every wrapper alive; then lowers each wrapper again from its first
call's signature (``jax.jit(...).lower(...).as_text()``) and writes one
file a program and ``index.json``: {"<program> <key>": [sha256, labels]}.
Take the parent from ``git archive``; with one ``JAX_COMPILATION_CACHE_DIR``
for both trees the second also shows its cache hits. What the CPU cannot
lower (Mosaic kernels) is tests/test_tpu_compile.py's and the chip's.
"""
import hashlib
import json
import os
import runpy
import sys


def run(tree: str, out_dir: str, cell: str, pallas: bool) -> None:
    tree = os.path.abspath(tree)
    out_dir = os.path.join(os.path.abspath(out_dir),
                           cell + (".pallas" if pallas else ""))
    os.makedirs(out_dir, exist_ok=True)
    os.chdir(tree)
    sys.path.insert(0, tree)
    os.environ["JAX_PLATFORMS"] = "cpu"
    from dynamo_tpu.engine import config as config_mod
    from dynamo_tpu.engine import perf

    kept = []
    wrap = perf.CompileRegistry.wrap

    def keeping(self, program, fn, **kw):
        w = wrap(self, program, fn, **kw)
        kept.append(w)
        return w

    perf.CompileRegistry.wrap = keeping
    if pallas:
        init = config_mod.EngineConfig.__init__

        def asked(self, *a, **kw):
            kw["attention_backend"] = "pallas"
            init(self, *a, **kw)

        config_mod.EngineConfig.__init__ = asked
    sys.argv = ["run.py", "--workload", cell, "--seed", "1", "--seconds",
                "4", "--rehearse-cpu"]
    rc = 0
    try:
        runpy.run_path(os.path.join(tree, "benchmark", "run.py"),
                       run_name="__main__")
    except SystemExit as e:
        rc = e.code or 0
    index = {}
    for w in kept:
        if w._program not in ("prefill", "decode_window") \
                or w._signature is None:
            continue
        args, kwargs = w._signature
        text = w._fn.lower(*args, **kwargs).as_text()
        name = f"{w._program} {w._key!r}"
        index[name] = [hashlib.sha256(text.encode()).hexdigest(),
                       dict(w._labels)]
        with open(os.path.join(out_dir, name.replace(" ", "_") + ".txt"),
                  "w") as f:
            f.write(text)
    with open(os.path.join(out_dir, "index.json"), "w") as f:
        json.dump({"rc": rc, "programs": index}, f, indent=1, sort_keys=True)
    print("LOWERED", cell, "pallas" if pallas else "auto", "rc", rc,
          len(index), "programs")


def compare(a_dir: str, b_dir: str) -> int:
    same = diff = 0
    for cell in sorted(os.listdir(a_dir)):
        paths = [os.path.join(d, cell, "index.json") for d in (a_dir, b_dir)]
        if not all(map(os.path.exists, paths)):
            print(f"{cell}: on one side only")
            diff += 1
            continue
        a, b = (json.load(open(p)) for p in paths)
        keys = sorted(set(a["programs"]) | set(b["programs"]))
        bad = [k for k in keys if a["programs"].get(k) != b["programs"].get(k)]
        same += len(keys) - len(bad)
        diff += len(bad)
        print(f"{cell}: rc {a['rc']}|{b['rc']} programs "
              f"{len(a['programs'])}|{len(b['programs'])} identical text "
              f"and labels {len(keys) - len(bad)} different {len(bad)}")
        for k in bad:
            print("   DIFFERS", k)
    print(f"TOTAL identical {same} different {diff}")
    return 1 if diff else 0


if __name__ == "__main__":
    if sys.argv[1] == "run":
        run(sys.argv[2], sys.argv[3], sys.argv[4],
            len(sys.argv) > 5 and sys.argv[5] == "pallas")
    else:
        sys.exit(compare(sys.argv[2], sys.argv[3]))
