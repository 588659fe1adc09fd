"""The served path of the Falcon-H1 cell against its reference and every
control over many seeds in ONE process (PR 54; the table in
benchmark/references/falcon_h1.py): scripts/ouro_ref_seeds.py's probe on
another cell. A builder's chip run, not a run the driver makes.

    python3 scripts/falcon_ref_seeds.py <seed,seed,...> [control,...] [wk=<size>]

Half a minute a seed without controls on one v5e (the weights of 7.8 G
values are made anew), a quarter of a minute more with sixteen controls.

``wk=<size>``: K's projection at ``size`` / (key_multiplier sqrt(hidden)) a
value and not the weight law's 1 / sqrt(hidden) (its int8 leaf's scales
times a constant), so that a score is ``size``'s size and not
``key_multiplier``'s: what shows on the chip, at the published widths,
whether the served path rotates as the reference does (``rope=false``), which
the benchmark's own law cannot (the reference's docstring). The other leaves
are the law's.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ouro_ref_seeds  # noqa: E402


def scaled_keys(size: float):
    """lib/weights.py ``make_params`` with ``wk``'s scales times ``size`` /
    key_multiplier."""
    from benchmark.lib import weights
    law = weights.make_params

    def make(spec, mesh, seed):
        params = law(spec, mesh, seed)
        wk = params["layers"]["wk"]
        params["layers"]["wk"] = type(wk)(
            wk.q, wk.s * (size / spec.key_multiplier))
        return params

    weights.make_params = make


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("wk=")]
    for a in sys.argv[1:]:
        if a.startswith("wk="):
            scaled_keys(float(a[3:]))
    sys.exit(ouro_ref_seeds.main(args, cell="falcon-h1-34b.reasoning-long",
                                 num_pages=64))
