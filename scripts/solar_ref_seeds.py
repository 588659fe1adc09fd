"""The served path of the Solar-Open2 cell against its reference and every
control over many seeds in ONE process (PR 52; the table in
benchmark/references/solar_open2.py): scripts/ouro_ref_seeds.py's probe on
another cell. A builder's chip run, not a run the driver makes.

    python3 scripts/solar_ref_seeds.py <seed,seed,...> [control,control,...]

Half a minute a seed without controls on one v5e (the weights of 9.5 G
values are made anew), two with the eleven controls.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ouro_ref_seeds  # noqa: E402

if __name__ == "__main__":
    sys.exit(ouro_ref_seeds.main(sys.argv[1:],
                                 cell="solar-open2-250b.reasoning",
                                 num_pages=64))
