"""The AIMD concurrency limit of the HTTP admission (runtime/overload.py) as
/metrics shows it when the window closes. It starts at 16; streams beyond
it wait in the admission queue, so it caps the decode batch."""
import re

NAME = "admit_limit"
UNIT = "count"
BETTER = "higher"
LAYER = "http admission"
MOVES = "out_tok_s"
SOURCE = "program_counter"

_LINE = re.compile(r"^[A-Za-z0-9_:]*concurrency_limit(?:\{[^}]*\})?\s+"
                   r"([0-9.eE+-]+)\s*$", re.M)


def read(r):
    found = _LINE.findall(r.metrics_text or "")
    return float(found[-1]) if found else None
