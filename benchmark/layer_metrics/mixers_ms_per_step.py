"""Device time of one decode step inside a layer's TWO mixers together, in a
block whose recurrent mixer and attention layer run side by side on one
normed input (the Falcon-H1 block): the scopes ``ssm`` (the shared norm, the
Mamba-2 branch, the ONE residual sum) and ``attn.qkv``, ``attn.kv_gather``,
``attn.core``, ``attn.out`` (the attention branch), by lib/scopes.py's own
attribution (an instruction counts under the first of its scopes in
PRECEDENCE), over the steps traced. None where the program's record says no
mixers run side by side (every other block, a program before PR 54) or the
executable carries no scopes."""

NAME = "mixers_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("ssm", "attn.qkv", "attn.kv_gather", "attn.core", "attn.out")


def side_by_side(r) -> bool:
    """The served program's own fact, from ``/metrics``
    (``dynamo_tpu_perf_ssm_state_info{...,parallel="1"}``, a label since PR
    54): a layer's two mixers read one normed input."""
    return any("perf_ssm_state_info{" in line and 'parallel="1"' in line
               for line in (r.metrics_text or "").splitlines())


def read(r):
    from benchmark.lib import scopes
    if r.trace is None or not side_by_side(r):
        return None
    return scopes.ms_per_step(r, SCOPES) or None
