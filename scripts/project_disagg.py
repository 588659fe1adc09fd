"""SLO-constrained agg-vs-disagg projection from MEASURED single-chip
numbers (ladder step 3 evidence, round-3 VERDICT weak #4).

Inputs (defaults = the round-4 chip measurements in docs/PERF_NOTES.md,
llama-3-8b int8 on one v5e):

  prefill_tok_s   single-chip prefill throughput
  decode_tok_s    single-chip decode throughput at the SLO batch
  itl_ms          per-token decode latency at that batch
  transfer_ms     disagg KV transfer tax per request (plane path,
                  a projection, not a measurement)
  ttft_slo_ms     the north-star 500 ms p99 TTFT budget

Model (stated, simple, conservative):

- AGGREGATED: prefill and decode share the chip. A prompt of ISL tokens
  occupies the chip ISL/prefill_tok_s seconds; every concurrent decode
  stream stalls for that long (chunked prefill interleaves the stall but
  does not reduce the compute), and the prompt's own TTFT cannot be less
  than its prefill compute. Aggregated serving therefore CANNOT meet the
  TTFT SLO for ISL > prefill_tok_s * slo, at any load.
- DISAGGREGATED: prefill workers shard the prompt over tp chips
  (prefill parallelizes; efficiency factor per the L8 sweep), decode
  chips run pure decode at the measured rate with ITL untouched by
  prefills. TTFT = ISL/(tp * prefill_tok_s * eff) + transfer. Chip
  budget splits so prefill capacity matches decode demand; throughput
  per TOTAL chip is reported for both.

The headline comparison is throughput UNDER THE SLO: past the agg TTFT
wall, aggregated SLO-compliant throughput is zero while disagg serves at
its full per-chip rate — the reference's >=2x-at-SLO claim is the same
argument (docs/architecture/disagg_serving.md).
"""

from __future__ import annotations

import json
import os

ISL = int(os.environ.get("PROJ_ISL", "3000"))   # reference perf.sh workload
OSL = int(os.environ.get("PROJ_OSL", "150"))
PREFILL_TOK_S = float(os.environ.get("PROJ_PREFILL_TOK_S", "5063"))
DECODE_TOK_S = float(os.environ.get("PROJ_DECODE_TOK_S", "2256"))
ITL_MS = float(os.environ.get("PROJ_ITL_MS", "17.7"))
TRANSFER_MS = float(os.environ.get("PROJ_TRANSFER_MS", "20"))
TTFT_SLO_MS = float(os.environ.get("PROJ_TTFT_SLO_MS", "500"))
PREFILL_TP = int(os.environ.get("PROJ_PREFILL_TP", "4"))
TP_EFF = float(os.environ.get("PROJ_TP_EFF", "0.85"))


def main() -> None:
    # Aggregated: TTFT floor is the prompt's own prefill compute.
    agg_ttft_floor_ms = 1e3 * ISL / PREFILL_TOK_S
    agg_meets_slo = agg_ttft_floor_ms + ITL_MS <= TTFT_SLO_MS
    # Chip-seconds per request under aggregation.
    agg_chip_s = ISL / PREFILL_TOK_S + OSL * (ITL_MS / 1e3) \
        * (DECODE_TOK_S * ITL_MS / 1e3) ** 0  # decode share below
    # Decode chip-seconds per request = OSL / decode_tok_s (the batch is
    # folded into decode_tok_s already).
    decode_chip_s = OSL / DECODE_TOK_S
    prefill_chip_s = ISL / PREFILL_TOK_S
    agg_chip_s = decode_chip_s + prefill_chip_s
    agg_tok_s_per_chip = OSL / agg_chip_s  # output tokens per chip-second

    # Disaggregated: tp-sharded prefill meets the SLO; chips split in
    # proportion to demand.
    dis_ttft_ms = (1e3 * ISL / (PREFILL_TP * PREFILL_TOK_S * TP_EFF)
                   + TRANSFER_MS)
    dis_meets_slo = dis_ttft_ms + ITL_MS <= TTFT_SLO_MS
    # Per TOTAL chip (prefill chips + decode chips).
    dis_tok_s_per_chip = OSL / (decode_chip_s
                                + prefill_chip_s / TP_EFF)

    out = {
        "metric": "disagg_projection_llama-3-8b_int8",
        "workload": {"isl": ISL, "osl": OSL,
                     "ttft_slo_ms": TTFT_SLO_MS},
        "measured_inputs": {"prefill_tok_s": PREFILL_TOK_S,
                            "decode_tok_s": DECODE_TOK_S,
                            "itl_ms": ITL_MS,
                            "transfer_ms": TRANSFER_MS},
        "aggregated": {
            "ttft_floor_ms": round(agg_ttft_floor_ms, 1),
            "meets_slo": agg_meets_slo,
            "tok_s_per_chip_unconstrained": round(agg_tok_s_per_chip, 1),
            "tok_s_per_chip_at_slo": round(agg_tok_s_per_chip, 1)
            if agg_meets_slo else 0.0,
        },
        "disaggregated": {
            "prefill_tp": PREFILL_TP,
            "ttft_ms": round(dis_ttft_ms, 1),
            "meets_slo": dis_meets_slo,
            "tok_s_per_total_chip": round(dis_tok_s_per_chip, 1),
        },
        "slo_speedup": ("inf (agg cannot meet the TTFT SLO at this ISL)"
                        if not agg_meets_slo and dis_meets_slo
                        else round(dis_tok_s_per_chip
                                   / max(1e-9, agg_tok_s_per_chip), 2)),
        "agg_ttft_wall_isl": int(PREFILL_TOK_S * TTFT_SLO_MS / 1e3),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
