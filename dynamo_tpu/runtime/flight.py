"""Engine flight recorder: a fixed-slot ring of per-window engine state,
frozen into a diagnostic bundle when an anomaly fires.

"What exactly was the engine doing when latency spiked five minutes
ago?" — the span recorder answers per-request, but the *engine-level*
picture (batch occupancy, free KV pages, chunk tokens in flight,
preemptions, brownout level, window pacing) lives only in transient
loop state. This module records one compact row per engine window into
preallocated numpy columns — no Python objects are created or retained
on the hot path, and idle-stable windows (nothing active, nothing
changed) are skipped entirely, so the steady-state cost is a few array
stores (asserted allocation-free in tests/test_slo.py in the style of
``test_disabled_recorder_zero_allocations``).

Anomaly capture: an SLO fast-burn page (runtime/slo.py ``on_page``) or
a decode-stall tail spike (engine/engine.py consults
``stall_threshold_s``) calls ``trigger(reason)`` — the ring freezes
for a copy of itself, and a background thread writes a **diagnostic
bundle** (the copy +
recent spans + metrics snapshot + config fingerprint) as one JSON file
under ``bundle_dir``. Captures are throttled by ``cooldown_s`` so a
sustained incident produces one bundle, not a disk flood. ``GET/POST
/debug/flight`` (runtime/health.py) serve the ring and take manual
captures.

Env knobs (read once at import; ``configure()`` overrides):
``DTPU_FLIGHT_CAPACITY`` (ring slots, default 8192: 300 s of windows of
40 ms, 1.8 MB; 0 disables),
``DTPU_FLIGHT_DIR`` (bundle directory, default /tmp/dtpu-flight),
``DTPU_FLIGHT_STALL_S`` (decode-stall trigger threshold, default 2.0,
0 disables), ``DTPU_FLIGHT_COOLDOWN_S`` (default 300).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time

import numpy as np

from dynamo_tpu.runtime import journal as journal_mod
from dynamo_tpu.runtime.journal import EventKind
from dynamo_tpu.runtime.logging import get_logger

log = get_logger("flight")

# Ring columns, in record() argument order. "tokens" (decode tokens
# emitted by the window) rides with "dur_s" (dispatch -> readback: the
# window's LATENCY THROUGH THE PIPELINE, pipeline_depth windows long when
# the pipe is full) so the perf plane's attribution is replayable from a
# frozen ring. The window's own clock: "period_s" (this window's readback
# complete minus the previous one's when this window was already queued
# behind it, i.e. a window of the device; 0 when the pipe was not full),
# "host_s" / "wait_s" / "idle_s" (engine-thread seconds since the previous
# row: in every phase but the two waits, in engine.readback_wait, in
# engine.idle; they add up to the time between two rows), "rows" (slot
# rows the window was dispatched with), "page_bucket" (the page-table
# width of its program) and "missed" (rows refused while frozen or skipped
# as idle since the previous row). A routed block's window adds what its
# expert layers' routing did to the live rows, summed over the window's
# steps and layers on the device: "moe_touched" (distinct experts chosen),
# "moe_load" (the fullest expert's tokens over the mean) and
# "moe_layer_steps" (how many (step, layer) pairs the two sums hold), all
# three over the experts the device HOLDS; 0 for a dense model. An expert
# layer that is told its share of a wider router adds "moe_local_picks"
# ((row, choice) pairs that fell on held experts) and "moe_picks" (all
# pairs); 0 for every other block. A latent block's window adds
# "attn_selected" (keys its live rows attended, summed over rows, layers
# and steps on the device) and "attn_context" (keys they had in context);
# 0 for every other block. A block that attends chosen blocks of keys adds
# "attn_index_read" (keys whose stripes its choice read: attn_context where
# a kernel walks the live rows' pages, slots x page-table bucket a layer
# and step under XLA's gather); 0 for every other block. A drafting window
# (spec_decode) adds "spec_drafted" (draft tokens its verify steps took
# in), "spec_accepted" (those the target's own draws confirmed) and
# "spec_row_steps" (verify steps of live rows: each emits one token and its
# accepted drafts, so the tokens emitted are spec_row_steps +
# spec_accepted), read back with the window's tokens; 0 without drafting.
# A block with recurrent layers adds
# "ssm_row_steps" (live rows summed over the window's steps, on the device:
# the rows whose recurrent state a step had to touch); 0 for every other
# block. A looped stack adds "loop_passes" (passes over the layers that
# the live rows took, summed over the window's steps where the passes run,
# on the device) and "loop_row_steps" (the live rows themselves, summed
# over the steps: loop_passes over it is the passes a token took); 0 for
# every other block. Beside "rows", taken at the same instant (the
# window's DISPATCH): "prefilling", the slots a request held without a row
# in this window (in chunked prefill, or stalled for pages, frozen for a
# preemption, or owed nothing but its first token's readback), so that
# rows + prefilling + empty slots = max_num_seqs in every row of the ring
# ("active" is taken at PROCESSING, pipeline_depth windows later, and cannot
# be subtracted from "rows"). "admit_stop": why the engine's own admission
# left requests queued since the previous row, a bit set of ADMIT_STOPS
# (0: it turned nobody away).
FIELDS = ("t_mono", "dur_s", "active", "waiting", "free_pages",
          "chunk_tokens", "chunks_inflight", "preempts", "brownout",
          "stall_s", "step", "tokens", "period_s", "host_s", "wait_s",
          "idle_s", "rows", "page_bucket", "missed", "moe_touched",
          "moe_load", "moe_layer_steps", "moe_local_picks", "moe_picks",
          "attn_selected", "attn_context", "prefilling", "admit_stop",
          "spec_drafted", "spec_accepted", "spec_row_steps",
          "ssm_row_steps", "attn_index_read", "loop_passes",
          "loop_row_steps")
_INT_FIELDS = ("active", "waiting", "free_pages", "chunk_tokens",
               "chunks_inflight", "preempts", "brownout", "step", "tokens",
               "rows", "page_bucket", "missed", "prefilling", "admit_stop")
#: What a window counted: the key its program returns a vector under (the
#: step functions of engine/model.py and engine/hybrid.py name them; "spec"
#: is the engine's own sums over a drafting window's rows) -> the ring's
#: columns its entries are, in order, each with the /metrics counter that
#: sums it (engine/perf.py registers them under these names) or None. The
#: ONE statement of where a count lands: the engine fills a window's counts
#: and its totals through ``columns_of``, ``record`` stores by column name,
#: perf's exporter walks the table. A vector may be shorter than its
#: columns ("moe" is [3] where the expert layer is not told its share,
#: "attn" [2] for a latent block).
COUNTS = {
    "moe": (("moe_touched", "moe_experts_touched_total"),
            ("moe_load", "moe_expert_load_max_over_mean_total"),
            ("moe_layer_steps", "moe_layer_steps_total"),
            ("moe_local_picks", "moe_local_picks_total"),
            ("moe_picks", "moe_picks_total")),
    "attn": (("attn_selected", "attn_selected_total"),
             ("attn_context", "attn_context_total"),
             ("attn_index_read", "attn_index_read_total")),
    "ssm": (("ssm_row_steps", "ssm_row_steps_total"),),
    "loop": (("loop_passes", "loop_passes_total"),
             ("loop_row_steps", "loop_row_steps_total")),
    "spec": (("spec_drafted", None), ("spec_accepted", None),
             ("spec_row_steps", None)),
}
COUNT_COLUMNS = tuple(column for columns in COUNTS.values()
                      for column, _ in columns)
_COUNT_SET = frozenset(COUNT_COLUMNS)


def columns_of(key: str, values) -> dict[str, float]:
    """A window's vector under ``key`` as {column: value}, in the table's
    order. Raises on a key the table lacks (KeyError) and on more entries
    than the key has columns."""
    columns = COUNTS[key]
    values = np.asarray(values, np.float64).reshape(-1)
    if len(values) > len(columns):
        raise ValueError(f"{key!r} counts {len(values)} values, the table "
                         f"has {len(columns)} columns for it")
    return {column: float(v) for (column, _), v in zip(columns, values)}


#: Why TPUEngine._admit ended with requests still queued, and the bit each
#: cause sets in "admit_stop" (the label values of
#: ``engine_admit_stops_total{cause}``).
ADMIT_STOPS = {"no_slot": 1, "no_pages": 2, "ttft_budget": 4}


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    return default if raw in (None, "") else int(raw)


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    return default if raw in (None, "") else float(raw)


class FlightRecorder:
    """Fixed-slot ring of per-window records (preallocated numpy
    columns; single engine-thread writer, any-thread readers)."""

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        self.capacity = max(1, capacity)
        self.enabled = enabled and capacity > 0
        self._cols = {name: np.zeros(self.capacity, np.float64)
                      for name in FIELDS}
        self._idx = 0
        self._count = 0
        # Preallocated cell, not a Python int: the idle-stable skip
        # path must retain no fresh objects (asserted by tracemalloc in
        # tests/test_slo.py).
        self._skipped = np.zeros(1, np.int64)
        # Rows refused (frozen) or skipped (idle) since the last row that
        # was stored, and the time of the last such row: between() says
        # how many rows a span lacks.
        self._missed = np.zeros(1, np.int64)
        self._missed_t = np.zeros(1, np.float64)
        self.frozen = False
        self.frozen_reason = ""
        self._was_idle = False
        # Guards freeze/dump vs. the writer; record() holds it only for
        # the column stores (sub-microsecond, no allocation).
        self._lock = threading.Lock()

    def record(self, t_mono: float, dur_s: float, active: int, waiting: int,
               free_pages: int, chunk_tokens: int, chunks_inflight: int,
               preempts: int, brownout: int, stall_s: float,
               step: int, tokens: int = 0, period_s: float = 0.0,
               host_s: float = 0.0, wait_s: float = 0.0,
               idle_s: float = 0.0, rows: int = 0,
               page_bucket: int = 0, prefilling: int = 0,
               admit_stop: int = 0, counts: dict | None = None) -> bool:
        """One engine-window row. ``counts``: what the window counted, by
        column name (COUNT_COLUMNS; ``columns_of`` makes them of a
        program's vectors); a column it does not name is 0, a name that is
        no such column is an error. Idle-stable windows (no active slots,
        no waiters, no chunk work — same as the previous call) are
        skipped without touching the ring. Returns False when the row
        was REJECTED (disabled / frozen mid-capture) so the caller
        keeps accumulating its deltas instead of losing them."""
        if counts and not counts.keys() <= _COUNT_SET:
            raise KeyError(f"no count column named "
                           f"{sorted(counts.keys() - _COUNT_SET)}")
        if not self.enabled:
            return False
        if self.frozen:
            self._missed[0] += 1
            self._missed_t[0] = t_mono
            return False
        idle = active == 0 and waiting == 0 and chunks_inflight == 0 \
            and chunk_tokens == 0
        if idle and self._was_idle:
            self._skipped[0] += 1
            self._missed[0] += 1
            self._missed_t[0] = t_mono
            return True
        self._was_idle = idle
        with self._lock:
            i = self._idx
            cols = self._cols
            cols["t_mono"][i] = t_mono
            cols["dur_s"][i] = dur_s
            cols["active"][i] = active
            cols["waiting"][i] = waiting
            cols["free_pages"][i] = free_pages
            cols["chunk_tokens"][i] = chunk_tokens
            cols["chunks_inflight"][i] = chunks_inflight
            cols["preempts"][i] = preempts
            cols["brownout"][i] = brownout
            cols["stall_s"][i] = stall_s
            cols["step"][i] = step
            cols["tokens"][i] = tokens
            cols["period_s"][i] = period_s
            cols["host_s"][i] = host_s
            cols["wait_s"][i] = wait_s
            cols["idle_s"][i] = idle_s
            cols["rows"][i] = rows
            cols["page_bucket"][i] = page_bucket
            cols["prefilling"][i] = prefilling
            cols["admit_stop"][i] = admit_stop
            for name in COUNT_COLUMNS:
                cols[name][i] = 0.0
            if counts:
                for name, value in counts.items():
                    cols[name][i] = value
            cols["missed"][i] = self._missed[0]
            self._missed[0] = 0
            self._idx = (i + 1) % self.capacity
            if self._count < self.capacity:
                self._count += 1
        return True

    # -- freeze / dump --------------------------------------------------------
    def freeze(self, reason: str) -> bool:
        """Stop overwriting (first freeze wins). Returns True when this
        call did the freezing."""
        with self._lock:
            if self.frozen:
                return False
            self.frozen = True
            self.frozen_reason = reason
            return True

    def thaw(self) -> None:
        with self._lock:
            self.frozen = False
            self.frozen_reason = ""

    def clear(self) -> None:
        """Drop all recorded windows (tests, operator reset)."""
        with self._lock:
            self._idx = 0
            self._count = 0
            self._skipped[0] = 0
            self._missed[0] = 0
            self._was_idle = False

    def columns(self) -> dict:
        """A copy of the ring's columns, oldest row first. The lock is
        held for the copy alone (record() waits on it)."""
        with self._lock:
            n = self._count
            start = (self._idx - n) % self.capacity
            order = (start + np.arange(n)) % self.capacity
            return {name: col[order] for name, col in self._cols.items()}

    def dump(self) -> list[dict]:
        """Ring contents oldest-first as dicts (the /debug/flight and
        bundle payload)."""
        return rows_of(self.columns())

    def between(self, t_lo: float, t_hi: float) -> dict:
        """The rows with ``t_lo <= t_mono <= t_hi``, oldest first, as one
        numpy array per column, with ``missed``: how many rows of that
        span the ring does not hold (refused while frozen for a bundle
        capture, skipped as idle, or overwritten because the ring turned
        over since ``t_lo``; the last counts as at least one). A reader
        that finds ``missed`` above zero has a part of the span: it says
        so and returns nothing, it does not average what is left."""
        with self._lock:
            n = self._count
            start = (self._idx - n) % self.capacity
            order = (start + np.arange(n)) % self.capacity
            t = self._cols["t_mono"][order]
            keep = order[(t >= t_lo) & (t <= t_hi)]
            cols = {name: col[keep].copy()
                    for name, col in self._cols.items()}
            missed = int(cols["missed"].sum())
            if self._missed[0] and t_lo <= self._missed_t[0] <= t_hi:
                missed += int(self._missed[0])
            if n == self.capacity and n and t[0] > t_lo:
                missed += 1  # the ring turned over inside the span
        return {"rows": len(keep), "missed": missed, "columns": cols}

    @property
    def skipped_idle(self) -> int:
        return int(self._skipped[0])

    def meta(self) -> dict:
        return {"enabled": self.enabled, "capacity": self.capacity,
                "records": self._count, "skipped_idle": self.skipped_idle,
                "frozen": self.frozen, "frozen_reason": self.frozen_reason}


def rows_of(columns: dict) -> list[dict]:
    """``FlightRecorder.columns()`` as one dict a row."""
    lists = {name: (col.astype(np.int64) if name in _INT_FIELDS
                    else col).tolist() for name, col in columns.items()}
    return [dict(zip(lists, values)) for values in zip(*lists.values())]


# -- process-global recorder + anomaly capture ---------------------------------

_RECORDER = FlightRecorder(
    capacity=_env_int("DTPU_FLIGHT_CAPACITY", 8192))

#: Decode-stall trigger threshold consulted by the engine loop (0
#: disables the automatic trigger; the manual POST /debug/flight and
#: SLO-page triggers are independent of it).
stall_threshold_s = _env_float("DTPU_FLIGHT_STALL_S", 2.0)

_bundle_dir = os.environ.get("DTPU_FLIGHT_DIR", "/tmp/dtpu-flight")
_cooldown_s = _env_float("DTPU_FLIGHT_COOLDOWN_S", 300.0)
_last_trigger_t = -1e18
_trigger_lock = threading.Lock()
_metrics_registry = None
_config_fingerprint: dict = {}
triggers_total = 0


def get_recorder() -> FlightRecorder:
    return _RECORDER


def configure(metrics=None, config_fingerprint: dict | None = None,
              bundle_dir: str | None = None,
              stall_s: float | None = None,
              cooldown_s: float | None = None) -> None:
    """Entrypoint wiring: the metrics registry + config identity that
    go into bundles, and optional knob overrides."""
    global _metrics_registry, _config_fingerprint, _bundle_dir
    global stall_threshold_s, _cooldown_s
    if metrics is not None:
        _metrics_registry = metrics
    if config_fingerprint is not None:
        _config_fingerprint = config_fingerprint
    if bundle_dir is not None:
        _bundle_dir = bundle_dir
    if stall_s is not None:
        stall_threshold_s = stall_s
    if cooldown_s is not None:
        _cooldown_s = cooldown_s


def _fingerprint_payload() -> dict:
    body = json.dumps(_config_fingerprint, sort_keys=True, default=str)
    return {"config": _config_fingerprint,
            "sha256": hashlib.sha256(body.encode()).hexdigest()}


def capture_bundle(reason: str, out_dir: str | None = None,
                   ring: dict | None = None) -> str:
    """Write one diagnostic bundle NOW (blocking; call off the loop).
    ``ring``: the flight part as ``trigger`` copied it at the anomaly
    (default: the ring as it stands). Returns the bundle path."""
    from dynamo_tpu.runtime import tracing

    out_dir = out_dir or _bundle_dir
    os.makedirs(out_dir, exist_ok=True)
    rec = _RECORDER
    ts = time.time()
    safe_reason = "".join(c if c.isalnum() or c in "-_" else "_"
                          for c in reason)[:64]
    path = os.path.join(out_dir, f"flight-{int(ts)}-{safe_reason}.json")
    span_rec = tracing.get_recorder()
    bundle = {
        "reason": reason,
        "ts": ts,
        "flight": ring or {"meta": rec.meta(), "windows": rec.dump()},
        "spans": span_rec.export_chrome(),
        "metrics": (_metrics_registry.expose().decode()
                    if _metrics_registry is not None else None),
        # The recent decision-plane slice: one bundle is a complete
        # incident artifact — what the engine was doing (flight ring),
        # what requests were doing (spans), and WHY the fleet acted
        # (journal), side by side.
        "journal": journal_mod.get_journal().snapshot(limit=256),
        "config_fingerprint": _fingerprint_payload(),
    }
    # Whole or absent: whoever watches the directory (an operator's
    # script, a test) never reads half a bundle.
    with open(path + ".tmp", "w") as fh:
        json.dump(bundle, fh)
    os.replace(path + ".tmp", path)
    log.warning("flight bundle written: %s (%d windows, reason=%s)",
                path, len(bundle["flight"]["windows"]), reason)
    return path


def trigger(reason: str, clock=time.monotonic) -> bool:
    """Anomaly hook (SLO page, decode-stall spike): freeze the ring and
    write a bundle on a background thread. Throttled by the cooldown;
    returns True when a capture was actually started."""
    global _last_trigger_t, triggers_total
    with _trigger_lock:
        now = clock()
        if now - _last_trigger_t < _cooldown_s:
            return False
        _last_trigger_t = now
        triggers_total += 1
    # Frozen for the COPY alone, not while the bundle is serialised and
    # written (0.1 to 0.3 s, which cost three rows a capture): a row the
    # engine hands in meanwhile is kept, so a reader of the window that
    # holds the anomaly (``between``) still finds every row of it. A
    # trigger from the engine thread itself (the decode stall) loses none.
    _RECORDER.freeze(reason)
    try:
        meta, columns = _RECORDER.meta(), _RECORDER.columns()
    finally:
        _RECORDER.thaw()
    # Decision plane: an anomaly capture is itself a fleet decision.
    # Cause: the SLO page that pulled the trigger, else (decode-stall
    # path) the chaos injection that froze the engine, when either is
    # on the recent record.
    journal_mod.emit(
        EventKind.FLIGHT_BUNDLE,
        cause=(journal_mod.recent_ref(EventKind.SLO_ALERT_FIRE)
               if reason.startswith("slo_burn")
               else journal_mod.recent_ref(EventKind.CHAOS_INJECT)),
        reason=reason)

    def _write() -> None:
        try:
            capture_bundle(reason, ring={"meta": meta,
                                         "windows": rows_of(columns)})
        except Exception:  # noqa: BLE001 — diagnostics must never crash serving
            log.exception("flight bundle capture failed")

    threading.Thread(target=_write, name="flight-bundle",
                     daemon=True).start()
    return True


def on_slo_page(target: str, severity: str) -> None:
    """SloPlane.on_page adapter: page-severity alerts freeze + capture."""
    if severity == "fast":
        trigger(f"slo_burn_{target}")
