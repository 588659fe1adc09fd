"""Layered runtime configuration.

Capability parity with the reference's figment-based config
(lib/runtime/src/config.rs:66-214): defaults <- optional TOML file <- environment
variables. Env prefix is ``DTPU_`` (reference uses ``DYN_RUNTIME_``/``DYN_SYSTEM_``).
"""

from __future__ import annotations

import dataclasses
import os
import tomllib
from typing import Any

from dynamo_tpu.runtime.overload import OverloadConfig
from dynamo_tpu.runtime.slo import SloConfig

ENV_PREFIX = "DTPU_"


def _env(name: str, default: str | None = None) -> str | None:
    return os.environ.get(ENV_PREFIX + name, default)


def _env_bool(name: str, default: bool) -> bool:
    raw = _env(name)
    if raw is None:
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    raw = _env(name)
    return default if raw is None else int(raw)


def _env_float(name: str, default: float) -> float:
    raw = _env(name)
    return default if raw is None else float(raw)


def _apply_scalar_env(prefix: str, obj: Any) -> None:
    """Generic DTPU_<PREFIX>_<FIELD> override for all-scalar config
    dataclasses (OverloadConfig, SloConfig): the mapping is mechanical
    because every field is a plain bool/int/float/str."""
    for field in dataclasses.fields(type(obj)):
        raw = _env(f"{prefix}_" + field.name.upper())
        if raw is None:
            continue
        current = getattr(obj, field.name)
        if isinstance(current, bool):
            value: Any = raw.strip().lower() in ("1", "true", "yes", "on")
        elif isinstance(current, int):
            value = int(raw)
        elif isinstance(current, float):
            value = float(raw)
        else:
            value = raw
        setattr(obj, field.name, value)


@dataclasses.dataclass
class RuntimeConfig:
    """Node-level runtime settings.

    Mirrors reference RuntimeConfig (lib/runtime/src/config.rs:66) plus the
    DYN_SYSTEM_* health-server knobs (config.rs:85-123), collapsed into one
    dataclass because we have a single process model.
    """

    # Control plane (coordinator = etcd+NATS equivalent).
    coordinator_url: str = "tcp://127.0.0.1:4222"
    # Static mode: no discovery; endpoints are addressed directly
    # (reference: DistributedRuntime::from_settings_without_discovery,
    # lib/runtime/src/distributed.rs:178).
    static_mode: bool = False

    # Namespace default for this process.
    namespace: str = "dynamo"

    # Lease TTL for liveness (reference etcd lease, transports/etcd/lease.rs).
    lease_ttl_s: float = 10.0

    # Request-plane bind host for worker endpoints (0 => ephemeral port).
    bind_host: str = "127.0.0.1"
    advertise_host: str | None = None

    # System status server (reference system_status_server.rs:85-121).
    system_enabled: bool = False
    system_port: int = 0  # 0 => ephemeral

    # Async runtime sizing (reference worker/runtime threads; here: thread pools).
    num_worker_threads: int = 4

    # Graceful-shutdown drain timeout.
    shutdown_timeout_s: float = 10.0

    # How long a deregistered instance's in-flight streams may keep
    # draining before the request-plane connection is force-closed
    # (runtime/client.py retire-on-delete path).
    retire_drain_s: float = 30.0

    # Per-stream inter-frame deadline on the request plane: a stream
    # with no frames for this long fails typed (StreamIncompleteError
    # -> migration) instead of hanging on a zombie connection. 0
    # disables.
    stream_idle_timeout_s: float = 300.0

    # Overload defense (runtime/overload.py): adaptive admission,
    # deadline-aware shedding, per-worker circuit breakers, brownout.
    # TOML: an [overload] table; env: DTPU_OVERLOAD_<FIELD>.
    overload: OverloadConfig = dataclasses.field(
        default_factory=OverloadConfig)

    # SLO plane (runtime/slo.py): declarative targets, sliding-window
    # SLIs, multi-window burn-rate alerting, per-request accounting.
    # TOML: an [slo] table; env: DTPU_SLO_<FIELD>.
    slo: SloConfig = dataclasses.field(default_factory=SloConfig)

    @classmethod
    def from_settings(cls, path: str | None = None) -> "RuntimeConfig":
        """defaults <- TOML (DTPU_CONFIG_PATH or ``path``) <- DTPU_* env."""
        cfg = cls()
        toml_path = path or _env("CONFIG_PATH")
        if toml_path and os.path.exists(toml_path):
            # dtpu: ignore[blocking-call-in-async] -- tiny local settings file, read once at process startup (allowed-to-block leaf)
            with open(toml_path, "rb") as fh:
                data: dict[str, Any] = tomllib.load(fh)
            for field in dataclasses.fields(cls):
                if field.name in data:
                    value = data[field.name]
                    if field.name == "overload" and isinstance(value, dict):
                        value = OverloadConfig(**value)
                    if field.name == "slo" and isinstance(value, dict):
                        value = SloConfig(**value)
                    setattr(cfg, field.name, value)
        cfg.coordinator_url = _env("COORDINATOR_URL", cfg.coordinator_url)
        cfg.static_mode = _env_bool("STATIC_MODE", cfg.static_mode)
        cfg.namespace = _env("NAMESPACE", cfg.namespace)
        cfg.lease_ttl_s = _env_float("LEASE_TTL_S", cfg.lease_ttl_s)
        cfg.bind_host = _env("BIND_HOST", cfg.bind_host)
        cfg.advertise_host = _env("ADVERTISE_HOST", cfg.advertise_host)
        cfg.system_enabled = _env_bool("SYSTEM_ENABLED", cfg.system_enabled)
        cfg.system_port = _env_int("SYSTEM_PORT", cfg.system_port)
        cfg.num_worker_threads = _env_int("NUM_WORKER_THREADS", cfg.num_worker_threads)
        cfg.shutdown_timeout_s = _env_float("SHUTDOWN_TIMEOUT_S", cfg.shutdown_timeout_s)
        cfg.retire_drain_s = _env_float("RETIRE_DRAIN_S", cfg.retire_drain_s)
        cfg.stream_idle_timeout_s = _env_float(
            "STREAM_IDLE_TIMEOUT_S", cfg.stream_idle_timeout_s)
        _apply_scalar_env("OVERLOAD", cfg.overload)
        _apply_scalar_env("SLO", cfg.slo)
        return cfg

    @property
    def coordinator_addr(self) -> tuple[str, int]:
        url = self.coordinator_url
        if "://" in url:
            url = url.split("://", 1)[1]
        host, _, port = url.rpartition(":")
        return host or "127.0.0.1", int(port)
