"""Direct worker<->worker KV data plane — the NIXL role, TPU-first.

The reference moves KV blocks GPU<->GPU/host/disk over NIXL RDMA with a
layout/metadata handshake (lib/llm/src/block_manager/storage/nixl.rs,
block_manager/layout/nixl.rs, docs/architecture/dynamo_flow.md §NIXL).
This module is the TPU-native equivalent: a dedicated bulk-transfer plane
between workers that keeps KV bytes OFF the coordinator-discovered
request plane. Paths, fastest first, negotiated per transfer by a
metadata ticket (the role of NIXL's metadata exchange through etcd):

1. ``jax``  — ``jax.experimental.transfer``: device-to-device pull over
   ICI/DCN with no host staging. Probed at import-site: the probe
   actually stages and pulls a loopback array, because some PJRT
   builds advertise the module but raise UNIMPLEMENTED on
   ``PJRT_Client_CreateBuffersForAsyncHostToDevice``. Where the probe
   fails the plane uses the socket path; ``jax_probe_error`` says why.
2. ``socket`` — a direct TCP bulk plane: the source worker serves its
   extracted KV (host-staged via the runner's async D2H copy, which
   overlaps decode windows) on its OWN listening socket; the sink pulls
   with ``recv_into`` a preallocated buffer. One NIC hop, no msgpack
   re-framing of multi-MB payloads, no coordinator in the data path.
3. Inline parcel chunks on the request plane (llm/kv_transfer.py) — the
   v0 fallback, still emitted when the prefill worker has no plane.

The ticket contract: ``{"id", "addr", "jax_addr"?, "shape", "dtype",
"nbytes", "prompt_len"}`` rides the ordinary (small) response stream;
only the bulk bytes take the direct path.

The same socket also serves ``blocks`` requests — peer workers fetch KV
blocks from this worker's G2/G3 host tiers by block hash (the G4
remote-tier role, block_manager.rs:76-82 CacheLevel G1..G4), enabling
cross-worker prefix reuse without recompute.
"""

from __future__ import annotations

import asyncio
import socket
import struct
import threading
import time
from typing import Callable

import msgpack
import numpy as np

from dynamo_tpu.runtime import chaos
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.retry import Backoff, policies

log = get_logger("kv_plane")

_LEN = struct.Struct(">I")
_MAX_CTRL = 64 * 1024 * 1024  # control frames stay small; bulk is raw
_SEND_CHUNK = 4 << 20

STAGED_TTL_S = 120.0  # unseen tickets expire (sink crashed mid-handshake)


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def dtype_of(name: str) -> np.dtype:
    return np.dtype(_bf16() if name == "bfloat16" else name)


# -- sync frame helpers (server thread + client executor threads) -------------

def _send_ctrl(sock: socket.socket, obj: dict) -> None:
    body = msgpack.packb(obj, use_bin_type=True)
    sock.sendall(_LEN.pack(len(body)) + body)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError("peer closed mid-frame")
        got += r
    return bytes(buf)


def _recv_ctrl(sock: socket.socket) -> dict:
    (length,) = _LEN.unpack(_recv_exact(sock, 4))
    if length > _MAX_CTRL:
        raise ValueError(f"control frame too large: {length}")
    return msgpack.unpackb(_recv_exact(sock, length), raw=False)


def _send_bulk(sock: socket.socket, arr: np.ndarray) -> None:
    # uint8 view first: bfloat16 has no buffer-protocol format char, and
    # the view + memoryview is zero-copy from the numpy buffer either way.
    data = memoryview(np.ascontiguousarray(arr).view(np.uint8).reshape(-1))
    for off in range(0, len(data), _SEND_CHUNK):
        sock.sendall(data[off:off + _SEND_CHUNK])


def _recv_bulk_into(sock: socket.socket, buf: memoryview,
                    deadline: float | None = None) -> None:
    """Fill ``buf`` from the socket. ``deadline`` (time.monotonic value)
    bounds the WHOLE payload, not just each recv: per-recv timeouts
    reset on every arriving segment, so a trickling peer could stretch a
    multi-MB transfer arbitrarily while never tripping them (the G4
    consult's engine-thread budget must be a hard wall clock)."""
    got = 0
    n = len(buf)
    while got < n:
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise socket.timeout(
                    f"bulk recv deadline exceeded ({got}/{n} bytes)")
            sock.settimeout(remaining)
        r = sock.recv_into(buf[got:], n - got)
        if r == 0:
            raise ConnectionError("peer closed mid-payload")
        got += r


# -- jax.experimental.transfer probe ------------------------------------------

_jax_probe: bool | None = None
#: Why the probe said no ("Type: message"), for callers that report it
#: (chip_smoke.py prints it); None while unprobed or when it passed.
jax_probe_error: str | None = None
_jax_server = None


def jax_transfer_usable() -> bool:
    """True iff the device-to-device transfer engine actually works on
    this backend (loopback stage+pull; cached). Some PJRT builds
    advertise the module but raise UNIMPLEMENTED from the buffer-import
    hook, so a hasattr check is not enough."""
    global _jax_probe, jax_probe_error
    if _jax_probe is not None:
        return _jax_probe
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import transfer
        from jax.sharding import SingleDeviceSharding

        dev = jax.local_devices()[0]
        srv = transfer.start_transfer_server(dev.client)
        arr = jnp.arange(8, dtype=jnp.float32)
        # dtpu: ignore[blocking-call-in-async] -- one-shot 8-float capability probe at server construction
        arr.block_until_ready()
        srv.await_pull(0, [arr])
        conn = srv.connect(srv.address())
        out = conn.pull(0, [jax.ShapeDtypeStruct(
            arr.shape, arr.dtype, sharding=SingleDeviceSharding(dev))])
        np.asarray(out[0])
        _jax_probe = True
    except Exception as exc:  # noqa: BLE001 — any failure means "no"
        log.info("jax.experimental.transfer unusable on this backend "
                 "(%s: %s); KV plane uses the socket path",
                 type(exc).__name__, exc)
        jax_probe_error = f"{type(exc).__name__}: {exc}"
        _jax_probe = False
    return _jax_probe


def _get_jax_server():
    """Process-wide transfer server (lazy; only when the probe passed)."""
    global _jax_server
    if _jax_server is None:
        import jax
        from jax.experimental import transfer

        _jax_server = transfer.start_transfer_server(
            jax.local_devices()[0].client)
    return _jax_server


class _Staged:
    __slots__ = ("meta", "payload", "resolve", "t", "jax_uuid", "groups",
                 "in_progress")

    def __init__(self, meta: dict, payload, resolve, jax_uuid,
                 groups=None):
        self.meta = meta
        self.payload = payload      # np.ndarray once resolved
        self.resolve = resolve      # () -> np.ndarray, or None
        self.t = time.monotonic()
        self.jax_uuid = jax_uuid
        # Claimed by a pull connection (under the server lock): a second
        # concurrent pull of the same ticket must not also transmit —
        # double-serving runs grouped resolvers twice concurrently and
        # double-counts transfer metrics. Cleared if the send fails, so
        # the sink's retry still finds the parcel staged.
        self.in_progress = False
        # Pipelined socket path: [(n_pages, () -> np.ndarray), ...] —
        # page-group resolvers whose D2H copies were dispatched together
        # at extract time, so sending group i overlaps group i+1's copy
        # (reference offload.rs MAX_CONCURRENT_TRANSFERS overlap).
        self.groups = groups

    def array(self) -> np.ndarray:
        if self.payload is None:
            self.payload = self.resolve()
            self.resolve = None
        return self.payload


class KvPlaneServer:
    """Source side: stages KV parcels for direct pull and serves host-tier
    blocks to peers. One per worker process; thread-based (bulk socket
    I/O must not share the event loop with request-plane latency)."""

    def __init__(self, host: str = "127.0.0.1",
                 block_provider: Callable[[int], np.ndarray | None] | None = None,
                 use_jax_path: bool | None = None):
        self.host = host
        self.port = 0
        self.block_provider = block_provider
        self._staged: dict[int, _Staged] = {}
        self._next_id = 1
        self._lock = threading.Lock()
        self._sock: socket.socket | None = None
        self._threads: list[threading.Thread] = []
        self._running = False
        self._use_jax = (jax_transfer_usable() if use_jax_path is None
                         else use_jax_path)
        # Telemetry (stats(); engine/kv_metrics.py exports it).
        self.transfers = 0
        self.bytes_out = 0
        self.block_requests = 0
        self.blocks_served = 0

    def stats(self) -> dict:
        with self._lock:
            staged = len(self._staged)
        return {"transfers": self.transfers, "bytes_out": self.bytes_out,
                "block_requests": self.block_requests,
                "blocks_served": self.blocks_served, "staged": staged,
                "addr": self.address}

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((self.host, 0))
        self._sock.listen(16)
        self.port = self._sock.getsockname()[1]
        self._running = True
        t = threading.Thread(target=self._accept_loop, name="kv-plane",
                             daemon=True)
        t.start()
        self._threads.append(t)
        # Periodic GC: unclaimed tickets pin the extract's DEVICE buffer
        # through their resolve closure — a crashed sink must not hold
        # HBM past the TTL just because no new prefill triggers stage().
        g = threading.Thread(target=self._gc_loop, name="kv-plane-gc",
                             daemon=True)
        g.start()
        self._threads.append(g)
        log.info("KV plane listening on %s (jax path: %s)", self.address,
                 "on" if self._use_jax else "off")

    def _gc_loop(self) -> None:
        while self._running:
            time.sleep(min(30.0, STAGED_TTL_S / 4))
            with self._lock:
                self._gc_locked()

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def close(self) -> None:
        self._running = False
        if self._sock is not None:
            try:
                # shutdown() first: a thread blocked in accept() holds a
                # kernel reference, so close() alone leaves the port
                # listening until the accept returns.
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._sock.close()
            except OSError:
                pass
        with self._lock:
            self._staged.clear()

    # -- staging ------------------------------------------------------------
    def stage(self, kv=None, meta: dict | None = None,
              resolve: Callable[[], np.ndarray] | None = None,
              device_array=None, prompt_len: int | None = None,
              resolve_groups: list | None = None) -> dict:
        """Stage a parcel; returns the transfer ticket to send over the
        (small) response stream. Either ``kv`` (host array), ``resolve``
        (deferred host fetch — lets the D2H copy overlap decode windows;
        resolved on the plane thread at pull time), or ``resolve_groups``
        ([(n_pages, resolver)] page groups streamed pipelined: group i's
        socket send overlaps group i+1's D2H) must be given.
        ``device_array`` additionally registers the parcel with the jax
        transfer server for a zero-host-copy pull when both ends support
        it."""
        meta = dict(meta or {})
        if kv is not None:
            meta.setdefault("shape", list(kv.shape))
            meta.setdefault("dtype", str(kv.dtype))
        shape, dt = meta["shape"], dtype_of(meta["dtype"])
        meta["nbytes"] = int(np.prod(shape)) * dt.itemsize
        if prompt_len is not None:
            meta["prompt_len"] = prompt_len
        with self._lock:
            tid = self._next_id
            self._next_id += 1
            jax_uuid = None
            if self._use_jax and device_array is not None:
                jax_uuid = tid
                try:
                    _get_jax_server().await_pull(jax_uuid, [device_array])
                except Exception:  # noqa: BLE001 — fall back to socket
                    log.exception("jax-path staging failed; socket only")
                    jax_uuid = None
            self._staged[tid] = _Staged(meta, kv, resolve, jax_uuid,
                                        groups=resolve_groups)
            self._gc_locked()
        ticket = {"id": tid, "addr": self.address, **meta}
        if jax_uuid is not None:
            ticket["jax_addr"] = _get_jax_server().address()
            ticket["jax_uuid"] = jax_uuid
        return ticket

    def _gc_locked(self) -> None:
        now = time.monotonic()
        dead = [tid for tid, s in self._staged.items()
                if now - s.t > STAGED_TTL_S]
        for tid in dead:
            del self._staged[tid]
        if dead:
            log.warning("expired %d unclaimed KV transfers", len(dead))

    # -- server loops --------------------------------------------------------
    def _accept_loop(self) -> None:
        while self._running:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            while True:
                try:
                    req = _recv_ctrl(conn)
                except (ConnectionError, OSError):
                    return
                op = req.get("op")
                if op == "pull":
                    self._handle_pull(conn, req)
                elif op == "blocks":
                    self._handle_blocks(conn, req)
                elif op == "done":
                    # Fire-and-forget: a jax-path pull completed — drop
                    # the staged entry now instead of pinning the device
                    # array until the TTL.
                    with self._lock:
                        self._staged.pop(int(req.get("id", -1)), None)
                else:
                    _send_ctrl(conn, {"err": f"unknown op {op!r}"})
        except (ConnectionError, OSError, ValueError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _handle_pull(self, conn: socket.socket, req: dict) -> None:
        tid = int(req["id"])
        if chaos.ACTIVE:
            stall = chaos.value("kv.stall_ms", "kv")
            if stall is not None:
                time.sleep(stall / 1000.0)
            if chaos.fire("kv.pull_error", "kv"):
                _send_ctrl(conn, {"err": "chaos: injected pull error"})
                return
        busy = False
        with self._lock:
            staged = self._staged.get(tid)
            if staged is not None and staged.in_progress:
                # Another connection is already transmitting this ticket:
                # serving it twice would run grouped resolvers
                # concurrently and double-count transfer metrics.
                staged, busy = None, True
            elif staged is not None:
                staged.in_progress = True
        if staged is None:
            _send_ctrl(conn, {"err": "transfer already in progress" if busy
                              else "unknown or expired transfer id"})
            return
        # The entry stays staged until the bulk send COMPLETES: a
        # transient network failure mid-send would otherwise drop the
        # parcel permanently and force the sink to re-prefill locally
        # (its retry would see "expired transfer id"). The in_progress
        # claim is released on failure so that retry can win the ticket;
        # the TTL GC remains the backstop for sinks that never come back.
        served = False
        resolve_err: str | None = None
        try:
            served, resolve_err = self._transmit_staged(conn, staged)
        finally:
            # Release the claim BEFORE any error frame goes out: the sink
            # retries the moment it reads the error, and must not find
            # the ticket still claimed by this failed attempt.
            with self._lock:
                if served:
                    self._staged.pop(tid, None)
                else:
                    staged.in_progress = False
        if resolve_err is not None:
            _send_ctrl(conn, {"err": resolve_err})

    def _transmit_staged(self, conn: socket.socket,
                         staged: _Staged) -> tuple[bool, str | None]:
        """Resolve and send one staged parcel. Returns (served, err):
        served True only once every bulk byte is on the wire; err is a
        resolve-failure message for the caller to report AFTER releasing
        the in-progress claim."""
        if staged.groups is not None:
            # Pipelined page groups: group i rides the wire while group
            # i+1's D2H copy (dispatched at extract time) completes.
            try:
                first = np.ascontiguousarray(staged.groups[0][1]())
            except Exception as exc:  # noqa: BLE001
                log.exception("staged KV group resolve failed")
                return False, f"resolve failed: {exc}"
            _send_ctrl(conn, {"ok": True, **staged.meta,
                              "groups": [n for n, _ in staged.groups]})
            sent = first.nbytes
            _send_bulk(conn, first)
            for _, resolver in staged.groups[1:]:
                arr = np.ascontiguousarray(resolver())
                _send_bulk(conn, arr)
                sent += arr.nbytes
            self.transfers += 1
            self.bytes_out += sent
            return True, None
        try:
            arr = np.ascontiguousarray(staged.array())
        except Exception as exc:  # noqa: BLE001 — resolve() device fault
            log.exception("staged KV resolve failed")
            return False, f"resolve failed: {exc}"
        _send_ctrl(conn, {"ok": True, **staged.meta})
        if chaos.ACTIVE and chaos.fire("kv.partial", "kv"):
            # Send half the parcel, then sever: the sink's short read
            # must surface as a connection error and the parcel must
            # stay staged for its retry.
            data = memoryview(arr.view(np.uint8).reshape(-1))
            conn.sendall(data[:max(1, arr.nbytes // 2)])
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            return False, None
        _send_bulk(conn, arr)
        self.transfers += 1
        self.bytes_out += arr.nbytes
        return True, None

    def _handle_blocks(self, conn: socket.socket, req: dict) -> None:
        """G4 remote-tier serve: return which of the requested block hashes
        this worker holds in its host tiers, with their bytes, stopping at
        the first miss (prefix semantics: later blocks are useless without
        earlier ones)."""
        self.block_requests += 1
        provider = self.block_provider
        hashes = [int(h) for h in req.get("hashes", [])]
        limit = int(req.get("max", 64))
        found: list[np.ndarray] = []
        found_hashes: list[int] = []
        if provider is not None:
            for h in hashes[:limit]:
                kv = provider(h)
                if kv is None:
                    break
                found.append(np.ascontiguousarray(kv))
                found_hashes.append(h)
        if not found:
            _send_ctrl(conn, {"ok": True, "hashes": [], "shape": [],
                              "dtype": "", "nbytes": 0})
            return
        stacked = np.stack(found)  # [n, 2, L, Nkv, page, D]
        _send_ctrl(conn, {"ok": True, "hashes": found_hashes,
                          "shape": list(stacked.shape),
                          "dtype": str(stacked.dtype),
                          "nbytes": stacked.nbytes})
        _send_bulk(conn, stacked)
        self.blocks_served += len(found)


class KvPlaneClient:
    """Sink side: pulls staged parcels / peer host-tier blocks. Blocking
    socket I/O runs on executor threads; per-address connections are
    cached (pulls from the same prefill worker reuse one TCP stream)."""

    def __init__(self, timeout: float = 30.0):
        # addr -> (socket, per-connection lock): pulls run on executor
        # threads, and two concurrent request/response cycles on one
        # socket would interleave frames — the lock serializes the full
        # cycle per connection. ``timeout`` bounds connect AND each recv:
        # callers on latency-sensitive threads (the engine's G4 consult)
        # pass a small value so a blackholed peer can't stall them long.
        self.timeout = timeout
        self._conns: dict[str, tuple[socket.socket, threading.Lock]] = {}
        self._lock = threading.Lock()
        self.transfers = 0
        self.bytes_in = 0
        self.jax_pulls = 0
        # Pull-latency aggregates (count + wall-clock sum): rate(sum)/
        # rate(count) is the fleet's mean pull latency on /metrics.
        self.pull_seconds_total = 0.0
        self.pull_failures = 0
        self._use_jax = None  # probed on first jax-path ticket

    def stats(self) -> dict:
        return {"transfers": self.transfers, "bytes_in": self.bytes_in,
                "jax_pulls": self.jax_pulls,
                "pull_seconds_total": self.pull_seconds_total,
                "pull_failures": self.pull_failures}

    # -- sync core (executor) ------------------------------------------------
    def _conn_for(self, addr: str) -> tuple[socket.socket, threading.Lock]:
        with self._lock:
            entry = self._conns.get(addr)
        if entry is not None:
            return entry
        host, port = addr.rsplit(":", 1)
        sock = socket.create_connection((host, int(port)),
                                        timeout=self.timeout)
        sock.settimeout(self.timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        with self._lock:
            old = self._conns.get(addr)
            if old is not None:
                sock.close()
                return old
            entry = (sock, threading.Lock())
            self._conns[addr] = entry
        return entry

    def _drop_conn(self, addr: str) -> None:
        with self._lock:
            entry = self._conns.pop(addr, None)
        if entry is not None:
            try:
                entry[0].close()
            except OSError:
                pass

    def _pull_jax(self, ticket: dict) -> np.ndarray | None:
        if self._use_jax is None:
            self._use_jax = jax_transfer_usable()
        if not self._use_jax or "jax_addr" not in ticket:
            return None
        try:
            import jax
            from jax.sharding import SingleDeviceSharding

            conn = _get_jax_server().connect(ticket["jax_addr"])
            dev = jax.local_devices()[0]
            spec = jax.ShapeDtypeStruct(
                tuple(ticket["shape"]), dtype_of(ticket["dtype"]),
                sharding=SingleDeviceSharding(dev))
            out = conn.pull(int(ticket["jax_uuid"]), [spec])
            self.jax_pulls += 1
            return np.asarray(out[0])
        except Exception:  # noqa: BLE001 — fall through to the socket path
            log.exception("jax-path pull failed; falling back to socket")
            return None

    def pull_sync(self, ticket: dict) -> np.ndarray:
        t0 = time.monotonic()
        try:
            out = self._pull_sync_inner(ticket)
        except (ConnectionError, OSError):
            self.pull_failures += 1
            raise
        finally:
            self.pull_seconds_total += time.monotonic() - t0
        return out

    def _pull_sync_inner(self, ticket: dict) -> np.ndarray:
        out = self._pull_jax(ticket)
        if out is not None:
            self.transfers += 1
            try:  # release the server's staged entry (best-effort)
                sock, conn_lock = self._conn_for(ticket["addr"])
                with conn_lock:
                    _send_ctrl(sock, {"op": "done",
                                      "id": int(ticket["id"])})
            except (ConnectionError, OSError):
                pass  # TTL GC covers it
            return out
        # Transient failures (reset mid-transfer, a racing pull holding
        # the in-progress claim) retry through the unified policy — the
        # parcel stays staged on the source until every byte lands, so a
        # retry finds it. An expired/unknown ticket can never succeed:
        # fail fast and let the caller prefill locally.
        backoff = Backoff(policies.KV_PULL)
        while True:
            try:
                return self._pull_socket_once(ticket)
            except (ConnectionError, OSError) as exc:
                if "expired transfer" in str(exc) or not backoff.sleep_sync():
                    raise
                log.warning("KV pull failed (%s); retrying", exc)

    def _pull_socket_once(self, ticket: dict) -> np.ndarray:
        addr = ticket["addr"]
        sock, conn_lock = self._conn_for(addr)
        try:
            with conn_lock:
                _send_ctrl(sock, {"op": "pull", "id": int(ticket["id"])})
                resp = _recv_ctrl(sock)
                if "err" in resp:
                    raise ConnectionError(f"KV pull refused: {resp['err']}")
                shape = resp["shape"]
                dt = dtype_of(resp["dtype"])
                if "groups" in resp:
                    # Pipelined page groups along the pages axis (3):
                    # reassemble into the full parcel as they arrive.
                    full = np.empty(shape, dt)
                    off = 0
                    for g in resp["groups"]:
                        gshape = list(shape)
                        gshape[3] = g
                        buf = np.empty(
                            int(np.prod(gshape)) * dt.itemsize, np.uint8)
                        _recv_bulk_into(sock, memoryview(buf))
                        full[:, :, :, off:off + g] = \
                            buf.view(dt).reshape(gshape)
                        off += g
                    self.transfers += 1
                    self.bytes_in += full.nbytes
                    return full
                buf = np.empty(int(resp["nbytes"]), np.uint8)
                _recv_bulk_into(sock, memoryview(buf))
        except (ConnectionError, OSError):
            self._drop_conn(addr)
            raise
        self.transfers += 1
        self.bytes_in += buf.nbytes
        return buf.view(dt).reshape(shape)

    def fetch_blocks_sync(self, addr: str, hashes: list[int],
                          max_blocks: int = 64,
                          timeout: float | None = None
                          ) -> tuple[list[int], np.ndarray | None]:
        """G4: ask a peer for a consecutive run of block hashes from its
        host tiers. Returns (hashes found, [n, 2, L, Nkv, page, D]).
        ``timeout`` overrides the connection's per-recv timeout for this
        cycle (the G4 consult's overall deadline is the caller's)."""
        sock, conn_lock = self._conn_for(addr)
        deadline = (time.monotonic() + timeout) if timeout is not None \
            else None
        try:
            with conn_lock:
                if timeout is not None:
                    sock.settimeout(max(0.01, timeout))
                _send_ctrl(sock, {"op": "blocks", "hashes": hashes,
                                  "max": max_blocks})
                resp = _recv_ctrl(sock)
                if "err" in resp:
                    raise ConnectionError(
                        f"block fetch refused: {resp['err']}")
                if not resp["hashes"]:
                    if timeout is not None:
                        sock.settimeout(self.timeout)
                    return [], None
                dt = dtype_of(resp["dtype"])
                buf = np.empty(int(resp["nbytes"]), np.uint8)
                _recv_bulk_into(sock, memoryview(buf), deadline=deadline)
                if timeout is not None:
                    sock.settimeout(self.timeout)
        except (ConnectionError, OSError):
            self._drop_conn(addr)
            raise
        self.bytes_in += buf.nbytes
        return resp["hashes"], buf.view(dt).reshape(resp["shape"])

    # -- async wrappers ------------------------------------------------------
    async def pull(self, ticket: dict) -> np.ndarray:
        from dynamo_tpu.runtime.tracing import span

        with span("kv.plane.pull", ticket=ticket.get("id"),
                  nbytes=ticket.get("nbytes")):
            return await asyncio.get_running_loop().run_in_executor(
                None, self.pull_sync, ticket)

    async def fetch_blocks(self, addr: str, hashes: list[int],
                           max_blocks: int = 64):
        return await asyncio.get_running_loop().run_in_executor(
            None, self.fetch_blocks_sync, addr, hashes, max_blocks)

    def close(self) -> None:
        with self._lock:
            conns, self._conns = dict(self._conns), {}
        for sock, _ in conns.values():
            try:
                sock.close()
            except OSError:
                pass


class RemoteBlockSource:
    """G4 remote tier: fetch KV blocks from PEER workers' host tiers by
    block hash (reference CacheLevel G4, block_manager.rs:76-82 + the
    distributed leader/worker's cross-worker reuse role). The engine's
    KVBM consults it when a prefix extension misses G1/G2/G3 — one
    bounded round trip per peer, first hit wins; content-hashed blocks
    make the result trustworthy regardless of which worker computed
    them.

    ``peers`` is swapped wholesale by the worker's coordinator watcher
    (kvplane/ registrations), so the engine thread only ever reads a
    consistent list.

    Per-peer breaker discipline (runtime/retry.py): a failing peer
    opens for a cooldown that walks the G4_PEER_BREAKER policy curve —
    successive failures back off exponentially, a post-cooldown consult
    is the half-open probe, and one success resets the curve. Every
    consult outcome journals as a ``kv_peer_pull`` event
    (runtime/journal.py) so /debug/timeline shows cross-worker reuse —
    and its failures — as part of the fleet's decision history."""

    # G4 fetches run on the ENGINE thread between windows: the WHOLE
    # consult — every peer together — gets one sub-window budget, so
    # neither a dead peer nor a slow-but-alive one can stall unrelated
    # in-flight decode streams for more than ~one window period.
    # Recomputing the prefix is always the cheap safe fallback.
    G4_BUDGET_S = 0.1

    def __init__(self, client: KvPlaneClient | None = None,
                 self_addr: str | None = None, max_peers: int = 4,
                 budget_s: float | None = None):
        from dynamo_tpu.runtime.retry import policies
        self.budget_s = self.G4_BUDGET_S if budget_s is None else budget_s
        self.client = client or KvPlaneClient(timeout=self.budget_s)
        self.self_addr = self_addr
        self.max_peers = max_peers
        self.peers: list[str] = []
        self.breaker_policy = policies.G4_PEER_BREAKER
        self._cooldown: dict[str, float] = {}  # addr -> half-open time
        self._fail_streak: dict[str, int] = {}  # addr -> breaker curve pos
        self.fetched_blocks = 0
        self.fetch_failures = 0
        self.slow_peer_cooldowns = 0
        self.breaker_open_skips = 0   # consults skipped on open breakers

    def stats(self) -> dict:
        now = time.monotonic()
        return {"peers": len(self.peers),
                "fetched_blocks": self.fetched_blocks,
                "fetch_failures": self.fetch_failures,
                "slow_peer_cooldowns": self.slow_peer_cooldowns,
                "breaker_open_skips": self.breaker_open_skips,
                "breakers_open": sum(1 for t in self._cooldown.values()
                                     if t > now),
                **{f"client_{k}": v for k, v in self.client.stats().items()}}

    def _open_breaker(self, addr: str, reason: str) -> None:
        """One more failure on this peer: advance its breaker curve and
        cool it down for the policy's delay at that position (no jitter
        rng threading needed — the curve IS the discipline)."""
        streak = self._fail_streak.get(addr, 0)
        delay = self.breaker_policy.delay(streak)
        self._fail_streak[addr] = streak + 1
        self._cooldown[addr] = time.monotonic() + delay
        log.warning("G4 peer %s %s; breaker open %.1fs (streak %d)",
                    addr, reason, delay, streak + 1)

    def _note_success(self, addr: str) -> None:
        self._cooldown.pop(addr, None)
        self._fail_streak.pop(addr, None)

    def drop_peer(self, addr: str) -> None:
        """Fleet-membership hook (worker_leave / scale-in): forget the
        peer NOW — its address leaves the consult list and its breaker
        state dies with it, instead of waiting out staleness TTLs. A
        worker that later rejoins on the same address starts with a
        clean breaker rather than inheriting the dead incarnation's
        open curve."""
        self.peers = [a for a in self.peers if a != addr]
        self._cooldown.pop(addr, None)
        self._fail_streak.pop(addr, None)

    def fetch(self, hashes: list[int], max_blocks: int,
              trace_id: str | None = None) -> list[tuple[int, np.ndarray]]:
        """SYNC (engine thread, between windows): returns the longest
        consecutive run of requested blocks any single peer holds,
        giving the whole consult ``budget_s`` of wall clock."""
        from dynamo_tpu.runtime import journal
        from dynamo_tpu.runtime.journal import EventKind

        deadline = time.monotonic() + self.budget_s
        for addr in list(self.peers)[:self.max_peers]:
            if addr == self.self_addr or not addr:
                continue
            now = time.monotonic()
            remaining = deadline - now
            if remaining <= 0:
                break
            if self._cooldown.get(addr, 0.0) > now:
                self.breaker_open_skips += 1
                continue
            t0 = now
            try:
                found, arr = self.client.fetch_blocks_sync(
                    addr, hashes, max_blocks, timeout=remaining)
            except (ConnectionError, OSError) as exc:
                self.fetch_failures += 1
                slow = isinstance(exc, (socket.timeout, TimeoutError))
                if slow:
                    self.slow_peer_cooldowns += 1
                self._open_breaker(addr,
                                   "too slow" if slow else "unreachable")
                journal.emit(
                    EventKind.KV_PEER_PULL, trace_id=trace_id,
                    outcome="timeout" if slow else "error", peer=addr,
                    cause=journal.recent_ref(EventKind.CHAOS_INJECT))
                continue
            if time.monotonic() - t0 > self.budget_s:
                # Answered, but ate the whole consult budget: treat as
                # slow and stop consulting it for a while.
                self.slow_peer_cooldowns += 1
                self._open_breaker(addr, "consult overran budget")
            else:
                self._note_success(addr)
            if found:
                self.fetched_blocks += len(found)
                journal.emit(
                    EventKind.KV_PEER_PULL, trace_id=trace_id,
                    outcome="ok", peer=addr, blocks=len(found),
                    nbytes=int(arr.nbytes),
                    cause=journal.recent_ref(EventKind.KV_DEMOTE))
                return [(h, arr[i]) for i, h in enumerate(found)]
        return []
