"""Direct KV data plane tests (llm/kv_plane.py — the NIXL role).

Unit: stage/pull round-trips (eager + deferred resolve), expired tickets,
peer block fetch (G4 op). E2E: the disagg stack moving its parcel over
the plane's direct socket path with ZERO inline kv_chunk frames, token-
identical to aggregated, including the TP-mismatch re-shard.
Reference semantics: lib/llm/src/block_manager/storage/nixl.rs (RDMA KV
plane), docs/architecture/dynamo_flow.md §NIXL (metadata handshake).
"""

import asyncio

import numpy as np
import pytest
from conftest import async_test

from dynamo_tpu.llm.kv_plane import KvPlaneClient, KvPlaneServer
from test_disagg import (
    _prompt, run_agg, run_request, start_stack, stop_stack)


def _rand_kv(shape=(2, 2, 2, 3, 16, 32), seed=0):
    import ml_dtypes
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(ml_dtypes.bfloat16)


@pytest.fixture
def plane():
    server = KvPlaneServer(use_jax_path=False)
    server.start()
    client = KvPlaneClient()
    yield server, client
    client.close()
    server.close()


@async_test
async def test_stage_pull_roundtrip(plane):
    server, client = plane
    kv = _rand_kv()
    ticket = server.stage(kv=kv, prompt_len=48)
    assert ticket["prompt_len"] == 48
    assert ticket["nbytes"] == kv.nbytes
    out = await client.pull(ticket)
    assert out.dtype == kv.dtype
    np.testing.assert_array_equal(kv.view(np.uint16), out.view(np.uint16))
    assert client.transfers == 1 and client.bytes_in == kv.nbytes
    for _ in range(200):  # server thread counts after its last send;
        # bytes_out is written LAST, so poll on it, not transfers.
        if server.bytes_out == kv.nbytes:
            break
        await asyncio.sleep(0.01)
    assert server.transfers == 1 and server.bytes_out == kv.nbytes


@async_test
async def test_deferred_resolve_runs_on_pull(plane):
    """The staged parcel may be a deferred device fetch: resolve() runs on
    the plane thread at pull time (overlap with the engine's windows)."""
    server, client = plane
    kv = _rand_kv(seed=1)
    calls = []

    def resolve():
        calls.append(1)
        return kv

    ticket = server.stage(meta={"shape": list(kv.shape),
                                "dtype": "bfloat16"}, resolve=resolve)
    assert not calls  # staging must not resolve
    out = await client.pull(ticket)
    assert calls == [1]
    np.testing.assert_array_equal(kv.view(np.uint16), out.view(np.uint16))


@async_test
async def test_pull_twice_and_unknown_id_fail(plane):
    server, client = plane
    kv = _rand_kv(seed=2)
    ticket = server.stage(kv=kv)
    await client.pull(ticket)
    with pytest.raises((ConnectionError, OSError)):
        await client.pull(ticket)  # one-shot: consumed
    with pytest.raises((ConnectionError, OSError)):
        await client.pull({**ticket, "id": 999999})


@async_test
async def test_concurrent_pulls_serve_exactly_once(plane):
    """Two racing pulls of the same ticket: only one may transmit (the
    other gets 'transfer already in progress'), so transfers/bytes_out
    count the parcel once and grouped resolvers never run concurrently
    (round-5 ADVICE low: _handle_pull double-serve)."""
    import threading

    server, client = plane
    kv = _rand_kv(seed=7)
    release = threading.Event()
    calls = []

    def resolve():
        calls.append(1)
        release.wait(timeout=10)  # hold the first pull mid-serve
        return kv

    ticket = server.stage(meta={"shape": list(kv.shape),
                                "dtype": "bfloat16"}, resolve=resolve)
    first = asyncio.create_task(client.pull(ticket))
    for _ in range(200):  # wait until pull #1 has claimed the ticket
        if calls:
            break
        await asyncio.sleep(0.01)
    assert calls == [1]
    # Second puller on its own connection while #1 is mid-serve.
    rival = KvPlaneClient()
    try:
        with pytest.raises((ConnectionError, OSError)):
            await rival.pull(ticket)
        release.set()
        out = await first
        np.testing.assert_array_equal(kv.view(np.uint16), out.view(np.uint16))
    finally:
        release.set()
        rival.close()
    for _ in range(200):
        if server.bytes_out:
            break
        await asyncio.sleep(0.01)
    assert server.transfers == 1 and server.bytes_out == kv.nbytes
    assert calls == [1]


@async_test
async def test_failed_send_restages_ticket(plane):
    """A pull whose resolve fails must release the in-progress claim so
    a retry still finds the parcel staged. A single transient fault is
    now absorbed by the client's own unified retry (runtime/retry.py,
    policies.KV_PULL); a persistent fault exhausts it and raises, and a
    LATER client still finds the parcel staged once the fault clears."""
    server, client = plane
    kv = _rand_kv(seed=8)
    # One transient fault: the same pull() call recovers by itself.
    boom = [True]

    def resolve():
        if boom.pop() if boom else False:
            raise RuntimeError("device fault")
        return kv

    ticket = server.stage(meta={"shape": list(kv.shape),
                                "dtype": "bfloat16"}, resolve=resolve)
    out = await client.pull(ticket)
    np.testing.assert_array_equal(kv.view(np.uint16), out.view(np.uint16))

    # Persistent fault (outlives the retry policy's attempts): the pull
    # raises, but the parcel stays staged for a later retry.
    # 6 faults: the first pull's 4 attempts (1 + 3 retries) all fail;
    # the later client fails twice more, then succeeds.
    boom2 = [True] * 6

    def resolve2():
        if boom2.pop() if boom2 else False:
            raise RuntimeError("device fault")
        return kv

    ticket2 = server.stage(meta={"shape": list(kv.shape),
                                 "dtype": "bfloat16"}, resolve=resolve2)
    with pytest.raises((ConnectionError, OSError)):
        await client.pull(ticket2)
    retry = KvPlaneClient()
    try:
        out = await retry.pull(ticket2)
        np.testing.assert_array_equal(kv.view(np.uint16), out.view(np.uint16))
    finally:
        retry.close()


@async_test
async def test_large_parcel_multi_chunk(plane):
    """Parcels far larger than the send chunk stream intact."""
    server, client = plane
    kv = np.arange(6 << 20, dtype=np.float32).reshape(2, 3 << 20 >> 1, 2)
    ticket = server.stage(kv=kv)
    out = await client.pull(ticket)
    np.testing.assert_array_equal(kv, out)


@async_test
async def test_block_fetch_prefix_semantics(plane):
    """The G4 op returns the consecutive run of requested hashes the peer
    holds, stopping at the first miss."""
    server, client = plane
    store = {10: _rand_kv((2, 2, 2, 16, 32), seed=3),
             11: _rand_kv((2, 2, 2, 16, 32), seed=4),
             13: _rand_kv((2, 2, 2, 16, 32), seed=5)}
    server.block_provider = store.get
    hashes, blocks = await client.fetch_blocks(
        server.address, [10, 11, 12, 13])
    assert hashes == [10, 11]  # 12 missing stops the run; 13 unreachable
    assert blocks.shape[0] == 2
    np.testing.assert_array_equal(blocks[0].view(np.uint16),
                                  store[10].view(np.uint16))
    np.testing.assert_array_equal(blocks[1].view(np.uint16),
                                  store[11].view(np.uint16))
    hashes, blocks = await client.fetch_blocks(server.address, [99])
    assert hashes == [] and blocks is None
    assert server.block_requests == 2 and server.blocks_served == 2


@async_test
async def test_no_provider_returns_empty(plane):
    server, client = plane
    hashes, blocks = await client.fetch_blocks(server.address, [1, 2])
    assert hashes == [] and blocks is None


@async_test
async def test_quant_parcel_stage_pull_roundtrip(plane):
    """Packed int8+scales parcels (--quant-kv, engine/kv_quant.py) ride
    the plane as uint8 and round-trip byte-identical through stage ->
    pull — at (D+4)/(2D) of the bf16 parcel bytes."""
    from dynamo_tpu.engine.kv_quant import pack_parcel, unpack_parcel

    server, client = plane
    rng = np.random.default_rng(6)
    d = 32
    data = rng.integers(-127, 128, size=(2, 2, 2, 3, 16, d), dtype=np.int8)
    scale = rng.random((2, 2, 2, 3, 16)).astype(np.float32)
    kv = pack_parcel(data, scale)
    assert kv.dtype == np.uint8
    ticket = server.stage(kv=kv, prompt_len=48)
    assert ticket["dtype"] == "uint8"
    assert ticket["nbytes"] == kv.nbytes
    bf16_nbytes = data.size * 2
    assert kv.nbytes / bf16_nbytes == (d + 4) / (2 * d)
    out = await client.pull(ticket)
    assert out.dtype == np.uint8
    np.testing.assert_array_equal(out, kv)
    d2, s2 = unpack_parcel(out)
    np.testing.assert_array_equal(d2, data)
    np.testing.assert_array_equal(s2, scale)


# ---------------------------------------------------------------------------
# e2e: disagg over the plane
# ---------------------------------------------------------------------------

@async_test
async def test_disagg_over_plane_token_identical():
    """1P+1D with the KV parcel on the direct plane: greedy output matches
    the aggregated engine, exactly one plane transfer, and no inline
    kv_chunk ever rides the request plane."""
    s = await start_stack(max_local=8, plane=True)
    try:
        prompt = _prompt(30, 24)
        got = await run_request(s.caller, prompt, 10)
        assert s.handler.remote_prefills == 1
        assert s.handler.remote_failures == 0
        assert s.plane.transfers == 1
        assert s.handler.plane_client.transfers == 1
        ref = await run_agg(prompt, 10)
        assert got == ref
    finally:
        await stop_stack(s)


@async_test(timeout=240)
async def test_disagg_over_plane_quantized_kv():
    """1P+1D with --quant-kv int8 on BOTH ends: the parcel crosses the
    plane as the packed uint8 form at ~half the bf16 bulk bytes, and the
    greedy output matches the quantized aggregated engine exactly."""
    from dynamo_tpu.engine.kv_quant import KV_SCALE_BYTES

    s = await start_stack(max_local=8, plane=True,
                          engine_kw={"quant_kv": "int8"})
    try:
        prompt = _prompt(30, 24)
        got = await run_request(s.caller, prompt, 10)
        assert s.handler.remote_prefills == 1
        assert s.handler.remote_failures == 0
        assert s.plane.transfers == 1
        ref = await run_agg(prompt, 10, quant_kv="int8")
        assert got == ref
        # Bulk bytes ≈ halved: the packed parcel is (D+4)/(2D) of bf16.
        spec = s.p_engine.runner.spec
        n_pages = -(-len(prompt) // s.p_engine.config.page_size)
        bf16_bytes = (2 * spec.num_layers * spec.num_kv_heads * n_pages
                      * s.p_engine.config.page_size * spec.head_dim * 2)
        expected = bf16_bytes * (spec.head_dim + KV_SCALE_BYTES) \
            // (2 * spec.head_dim)
        assert s.plane.bytes_out == expected
        assert s.plane.bytes_out < 0.6 * bf16_bytes
    finally:
        await stop_stack(s)


@async_test
async def test_disagg_over_plane_tp_mismatch():
    """tp=1 prefill -> tp=2 decode over the plane: the deferred resolve
    dedups KV-head replicas and the decode mesh re-shards on upload."""
    s = await start_stack(prefill_tp=1, decode_tp=2, max_local=8, plane=True)
    try:
        prompt = _prompt(31, 24)
        got = await run_request(s.caller, prompt, 8)
        assert s.handler.remote_prefills == 1
        assert s.plane.transfers == 1
        ref = await run_agg(prompt, 8, tp=2)
        assert got == ref
    finally:
        await stop_stack(s)


@async_test
async def test_plane_death_falls_back_to_local_prefill():
    """Plane server dies between staging and pull: the decode worker
    degrades to local prefill instead of failing the request."""
    s = await start_stack(max_local=8, plane=True)
    try:
        s.plane.close()  # tickets still issued; pulls now fail
        prompt = _prompt(32, 24)
        got = await run_request(s.caller, prompt, 6)
        assert len(got) == 6
        assert s.handler.remote_failures == 1
        assert s.handler.local_prefills == 1
    finally:
        await stop_stack(s)


# ---------------------------------------------------------------------------
# jax.experimental.transfer device path (the NIXL role's defining feature)
# ---------------------------------------------------------------------------

@async_test
async def test_jax_device_path_stage_pull():
    """The device-to-device path END TO END on a backend whose PJRT
    supports the transfer engine (pure-CPU jax here; a backend that raises
    UNIMPLEMENTED falls back to the socket path): stage(device_array)
    -> client _pull_jax -> bytes identical, no socket bulk transfer, and
    the fire-and-forget "done" releases the staged entry."""
    import jax.numpy as jnp

    from dynamo_tpu.llm.kv_plane import jax_transfer_usable

    if not jax_transfer_usable():
        pytest.skip("transfer engine unsupported on this backend")
    server = KvPlaneServer(use_jax_path=True)
    server.start()
    client = KvPlaneClient()
    try:
        host = np.arange(2 * 3 * 2 * 4 * 16 * 8, dtype=np.float32) \
            .reshape(2, 3, 2, 4, 16, 8)
        dev = jnp.asarray(host)
        ticket = server.stage(
            meta={"shape": list(host.shape), "dtype": str(host.dtype)},
            resolve=lambda: host, device_array=dev, prompt_len=64)
        assert "jax_addr" in ticket, "device path was not offered"
        out = await client.pull(ticket)
        np.testing.assert_array_equal(np.asarray(out), host)
        assert client.jax_pulls == 1, "pull did not take the device path"
        assert server.transfers == 0, "bulk socket path should be unused"
        for _ in range(100):  # the "done" release is fire-and-forget
            if not server._staged:
                break
            await asyncio.sleep(0.02)
        assert not server._staged, "done op did not release the parcel"
    finally:
        client.close()
        server.close()


@async_test(timeout=240)
async def test_disagg_device_path_e2e():
    """Full disaggregated 1P+1D e2e with the KV parcel moving over the
    jax transfer engine (no host-staged socket bulk): the 128-token
    prompt fills its page bucket exactly, so the prefill worker offers
    the device array, and the decode side's pull must take the jax path
    — token-identical to aggregated serving."""
    from dynamo_tpu.llm.kv_plane import jax_transfer_usable

    if not jax_transfer_usable():
        pytest.skip("transfer engine unsupported on this backend")
    s = await start_stack(max_local=8, plane=True)
    try:
        prompt = _prompt(33, 128)  # 8 pages == the extract page bucket
        got = await run_request(s.caller, prompt, 8)
        assert s.handler.remote_prefills == 1
        assert s.handler.plane_client.jax_pulls == 1, (
            "KV parcel did not ride the device path")
        assert s.plane.transfers == 0, (
            "socket bulk path used despite the device path")
        ref = await run_agg(prompt, 8)
        assert got == ref
    finally:
        await stop_stack(s)


@async_test
async def test_grouped_stage_pull_roundtrip(plane):
    """Pipelined socket path: page groups streamed in order reassemble
    into the exact parcel bytes."""
    server, client = plane
    kv = _rand_kv(shape=(2, 2, 2, 7, 16, 32), seed=5)
    groups = [(3, lambda: np.ascontiguousarray(kv[:, :, :, :3])),
              (3, lambda: np.ascontiguousarray(kv[:, :, :, 3:6])),
              (1, lambda: np.ascontiguousarray(kv[:, :, :, 6:]))]
    ticket = server.stage(meta={"shape": list(kv.shape),
                                "dtype": str(kv.dtype)},
                          resolve_groups=groups, prompt_len=112)
    out = await client.pull(ticket)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(kv))
    assert client.transfers == 1
    for _ in range(200):  # server thread counts after its last send
        if server.transfers == 1:
            break
        await asyncio.sleep(0.01)
    assert server.transfers == 1
