"""Persistent-connection wire edge tests (ISSUE 20): the batched socket
edge feeding staging arenas.

Pinned contracts:

* **WireBatcher** — arrival-window accumulation, size/deadline adaptive
  flush, (tenant, wire-format) run splitting in arrival order, WAL-gated
  ack callbacks, arena-stall shed (``on_stall``), barrier acks.
* **MQTT 3.1.1 server codec under adversarial framing** — byte-at-a-time
  fragmented reads across varint remaining-length boundaries, QoS 1
  duplicate redelivery (no double ingest, ack regenerated), QoS 2
  park/release, oversized-frame rejection, keepalive timeout.
* **SWP framing** — handshake validation, cumulative durable acks, shed
  codes with Retry-After, oversized-frame error records.
* **Byte-identity** — frames through the batched wire path produce a
  store byte-identical to direct ``ingest_json_batch`` calls with the
  same batch boundaries, for ``Engine`` AND ``SpmdEngine`` at
  ``scan_chunk`` 1 and 2, metrics dict-equal, conservation clean.
* **Conservation "wire" stage** — the disposition equation balances and
  is falsifiable (a one-frame perturbation is a Violation).
* **Observability split** — ``swtpu_wire_*`` series exist only at scrape
  time; ``engine.metrics()`` keys are identical with and without an
  edge attached (dispatch-shape equality pin).
"""

import asyncio
import dataclasses
import json
import struct
import threading
import types

import jax
import numpy as np
import pytest

from sitewhere_tpu.engine import Engine, EngineConfig
from sitewhere_tpu.core.events import EpochBase
from sitewhere_tpu.ingest.decoders import JsonDeviceRequestDecoder
from sitewhere_tpu.ingest.dedup import AlternateIdDeduplicator
from sitewhere_tpu.ingest.mqtt import (
    CONNACK,
    DISCONNECT,
    PINGREQ,
    PINGRESP,
    PUBACK,
    PUBCOMP,
    PUBLISH,
    PUBREC,
    PUBREL,
    encode_connect,
    encode_packet,
    encode_publish,
    read_packet,
)
from sitewhere_tpu.ingest.sources import (
    EventSourcesManager,
    InboundEventSource,
    InMemoryEventReceiver,
)
from sitewhere_tpu.ingest.wire_edge import (
    SWP_ACK,
    SWP_ERR,
    SWP_MAGIC,
    SWP_SHED,
    AltIdRing,
    WireBatcher,
    WireEdge,
    WireEdgeConfig,
    aggregate_wire_snapshot,
    extract_alternate_id,
)
from sitewhere_tpu.utils.conservation import build_ledger, check_conservation

W_CFG = dict(device_capacity=64, token_capacity=128, assignment_capacity=128,
             store_capacity=2048, batch_capacity=32, channels=4)


class FixedEpoch(EpochBase):
    """Deterministic received_ms so paired executions stamp identical rows."""

    def __init__(self, now_ms: int = 500_000):
        super().__init__(0.0)
        self._now = now_ms

    def now_ms(self) -> int:
        return self._now


class FakeEngine:
    """Engine facade for protocol tests: records batch-ingest calls, no
    jax. ``qos=None`` admits everything (utils/qos.admit_or_raise)."""

    def __init__(self):
        self.qos = None
        self.wal = None
        self.wire_edges = []
        self.json_batches: list[tuple[list[bytes], str]] = []
        self.binary_batches: list[tuple[list[bytes], str]] = []

    def ingest_json_batch(self, payloads, tenant="default", **kw):
        self.json_batches.append((list(payloads), tenant))
        return {"rows": len(payloads)}

    def ingest_binary_batch(self, payloads, tenant="default", **kw):
        self.binary_batches.append((list(payloads), tenant))
        return {"rows": len(payloads)}


class _DenyAll:
    """QoS gate refusing every admission (forces the shed paths)."""

    def admit(self, tenant, n):
        return types.SimpleNamespace(admitted=False, retry_after_s=0.25,
                                     reason="rate")


def _payload(i, dev=6):
    return json.dumps({
        "deviceToken": f"wd-{i % dev}", "type": "DeviceMeasurement",
        "request": {"name": "temp", "value": 20.0 + i,
                    "eventDate": 1_000 + 10 * i},
    }).encode()


def _alt_payload(alt, i=0):
    return json.dumps({
        "deviceToken": "wd-0", "type": "DeviceMeasurement",
        "request": {"name": "temp", "value": 1.0 + i, "eventDate": 1_000,
                    "alternateId": alt},
    }).encode()


# --- alternate-id byte scan --------------------------------------------------


def test_extract_alternate_id_variants():
    assert extract_alternate_id(_alt_payload("m-7")) == "m-7"
    assert extract_alternate_id(b'{"alternateId" \t:\n "a b"}') == "a b"
    assert extract_alternate_id(b'{"alternateId": "q\\"x"}') == 'q"x'
    assert extract_alternate_id(_payload(0)) is None          # key absent
    assert extract_alternate_id(b'{"alternateId": 12}') is None   # non-str
    assert extract_alternate_id(b'{"alternateId": "open') is None  # truncated
    assert extract_alternate_id(b'{"alternateId"}') is None   # no colon


def test_alt_id_ring_bounded_fifo():
    ring = AltIdRing(capacity=3)
    for x in ("a", "b", "c"):
        ring.add(x)
    assert all(ring.seen(x) for x in ("a", "b", "c"))
    ring.add("d")                       # evicts "a" (FIFO)
    assert not ring.seen("a")
    assert ring.seen("d") and ring.seen("b")
    ring.add("b")                       # re-add of a member is a no-op
    ring.add("e")                       # evicts "b" (original position)
    assert not ring.seen("b")


# --- WireBatcher -------------------------------------------------------------


def test_batcher_size_flush_and_run_splitting():
    eng = FakeEngine()
    b = WireBatcher(eng, flush_rows=64, auto=False)
    # arrival order: t1 json, t1 json, t2 json, t1 binary, t1 binary
    b.add(b"a", tenant="t1")
    b.add(b"b", tenant="t1")
    b.add(b"c", tenant="t2")
    b.add(b"x", tenant="t1", binary=True)
    b.add(b"y", tenant="t1", binary=True)
    assert b.pending == 5
    assert b.flush() == 5
    assert b.pending == 0
    # one engine call per (tenant, format) run, arrival order preserved
    assert eng.json_batches == [([b"a", b"b"], "t1"), ([b"c"], "t2")]
    assert eng.binary_batches == [([b"x", b"y"], "t1")]
    c = b.counters()
    assert c["rows_submitted"] == 5
    assert c["flushes"] == c["flushes_drain"] == 1
    assert c["flush_rows_sum"] == 5
    b.close()


def test_batcher_auto_size_threshold():
    eng = FakeEngine()
    b = WireBatcher(eng, flush_rows=4, flush_interval_s=30.0, auto=True)
    done = threading.Event()
    for i in range(4):
        b.add(b"p%d" % i, on_durable=done.set if i == 3 else None)
    assert done.wait(5.0), "size-threshold flush never fired"
    assert eng.json_batches == [([b"p0", b"p1", b"p2", b"p3"], "default")]
    assert b.counters()["flushes_size"] == 1
    b.close()


def test_batcher_auto_deadline_flush():
    """Sub-threshold arrival windows drain at the deadline — the fix for
    the flusher never arming its timer on the first frame."""
    eng = FakeEngine()
    b = WireBatcher(eng, flush_rows=100, flush_interval_s=0.05, auto=True)
    acked = []
    for i in range(3):
        b.add(b"d%d" % i, on_durable=lambda i=i: acked.append(i))
    deadline_fired = threading.Event()
    b.add_barrier(deadline_fired.set)
    assert deadline_fired.wait(5.0), "deadline flush never fired"
    assert eng.json_batches == [([b"d0", b"d1", b"d2"], "default")]
    assert acked == [0, 1, 2]           # ack order == ingest order
    assert b.counters()["flushes_deadline"] >= 1
    b.close()


def test_batcher_shed_withholds_acks():
    eng = FakeEngine()
    from sitewhere_tpu.utils.qos import ShedError

    def raise_shed(payloads, tenant="default", **kw):
        raise ShedError("arena stall", tenant=tenant, retry_after_s=0.1,
                        reason="stall")
    eng.ingest_json_batch = raise_shed
    b = WireBatcher(eng, flush_rows=64, auto=False)
    acks, stalls = [], []
    b.add(b"s0", on_durable=lambda: acks.append(0),
          on_stall=lambda e: stalls.append(e))
    assert b.flush() == 0
    # the frame was never staged: ack withheld, stall surfaced, counted
    assert acks == []
    assert len(stalls) == 1 and stalls[0].reason == "stall"
    assert b.counters()["frames_stalled"] == 1
    b.close()


def test_batcher_closed_raises():
    b = WireBatcher(FakeEngine(), auto=False)
    b.close()
    with pytest.raises(RuntimeError):
        b.add(b"late")
    with pytest.raises(RuntimeError):
        b.add_barrier(lambda: None)


# --- sources: batched submit API (satellite) --------------------------------


def test_source_routes_through_batched_submit():
    eng = FakeEngine()
    batcher = WireBatcher(eng, flush_rows=64, auto=False)
    mgr = EventSourcesManager(on_event_request=lambda r: None,
                              batcher=batcher)
    recv = InMemoryEventReceiver()
    src = InboundEventSource("batched", JsonDeviceRequestDecoder(), [recv])
    mgr.add_source(src)
    # a batchable decoder (wire_tag) inherits the manager's batcher
    assert src.batcher is batcher
    fired = []
    for i in range(3):
        recv.submit(_payload(i), on_durable=lambda i=i: fired.append(i))
    # payloads ride the arrival window by reference — no per-event
    # decode, no per-event engine call, acks gated on the flush
    assert src.batched_count == 3 and src.decoded_count == 0
    assert batcher.pending == 3 and eng.json_batches == [] and fired == []
    batcher.flush()
    assert eng.json_batches == [([_payload(0), _payload(1), _payload(2)],
                                 "default")]
    assert fired == [0, 1, 2]
    batcher.close()


def test_source_per_payload_path_acks_synchronously():
    eng = FakeEngine()
    got = []
    mgr = EventSourcesManager(on_event_request=got.append)
    recv = InMemoryEventReceiver()
    mgr.add_source(InboundEventSource("plain", JsonDeviceRequestDecoder(),
                                      [recv]))
    fired = []
    recv.submit(_payload(0), on_durable=lambda: fired.append("ok"))
    assert len(got) == 1 and fired == ["ok"]
    # decode failure still releases the sender (dead letter, then ack)
    recv.submit(b"not json", on_durable=lambda: fired.append("dlq"))
    assert fired == ["ok", "dlq"]


def test_source_batcher_dedup_mutually_exclusive():
    with pytest.raises(ValueError):
        InboundEventSource("x", JsonDeviceRequestDecoder(),
                           [InMemoryEventReceiver()],
                           deduplicator=AlternateIdDeduplicator(),
                           batcher=WireBatcher(FakeEngine(), auto=False))


# --- MQTT server: adversarial framing ---------------------------------------


def _edge_cfg(**kw):
    base = dict(mqtt_port=0, tcp_port=None, flush_rows=1,
                flush_interval_s=0.01)
    base.update(kw)
    return WireEdgeConfig(**base)


async def _mqtt_connect(port, keepalive=0, fragment=False):
    r, w = await asyncio.open_connection("127.0.0.1", port)
    pkt = encode_connect("t-client", keepalive=keepalive)
    if fragment:
        for i in range(len(pkt)):
            w.write(pkt[i:i + 1])
            await w.drain()
            await asyncio.sleep(0.001)
    else:
        w.write(pkt)
        await w.drain()
    ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
    assert ptype == CONNACK and body == b"\x00\x00"
    return r, w


def test_mqtt_fragmented_frames_across_varint_boundary():
    """Byte-at-a-time delivery of CONNECT and of a PUBLISH whose
    remaining length needs a 2-byte varint (>127) must frame exactly as
    contiguous delivery would."""
    eng = FakeEngine()

    async def run():
        edge = WireEdge(eng, _edge_cfg())
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port, fragment=True)
            payload = _payload(0) + b" " * 160     # force 2-byte varint
            pkt = encode_publish("swtpu/default/events", payload, qos=1,
                                 packet_id=3)
            assert len(pkt) > 129                  # varint spans 2 bytes
            for i in range(len(pkt)):
                w.write(pkt[i:i + 1])
                await w.drain()
                await asyncio.sleep(0.0005)
            ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBACK
            assert int.from_bytes(body[:2], "big") == 3
            w.close()
        finally:
            await edge.stop()

    asyncio.run(run())
    assert eng.json_batches == [([_payload(0) + b" " * 160], "default")]


def test_mqtt_qos1_duplicate_redelivery_no_double_ingest():
    """QoS 1 redelivery of an alternateId-bearing frame (lost PUBACK)
    regenerates the ack WITHOUT a second ingest."""
    eng = FakeEngine()
    snap = {}

    async def run():
        edge = WireEdge(eng, _edge_cfg())
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port)
            dup = _alt_payload("alt-42")
            for pid in (7, 8):          # second offer = DUP redelivery
                w.write(encode_publish("swtpu/default/events", dup,
                                       qos=1, packet_id=pid))
                await w.drain()
                ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
                assert ptype == PUBACK  # both offers acked...
                assert int.from_bytes(body[:2], "big") == pid
            w.close()
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    # ...but exactly one ingest reached the engine
    assert eng.json_batches == [([_alt_payload("alt-42")], "default")]
    assert snap["frames_received"] == 2
    assert snap["frames_admitted"] == 1
    assert snap["frames_duplicate"] == 1


def test_mqtt_qos2_park_release_single_ingest():
    eng = FakeEngine()

    async def run():
        edge = WireEdge(eng, _edge_cfg())
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port)
            pub = encode_publish("swtpu/default/events", _payload(1),
                                 qos=2, packet_id=9)
            w.write(pub)
            await w.drain()
            ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBREC
            # redelivered PUBLISH with the same pid replaces the parked
            # copy — never a second ingest
            w.write(pub)
            await w.drain()
            ptype, _, _ = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBREC
            w.write(encode_packet(PUBREL, 2, (9).to_bytes(2, "big")))
            await w.drain()
            ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBCOMP
            assert int.from_bytes(body[:2], "big") == 9
            w.close()
        finally:
            await edge.stop()

    asyncio.run(run())
    assert eng.json_batches == [([_payload(1)], "default")]


def test_mqtt_oversized_frame_rejected_before_body():
    eng = FakeEngine()
    snap = {}

    async def run():
        edge = WireEdge(eng, _edge_cfg(max_frame_bytes=64))
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port)
            w.write(encode_publish("swtpu/default/events", b"z" * 256,
                                   qos=1, packet_id=1))
            await w.drain()
            # server drops the connection without reading the body
            assert await asyncio.wait_for(r.read(16), 10) == b""
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert snap["frames_invalid"] == 1
    assert eng.json_batches == []


def test_mqtt_keepalive_timeout_disconnects():
    eng = FakeEngine()
    snap = {}

    async def run():
        edge = WireEdge(eng, _edge_cfg(keepalive_grace=0.3))
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port, keepalive=1)
            # a PINGREQ inside the window keeps the session alive
            w.write(encode_packet(PINGREQ, 0, b""))
            await w.drain()
            ptype, _, _ = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PINGRESP
            # then silence past 1.5x-style grace: server must hang up
            assert await asyncio.wait_for(r.read(16), 10) == b""
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert snap["keepalive_timeouts"] == 1
    assert snap["connections_live"] == 0


def test_mqtt_shed_withholds_puback_and_disconnects():
    eng = FakeEngine()
    eng.qos = _DenyAll()
    snap = {}

    async def run():
        edge = WireEdge(eng, _edge_cfg())
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port)
            w.write(encode_publish("swtpu/default/events", _payload(0),
                                   qos=1, packet_id=5))
            await w.drain()
            # no PUBACK ever — the connection closes so the client's
            # redelivery loop backs off
            assert await asyncio.wait_for(r.read(16), 10) == b""
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert snap["frames_shed"] == 1
    assert snap["frames_admitted"] == 0
    assert snap["backpressure_events"] == 1
    assert eng.json_batches == []


# --- SWP server --------------------------------------------------------------


async def _swp_connect(port, tenant=b"default", fmt=b"json"):
    r, w = await asyncio.open_connection("127.0.0.1", port)
    w.write(SWP_MAGIC + b" " + tenant + b" " + fmt + b"\n")
    await w.drain()
    return r, w


async def _swp_rec(r, timeout=10):
    code, val = struct.unpack("!BI", await asyncio.wait_for(
        r.readexactly(5), timeout))
    return code, val


def test_swp_cumulative_durable_acks():
    eng = FakeEngine()

    async def run():
        edge = WireEdge(eng, WireEdgeConfig(
            mqtt_port=None, tcp_port=0, flush_rows=64,
            flush_interval_s=5.0))
        await edge.start()
        try:
            r, w = await _swp_connect(edge.tcp_port)
            for i in range(3):
                p = _payload(i)
                w.write(struct.pack("!I", len(p)) + p)
            w.write(struct.pack("!I", 0))      # flush hint
            await w.drain()
            acked = 0
            while acked < 3:
                code, acked = await _swp_rec(r)
                assert code == SWP_ACK
            w.close()
        finally:
            await edge.stop()

    asyncio.run(run())
    # one arrival window -> ONE engine call for all three frames
    assert eng.json_batches == [([_payload(0), _payload(1), _payload(2)],
                                 "default")]


def test_swp_bad_handshake_and_oversize():
    eng = FakeEngine()
    snaps = []

    async def run():
        edge = WireEdge(eng, WireEdgeConfig(
            mqtt_port=None, tcp_port=0, max_frame_bytes=64))
        await edge.start()
        try:
            r, w = await asyncio.open_connection("127.0.0.1", edge.tcp_port)
            w.write(b"NOTSWP default json\n")
            await w.drain()
            code, val = await _swp_rec(r)
            assert code == SWP_ERR and val == 64
            w.close()
            r, w = await _swp_connect(edge.tcp_port)
            w.write(struct.pack("!I", 4096))   # oversized length prefix
            await w.drain()
            code, val = await _swp_rec(r)
            assert code == SWP_ERR and val == 64
            w.close()
            snaps.append(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert snaps[0]["frames_invalid"] == 2
    assert eng.json_batches == []


def test_swp_shed_code_carries_retry_after():
    eng = FakeEngine()
    eng.qos = _DenyAll()

    async def run():
        edge = WireEdge(eng, WireEdgeConfig(mqtt_port=None, tcp_port=0))
        await edge.start()
        try:
            r, w = await _swp_connect(edge.tcp_port)
            p = _payload(0)
            w.write(struct.pack("!I", len(p)) + p)
            await w.drain()
            code, retry_ms = await _swp_rec(r)
            assert code == SWP_SHED
            assert retry_ms == 250             # _DenyAll's 0.25s
            w.close()
        finally:
            await edge.stop()

    asyncio.run(run())
    assert eng.json_batches == []


# --- byte-identity vs the direct batch-ingest path ---------------------------


def _make_engines(kind, scan_chunk):
    if kind == "engine":
        mk = lambda: Engine(EngineConfig(**W_CFG))
    else:
        from sitewhere_tpu.parallel.sharded import SpmdEngine

        mk = lambda: SpmdEngine(
            EngineConfig(**{**W_CFG, "scan_chunk": scan_chunk}), n_shards=2)
    a, b = mk(), mk()
    for e in (a, b):
        e.epoch = FixedEpoch()
    return a, b


def _settle(e):
    e.flush()
    for fn in ("barrier", "drain"):
        m = getattr(e, fn, None)
        if m is not None:
            m()


def _assert_store_identical(a, b):
    sa, sb = jax.device_get(a.state.store), jax.device_get(b.state.store)
    for f in dataclasses.fields(sa):
        va, vb = getattr(sa, f.name), getattr(sb, f.name)
        assert np.array_equal(np.asarray(va), np.asarray(vb)), \
            f"store field {f.name} diverged"


@pytest.mark.parametrize("kind,scan_chunk", [
    ("engine", None), ("spmd", 1), ("spmd", 2),
])
def test_wire_batched_path_byte_identical(kind, scan_chunk):
    """Frames through the wire batcher == direct ingest_json_batch with
    the same batch boundaries: identical store bytes, identical
    metrics() dict, conservation clean — Engine and SpmdEngine, packed
    and unpacked scan."""
    a, b = _make_engines(kind, scan_chunk)
    batcher = WireBatcher(a, flush_rows=16, auto=False)
    payloads = [_payload(i) for i in range(48)]
    for lo in range(0, len(payloads), 16):
        chunk = payloads[lo:lo + 16]
        for p in chunk:
            batcher.add(p)
        batcher.flush()                 # same split as the oracle call
        b.ingest_json_batch(chunk)
    _settle(a)
    _settle(b)
    _assert_store_identical(a, b)
    assert a.metrics() == b.metrics()
    for e in (a, b):
        assert check_conservation(build_ledger(e)) == []
    batcher.close()


def test_swp_socket_byte_identical_and_conservation():
    """End-to-end: live SWP frames -> edge -> arena path, vs the oracle's
    direct batch calls. Also pins the conservation "wire" stage (present
    and falsifiable while the edge is attached) and the dispatch-shape
    equality of metrics() with an edge attached."""
    a, b = _make_engines("engine", None)
    payloads = [_payload(i) for i in range(32)]
    wire_violations = []
    perturbed = []

    async def run():
        edge = WireEdge(a, WireEdgeConfig(
            mqtt_port=None, tcp_port=0, flush_rows=16,
            flush_interval_s=5.0))
        await edge.start()
        r, w = await _swp_connect(edge.tcp_port)
        acked = 0
        for lo in range(0, len(payloads), 16):
            chunk = payloads[lo:lo + 16]
            for p in chunk:
                w.write(struct.pack("!I", len(p)) + p)
            w.write(struct.pack("!I", 0))      # flush hint: drain now
            await w.drain()
            while acked < lo + 16:             # ack barrier: same batch
                code, acked = await _swp_rec(r, timeout=60)  # split as
                assert code == SWP_ACK                       # the oracle
            b.ingest_json_batch(chunk)
        w.close()
        # an invalid frame (bad handshake) must balance too: it counts as
        # received AND invalid, not invalid-only (which would permanently
        # violate wire-frames for every malformed client)
        r2, w2 = await asyncio.open_connection("127.0.0.1", edge.tcp_port)
        w2.write(b"NOTSWP default json\n")
        await w2.drain()
        code, _ = await _swp_rec(r2)
        assert code == SWP_ERR
        w2.close()
        assert edge.snapshot()["frames_invalid"] == 1
        # conservation audits run while the edge is attached
        _settle(a)
        wire_violations.extend(check_conservation(build_ledger(a)))
        ledger = build_ledger(a)
        assert "wire" in ledger["stages"]
        # falsifiability: one phantom frame must be a Violation
        edge.frames_received += 1
        perturbed.extend(check_conservation(build_ledger(a)))
        edge.frames_received -= 1
        await edge.stop()

    asyncio.run(run())
    _settle(b)
    _assert_store_identical(a, b)
    # dispatch-shape equality pin: wire series never leak into metrics()
    assert a.metrics() == b.metrics()
    assert not any("wire" in k for k in a.metrics())
    assert wire_violations == []
    assert any(v.equation == "wire-frames" for v in perturbed)


async def _mqtt_clients(port, n, prefix):
    out = []
    for i in range(n):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        w.write(encode_connect(f"{prefix}-{i}"))
        await w.drain()
        ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
        assert ptype == CONNACK and body == b"\x00\x00"
        out.append((r, w))
    return out


def test_mqtt_connections_stage_copy_free_and_compile_nothing():
    """QoS 1 frames from many live MQTT connections stage straight into
    the arenas, with no copy-staged row, and once the engine and the
    edge are warm they compile no program and trip no retrace."""
    from sitewhere_tpu.utils.devicewatch import WATCH

    eng = Engine(EngineConfig(**W_CFG))
    if eng._arena_pool is None:
        pytest.skip("native arena path unavailable")
    eng.epoch = FixedEpoch()
    eng.ingest_json_batch([_payload(i) for i in range(32)])
    eng.flush()
    seen = {}

    async def publish(clients, first, per_conn):
        async def one(k, r, w):
            for j in range(per_conn):
                w.write(encode_publish(
                    "swtpu/default/events",
                    _payload(first + k * per_conn + j), qos=1,
                    packet_id=j + 1))
                await w.drain()
                ptype, _, body = await asyncio.wait_for(read_packet(r), 30)
                assert ptype == PUBACK
                assert int.from_bytes(body[:2], "big") == j + 1
        await asyncio.gather(*(one(k, r, w)
                               for k, (r, w) in enumerate(clients)))

    async def run():
        edge = WireEdge(eng, _edge_cfg(flush_rows=16,
                                       flush_interval_s=0.005))
        await edge.start()
        try:
            await publish(await _mqtt_clients(edge.mqtt_port, 2, "warm"),
                          100, 8)
            eng.flush()
            seen["compiles"] = WATCH.compile_totals()
            seen["excess"] = WATCH.excess_total()
            seen["counters"] = dict(eng.host_counters)
            clients = await _mqtt_clients(edge.mqtt_port, 24, "live")
            await publish(clients, 1_000, 6)
            eng.flush()
            seen["peak"] = edge.snapshot()["connections_peak"]
            for _, w in clients:
                w.close()
        finally:
            await edge.stop()

    asyncio.run(run())
    assert seen["peak"] >= 24
    assert WATCH.compile_totals() == seen["compiles"]
    assert WATCH.excess_total() == seen["excess"]
    hc = eng.host_counters
    assert hc.get("staged_copy_rows", 0) == \
        seen["counters"].get("staged_copy_rows", 0) == 0
    assert hc["arena_rows"] - seen["counters"]["arena_rows"] == 24 * 6
    assert eng.metrics()["persisted"] == 32 + 2 * 8 + 24 * 6


def test_swp_kill_mid_stream_loses_no_acked_frame(tmp_path):
    """Crash drill: SWP acks wait for the WAL's group-commit fsync, so
    after a kill mid-stream (sockets dropped, no batcher drain) a fresh
    engine replaying the log holds every frame a client saw acked."""
    import time

    from sitewhere_tpu.utils.checkpoint import replay_wal_into

    cfg = dict(W_CFG, store_capacity=1 << 14)
    wal = tmp_path / "wal"
    eng = Engine(EngineConfig(**cfg, wal_dir=str(wal),
                              wal_group_commit=True))
    eng.epoch = FixedEpoch()
    warm = [_payload(i) for i in range(32)]
    eng.ingest_json_batch(warm)
    eng.flush()
    n_conn, per_conn = 4, 1_000
    acks = [0] * n_conn

    def frame(k, i):
        return _payload(1_000 + k * per_conn + i)

    async def run():
        edge = WireEdge(eng, WireEdgeConfig(
            mqtt_port=None, tcp_port=0, flush_rows=16,
            flush_interval_s=0.002))
        await edge.start()
        conns = [await _swp_connect(edge.tcp_port) for _ in range(n_conn)]

        async def pump(k):
            w = conns[k][1]
            try:
                for i in range(per_conn):
                    p = frame(k, i)
                    w.write(struct.pack("!I", len(p)) + p)
                    await w.drain()
            except ConnectionError:
                pass

        async def reap(k):
            r = conns[k][0]
            try:
                while True:
                    code, val = await _swp_rec(r, timeout=30)
                    if code == SWP_ACK:
                        acks[k] = val
            except (asyncio.IncompleteReadError, asyncio.TimeoutError,
                    ConnectionError):
                pass

        tasks = [asyncio.ensure_future(f(k)) for f in (pump, reap)
                 for k in range(n_conn)]
        deadline = time.monotonic() + 60
        while sum(acks) < 100 and time.monotonic() < deadline:
            await asyncio.sleep(0.005)
        acked = list(acks)          # what the clients saw before the crash
        edge.kill()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return edge, acked

    edge, acked = asyncio.run(run())
    for b in edge.batchers:       # quiesce the flusher threads
        b.close()
    eng.wal.close()
    assert sum(acked) >= 100, "no ack before the kill: the drill is vacuous"
    rec = Engine(EngineConfig(**cfg))
    rec.epoch = FixedEpoch()
    replay_wal_into(rec, -1, wal)
    rec.flush()
    st = jax.device_get(rec.state.store)
    have = set(np.asarray(st.ts_ms)[np.asarray(st.valid)].tolist())
    for k in range(n_conn):
        for i in range(acked[k]):
            ts = json.loads(frame(k, i))["request"]["eventDate"]
            assert ts in have, f"acked frame {i} of connection {k} lost"


# --- observability plane -----------------------------------------------------


def test_wire_scrape_series_only_with_edge_attached():
    from sitewhere_tpu.utils.metrics import MetricsRegistry, export_wire_metrics

    eng = FakeEngine()

    async def run():
        edge = WireEdge(eng, WireEdgeConfig(mqtt_port=None, tcp_port=0,
                                            flush_rows=64,
                                            flush_interval_s=5.0))
        await edge.start()
        try:
            r, w = await _swp_connect(edge.tcp_port)
            for i in range(2):
                p = _payload(i)
                w.write(struct.pack("!I", len(p)) + p)
            w.write(struct.pack("!I", 0))
            await w.drain()
            acked = 0
            while acked < 2:
                _, acked = await _swp_rec(r)
            reg = MetricsRegistry()
            export_wire_metrics(eng, reg)
            text = reg.expose_text()
            assert 'swtpu_wire_frames_total{disposition="admitted"} 2' in text
            assert 'swtpu_wire_frames_total{disposition="received"} 2' in text
            assert "swtpu_wire_connections_live 1" in text
            assert "swtpu_wire_rows_submitted_total 2" in text
            assert "swtpu_wire_flush_occupancy_pct" in text
            w.close()
        finally:
            await edge.stop()

    asyncio.run(run())
    # no edge attached -> the exporter emits nothing
    reg2_engine = FakeEngine()
    from sitewhere_tpu.utils.metrics import MetricsRegistry as _MR
    from sitewhere_tpu.utils.metrics import export_wire_metrics as _ex

    reg2 = _MR()
    _ex(reg2_engine, reg2)
    assert "swtpu_wire" not in reg2.expose_text()
    assert aggregate_wire_snapshot(reg2_engine) is None


def test_batcher_on_staged_fires_only_on_success():
    """on_staged (the dedup-ring commit point) fires for staged frames
    only — a shed run's hook never fires."""
    eng = FakeEngine()
    from sitewhere_tpu.utils.qos import ShedError

    calls = {"n": 0}

    def shed_once(payloads, tenant="default", **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ShedError("arena stall", tenant=tenant,
                            retry_after_s=0.1, reason="stall")
        eng.json_batches.append((list(payloads), tenant))
        return {"rows": len(payloads)}
    eng.ingest_json_batch = shed_once
    b = WireBatcher(eng, flush_rows=64, auto=False)
    staged = []
    b.add(b"s0", on_staged=lambda: staged.append(0))
    b.flush()
    assert staged == []                 # stalled: no commit
    b.add(b"s0", on_staged=lambda: staged.append(1))
    b.flush()
    assert staged == [1]                # staged: committed
    b.close()


def test_shed_frame_leaves_no_dedup_entry_redelivery_reingested():
    """A frame shed at admission must NOT poison the dedup ring: the
    client's redelivery (same alternateId) is re-admitted and ingested,
    never acked as a duplicate of an ingest that didn't happen."""
    eng = FakeEngine()
    eng.qos = _DenyAll()
    snap = {}

    async def run():
        edge = WireEdge(eng, WireEdgeConfig(
            mqtt_port=None, tcp_port=0, flush_rows=1,
            flush_interval_s=0.01))
        await edge.start()
        try:
            r, w = await _swp_connect(edge.tcp_port)
            p = _alt_payload("shed-1")
            w.write(struct.pack("!I", len(p)) + p)
            await w.drain()
            code, _ = await _swp_rec(r)
            assert code == SWP_SHED
            eng.qos = None              # pressure clears; client resends
            w.write(struct.pack("!I", len(p)) + p)
            await w.drain()
            code, acked = await _swp_rec(r)
            assert code == SWP_ACK and acked == 1
            w.close()
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert eng.json_batches == [([_alt_payload("shed-1")], "default")]
    assert snap["frames_shed"] == 1
    assert snap["frames_admitted"] == 1
    assert snap["frames_duplicate"] == 0


def test_stalled_frame_leaves_no_dedup_entry_redelivery_reingested():
    """Same ack-without-ingest hole via the other path: admitted but the
    run STALLS (arena shed inside the engine call). The redelivery must
    ingest; the ring committed nothing for the stalled frame."""
    from sitewhere_tpu.utils.qos import ShedError

    eng = FakeEngine()
    calls = {"n": 0}

    def stall_once(payloads, tenant="default", **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ShedError("arena stall", tenant=tenant,
                            retry_after_s=0.05, reason="stall")
        eng.json_batches.append((list(payloads), tenant))
        return {"rows": len(payloads)}
    eng.ingest_json_batch = stall_once
    snap = {}

    async def run():
        edge = WireEdge(eng, WireEdgeConfig(
            mqtt_port=None, tcp_port=0, flush_rows=1,
            flush_interval_s=0.01))
        await edge.start()
        try:
            r, w = await _swp_connect(edge.tcp_port)
            p = _alt_payload("stall-1")
            w.write(struct.pack("!I", len(p)) + p)
            await w.drain()
            code, _ = await _swp_rec(r)
            assert code == SWP_SHED     # stall surfaced, ack withheld
            w.write(struct.pack("!I", len(p)) + p)
            await w.drain()
            code, acked = await _swp_rec(r)
            assert code == SWP_ACK and acked == 1
            w.close()
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert eng.json_batches == [([_alt_payload("stall-1")], "default")]
    assert snap["frames_stalled"] == 1
    assert snap["frames_duplicate"] == 0
    assert snap["frames_admitted"] == 2     # both offers were admitted


def test_dedup_key_scoped_by_tenant_and_device():
    """The ring keys by (tenant, deviceToken, alternateId) — the repo's
    established dedup triple. An alternateId reused across tenants or
    devices is NOT a duplicate; only the full triple dedups."""
    eng = FakeEngine()
    snap = {}

    def _pay(dev, alt):
        return json.dumps({
            "deviceToken": dev, "type": "DeviceMeasurement",
            "request": {"name": "temp", "value": 1.0, "eventDate": 1_000,
                        "alternateId": alt},
        }).encode()

    async def run():
        edge = WireEdge(eng, _edge_cfg())
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port)
            offers = [
                ("swtpu/t1/events", _pay("wd-0", "seq-1")),   # ingests
                ("swtpu/t2/events", _pay("wd-0", "seq-1")),   # other tenant
                ("swtpu/t1/events", _pay("wd-1", "seq-1")),   # other device
                ("swtpu/t1/events", _pay("wd-0", "seq-1")),   # true dup
            ]
            for pid, (topic, payload) in enumerate(offers, start=1):
                w.write(encode_publish(topic, payload, qos=1,
                                       packet_id=pid))
                await w.drain()
                ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
                assert ptype == PUBACK
                assert int.from_bytes(body[:2], "big") == pid
            w.close()
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert snap["frames_admitted"] == 3
    assert snap["frames_duplicate"] == 1
    assert [t for _, t in eng.json_batches] == ["t1", "t2", "t1"]


def test_mqtt_qos2_shed_release_withholds_pubcomp_until_ingest():
    """QoS 2 exactly-once under shed: a PUBREL whose released frame is
    shed must NOT complete on the client's PUBREL retransmission — the
    payload re-parks, PUBCOMP stays withheld until a release actually
    stages. PUBCOMP therefore implies ingest."""
    eng = FakeEngine()
    eng.qos = _DenyAll()

    async def run():
        edge = WireEdge(eng, _edge_cfg())
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port)
            w.write(encode_publish("swtpu/default/events", _payload(2),
                                   qos=2, packet_id=11))
            await w.drain()
            ptype, _, _ = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBREC
            rel = encode_packet(PUBREL, 2, (11).to_bytes(2, "big"))
            # release is shed twice; neither may produce a PUBCOMP
            for _ in range(2):
                w.write(rel)
                await w.drain()
                with pytest.raises(asyncio.TimeoutError):
                    await asyncio.wait_for(read_packet(r), 0.3)
            eng.qos = None          # pressure clears
            w.write(rel)
            await w.drain()
            ptype, _, body = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBCOMP
            assert int.from_bytes(body[:2], "big") == 11
            # pid settled: one more PUBREL is a true duplicate -> re-comp
            w.write(rel)
            await w.drain()
            ptype, _, _ = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBCOMP
            w.close()
        finally:
            await edge.stop()

    asyncio.run(run())
    # exactly ONE ingest despite four PUBRELs
    assert eng.json_batches == [([_payload(2)], "default")]


def test_aggregate_multi_edge_peak_and_occupancy():
    """Multi-edge aggregation: counters sum, but connections_peak is a
    max and flush occupancy a capacity-weighted mean — two edges at 80%
    report 80%, not 160%."""
    eng = FakeEngine()
    cfg = WireEdgeConfig(mqtt_port=None, tcp_port=None, flush_rows=100)
    e1, e2 = WireEdge(eng, cfg), WireEdge(eng, cfg)
    eng.wire_edges = [e1, e2]
    for edge, peak, flushes, rows in ((e1, 5, 10, 800), (e2, 3, 10, 800)):
        edge.connections_peak = peak
        edge.frames_received = edge.frames_admitted = rows
        b = edge.batchers[0]
        b.flushes_drain = flushes
        b.flush_rows_sum = b.rows_submitted = rows
    total = aggregate_wire_snapshot(eng)
    assert total["connections_peak"] == 5           # max, not 8
    assert total["flush_occupancy_pct"] == 80.0     # weighted, not 160
    assert total["frames_received"] == 1600          # counters still sum
    assert total["flushes"] == 20
    for e in (e1, e2):
        e.batchers[0].close()


def test_wire_snapshot_disposition_balance():
    """Every disposition path in one session: the snapshot's own terms
    satisfy the wire-frames equation the ledger checks."""
    eng = FakeEngine()
    snap = {}

    async def run():
        edge = WireEdge(eng, _edge_cfg(max_frame_bytes=4096))
        await edge.start()
        try:
            r, w = await _mqtt_connect(edge.mqtt_port)
            dup = _alt_payload("bal-1")
            w.write(encode_publish("swtpu/default/events", dup, qos=1,
                                   packet_id=1))
            await w.drain()
            # PUBACK implies the frame staged (ring committed) — only
            # then is a redelivery classified duplicate
            ptype, _, _ = await asyncio.wait_for(read_packet(r), 10)
            assert ptype == PUBACK
            w.write(encode_publish("swtpu/default/events", dup, qos=1,
                                   packet_id=2))          # duplicate
            w.write(encode_publish("swtpu/default/events", _payload(3),
                                   qos=1, packet_id=3))   # admitted
            await w.drain()
            for _ in range(2):
                ptype, _, _ = await asyncio.wait_for(read_packet(r), 10)
                assert ptype == PUBACK
            w.write(encode_packet(DISCONNECT, 0, b""))
            await w.drain()
            w.close()
            snap.update(edge.snapshot())
        finally:
            await edge.stop()

    asyncio.run(run())
    assert snap["frames_received"] == (
        snap["frames_admitted"] + snap["frames_shed"]
        + snap["frames_invalid"] + snap["frames_duplicate"])
    assert snap["frames_admitted"] == (
        snap["rows_submitted"] + snap["frames_stalled"] + snap["pending"])
    assert snap["frames_duplicate"] == 1
