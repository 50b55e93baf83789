"""The one traffic generator: every mix under ``benchmark/traffic/`` is a
JSON file of parameters that this module reads. A new mix is a new data
file, never new code here.

Everything is a pure function of (deployment, mix, seed): the same seed
gives the same payload bytes, the same keys and the same order.

Events are SiteWhere 3 JSON ``DeviceRequest`` envelopes: a
DeviceMeasurement carries one ``name``/``value`` (SiteWhere 3's
``DeviceMeasurementCreateRequest``), a DeviceLocation its coordinates, a
DeviceAlert its type and level; each has an explicit ``eventDate`` as its
last field, so a plain reference can replay them exactly and a payload
can be stamped with another date without encoding it again. Every value
is a multiple of 1/64 (measurements) or 1/1024 (coordinates): exact in
float32 and in the decimal text.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

KIND_MEAS, KIND_LOC, KIND_ALERT = 0, 1, 2
ALERT_LEVELS = ("Info", "Warning", "Error", "Critical")
WAL_JSON_TAG = b"\x01"   # the log's record tag for a JSON wire batch
DATE_DIGITS = 13         # unix ms from 2001 to 2286
TAIL = len(b"}}") + DATE_DIGITS   # bytes after a payload's stamp prefix


def tenant_names(n: int) -> list[str]:
    return [f"tenant-{t:02d}" for t in range(n)]


def device_tokens(prefix: str, n: int) -> list[str]:
    return [f"{prefix}-{i:07d}" for i in range(n)]


@dataclasses.dataclass
class EventTable:
    """Unique events, one row each, in generation order. ``dev`` indexes
    the fleet's device tokens, ``ten`` the tenant names. ``meas`` holds
    NaN where an event does not carry a channel."""

    kind: np.ndarray      # int8[U]
    dev: np.ndarray       # int32[U]
    ten: np.ndarray       # int16[U]
    ts_abs: np.ndarray    # int64[U] eventDate, unix ms
    meas: np.ndarray      # float64[U, K]
    loc: np.ndarray       # float64[U, 3]
    alevel: np.ndarray    # int8[U]
    atype: np.ndarray     # int16[U]
    payloads: list        # list[bytes], one per row

    def __len__(self) -> int:
        return len(self.kind)


def concat_tables(tables: list[EventTable]) -> EventTable:
    return EventTable(**{
        f.name: (sum((getattr(t, f.name) for t in tables), [])
                 if f.name == "payloads"
                 else np.concatenate([getattr(t, f.name) for t in tables]))
        for f in dataclasses.fields(EventTable)})


def _num(v: float) -> str:
    # shortest round-trip text of a dyadic rational: exact in the decoder
    return repr(float(v))


def make_events(rng: np.random.Generator, dev: np.ndarray, ten: np.ndarray,
                ts_abs: np.ndarray, cfg: dict, tokens: list[str],
                kinds: np.ndarray | None = None) -> EventTable:
    """Events for the given (device, tenant, eventDate) rows; kinds follow
    the deployment's ``event_mix`` unless given. A measurement names one
    of the deployment's ``measurement_names``, uniformly."""
    n = len(dev)
    mix = cfg["event_mix"]
    names = cfg["measurement_names"]
    atypes = cfg["alert_types"]
    if len(ts_abs) and not (10 ** (DATE_DIGITS - 1) <= ts_abs.min()
                            and ts_abs.max() < 10 ** DATE_DIGITS):
        raise ValueError("eventDate outside the stamp's 13 digits")
    if kinds is None:
        p = np.array([mix["measurement"], mix["location"], mix["alert"]])
        kinds = rng.choice(3, size=n, p=p / p.sum()).astype(np.int8)
    chan = rng.integers(0, len(names), n)
    vals = 20.0 + rng.integers(0, 1 << 14, n) / 64.0
    meas = np.full((n, len(names)), np.nan)
    is_m = kinds == KIND_MEAS
    meas[np.nonzero(is_m)[0], chan[is_m]] = vals[is_m]
    loc = np.stack([rng.integers(-90 * 1024, 90 * 1024, n) / 1024.0,
                    rng.integers(-180 * 1024, 180 * 1024, n) / 1024.0,
                    rng.integers(0, 4096 * 16, n) / 16.0], axis=1)
    loc[kinds != KIND_LOC] = np.nan
    alevel = rng.integers(0, 4, n).astype(np.int8)
    atype = rng.integers(0, len(atypes), n).astype(np.int16)
    alevel[kinds != KIND_ALERT] = -1
    atype[kinds != KIND_ALERT] = -1
    payloads = []
    toks = [tokens[d] for d in dev.tolist()]
    locs = loc.tolist()
    for i, (kd, ts, c, v) in enumerate(zip(kinds.tolist(), ts_abs.tolist(),
                                           chan.tolist(), vals.tolist())):
        if kd == KIND_MEAS:
            s = ('{"deviceToken":"%s","type":"DeviceMeasurement","request":'
                 '{"name":"%s","value":%s,"eventDate":%d}}'
                 % (toks[i], names[c], _num(v), ts))
        elif kd == KIND_LOC:
            la, lo, el = locs[i]
            s = ('{"deviceToken":"%s","type":"DeviceLocation","request":'
                 '{"latitude":%s,"longitude":%s,"elevation":%s,'
                 '"eventDate":%d}}'
                 % (toks[i], _num(la), _num(lo), _num(el), ts))
        else:
            s = ('{"deviceToken":"%s","type":"DeviceAlert","request":'
                 '{"type":"%s","level":"%s","message":"threshold",'
                 '"eventDate":%d}}'
                 % (toks[i], atypes[atype[i]], ALERT_LEVELS[alevel[i]], ts))
        payloads.append(s.encode())
    return EventTable(kind=kinds.astype(np.int8), dev=dev.astype(np.int32),
                      ten=ten.astype(np.int16), ts_abs=ts_abs.astype(np.int64),
                      meas=meas, loc=loc, alevel=alevel, atype=atype,
                      payloads=payloads)


def stamp(payloads: list[bytes], ts_abs: np.ndarray) -> list[bytes]:
    """The payloads with their eventDate replaced by ``ts_abs`` (13
    digits: the bytes keep their length)."""
    return [b"%s%d}}" % (p[:-TAIL], t)
            for p, t in zip(payloads, ts_abs.tolist())]


def backlog_pool(cfg: dict, mix: dict, seed: int, t0_ms: int,
                 tokens: list[str], dev_tenant: np.ndarray,
                 n_tenants: int, devices: np.ndarray) -> tuple:
    """A pool of ``pool_events`` events in frames of ``frame_events``,
    one tenant per frame (a broker consumer delivers per-tenant topic
    batches), tenants in turn, keys uniform over the tenant's devices.
    eventDate is ``t0_ms`` plus the row (1 ms apart); a later pass over
    the pool is the same events stamped ``pool_events`` ms later (see
    :func:`pass_shift_ms`). Returns the table and the frame tenants."""
    rng = np.random.default_rng([seed, 0xBA])
    frame = int(mix["frame_events"])
    n_frames = int(mix["pool_events"]) // frame
    by_tenant = [devices[dev_tenant[devices] == t] for t in range(n_tenants)]
    frame_ten = np.arange(n_frames) % n_tenants
    dev = np.concatenate([
        rng.choice(by_tenant[t], size=frame) for t in frame_ten])
    ten = np.repeat(frame_ten, frame)
    ts = t0_ms + np.arange(len(dev), dtype=np.int64)
    return make_events(rng, dev, ten, ts, cfg, tokens), frame_ten


def pass_shift_ms(mix: dict, k: int) -> int:
    """eventDate shift of the k-th pass over the backlog pool: each of
    the first ``stamps`` passes is the pool ``pool_events`` ms later than
    the one before, so eventDates rise with arrival; after those the
    stamps repeat."""
    return (k % int(mix["stamps"])) * int(mix["pool_events"])


def payload_crc(tenant: str, payload: bytes) -> int:
    """CRC32 of a WAL record body (tag + tenant + NUL + payload), as the
    log frames it; the WAL reader compares these."""
    return zlib.crc32(WAL_JSON_TAG + tenant.encode() + b"\x00" + payload)
