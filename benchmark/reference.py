"""The plain reference: SiteWhere's device-state and event-store semantics
written out on the host, independent of the code under test (it imports
nothing of ``sitewhere_tpu``).

Semantics it holds the engine to, per deployment file:

- every accepted event of a registered device persists exactly one row
  (one active assignment per device) into a ring of ``store_capacity``
  rows that keeps the newest rows in arrival order;
- device state keeps, per measurement channel, the value of the newest
  event by (eventDate, arrival); the 3 newest locations and alerts by the
  same order (``recent_depth``, RdbDeviceStateMergeStrategy's MAX_RECENT);
  per-type event counts; the newest eventDate as last interaction;
- an event query is newest-first by eventDate over the ring, ties in ring
  order, filtered by device / tenant, ``total`` counting every match in
  the ring.

Arrivals are given in order as event-table rows with the eventDate each
was sent with (a pass over a backlog pool sends the pool's rows again,
stamped later).
"""

from __future__ import annotations

import numpy as np

from benchmark.traffic_gen import KIND_ALERT, KIND_LOC, KIND_MEAS

EVENT_TYPE_NAMES = ("MEASUREMENT", "LOCATION", "ALERT", "COMMAND_INVOCATION",
                    "COMMAND_RESPONSE", "STATE_CHANGE")
_F32 = np.float32


class Reference:
    def __init__(self, table, rows: np.ndarray, ts_abs: np.ndarray,
                 tokens: list[str], names: list[str], atypes: list[str],
                 base_ms: int, store_capacity: int, recent_depth: int = 3,
                 value_round=None):
        self.t = table
        self.rows = np.asarray(rows, np.int64)
        self.ts = np.asarray(ts_abs, np.int64) - int(base_ms)
        self.n = len(self.rows)
        self.tokens = tokens
        self.names = names
        self.atypes = atypes
        self.cap = int(store_capacity)
        self.depth = int(recent_depth)
        # a control may round stored values (the precision it would
        # tempt a later change to store them at); None keeps them exact
        self.meas = table.meas if value_round is None else value_round(
            table.meas)
        self.loc = table.loc if value_round is None else value_round(
            table.loc)
        dev = table.dev[self.rows]
        self._order = np.argsort(dev, kind="stable")
        self._dev_sorted = dev[self._order]

    # -------------------------------------------------------- device state
    def final_state(self, d: int) -> dict | None:
        """Device ``d`` after every arrival: newest by (eventDate,
        arrival)."""
        a, b = np.searchsorted(self._dev_sorted, [d, d + 1])
        pos = self._order[a:b]
        if len(pos) == 0:
            return None
        pos = pos[np.lexsort((-pos, -self.ts[pos]))]   # newest first
        rows, ts = self.rows[pos], self.ts[pos]
        kind = self.t.kind[rows]
        chans = {}
        for c, name in enumerate(self.names):
            hit = np.nonzero((kind == KIND_MEAS)
                             & ~np.isnan(self.meas[rows, c]))[0]
            if len(hit):
                chans[name] = {"value": float(_F32(self.meas[rows[hit[0]],
                                                              c])),
                               "ts_ms": int(ts[hit[0]])}
        locs = [{"latitude": float(_F32(self.loc[r, 0])),
                 "longitude": float(_F32(self.loc[r, 1])),
                 "elevation": float(_F32(self.loc[r, 2])),
                 "ts_ms": int(t)}
                for r, t in zip(rows[kind == KIND_LOC][:self.depth],
                                ts[kind == KIND_LOC][:self.depth])]
        alerts = [{"level": int(self.t.alevel[r]),
                   "type": self.atypes[self.t.atype[r]], "ts_ms": int(t)}
                  for r, t in zip(rows[kind == KIND_ALERT][:self.depth],
                                  ts[kind == KIND_ALERT][:self.depth])]
        counts = np.bincount(kind, minlength=6)
        return {
            "device": self.tokens[d],
            "presence": "PRESENT",
            "last_interaction_ms": int(ts.max()),
            "measurements": chans,
            "recent_locations": locs,
            "recent_alerts": alerts,
            "event_counts": {EVENT_TYPE_NAMES[e]: int(counts[e])
                             for e in range(6)},
        }

    # -------------------------------------------------------------- store
    def format_row(self, r: int, ts: int) -> dict:
        """An event as the query API pages it, without its ids and its
        receive stamp (neither is the sender's)."""
        k = int(self.t.kind[r])
        ev = {"type": EVENT_TYPE_NAMES[k], "deviceToken": self.tokens[
            self.t.dev[r]], "eventDateMs": int(ts)}
        if k == KIND_MEAS:
            ev["measurements"] = {
                name: float(_F32(self.meas[r, c]))
                for c, name in enumerate(self.names)
                if not np.isnan(self.meas[r, c])}
        elif k == KIND_LOC:
            ev["latitude"], ev["longitude"], ev["elevation"] = (
                float(_F32(self.loc[r, 0])), float(_F32(self.loc[r, 1])),
                float(_F32(self.loc[r, 2])))
        else:
            ev["level"] = int(self.t.alevel[r])
            ev["alertType"] = self.atypes[self.t.atype[r]]
        return ev

    def query(self, limit: int, device: int | None = None,
              tenant: int | None = None) -> dict:
        """A page over the ring: the newest ``store_capacity`` arrivals."""
        pos = np.arange(max(0, self.n - self.cap), self.n)
        rows = self.rows[pos]
        m = np.ones(len(pos), bool)
        if device is not None:
            m &= self.t.dev[rows] == device
        if tenant is not None:
            m &= self.t.ten[rows] == tenant
        pos = pos[m]
        order = np.lexsort((pos % self.cap, -self.ts[pos]))[:limit]
        return {"total": int(m.sum()),
                "events": [self.format_row(self.rows[p], self.ts[p])
                           for p in pos[order]]}


def page_view(page: dict) -> dict:
    """An engine query page with the fields the reference does not own
    (assignment id, receive stamp) dropped."""
    return {"total": page["total"],
            "events": [{k: v for k, v in ev.items()
                        if k not in ("assignmentId", "receivedDateMs")}
                       for ev in page["events"]]}
