"""Request-lifecycle tracing, on the sim tick clock or on a wall clock.

A ``Tracer`` collects, per request, the spans of its lifecycle —

    queued -> prefill [prefill_chunk[k]...] -> held -> (parked ->
    page_migration ->) decode -> finish

— plus fleet-level instant events (``routed``, ``admitted``,
``page_migration``, ``reroute``, ``failover``, ``autoscale``), each
annotated with the replica / shard group it ran on and page / prefix /
migration detail.

By default time is the simulation tick clock: the scheduler stamps its own
``step_idx`` when standalone, and the fabric router stamps the *fleet*
clock for every replica it drives (replica clocks drift through idle-gap
skipping, so per-replica ticks would not line up on one timeline).
``Tracer(clock=time.perf_counter)`` stamps every hook from that clock
instead, in seconds; a scheduler driving such a tracer also records the
phases of each ``step()`` (``step``, ``admit``, ``dispatch``, ``wait``) on
the scheduler lane, ``rid == LANE``.

Tracing is read-only by contract: hooks observe scheduler state and never
touch it, so a traced run emits byte-identical tokens to an untraced one
(asserted in tests/test_obs_plane.py).

Exports:

* ``write_chrome`` — Chrome trace-event JSON (open in Perfetto or
  ``chrome://tracing``): spans are ``"X"`` complete events with
  ``pid``/``process_name`` per replica and ``tid`` per request, one tick
  rendered as 1 ms, or one second as 1 s on a wall clock;
* ``write_jsonl`` / ``from_jsonl`` — a lossless JSON-lines round trip
  with the same fail-loud contract as ``repro.core.events.EventLog``
  (malformed input raises ``ValueError`` naming the 1-based line);
* ``to_event_log`` — the trace as an ``EventLog`` so any serving run
  (autoscaled or not) can export ``--events-out`` and replay it with the
  existing assertion helpers.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core.events import EventLog

__all__ = ["Span", "Instant", "Tracer", "TICK_US", "LANE"]

# one sim tick rendered as 1000 trace-event microseconds (= 1 ms), so a
# few-hundred-tick serve run spans a readable fraction of a second
TICK_US = 1000.0
# a wall-clock second in trace-event microseconds
SECOND_US = 1e6
# the ``rid`` of spans that belong to a scheduler rather than a request
LANE = -1


@dataclasses.dataclass
class Span:
    name: str
    rid: int
    t0: float
    t1: float
    replica: Optional[int] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": "span", "name": self.name, "rid": self.rid,
                "t0": self.t0, "t1": self.t1, "replica": self.replica,
                "attrs": self.attrs}


@dataclasses.dataclass
class Instant:
    name: str
    t: float
    rid: Optional[int] = None
    replica: Optional[int] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": "instant", "name": self.name, "t": self.t,
                "rid": self.rid, "replica": self.replica,
                "attrs": self.attrs}


class Tracer:
    """Span/instant collector on a settable tick clock or a wall clock.

    Without ``clock`` the time (``t``) is pushed by whoever owns the
    timeline — ``set_tick`` from the scheduler's or router's step loop —
    so hook sites just call ``begin``/``end``/``span``/``instant`` without
    plumbing a time argument. With ``clock`` (any callable returning
    seconds, e.g. ``time.perf_counter``) every hook without an explicit
    ``t`` reads it. ``begin`` on an already-open ``(rid, name)`` and
    ``end`` on a never-opened one are silent no-ops: a request may predate
    the tracer's attachment, and the fleet path opens ``queued`` at the
    router while the replica path would open it again.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None) -> None:
        self.clock = clock
        self.t = 0.0
        self.spans: List[Span] = []
        self.instants: List[Instant] = []
        self.process_names: Dict[int, str] = {}
        self._open: Dict[Tuple[int, str], Span] = {}
        self._indices: Dict[Tuple[int, str], int] = {}

    def set_tick(self, t: float) -> None:
        self.t = float(t)

    def now(self) -> float:
        """The current time: the wall clock's reading, else the tick."""
        return self.t if self.clock is None else float(self.clock())

    # ------------------------------------------------------------- record --
    def begin(self, name: str, rid: int, *, t: Optional[float] = None,
              replica: Optional[int] = None, **attrs: Any) -> None:
        key = (rid, name)
        if key in self._open:
            return                        # first opener wins (fleet submit)
        self._open[key] = Span(name, rid, self.now() if t is None
                               else float(t), -1.0, replica, dict(attrs))

    def end(self, name: str, rid: int, *, t: Optional[float] = None,
            **attrs: Any) -> None:
        span = self._open.pop((rid, name), None)
        if span is None:
            return                        # unmatched end: tolerated no-op
        span.t1 = self.now() if t is None else float(t)
        span.attrs.update(attrs)
        self.spans.append(span)

    def annotate(self, name: str, rid: int, **attrs: Any) -> None:
        """Set attributes of an open span (no-op when it is not open)."""
        span = self._open.get((rid, name))
        if span is not None:
            span.attrs.update(attrs)

    def span(self, name: str, rid: int, t0: float, t1: float, *,
             replica: Optional[int] = None, **attrs: Any) -> None:
        """A complete span in one call (e.g. a prefill chunk landing
        within a single tick)."""
        self.spans.append(Span(name, rid, float(t0), float(t1), replica,
                               dict(attrs)))

    def instant(self, name: str, *, rid: Optional[int] = None,
                t: Optional[float] = None, replica: Optional[int] = None,
                **attrs: Any) -> None:
        self.instants.append(Instant(name, self.now() if t is None
                                     else float(t), rid, replica,
                                     dict(attrs)))

    def next_index(self, rid: int, name: str) -> int:
        """Per-(request, name) running index — chunk numbering."""
        key = (rid, name)
        self._indices[key] = self._indices.get(key, -1) + 1
        return self._indices[key]

    def set_process_name(self, pid: int, label: str) -> None:
        self.process_names[int(pid)] = str(label)

    def finish_open(self, t: Optional[float] = None) -> int:
        """Close every still-open span at ``t``, by default now (export
        time on a run that was interrupted or is mid-flight), marking it
        ``open=True``; returns how many were flushed."""
        t = self.now() if t is None else float(t)
        n = 0
        for key in sorted(self._open, key=lambda k: (str(k[1]), k[0])):
            span = self._open.pop(key)
            span.t1 = max(t, span.t0)
            span.attrs["open"] = True
            self.spans.append(span)
            n += 1
        return n

    # ----------------------------------------------------- chrome export --
    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object (Perfetto / chrome://tracing).

        ``pid`` = replica id (+1; pid 0 is the fleet/router plane so
        replica 0 keeps its own lane), ``tid`` = request id (``LANE`` for
        a scheduler's step phases), ``ts``/``dur`` in microseconds at
        ``TICK_US`` per tick, or ``SECOND_US`` per second on a wall clock
        (``otherData.clock`` says which). Span/instant attrs (including
        ``replica``) travel in ``args``.
        """
        unit = TICK_US if self.clock is None else SECOND_US
        events: List[Dict[str, Any]] = []
        events.append({"ph": "M", "name": "process_name", "pid": 0,
                       "args": {"name": "fleet"}})
        for pid, label in sorted(self.process_names.items()):
            events.append({"ph": "M", "name": "process_name", "pid": pid + 1,
                           "args": {"name": label}})

        def _pid(replica):
            return 0 if replica is None else int(replica) + 1

        for s in self.spans:
            events.append({
                "ph": "X", "name": s.name, "cat": "request",
                "pid": _pid(s.replica), "tid": int(s.rid),
                "ts": s.t0 * unit,
                "dur": max(s.t1 - s.t0, 0.0) * unit,
                "args": {"rid": s.rid, "replica": s.replica, **s.attrs},
            })
        for i in self.instants:
            events.append({
                "ph": "i", "name": i.name, "cat": "fleet",
                "pid": _pid(i.replica),
                "tid": int(i.rid) if i.rid is not None else 0,
                "ts": i.t * unit,
                "s": "g" if i.rid is None else "t",
                "args": {"rid": i.rid, "replica": i.replica, **i.attrs},
            })
        clock = (f"sim tick = {TICK_US} us" if self.clock is None
                 else f"wall clock, 1 s = {SECOND_US:g} us")
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"clock": clock}}

    def write_chrome(self, path: str) -> int:
        """Write Chrome trace JSON; returns the number of trace events."""
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"])

    # ------------------------------------------------------ jsonl roundtrip --
    def to_jsonl(self) -> str:
        lines = [json.dumps({"kind": "meta", "pid": pid, "label": label},
                            sort_keys=True)
                 for pid, label in sorted(self.process_names.items())]
        lines += [json.dumps(s.to_dict(), sort_keys=True, default=str)
                  for s in self.spans]
        lines += [json.dumps(i.to_dict(), sort_keys=True, default=str)
                  for i in self.instants]
        return "".join(line + "\n" for line in lines)

    def write_jsonl(self, path: str) -> int:
        with open(path, "w") as f:
            f.write(self.to_jsonl())
        return len(self.spans) + len(self.instants)

    _REQUIRED = {"span": ("name", "rid", "t0", "t1"),
                 "instant": ("name", "t"),
                 "meta": ("pid", "label")}

    @classmethod
    def from_jsonl(cls, path: str) -> "Tracer":
        """Load an exported trace; same fail-loud contract as
        ``EventLog.from_jsonl`` — malformed input raises ``ValueError``
        naming the offending 1-based line, so a truncated or hand-edited
        trace fails loud instead of replaying silently wrong."""
        tr = cls()
        with open(path) as f:
            for lineno, line in enumerate(f, start=1):
                line = line.strip()
                if not line:
                    continue
                try:
                    d = json.loads(line)
                except json.JSONDecodeError as e:
                    raise ValueError(
                        f"{path}: line {lineno} is not valid JSON "
                        f"({e.msg} at column {e.colno})") from e
                if not isinstance(d, dict):
                    raise ValueError(
                        f"{path}: line {lineno} holds a JSON "
                        f"{type(d).__name__}, not a trace record")
                kind = d.get("kind")
                if kind not in cls._REQUIRED:
                    raise ValueError(
                        f"{path}: line {lineno} has unknown trace record "
                        f"kind {kind!r} (expected one of "
                        f"{sorted(cls._REQUIRED)})")
                missing = [k for k in cls._REQUIRED[kind] if k not in d]
                if missing:
                    raise ValueError(
                        f"{path}: line {lineno} ({kind}) is missing "
                        f"field(s) {missing} (has {sorted(d)})")
                attrs = d.get("attrs", {})
                if not isinstance(attrs, dict):
                    raise ValueError(
                        f"{path}: line {lineno} has a non-object 'attrs' "
                        f"({type(attrs).__name__})")
                if kind == "meta":
                    tr.process_names[int(d["pid"])] = str(d["label"])
                elif kind == "span":
                    tr.spans.append(Span(d["name"], d["rid"], d["t0"],
                                         d["t1"], d.get("replica"),
                                         dict(attrs)))
                else:
                    tr.instants.append(Instant(d["name"], d["t"],
                                               d.get("rid"),
                                               d.get("replica"),
                                               dict(attrs)))
        return tr

    # ----------------------------------------------------------- EventLog --
    def to_event_log(self) -> EventLog:
        """The trace as an ``EventLog`` (time-ordered; spans keyed at their
        start): lets any serving run export ``--events-out`` and reuse the
        existing replay/assertion machinery, autoscaled or not."""
        log = EventLog()
        records: List[Tuple[float, int, str, str, Dict[str, Any]]] = []
        for n, s in enumerate(self.spans):
            actor = "fleet" if s.replica is None else f"replica-{s.replica}"
            records.append((s.t0, n, actor, s.name,
                            {"rid": s.rid, "dur": s.t1 - s.t0, **s.attrs}))
        base = len(self.spans)
        for n, i in enumerate(self.instants):
            actor = "fleet" if i.replica is None else f"replica-{i.replica}"
            detail = dict(i.attrs)
            if i.rid is not None:
                detail["rid"] = i.rid
            records.append((i.t, base + n, actor, i.name, detail))
        for t, _, actor, action, detail in sorted(records,
                                                  key=lambda r: (r[0], r[1])):
            log.emit(t, actor, action, **detail)
        return log
