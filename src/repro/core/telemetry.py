"""Per-node metrics, the envelope flight recorder, and the telemetry
wire messages — the fabric's observability plane.

Three pieces, one per failure mode the fleet used to hide:

* :class:`Metrics` — counters and histograms behind a single lock;
  ``inc``/``observe`` are a dict update each, cheap enough to sit on
  the envelope path. Counted at the ``Node`` choke points so
  ``msgs_out.<tag>`` / ``msgs_in.<tag>`` / ``bytes_out.<tag>`` match
  exact message counts (the fault-harness tests rely on this).
* :class:`FlightRecorder` — a bounded ring of recent envelope events
  (direction, tag, peer, size, trace ids). Dumped to stderr as one
  JSON object on node crash, eviction, or dead-letter, so a silent
  failure leaves a post-mortem artifact instead of nothing.
* :class:`TelemetryPull` / :class:`TelemetrySnapshot` — the registered
  wire messages that move a node's metrics + span buffer + ring to the
  user node. Pulls follow the registration tree (user → entry node →
  shards → clients) because TCP clients can only dial the node they
  registered with; snapshots hop back up the same path.

The train and serve hot paths use the same :class:`Metrics`: :func:`timed`
puts a leaf of host work in the profiler's trace as ``repro.<name>`` and
in a histogram, and :func:`rebuild_span` keeps each executable rebuild
as a span record with the compile seconds that
:class:`CompileListener`, the process's one ``jax.monitoring``
listener, saw on its thread.

Everything hangs off one :class:`NodeTelemetry` per node, created by
``Fleet.create(telemetry=True)``. With ``telemetry=False`` no
``NodeTelemetry`` exists, no trace context is ever opened, and the
envelope path is byte-identical to the pre-observability fabric.
"""
from __future__ import annotations

import contextlib
import json
import logging
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax

from repro.core import codec
from repro.core.tracing import SpanRecorder, TraceContext

log = logging.getLogger("repro.fabric")

# strips instance numbers from actor names for dead-letter dump dedup
_DIGITS_OUT = str.maketrans("", "", "0123456789")


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


class Metrics:
    """Counters + histograms for one node. Histogram summaries are
    count/sum/min/max — enough to answer "how many / how big / worst
    case" without binning policy."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._hists: Dict[str, List[float]] = {}   # [count, sum, min, max]

    def inc(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                self._hists[name] = [1, value, value, value]
            else:
                h[0] += 1
                h[1] += value
                h[2] = min(h[2], value)
                h[3] = max(h[3], value)

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._counters[name] = value

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0)

    def counters(self, prefix: str = "") -> Dict[str, float]:
        with self._lock:
            return {k: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def histograms(self, prefix: str = "") -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: {"count": h[0], "sum": h[1], "min": h[2], "max": h[3]}
                    for k, h in self._hists.items() if k.startswith(prefix)}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            hists = {k: {"count": h[0], "sum": h[1], "min": h[2], "max": h[3]}
                     for k, h in self._hists.items()}
            return {"counters": dict(self._counters), "histograms": hists}


# ---------------------------------------------------------------------------
# Hot-path spans on the profiler's clock
# ---------------------------------------------------------------------------


class _Timed:
    __slots__ = ("_metrics", "_name", "_ann", "_t0")

    def __init__(self, metrics: Metrics, name: str) -> None:
        self._metrics = metrics
        self._name = name
        self._ann = jax.profiler.TraceAnnotation("repro." + name)
        self._t0 = 0.0

    def __enter__(self) -> "_Timed":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        ms = (time.perf_counter() - self._t0) * 1e3
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._metrics.observe(self._name, ms)


def timed(metrics: Metrics, name: str) -> _Timed:
    """A host span ``repro.<name>`` in the profiler's trace (a few us
    when no trace is being taken) whose ``perf_counter`` milliseconds go
    to the ``<name>`` histogram of ``metrics`` when the body returns.

    Place it around a leaf of host work (a batch, a slot resolve, a
    rebuild), never around a whole step: the trace reader names an idle
    gap by the longest span over it."""
    return _Timed(metrics, name)


class StepArrays:
    """A per-step device array (such as the tokens a decode step routed
    to each expert), kept while ``on`` as a copy on the device: no host
    sync per step. ``drain`` fetches what was kept, once."""

    def __init__(self) -> None:
        self.on = False
        self._kept: List[Any] = []

    def record(self, x: Optional[jax.Array]) -> None:
        if self.on and x is not None:
            self._kept.append(jax.numpy.copy(x))

    def drain(self) -> List[Any]:
        kept, self._kept = self._kept, []
        return jax.device_get(kept)


# seconds of each compile phase that a rebuild record keeps
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _CompileRecord:
    """The compile events of one open rebuild record, as intervals:
    a jit traced inside another fires its own event inside the outer
    one's, so each phase's seconds are the length of their union."""

    __slots__ = ("attrs", "intervals")

    def __init__(self, attrs: Dict[str, Any]) -> None:
        self.attrs = attrs
        self.intervals: Dict[str, List[Tuple[float, float]]] = {}
        for key in COMPILE_EVENTS.values():
            attrs[key] = 0.0
            self.intervals[key] = []

    def add(self, key: str, end: float, seconds: float) -> None:
        ivs = self.intervals[key]
        ivs.append((end - seconds, end))
        total, reach = 0.0, float("-inf")
        for s, e in sorted(ivs):
            if e > reach:
                total += e - max(s, reach)
                reach = e
        self.attrs[key] = total


class CompileListener:
    """The process's one ``jax.monitoring`` listener. It keeps totals of
    the compile phases and of persistent-cache hits since it was
    installed, summed as JAX reports them, and adds each phase's
    seconds to the rebuild records open on the thread that compiles."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals = dict.fromkeys(COMPILE_EVENTS.values(), 0.0)
        self._totals["cache_hits"] = 0
        self._open = threading.local()

    def install(self) -> None:
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_: Any) -> None:
        key = COMPILE_EVENTS.get(event)
        if key is None:
            return
        end = time.perf_counter()
        with self._lock:
            self._totals[key] += duration
        for rec in getattr(self._open, "records", ()):
            rec.add(key, end, duration)

    def _event(self, event: str, **_: Any) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self._totals["cache_hits"] += 1

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    @contextlib.contextmanager
    def into(self, attrs: Dict[str, Any]) -> Iterator[None]:
        """Sets ``attrs``' ``trace_s``, ``lower_s`` and ``backend_s`` to
        the seconds this thread spends in each phase inside the block."""
        records = getattr(self._open, "records", None)
        if records is None:
            records = self._open.records = []
        rec = _CompileRecord(attrs)
        records.append(rec)
        try:
            yield
        finally:
            records.remove(rec)


_compile_listener: Optional[CompileListener] = None
_listener_lock = threading.Lock()


def compile_listener() -> CompileListener:
    """The process-wide listener, installed on first use."""
    global _compile_listener
    with _listener_lock:
        if _compile_listener is None:
            _compile_listener = CompileListener()
            _compile_listener.install()
        return _compile_listener


@contextlib.contextmanager
def rebuild_span(metrics: Metrics, spans: SpanRecorder, name: str,
                 md5s: Dict[str, str]) -> Iterator[None]:
    """A :func:`timed` span around building and first compiling one
    executable, kept as a record in ``spans`` whose attrs hold the new
    code's ``md5s`` and the trace, lower and backend-compile seconds that
    fired on this thread while it was open."""
    listener = compile_listener()
    with timed(metrics, name), spans.span(name, md5s=dict(md5s)) as s, \
            listener.into(s.span.attrs):
        yield


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded ring of recent envelope events on one node.

    Directions: ``out`` (routed to the wire), ``in`` (delivered off the
    wire), ``dead`` (dead-lettered), ``poison`` (undecodable frame).
    """

    def __init__(self, node_id: str, capacity: int = 512):
        self.node_id = node_id
        self.capacity = capacity
        self._lock = threading.Lock()
        self._ring: "deque[Dict[str, Any]]" = deque(maxlen=capacity)

    def record(self, direction: str, tag: str, peer: Optional[str],
               nbytes: int, trace: Optional[TraceContext] = None) -> None:
        ev: Dict[str, Any] = {"ts": time.time(), "dir": direction,
                              "tag": tag, "peer": peer, "bytes": nbytes}
        if trace is not None:
            ev["trace_id"] = trace.trace_id
            ev["span_id"] = trace.span_id
        with self._lock:
            self._ring.append(ev)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


# ---------------------------------------------------------------------------
# Per-node aggregate
# ---------------------------------------------------------------------------


class NodeTelemetry:
    """Everything one node records about itself: metrics, spans, and
    the envelope flight recorder, plus the dump path that turns a
    crash/eviction/dead-letter into a stderr JSON post-mortem."""

    def __init__(self, node_id: str, *, ring_capacity: int = 512,
                 span_capacity: int = 4096,
                 dump_stream: Any = None):
        self.node_id = node_id
        self.metrics = Metrics()
        self.spans = SpanRecorder(node_id, span_capacity)
        self.recorder = FlightRecorder(node_id, ring_capacity)
        # wired by Fleet.create when the transport is a FaultyTransport:
        # () -> dict, merged into every dump so a post-mortem shows the
        # faults that were injected next to the frames that suffered them
        self.fault_report_provider: Optional[Callable[[], Dict[str, Any]]] \
            = None
        self._dump_stream = dump_stream
        self._dead_seen: set = set()
        self._dead_lock = threading.Lock()
        # deploy-to-effect bridge: md5 of a freshly committed deploy ->
        # the shard_install span's context; the first analytics commit
        # won by that md5 pops it and parents a "first_commit" span there
        self._pending_effects: Dict[str, TraceContext] = {}
        self._effects_lock = threading.Lock()
        # staged rollouts: in-flight canary count behind the
        # rollouts_active gauge (see on_rollout_event)
        self._rollouts_active = 0

    # -- deploy-to-effect ---------------------------------------------------
    def register_pending_effect(self, md5: str, ctx: TraceContext) -> None:
        with self._effects_lock:
            self._pending_effects[md5] = ctx

    def take_pending_effect(self, md5: str) -> Optional[TraceContext]:
        with self._effects_lock:
            return self._pending_effects.pop(md5, None)

    # -- spans --------------------------------------------------------------
    def span(self, name: str, **attrs: Any):
        return self.spans.span(name, **attrs)

    # -- staged rollouts ----------------------------------------------------
    def on_rollout_event(self, ev: Any) -> None:
        """Rollout-state bookkeeping on the orchestrating node: one
        counter per event kind, a ``rollouts_active`` gauge, terminal
        decisions under ``rollout_decisions.*``, and — on auto-rollback
        — a flight-recorder dump so the frames around the unhealthy
        canary are preserved for post-mortem. ``ev`` is any object with
        the ``RolloutEvent`` surface (kind / rollout_id / slot / md5 /
        detail); duck-typed so telemetry stays import-light."""
        kind = ev.kind
        self.metrics.inc(f"rollout.{kind}")
        if kind == "canary_started":
            self._rollouts_active += 1
        elif kind in ("promoted", "rolled_back"):
            self._rollouts_active = max(0, self._rollouts_active - 1)
            self.metrics.inc(f"rollout_decisions.{kind}")
        self.metrics.set_gauge("rollouts_active",
                               float(self._rollouts_active))
        if kind == "rolled_back":
            self.dump(f"rollout-auto-rollback:{ev.rollout_id}:"
                      f"{ev.slot}@{ev.md5}: {ev.detail}")

    # -- envelope path hooks (called from Node.route/_deliver) --------------
    def on_send(self, tag: str, peer: Optional[str], nbytes: int,
                trace: Optional[TraceContext], encode_s: float,
                encoding: Optional[str] = None) -> None:
        m = self.metrics
        m.inc(f"msgs_out.{tag}")
        m.inc(f"bytes_out.{tag}", nbytes)
        m.observe("codec.encode_us", encode_s * 1e6)
        if encoding is not None:
            # per-frame wire-encoding label ("json", "binary",
            # "binary+zlib", ...): frame counts plus a bytes-per-frame
            # histogram, the bandwidth split the bench sweeps read out
            m.inc(f"frames_out.{encoding}")
            m.observe(f"frame_bytes_out.{encoding}", nbytes)
        self.recorder.record("out", tag, peer, nbytes, trace)

    def on_recv(self, tag: str, peer: Optional[str], nbytes: int,
                trace: Optional[TraceContext], decode_s: float,
                encoding: Optional[str] = None) -> None:
        m = self.metrics
        m.inc(f"msgs_in.{tag}")
        m.inc(f"bytes_in.{tag}", nbytes)
        m.observe("codec.decode_us", decode_s * 1e6)
        if encoding is not None:
            m.inc(f"frames_in.{encoding}")
            m.observe(f"frame_bytes_in.{encoding}", nbytes)
        self.recorder.record("in", tag, peer, nbytes, trace)

    def on_dead_letter(self, target: str, msg: Any) -> None:
        """A message had nowhere to go: count it, record it, and log
        the (tag, target) pair once — plus dump the ring the first time
        that pair is seen, so the silent-discard era leaves artifacts."""
        try:
            tag = codec.wire_tag_of(msg)
        except Exception:  # noqa: BLE001 - local-only message (tick, Down)
            tag = type(msg).__name__
        self.metrics.inc("dead_letters")
        self.recorder.record("dead", tag, target, 0)
        if tag == "stop_node":
            # shutdown is idempotent *by* dead-letter (a StopNode to an
            # already-stopped actor is the documented no-op), so a stop
            # is counted and ring-recorded but never worth a post-mortem
            return
        # per-assignment temporaries (cloud.asg12, shard0.asg12#3, ...)
        # differ only in their instance numbers; deduping on the exact
        # name would re-dump for every new assignment, turning expected
        # churn (a straggler task_done racing its cancelled handler)
        # into a dump per cancel — so the post-mortem fires once per
        # (tag, target-shape), not once per instance
        key = (tag, target.translate(_DIGITS_OUT))
        with self._dead_lock:
            first = key not in self._dead_seen
            if first:
                self._dead_seen.add(key)
        if first:
            log.warning("%s: dead letter %s -> unknown target %r "
                        "(logged once per pair)", self.node_id, tag, target)
            self.dump(f"dead-letter:{tag}->{target}")

    def on_poison_frame(self, nbytes: int) -> None:
        self.metrics.inc("poison_frames")
        self.recorder.record("poison", "?", None, nbytes)
        self.dump("poison-frame")

    # -- snapshot / dump ----------------------------------------------------
    def snapshot(self, mailbox_depths: Optional[Dict[str, int]] = None
                 ) -> Dict[str, Any]:
        if mailbox_depths:
            for name, depth in mailbox_depths.items():
                self.metrics.observe("mailbox_depth", depth)
        return {"node_id": self.node_id,
                "metrics": self.metrics.snapshot(),
                "spans": self.spans.drain(),
                "events": self.recorder.events()}

    def dump(self, reason: str, peer: Optional[str] = None,
             stream: Any = None) -> Dict[str, Any]:
        """Write the flight-recorder ring (filtered to ``peer`` if
        given), counters, and any injected-fault report as one JSON
        object on stderr; returns the dict for programmatic use."""
        events = self.recorder.events()
        if peer is not None:
            events = [e for e in events if e.get("peer") == peer]
        out: Dict[str, Any] = {"flight_recorder": True,
                               "node_id": self.node_id,
                               "reason": reason,
                               "ts": time.time(),
                               "counters": self.metrics.counters(),
                               "histograms": self.metrics.histograms(),
                               "events": events}
        if self.fault_report_provider is not None:
            try:
                out["fault_report"] = self.fault_report_provider()
            except Exception:  # noqa: BLE001 - reporting must not crash
                pass
        target = stream or self._dump_stream or sys.stderr
        try:
            print(json.dumps(out, sort_keys=True, default=str),
                  file=target, flush=True)
        except Exception:  # noqa: BLE001 - a broken stream must not
            pass           # take down the node being post-mortemed
        return out


# ---------------------------------------------------------------------------
# Wire messages
# ---------------------------------------------------------------------------


@dataclass
class TelemetryPull:
    """Ask a node for its telemetry snapshot (and to relay the pull to
    its registered children, pointing their replies back at itself)."""
    pull_id: str
    reply_to: str                      # "actor@node" to send snapshots to

    def to_wire_dict(self) -> Dict[str, Any]:
        return {"pull_id": self.pull_id, "reply_to": self.reply_to}

    @staticmethod
    def from_wire_dict(d: Dict[str, Any]) -> "TelemetryPull":
        return TelemetryPull(d["pull_id"], d["reply_to"])


@dataclass
class TelemetrySnapshot:
    """One node's telemetry, in flight back to whoever pulled it."""
    node_id: str
    pull_id: str
    metrics: Dict[str, Any] = field(default_factory=dict)
    spans: List[Dict[str, Any]] = field(default_factory=list)
    events: List[Dict[str, Any]] = field(default_factory=list)

    def to_wire_dict(self) -> Dict[str, Any]:
        return {"node_id": self.node_id, "pull_id": self.pull_id,
                "metrics": self.metrics, "spans": self.spans,
                "events": self.events}

    @staticmethod
    def from_wire_dict(d: Dict[str, Any]) -> "TelemetrySnapshot":
        return TelemetrySnapshot(d["node_id"], d["pull_id"],
                                 dict(d.get("metrics") or {}),
                                 list(d.get("spans") or []),
                                 list(d.get("events") or []))


codec.register_message("telemetry_pull", TelemetryPull)
codec.register_message("telemetry_snapshot", TelemetrySnapshot)


# ---------------------------------------------------------------------------
# Snapshot aggregation (user-side)
# ---------------------------------------------------------------------------


def merge_counters(snapshots: List[TelemetrySnapshot]
                   ) -> Dict[str, Dict[str, float]]:
    """Per-node counter tables keyed by node_id (the Fleet.metrics()
    shape); deduplicates by node_id, last snapshot wins."""
    out: Dict[str, Dict[str, float]] = {}
    for snap in snapshots:
        out[snap.node_id] = dict(
            (snap.metrics.get("counters") or {}).items())
    return out


def spans_of(snapshots: List[TelemetrySnapshot]) -> List[Dict[str, Any]]:
    seen: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for snap in snapshots:
        for d in snap.spans:
            seen[(d.get("trace_id", ""), d.get("span_id", ""))] = d
    return list(seen.values())
