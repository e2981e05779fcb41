"""Saturation & SLO observability plane (USE-method instrumentation).

PR 4's spans can say where ONE sampled request lost its time; this
module aggregates the same signals ALWAYS-ON, so the operator questions
("where do the p99 milliseconds go", "how full is the bucket table",
"are we burning the error budget") have live answers without sampling:

* **Latency attribution** — per-phase duration reservoirs covering the
  whole request waterfall (`WATERFALL`): the C++ edge's socket reads
  and hand-off -> ingress parse -> batch-window wait -> queue wait ->
  the five dispatch pipeline stages and the lock and gate waits between
  them -> peer-wire RTT -> response encode -> the edge's socket writes.
  Every phase site is one `with phase(...)`, whose reading also reaches
  the host sampler, the sampled span and the profiler's trace; the
  edge's three (`edge.recv`, `edge.handoff`, `edge.send`) are stamped in
  C++ by the acceptor threads, which run no Python, and observed from
  those stamps (`observe_phase`), one observation a request.  Each
  observation also feeds the
  `gubernator_latency_attribution_seconds{phase}` histogram of the
  registered metrics sink; `GET /debug/latency` serves ceil-rank
  percentile snapshots straight from the reservoirs.

* **SLO engine** — `SloEngine` turns per-request ingress latency into
  multi-window (5m / 1h) error-budget burn rates against
  `GUBER_LATENCY_TARGET_MS`; a fast burn (Google SRE's 14.4x on the
  short window) trips the PR 4 flight-recorder auto-dump path
  (`tracing.record_event("slo-fast-burn")`).

* **Hot-key sketch** — `HotKeySketch`, a count-min sketch + top-K
  tracker fed from the owner-code hashes `hash_ring.get_batch_codes`
  ALREADY computes (zero extra hashing on the hot path), served at
  `GET /debug/hotkeys` — the detection half of the ROADMAP item-5
  hot-key defense.

* **Saturation accumulators** — per-launch lane utilization (fill vs
  pow2 pad), dispatcher busy fraction, and ingress-queue depth
  samples, drained per metrics scrape like the dispatch-stage gauges.

Reservoirs/accumulators are MODULE-GLOBAL, like the tracing flight
recorder: one daemon per process in production, and in-process
multi-daemon tests share one plane exactly as they share one span ring.
Everything here is host-side arithmetic on data the hot path already
produced — the plane adds ZERO device programs (pinned by counting
dispatches, tests/test_observability.py).
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from . import native, profiling, tracing

# ---------------------------------------------------------------------
# Shared ceil-rank percentiles (one definition, so every percentile
# site — /debug/latency, queue-depth snapshots — indexes the same
# way).
# ---------------------------------------------------------------------


def percentile_rank(n: int, q: float) -> int:
    """0-based index of the q-quantile in a sorted n-sample list, by
    the NEAREST-RANK definition: 1-based rank ceil(q*n).  The floor
    form `min(n-1, int(n*q))` lands a rank off the nearest-rank tail
    value at small n, so thin tails would be judged against the wrong
    sample."""
    if n <= 0:
        raise ValueError("percentile of an empty sample")
    return min(n - 1, max(0, math.ceil(q * n) - 1))


def percentile(sorted_vals: Sequence[float], q: float) -> float:
    return sorted_vals[percentile_rank(len(sorted_vals), q)]


# ---------------------------------------------------------------------
# Latency attribution: per-phase reservoirs
# ---------------------------------------------------------------------

# The request waterfall, in flight order, as (phase, depth): depth 0 is
# a top-level phase of a request, a deeper phase lies INSIDE the nearest
# shallower phase above it (its time is part of that one's and must not
# be added to it).  Snapshots list phases in this order so a
# /debug/latency reader sees the pipeline shape; the document's
# `waterfall` key serves the table itself.
WATERFALL = (
    ("epoll.wait", 0),        # a gateway worker has no request (blocked in
                              # edge.next); the socket's own reads and writes
                              # are the acceptor threads' and are edge.recv /
                              # edge.send, not this
    ("pump.take", 0),         # the native pump has no frame (blocked in batcher.take)
    ("edge.recv", 0),         # C++ edge: the read that brought a request's first
                              # byte -> its last body byte framed
    ("edge.handoff", 0),      # C++ edge: body complete -> a worker holds the
                              # request (ready queue, wake-up, the interpreter
                              # taken back, the sniff)
    ("ingress.parse", 0),     # wire bytes -> IngressColumns (gateway)
    ("window.idle", 0),       # a BatchWindow's flusher has no submission
    ("window.hold", 0),       # ... holds submissions until the window closes or fills
    ("batch.window", 0),      # submit -> coalescing-window flush (batchers; a
                              # native frame: its arrival -> the take)
    ("pump.depth_wait", 0),   # native take: the pipeline-depth semaphore
    ("pump.admit", 0),        # native take: what stands in front of its launch
                              # besides the two phases below (the audit note; a
                              # traced take's edge stamps)
    ("calendar.resolve", 0),  # DURATION_IS_GREGORIAN lanes -> greg_expire /
                              # greg_duration at the dispatch's one clock
                              # reading: every native take (a plain one holds
                              # a test of the take's beh_or), and a Python-path
                              # request that holds one
    ("behavior.handle", 0),   # the callers' behaviour bits of a native take: the
                              # check for GLOBAL and MULTI_REGION lanes and, where
                              # there are some, the MULTI_REGION hits queued for
                              # the other regions (the GLOBAL lanes' book-keeping
                              # is dispatch.global_note, inside the plan); a
                              # Python-path request that holds such a lane
    ("express.submit", 0),    # express bypass: submit -> dispatch launched
                              # (replaces batch.window + queue.wait for
                              # express lanes — the express-vs-batched split;
                              # the bypass dispatches inline, so this take's
                              # dispatch.prepare..launch lie INSIDE it)
    ("queue.wait", 0),        # flush -> dispatch submit (backstop + concat)
    ("queue.backstop", 1),    # blocked on the oldest unresolved dispatch
    ("dispatch.prepare", 0),  # slot-table planning (pipeline stage 1)
    ("dispatch.plan_wait", 1),  # waiting for the plan lock
    ("dispatch.plan_native", 1),  # the C++ slot-table plan alone (begin + grouped plan)
    ("dispatch.global_note", 1),  # a batch's GLOBAL lanes, owned here (under the plan
                              # lock; entered only where the batch holds one).  A LANE:
                              # its key taken from the packed keys and stored in a dict
                              # (the last lane of a key wins).  A DISTINCT KEY: its gslot
                              # looked up or assigned, its configuration, the owner row
                              # dirty.  The mesh tally's globalLanes over globalKeys says
                              # how many lanes a key's work was paid for
    ("dispatch.stage", 0),    # wire encode + H2D upload start (stage 2)
    ("dispatch.upload", 1),   # the stage's transfer call alone: one device_put
                              # of one buffer, on either wire
    ("dispatch.gate_wait", 0),  # waiting for the ticket's launch turn
    ("dispatch.launch", 0),   # ticket-ordered jit call (stage 3)
    ("dispatch.launch_wait", 1),  # waiting for the store lock
    ("dispatch.moves", 1),    # two-tier table: the backlog's tier-move launches,
                              # entered only when a plan queued moves
    ("pump.handoff", 0),      # native take: queued for a done-pool worker
    ("dispatch.fetch", 0),    # device->host readback
    ("dispatch.commit", 0),   # decode + table commit
    ("pump.outcome", 0),      # native take: result copies + tenant outcome fold
    ("peer.rpc", 0),          # forwarded-hop round trip (peer_client)
    ("response.encode", 0),   # ColumnarResult -> wire bytes (gateway)
    ("edge.send", 0),         # C++ edge: the answer handed to the acceptor ->
                              # the kernel has accepted its last byte (eventfd
                              # wake, staging copy, EPOLLOUT round, sends)
    ("pump.account", 0),      # native take, what no request waits on: its observers
                              # (ring counters, black-box tap, tenant fold, hot-key
                              # sketch) once it has launched, while the device
                              # computes; request metrics after the answers left
    ("ingress.total", 0),     # whole-request wall time (GetRateLimits)
    ("global.sync_drain", 0),  # GLOBAL tick: pipeline drain + both locks
    ("global.sync", 0),       # GLOBAL tick, locks held: dispatch, read-back, commit
    ("global.tick_idle", 0),  # a GLOBAL tick with nothing pending: returns before drain and locks
)
PHASES = tuple(p for p, _ in WATERFALL)

PHASE_RING = 2048  # recent samples kept per phase


class _PhaseStats:
    """One phase's reservoir: a ring of recent durations plus lifetime
    count/sum.  A small lock per observation — observations happen per
    BATCH or per REQUEST, not per lane, so contention is negligible."""

    __slots__ = ("_buf", "_lock", "count", "sum_s", "max_s")

    def __init__(self):
        self._buf: List[float] = []
        self._lock = threading.Lock()
        self.count = 0
        self.sum_s = 0.0
        self.max_s = 0.0

    def observe(self, dt_s: float) -> None:
        with self._lock:
            self.count += 1
            self.sum_s += dt_s
            if dt_s > self.max_s:
                self.max_s = dt_s
            if len(self._buf) >= PHASE_RING:
                self._buf[self.count % PHASE_RING] = dt_s
            else:
                self._buf.append(dt_s)

    def observe_many(self, dts_s: Sequence[float]) -> None:
        with self._lock:
            buf = self._buf
            for dt_s in dts_s:
                self.count += 1
                self.sum_s += dt_s
                if dt_s > self.max_s:
                    self.max_s = dt_s
                if len(buf) >= PHASE_RING:
                    buf[self.count % PHASE_RING] = dt_s
                else:
                    buf.append(dt_s)

    def snapshot(self) -> Optional[dict]:
        with self._lock:
            if not self.count:
                return None
            vals = sorted(self._buf)
            return {
                "count": self.count,
                "sum_ms": round(self.sum_s * 1000.0, 3),
                "max_ms": round(self.max_s * 1000.0, 3),
                "p50_ms": round(percentile(vals, 0.50) * 1000.0, 3),
                "p90_ms": round(percentile(vals, 0.90) * 1000.0, 3),
                "p99_ms": round(percentile(vals, 0.99) * 1000.0, 3),
                "n_samples": len(vals),
            }


_phases: Dict[str, _PhaseStats] = {p: _PhaseStats() for p in PHASES}
# Prometheus sink: (histogram, {phase: child}) of the most recently
# constructed Metrics instance.  Last-wins, like the tracing rings —
# production runs one daemon per process; in-process test clusters
# share the plane.
_sink: Optional[list] = None
_sink_lock = threading.Lock()


def register_sink(histogram) -> None:
    """Attach a prometheus Histogram (labeled by `phase`) that every
    observe_phase ALSO feeds — metrics.py calls this at Metrics init."""
    global _sink
    with _sink_lock:
        _sink = [histogram, {}]


def observe_phase(phase: str, dt_s: float) -> None:
    """Record one completed phase interval.  Called from the hot path
    (per batch / per request): one lock, one ring write, one histogram
    observe."""
    st = _phases.get(phase)
    if st is None:  # unknown phase: record rather than drop
        st = _phases.setdefault(phase, _PhaseStats())
    st.observe(dt_s)
    sink = _sink
    if sink is not None:
        child = sink[1].get(phase)
        if child is None:
            try:
                child = sink[1][phase] = sink[0].labels(phase=phase)
            except Exception:  # noqa: BLE001 — a dead registry must not fail requests
                return
        child.observe(dt_s)


def observe_phases(phase: str, dts_s: Sequence[float]) -> None:
    """Record several completed intervals of one phase, an observation
    each, under one hold of the reservoir's lock: for intervals measured
    elsewhere and read together (the C++ edge's stamps of a take's
    frames, the answers drained from its send ring)."""
    st = _phases.get(phase)
    if st is None:  # unknown phase: record rather than drop
        st = _phases.setdefault(phase, _PhaseStats())
    st.observe_many(dts_s)
    sink = _sink
    if sink is not None:
        child = sink[1].get(phase)
        if child is None:
            try:
                child = sink[1][phase] = sink[0].labels(phase=phase)
            except Exception:  # noqa: BLE001 — a dead registry must not fail requests
                return
        for dt_s in dts_s:
            child.observe(dt_s)


_profiler_session_on = TraceAnnotation.is_enabled  # one atomic load


class phase:
    """THE span primitive of the waterfall: `with phase(name, bt, **ids):`
    takes the clock once around its body and the one reading lands in

    (a) the always-on reservoir `/debug/latency` serves (`observe_phase`);
    (b) the host sampler's per-thread tag (`/debug/pprof` folds by it);
    (c) a sampled span under the batch trace `bt`, linked to its member
        lanes and carrying `ids` (a take's spans share its `ticket`) —
        only when the batch was sampled (`bt` is not None);
    (d) a `jax.profiler.TraceAnnotation(name, **ids)` on this thread's
        line of the profiler's trace, on the device trace's clock — only
        while a profiler session runs (one check otherwise).

    It closes on an exception too (the span then carries `error`).
    `dt_s` holds the reading after exit, for a caller that feeds a gauge
    of its own from it.  `name` may be reassigned inside the interval:
    the reservoir and the span take the name it has at exit (a GLOBAL
    tick learns only after its drain whether it has anything to sync).
    An interval measured elsewhere (in C++, or from a stamp taken on
    another thread) goes to `observe_phase`."""

    __slots__ = ("name", "bt", "ids", "dt_s", "_t0", "_tagged", "_prev_tag",
                 "_ann")

    def __init__(self, name: str, bt: "Optional[tracing.BatchTrace]" = None,
                 **ids):
        self.name = name
        self.bt = bt
        self.ids = ids
        self.dt_s = 0.0

    @property
    def traced(self) -> bool:
        """Inside the interval: whether it is an event of a running
        profiler session (what `note` adds then reaches the trace)."""
        return self._ann is not None

    def note(self, **ids) -> None:
        """Identifiers learned inside the interval (a ticket is assigned
        under the plan lock): they join the sampled span's attributes
        and the profiler event's."""
        self.ids.update(ids)
        if self._ann is not None:
            self._ann.set_metadata(**ids)

    def __enter__(self) -> "phase":
        self._ann = None
        if _profiler_session_on():
            self._ann = TraceAnnotation(self.name, **self.ids)
            self._ann.__enter__()
        self._tagged = profiling.enabled()
        if self._tagged:
            self._prev_tag = profiling.push_scope(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dt_s = dt = time.perf_counter() - self._t0
        if self._tagged:
            profiling.pop_scope(self._prev_tag)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        observe_phase(self.name, dt)
        if self.bt is not None:
            if exc_type is not None:
                self.ids["error"] = str(exc)
            tracing.stage_span(self.name, dt, self.bt, **self.ids)
        return False


def edge_trace_note(anchor_ns: int, take_ns: Optional[int] = None,
                    stamps=(), sends=()) -> Dict[str, object]:
    """The C++ edge's stamps as metadata of a profiler event of the native
    pump (`phase.note`): an acceptor thread can write no event of its
    own, so its readings ride events that are written anyway, a take's
    `pump.admit` (its frames' stamps) and `pump.account` (the answers
    drained there).  `anchor_ns` is `time.monotonic_ns()` read beside the
    event's start: a reader takes the event's own start less `mono_ns`
    for the offset between the stamps' clock and the trace's, and every
    other value is nanoseconds from the anchor.  `edge` holds a
    `token:t_first_byte:t_body:arrival` a frame of the take and `take`
    the take's own clock reading; `sends` a `token:t_staged:t_last_byte`
    an answer drained (of earlier takes, as a rule); `;` between records.
    No `,`, `=` or `#`: the profiler splits an event's name on those."""
    note: Dict[str, object] = {"mono_ns": anchor_ns}
    if take_ns is not None:
        note["take"] = take_ns - anchor_ns
        note["edge"] = ";".join(
            ":".join(str(v) for v in (tok, fb - anchor_ns, body - anchor_ns, arr - anchor_ns))
            for tok, fb, body, arr in stamps)
    if sends:
        note["sends"] = ";".join(
            ":".join(str(v) for v in (tok, staged - anchor_ns, last - anchor_ns))
            for tok, staged, last in sends)
    return note


def phase_snapshot() -> Dict[str, dict]:
    """{phase: {count, sum_ms, max_ms, p50/p90/p99_ms, n_samples}} for
    every phase that has observations, in waterfall order."""
    out: Dict[str, dict] = {}
    for p in list(_phases):
        snap = _phases[p].snapshot()
        if snap is not None:
            out[p] = snap
    return out


# ---------------------------------------------------------------------
# Saturation accumulators (drained per metrics scrape)
# ---------------------------------------------------------------------
class LaneUtil:
    """Per-launch lane utilization: real lanes vs the pow2-padded shape
    the program actually scattered.  take() drains the deltas since the
    last scrape (the dispatch-stage gauge convention)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._lanes = 0
        self._padded = 0
        self._launches = 0

    def add(self, lanes: int, padded: int) -> None:
        with self._lock:
            self._lanes += int(lanes)
            self._padded += int(padded)
            self._launches += 1

    def take(self) -> Tuple[int, int, int]:
        with self._lock:
            out = (self._lanes, self._padded, self._launches)
            self._lanes = self._padded = self._launches = 0
        return out


class MeshTally:
    """What the mesh adds to a dispatch, summed over every columnar
    dispatch since the process started (cumulative: `GET /debug/device`
    serves it as `mesh` and a reader takes differences).  A take pads to
    the pad bucket of its FULLEST shard, so `fullest` against `lanes`
    says how uneven the shards were and `lanes` against `padded` how
    much of the launched shape was real.

    And which wire carried it (MeshBucketStore._stage_columns): a
    dispatch of at most 256 distinct configurations rides the dictionary
    wire (one i32 buffer, one transfer); one of more rides the per-lane
    wire (a word a value, one buffer and one transfer too) and counts under
    `laneWireDispatches` / `laneWireLanes`, so the dictionary's share is
    the difference from `dispatches` / `lanes`.  `configRows` sums the
    distinct configurations the native encode counted
    (NativeMeshPlanner.encode_wire; 0 where the dictionary was not
    tried: a forced wire, more than 255 rounds),
    `uploads` the host-to-device transfer calls the stages made.

    And what the calendar adds: `calendarLanes` sums the lanes that
    carried DURATION_IS_GREGORIAN, `wideDispatches` counts the
    dispatches whose answer was i64 on either wire (a monthly or yearly
    lane's expiry and duration pass i32: models/shard.py narrow_ok).

    And what the callers' behaviours add: `flaggedLanes` sums the lanes
    that carried NO_BATCHING, GLOBAL or MULTI_REGION into a columnar
    dispatch (models/shard.py split_routing_bits).

    And what the GLOBAL sync passes carried (MeshBucketStore.
    _sync_globals_locked): `syncPasses` counts the passes that ran,
    `syncTouched` sums the gslots they took (touched since the pass
    before), `syncRows` the rows their launches carried (the program's
    width a launch: what a pass pays for).

    And what the owner's book-keeping of GLOBAL lanes was handed
    (MeshBucketStore._note_global_owners, once a dispatch that holds
    such a lane): `globalLanes` sums the GLOBAL lanes, each of which
    costs a key read and a dict store, `globalKeys` the DISTINCT keys
    among a dispatch's, each of which costs a gslot lookup or
    assignment and a configuration row.

    And how the takes ran through the pipeline: `launches` counts the
    programs launched for columnar dispatches (ColumnarPipeline.
    _launch_group; a fused group of 2 or 4 dispatches is one) and
    `fusedDispatches` the dispatches that rode such a group; `takes` and
    `takeFrames` count the native ingress lane's takes and the frames
    they held, `inFlightSum` sums, at each take's admission, the takes
    admitted and not yet committed, that one included
    (gateway.NativeIngressPump), so over `takes` it is the pipeline's
    mean depth as a take finds it."""

    WIRE_KEYS = ("dispatches", "lanes", "laneWireDispatches", "laneWireLanes",
                 "configRows", "uploads", "calendarLanes", "wideDispatches")

    def __init__(self):
        self._lock = threading.Lock()
        self._shards = 0
        self._sums = dict.fromkeys(
            ("dispatches", "lanes", "paddedLanes", "fullestShardLanes", "rounds",
             "laneWireDispatches", "laneWireLanes", "configRows", "uploads",
             "calendarLanes", "wideDispatches", "flaggedLanes",
             "syncPasses", "syncRows", "syncTouched",
             "globalLanes", "globalKeys",
             "launches", "fusedDispatches", "takes", "takeFrames",
             "inFlightSum"), 0
        )

    def add(self, shards: int, lanes: int, padded: int, fullest: int,
            rounds: int, lane_wire: bool = False, config_rows: int = 0,
            uploads: int = 0, calendar_lanes: int = 0,
            wide: bool = False, flagged_lanes: int = 0) -> None:
        with self._lock:
            self._shards = shards
            s = self._sums
            s["dispatches"] += 1
            s["lanes"] += lanes
            s["paddedLanes"] += padded
            s["fullestShardLanes"] += fullest
            s["rounds"] += rounds
            if lane_wire:
                s["laneWireDispatches"] += 1
                s["laneWireLanes"] += lanes
            s["configRows"] += config_rows
            s["uploads"] += uploads
            s["calendarLanes"] += calendar_lanes
            s["wideDispatches"] += wide
            s["flaggedLanes"] += flagged_lanes

    def add_sync(self, rows: int, touched: int) -> None:
        with self._lock:
            s = self._sums
            s["syncPasses"] += 1
            s["syncRows"] += rows
            s["syncTouched"] += touched

    def add_global_note(self, lanes: int, keys: int) -> None:
        with self._lock:
            s = self._sums
            s["globalLanes"] += lanes
            s["globalKeys"] += keys

    def add_launch(self, dispatches: int) -> None:
        with self._lock:
            s = self._sums
            s["launches"] += 1
            if dispatches > 1:
                s["fusedDispatches"] += dispatches

    def add_take(self, frames: int, in_flight: int) -> None:
        with self._lock:
            s = self._sums
            s["takes"] += 1
            s["takeFrames"] += frames
            s["inFlightSum"] += in_flight

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"shards": self._shards, **self._sums}

    def wire_snapshot(self) -> Dict[str, int]:
        """The wire's split alone (`/debug/status` `wire`)."""
        with self._lock:
            return {k: self._sums[k] for k in self.WIRE_KEYS}


class BusyFraction:
    """Busy-seconds accumulator for the dispatcher (batch-window flush
    worker): take() returns (busy_s, elapsed_s) since the last take, so
    the scrape renders a utilization fraction."""

    def __init__(self, time_fn=time.monotonic):
        self._lock = threading.Lock()
        self._time = time_fn
        self._busy = 0.0
        self._last_take = time_fn()

    def add(self, dt_s: float) -> None:
        with self._lock:
            self._busy += dt_s

    def take(self) -> Tuple[float, float]:
        with self._lock:
            now = self._time()
            out = (self._busy, max(now - self._last_take, 1e-9))
            self._busy = 0.0
            self._last_take = now
        return out


class _DepthRing:
    """Lock-free ring of ingress-queue depth samples (one per admit),
    the tracing._Ring trick: itertools.count + slot store are atomic
    under the GIL."""

    CAP = 4096

    def __init__(self):
        self._buf: List[Optional[int]] = [None] * self.CAP
        self._seq = itertools.count()

    def record(self, depth: int) -> None:
        self._buf[next(self._seq) % self.CAP] = depth

    def snapshot(self) -> dict:
        vals = sorted(v for v in list(self._buf) if v is not None)
        if not vals:
            return {"n_samples": 0}
        return {
            "n_samples": len(vals),
            "p50": percentile(vals, 0.50),
            "p99": percentile(vals, 0.99),
            "max": vals[-1],
        }


class ExpressStats:
    """Express-vs-batched lane accounting (the PR 14 millisecond
    express lane).  Each dispatch notes which path its lanes took:

      * ``bypass``   — batcher shallow-queue bypass (direct dispatch,
                       no coalescing window)
      * ``native``   — NO_BATCHING frames served by the native ingress
                       express queue (gt_ingress_*)
      * ``windowed`` — lanes that rode a coalesced batch: a Python
                       window flush OR the native ring's bulk path
                       (the pump feeds both into this denominator)

    and each submission the batchers' admission rule sent to the window
    notes why it did not bypass (service._ExpressPolicy):

      * ``wide``      — more lanes than GUBER_EXPRESS_MAX_LANES
      * ``launching`` — a dispatch was under way (being planned, or
                        planned and not launched; or the batcher's
                        flusher inside its flush)
      * ``queued``    — lanes were waiting at the batcher

    `take()` drains per-scrape deltas for the gubernator_express_*
    counters; `snapshot()` serves cumulative counts + the hit rate at
    /debug/latency and /debug/status."""

    PATHS = ("bypass", "native", "windowed")
    DECLINED = ("wide", "launching", "queued")

    def __init__(self):
        self._lock = threading.Lock()
        self._lanes = {p: 0 for p in self.PATHS}
        self._dispatches = {p: 0 for p in self.PATHS}
        self._delta_lanes = {p: 0 for p in self.PATHS}
        self._declined_lanes = {r: 0 for r in self.DECLINED}
        self._declined = {r: 0 for r in self.DECLINED}

    def note(self, path: str, lanes: int) -> None:
        with self._lock:
            self._lanes[path] = self._lanes.get(path, 0) + int(lanes)
            self._dispatches[path] = self._dispatches.get(path, 0) + 1
            self._delta_lanes[path] = (
                self._delta_lanes.get(path, 0) + int(lanes)
            )

    def note_declined(self, reason: str, lanes: int) -> None:
        with self._lock:
            self._declined_lanes[reason] += int(lanes)
            self._declined[reason] += 1

    def take(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._delta_lanes)
            self._delta_lanes = {p: 0 for p in self.PATHS}
        return out

    def snapshot(self) -> dict:
        with self._lock:
            express = (
                self._lanes.get("bypass", 0) + self._lanes.get("native", 0)
            )
            windowed = self._lanes.get("windowed", 0)
            total = express + windowed
            return {
                "lanes": dict(self._lanes),
                "dispatches": dict(self._dispatches),
                "declined": {
                    "lanes": dict(self._declined_lanes),
                    "submissions": dict(self._declined),
                },
                "hitRate": round(express / total, 4) if total else 0.0,
            }


lane_util = LaneUtil()
mesh_tally = MeshTally()
dispatcher_busy = BusyFraction()
_queue_depths = _DepthRing()
express = ExpressStats()


def note_express(path: str, lanes: int) -> None:
    """Record one express/batched dispatch (see ExpressStats)."""
    express.note(path, lanes)


def note_express_declined(reason: str, lanes: int) -> None:
    """Record one submission the admission rule sent to the window."""
    express.note_declined(reason, lanes)


def express_snapshot() -> dict:
    return express.snapshot()


def observe_queue_depth(depth: int) -> None:
    _queue_depths.record(depth)


def queue_depth_snapshot() -> dict:
    return _queue_depths.snapshot()


# ---------------------------------------------------------------------
# SLO engine: multi-window error-budget burn rates
# ---------------------------------------------------------------------
class SloEngine:
    """Latency-SLO accounting: each ingress request is GOOD (answered
    under `target_ms`) or BAD; the error budget is `1 - objective` of
    requests, and the burn rate over a window is

        burn = (bad / total in window) / (1 - objective)

    (1.0 = burning the budget exactly as fast as it accrues; the SRE
    fast-burn page threshold is 14.4x over 5 minutes).  Counts live in
    10-second buckets covering one hour, so the 5m and 1h windows read
    from the same ring.  `target_ms <= 0` disables the engine: observe
    degrades to one comparison, every gauge reads 0."""

    BUCKET_S = 10
    N_BUCKETS = 360  # 1 hour
    WINDOWS = {"5m": 300, "1h": 3600}
    FAST_BURN = 14.4          # page-level burn on the short window
    FAST_WINDOW_S = 300
    # Volume floor for the fast-burn trip: a page-level verdict from a
    # handful of requests is noise shaped like an incident (one bad
    # warmup request after a restart would read burn=100).
    FAST_MIN_TOTAL = 100
    CHECK_INTERVAL_S = 1.0    # fast-burn evaluation cadence
    TRIP_MIN_INTERVAL_S = 30.0

    def __init__(self, target_ms: float, objective: float = 0.99,
                 time_fn=time.monotonic):
        self.target_ms = float(target_ms)
        self.objective = min(max(float(objective), 0.0), 0.9999)
        self.enabled = self.target_ms > 0
        self._time = time_fn
        self._lock = threading.Lock()
        self._good = np.zeros(self.N_BUCKETS, dtype=np.int64)
        self._bad = np.zeros(self.N_BUCKETS, dtype=np.int64)
        self._epoch = np.full(self.N_BUCKETS, -1, dtype=np.int64)
        self._next_check = 0.0
        self._last_trip = -float("inf")

    def observe(self, dt_s: float) -> Optional[bool]:
        """Record one request; returns True (good) / False (bad), or
        None when the engine is disabled."""
        if not self.enabled:
            return None
        good = dt_s * 1000.0 <= self.target_ms
        now = self._time()
        trip_burn = None
        with self._lock:
            i = self._slot(now)
            (self._good if good else self._bad)[i] += 1
            if now >= self._next_check:
                self._next_check = now + self.CHECK_INTERVAL_S
                w_good, w_bad = self._window_counts(now, self.FAST_WINDOW_S)
                total = w_good + w_bad
                burn = (
                    (w_bad / total) / max(1.0 - self.objective, 1e-9)
                    if total >= self.FAST_MIN_TOTAL else 0.0
                )
                if (burn >= self.FAST_BURN
                        and now - self._last_trip >= self.TRIP_MIN_INTERVAL_S):
                    self._last_trip = now
                    trip_burn = burn
        if trip_burn is not None:
            # The PR 4 auto-dump path: a fast burn is the same "the
            # service is losing its SLO" signal a breaker trip is —
            # dump the flight recorder.  OUTSIDE the engine lock: the
            # dump JSON-serializes and logs, and every ingress request
            # takes this lock — a slow log handler must not convoy the
            # whole service at the very moment it is burning.
            tracing.record_event(
                "slo-fast-burn", burn_rate=round(trip_burn, 2),
                window_s=self.FAST_WINDOW_S,
                target_ms=self.target_ms,
                objective=self.objective,
            )
        return good

    def _slot(self, now: float) -> int:
        """Bucket index for `now`, zeroing the slot if its epoch is
        stale (the ring wrapped past it).  Lock held."""
        epoch = int(now // self.BUCKET_S)
        i = epoch % self.N_BUCKETS
        if self._epoch[i] != epoch:
            self._epoch[i] = epoch
            self._good[i] = 0
            self._bad[i] = 0
        return i

    def _window_counts(self, now: float, window_s: int) -> Tuple[int, int]:
        epoch = int(now // self.BUCKET_S)
        lo = epoch - (window_s // self.BUCKET_S) + 1
        live = (self._epoch >= lo) & (self._epoch <= epoch)
        return int(self._good[live].sum()), int(self._bad[live].sum())

    def _burn_locked(self, now: float, window_s: int) -> float:
        good, bad = self._window_counts(now, window_s)
        total = good + bad
        if total == 0:
            return 0.0
        return (bad / total) / max(1.0 - self.objective, 1e-9)

    def burn_rate(self, window_s: int) -> float:
        if not self.enabled:
            return 0.0
        with self._lock:
            return self._burn_locked(self._time(), window_s)

    def snapshot(self) -> dict:
        out = {
            "enabled": self.enabled,
            "target_ms": self.target_ms,
            "objective": self.objective,
        }
        if not self.enabled:
            return out
        with self._lock:
            now = self._time()
            for name, w in self.WINDOWS.items():
                good, bad = self._window_counts(now, w)
                out[f"burn_rate_{name}"] = round(
                    self._burn_locked(now, w), 4
                )
                out[f"good_{name}"] = good
                out[f"bad_{name}"] = bad
        return out


# ---------------------------------------------------------------------
# Hot-key detection: count-min sketch + top-K
# ---------------------------------------------------------------------

# Odd 64-bit multipliers deriving d independent row indices from the
# ONE fnv1 hash the ring already computed (Dietzfelbinger-style
# multiply-shift; u64 wraparound is the intended arithmetic).
_CMS_SALTS = np.array(
    [0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
     0x27D4EB2F165667C5],
    dtype=np.uint64,
)


class HotKeySketch:
    """Count-min sketch over per-lane key hashes plus an exact top-K
    candidate list.  update() folds a batch in one native pass
    (native.cms_fold) that adds into this object's table, the one
    holder of the counts, and hands back at most `topk` candidates;
    key STRINGS are materialized only for those, the lanes whose
    estimate crosses the current top-K floor, so the hot path never
    builds per-lane Python objects.  Counts decay by halving every
    `decay_s` seconds — the sketch answers "hot NOW", not "hot ever"."""

    def __init__(self, width: int = 8192, depth: int = 4, topk: int = 16,
                 decay_s: float = 30.0, time_fn=time.monotonic):
        self.width = int(width)
        self.depth = min(int(depth), len(_CMS_SALTS))
        self.topk = int(topk)
        self.decay_s = float(decay_s)
        self._time = time_fn
        self._lock = threading.Lock()
        self._tab = np.zeros((self.depth, self.width), dtype=np.int64)
        self._salts = _CMS_SALTS[: self.depth]
        self._top: Dict[int, list] = {}  # hash -> [est, key_str]
        self._last_decay = time_fn()
        self.total_lanes = 0
        self.batches = 0
        self.candidates = 0  # top-K candidates Python touched, all folds

    def update(self, hashes: np.ndarray, keys) -> None:
        """Fold one batch: `hashes` u64[n] (the ring lookup's fnv1
        values), `keys` indexable by lane (list or PackedKeys)."""
        n = len(hashes)
        if n == 0:
            return
        hs = np.ascontiguousarray(hashes, dtype=np.uint64)
        with self._lock:
            now = self._time()
            if now - self._last_decay >= self.decay_s:
                self._last_decay = now
                self._tab >>= 1
                for rec in self._top.values():
                    rec[0] >>= 1
            # Top-K maintenance: only candidates at/above the current
            # floor materialize a key string, and never more than the K
            # largest — uniform traffic concentrates estimates near the
            # floor, and a 1000-unique batch must not loop 1000 lanes
            # in Python.  While the list is still filling the floor is
            # 0, which every estimate clears.
            floor = (
                min(rec[0] for rec in self._top.values())
                if len(self._top) >= self.topk else 0
            )
            uh, first, est, _, cand = native.cms_fold(
                self._tab, self._salts, hs, None, None, floor - 1, self.topk
            )
            self.total_lanes += n
            self.batches += 1
            self.candidates += len(cand)
            for j in cand:
                h = int(uh[j])
                rec = self._top.get(h)
                if rec is not None:
                    rec[0] = int(est[j])
                else:
                    self._top[h] = [int(est[j]), str(keys[int(first[j])])]
            if len(self._top) > self.topk:
                keep = sorted(
                    self._top.items(), key=lambda kv: kv[1][0], reverse=True
                )[: self.topk]
                self._top = dict(keep)

    def snapshot(self) -> dict:
        with self._lock:
            top = sorted(
                ({"key": rec[1], "estimate": int(rec[0])}
                 for rec in self._top.values()),
                key=lambda d: d["estimate"], reverse=True,
            )
            return {
                "topk": top,
                "total_lanes": self.total_lanes,
                "batches": self.batches,
                "width": self.width,
                "depth": self.depth,
                "decay_s": self.decay_s,
            }


# ---------------------------------------------------------------------
def reset() -> None:
    """Test hook: clear every module-global reservoir/accumulator."""
    global _phases, lane_util, mesh_tally, dispatcher_busy, _queue_depths, express
    _phases = {p: _PhaseStats() for p in PHASES}
    lane_util = LaneUtil()
    mesh_tally = MeshTally()
    dispatcher_busy = BusyFraction()
    _queue_depths = _DepthRing()
    express = ExpressStats()
