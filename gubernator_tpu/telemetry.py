"""XLA / device telemetry: compile tracking, recompile-storm detection,
per-program execution timings, device memory sampling.

PRs 4 and 6 made the HOST side of the daemon observable; this module
watches the layer below it — the XLA programs the dispatch pipeline
launches.  A shape-churn recompile storm (a batch size wobbling across
pad buckets after warmup, a config change invalidating a donated
layout) otherwise reads only as mysterious latency: each backend
compile steals tens of ms (CPU) to most of a minute (a TPU program
over a million-slot table) from whatever request triggered it.

Three signals, all host-side (the occupancy-from-readback rule: the
plane adds ZERO device programs):

* **Compile tracking** — a `jax.monitoring` duration listener counts
  and times every backend compile, attributed to the PROGRAM LABEL the
  launching thread declared via `program(label)` (labels name program
  identity: solo vs fused-K dispatches, wide/narrow wires, mesh twins,
  the GLOBAL sync collective, reshard gather/commit).  Compilation
  runs synchronously on the calling thread, so thread-local
  attribution is exact.

* **Steady-state recompiles** — after `mark_steady()` (the daemon
  calls it once startup warmup finishes) any further backend compile
  is SHAPE CHURN by definition and is counted per label.  A burst of them
  (`GUBER_XLA_STORM` compiles inside `GUBER_XLA_STORM_WINDOW` seconds)
  fires the PR 4 flight-recorder auto-dump (`recompile-storm` event)
  while the evidence of WHICH programs churned is still in the rings.

* **Execution timings** — `program(label)` also times the launch call
  itself (enqueue wall time, not device completion — the async
  dispatch returns at enqueue), aggregated per label and drained per
  metrics scrape like the dispatch-stage gauges.

`device_snapshot()` samples per-device memory (`memory_stats()` where
the backend reports it — TPU/GPU) and live-buffer counts/bytes
(`jax.live_arrays()`, the CPU fallback) — served by `GET /debug/device`
and the `gubernator_device_*` gauges.  Sampling happens per scrape /
debug request only, never on the hot path.

State is MODULE-GLOBAL like the tracing flight recorder and the
saturation plane: one daemon per process in production; in-process
multi-daemon tests share one plane.  `GUBER_XLA_TELEMETRY=0` disables
everything: `program()` returns a shared no-op context (one branch on
the hot path) and the listener body returns immediately.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from . import profiling, tracing
from .saturation import phase
from .utils.logging import category_logger

logger = category_logger("telemetry")

# The jax.monitoring duration event one XLA backend compile emits
# (jax 0.9: _src/dispatch.py BACKEND_COMPILE_EVENT; a persistent-cache
# hit emits it too, with the seconds the load took).  One logical
# compile emits a trace and a lowering event as well: those are NOT
# counted as compiles — the backend compile is the one that costs real
# time — but before `mark_steady()` every one of the four is summed per
# program label, which is what says where a start's seconds went.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_STARTUP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _COMPILE_EVENT: "backend_s",
    # Inside the backend event: reading, deserialising and loading a
    # persistent-cache entry (only a hit emits it).
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}

_UNLABELED = "unlabeled"


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name, "")
    if not v:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name, "")
    try:
        return int(v) if v else default
    except ValueError:
        return default


def _env_duration(name: str, default_s: float) -> float:
    """Go-duration env knob (the GUBER_* convention: '60s', '2m'; a
    bare number means ms), warn-free fallback on garbage — module
    import must never raise."""
    v = os.environ.get(name, "")
    if not v:
        return default_s
    try:
        from .config import parse_duration

        return parse_duration(v)
    except Exception:  # noqa: BLE001 — import-time safety
        return default_s


_ENABLED: bool = _env_flag("GUBER_XLA_TELEMETRY", True)
# Recompile-storm trip: >= STORM_THRESHOLD steady-state compiles within
# STORM_WINDOW_S seconds fires the flight-recorder dump.  Module-level
# env reads cover library embeddings; daemons re-apply their parsed
# config via set_storm (config-file -> env -> default precedence).
STORM_THRESHOLD = max(_env_int("GUBER_XLA_STORM", 3), 1)
STORM_WINDOW_S = max(_env_duration("GUBER_XLA_STORM_WINDOW", 60.0), 0.001)
_STORM_MIN_INTERVAL_S = 30.0  # between storm events (dump rate limit)

_lock = threading.Lock()
_tls = threading.local()

# label -> [count, total_s, max_s] (cumulative, process lifetime)
_compiles: Dict[str, list] = {}
# label -> count of compiles AFTER mark_steady() (shape churn)
_steady_recompiles: Dict[str, int] = {}
# label -> [count, total_s, max_s] execution (enqueue) wall; drained
# per metrics scrape (the dispatch-stage gauge convention)
_exec_stats: Dict[str, list] = {}
# distinct jitted callables created by the program caches
# (mesh._mesh_fused_packed_jit notes creations here)
_programs_created: Dict[str, int] = {}
# Set-up, cumulative over the process: seconds per part of a daemon
# start (`startup(part)`), per program label what its launches before
# `mark_steady()` spent (`_STARTUP_EVENTS` fields, `cache_hits`, and
# `call_s`, the wall of the labelled launches themselves), and the
# process's age when it last said `listening`.
_startup_parts: Dict[str, float] = {}
_startup_programs: Dict[str, Dict[str, float]] = {}
_startup_listening_s: List[Optional[float]] = [None]
_steady = False
_recent_steady_compiles: "deque[float]" = deque()
_storms = 0
_last_storm = [-float("inf")]
_listener_attempted = [False]
_listener_registered = [False]


def set_enabled(flag: bool) -> None:
    """Process-wide switch (the daemon applies its parsed
    GUBER_XLA_TELEMETRY at startup, like tracing.set_sample_rate)."""
    global _ENABLED
    _ENABLED = bool(flag)
    if _ENABLED:
        _ensure_listener()


def set_storm(threshold: int, window_s: float) -> None:
    """Process-wide storm-trip parameters (the daemon applies its
    parsed GUBER_XLA_STORM / GUBER_XLA_STORM_WINDOW at startup — the
    config-file -> env -> default precedence every other knob honors;
    the module-level env read only covers library embeddings)."""
    global STORM_THRESHOLD, STORM_WINDOW_S
    STORM_THRESHOLD = max(int(threshold), 1)
    STORM_WINDOW_S = max(float(window_s), 0.001)


def enabled() -> bool:
    return _ENABLED


def _ensure_listener() -> None:
    """Register the jax.monitoring compile listener exactly once.
    Listeners cannot be individually unregistered, so the body gates on
    _ENABLED instead — compile events are rare, the check is free."""
    with _lock:
        if _listener_attempted[0]:
            return
        _listener_attempted[0] = True
    try:
        import jax.monitoring as _mon

        _mon.register_event_duration_secs_listener(_on_duration_event)
        _listener_registered[0] = True
    except Exception as e:  # noqa: BLE001 — telemetry must never fail imports
        logger.warning("xla telemetry listener unavailable: %s", e)


def listener_active() -> bool:
    """Whether compile counting can actually observe compiles: the
    plane is on AND the jax.monitoring listener registered.  Consumers
    that would read an always-0 count as a verdict must SKIP instead
    when this is False."""
    return _ENABLED and _listener_registered[0]


def _on_duration_event(name: str, dur_s: float, **_kw) -> None:
    field = _STARTUP_EVENTS.get(name)
    if not _ENABLED or field is None:
        return
    label = getattr(_tls, "program", None) or _UNLABELED
    if not _steady:
        with _lock:
            row = _startup_programs.setdefault(label, {})
            row[field] = row.get(field, 0.0) + dur_s
            if field == "cache_load_s":
                row["cache_hits"] = row.get("cache_hits", 0) + 1
    if name != _COMPILE_EVENT:
        return
    lazy = bool(getattr(_tls, "program_lazy", False))
    now = time.monotonic()
    storm = None
    with _lock:
        st = _compiles.setdefault(label, [0, 0.0, 0.0])
        st[0] += 1
        st[1] += dur_s
        st[2] = max(st[2], dur_s)
        if _steady and not lazy:
            _steady_recompiles[label] = _steady_recompiles.get(label, 0) + 1
            _recent_steady_compiles.append(now)
            while (_recent_steady_compiles
                   and now - _recent_steady_compiles[0] > STORM_WINDOW_S):
                _recent_steady_compiles.popleft()
            if (len(_recent_steady_compiles) >= STORM_THRESHOLD
                    and now - _last_storm[0] >= _STORM_MIN_INTERVAL_S):
                _last_storm[0] = now
                globals()["_storms"] = _storms + 1
                storm = len(_recent_steady_compiles)
    if storm is not None:
        # The PR 4 auto-dump path — OUTSIDE the telemetry lock (the
        # dump serializes and logs; a slow handler must not stall
        # whichever dispatcher is unlucky enough to be compiling).
        tracing.record_event(
            "recompile-storm", compiles=storm, window_s=STORM_WINDOW_S,
            label=label,
        )
        logger.warning(
            "XLA recompile storm: %d steady-state compiles in %.0fs "
            "(last label %s) — shape churn after warmup",
            storm, STORM_WINDOW_S, label,
        )


# ---------------------------------------------------------------------
# Program label scopes (the launch-site hook)
# ---------------------------------------------------------------------
class _NoopProgram:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopProgram()


class _Program:
    __slots__ = ("label", "lazy", "_prev", "_prev_lazy", "_t0")

    def __init__(self, label: str, lazy: bool):
        self.label = label
        self.lazy = lazy

    def __enter__(self):
        self._prev = getattr(_tls, "program", None)
        self._prev_lazy = getattr(_tls, "program_lazy", False)
        _tls.program = self.label
        _tls.program_lazy = self.lazy
        if profiling.enabled():
            # Mirror the label into the cost-profiler's cross-thread
            # registry (thread-locals are invisible to the sampler):
            # samples taken during this launch carry program identity.
            profiling.set_program(self.label)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        _tls.program = self._prev
        _tls.program_lazy = self._prev_lazy
        # Unconditional (unlike the enter-side mirror): if the profiler
        # was toggled off mid-launch, a conditional restore would park
        # this label in the cross-thread registry forever and every
        # later sample of this thread would carry it.
        profiling.set_program(self._prev)
        with _lock:
            st = _exec_stats.setdefault(self.label, [0, 0.0, 0.0])
            st[0] += 1
            st[1] += dt
            st[2] = max(st[2], dt)
            if not _steady:
                row = _startup_programs.setdefault(self.label, {})
                row["calls"] = row.get("calls", 0) + 1
                row["call_s"] = row.get("call_s", 0.0) + dt
        return False


def program(label: str, lazy: bool = False):
    """Label scope for one program launch: attributes any compile the
    call triggers to `label` and aggregates the call's wall time.  The
    disabled path is one branch returning a shared no-op.

    `lazy=True` declares the program DELIBERATELY warmup-deferred
    (mesh warmup's own carve-outs: wide int64 wires, the reshard
    drain/commit pair — programs that structurally can only compile
    after mark_steady, e.g. the first membership change): their
    compiles are counted and timed per label but do NOT feed the
    steady-state recompile counter or the storm trip, so a healthy
    reshard event or a rare wide batch can never fire a false
    recompile-storm dump."""
    if not _ENABLED:
        return _NOOP
    return _Program(label, lazy)


def note_program_created(label: str) -> None:
    """One distinct jitted callable materialized by a program cache
    (mesh._mesh_fused_packed_jit): counted so the
    program-population growth is visible even before first dispatch."""
    if not _ENABLED:
        return
    with _lock:
        _programs_created[label] = _programs_created.get(label, 0) + 1


# ---------------------------------------------------------------------
# Set-up by part (process start -> `listening`)
# ---------------------------------------------------------------------
def process_age_s() -> float:
    """Seconds since the kernel started this process (Linux /proc; 0.0
    where that cannot be read, so a part then reads short, never wrong
    by more than itself)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        return max(uptime_s - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


@contextlib.contextmanager
def startup(part: str):
    """One part of a daemon start, as the phase `startup.<part>` (so it
    is in `/debug/latency`, and in a profile taken across a start) and
    summed under `/debug/device`'s `startup`."""
    ph = phase("startup." + part)
    try:
        with ph:
            yield
    finally:
        note_startup(part, ph.dt_s)


def note_startup(part: str, dur_s: float) -> None:
    with _lock:
        _startup_parts[part] = _startup_parts.get(part, 0.0) + dur_s


def note_listening() -> None:
    """The server entry calls this as it prints `listening`."""
    _startup_listening_s[0] = process_age_s()


def startup_snapshot() -> dict:
    """`/debug/device`'s `startup`: seconds per part, and per program
    label `trace_s`, `lower_s`, `cache_load_s` (persistent-cache hits),
    `compile_s` (the backend event less the cache load: the compiler on
    a miss, little more than the cache key on a hit) and `run_s` (the
    label's launches before `mark_steady()` less all of those: upload,
    enqueue, and whatever the first execution made the caller wait)."""
    with _lock:
        parts = {k: round(v, 6) for k, v in _startup_parts.items()}
        programs = {}
        for label, row in sorted(_startup_programs.items()):
            backend = row.get("backend_s", 0.0)
            load = row.get("cache_load_s", 0.0)
            spent = row.get("trace_s", 0.0) + row.get("lower_s", 0.0) + backend
            programs[label] = {
                "calls": int(row.get("calls", 0)),
                "cache_hits": int(row.get("cache_hits", 0)),
                "trace_s": round(row.get("trace_s", 0.0), 6),
                "lower_s": round(row.get("lower_s", 0.0), 6),
                "cache_load_s": round(load, 6),
                "compile_s": round(max(backend - load, 0.0), 6),
                "run_s": round(max(row.get("call_s", 0.0) - spent, 0.0), 6),
            }
    return {"parts_s": parts, "programs": programs,
            "listening_s": _startup_listening_s[0]}


# ---------------------------------------------------------------------
# Warmup fencing
# ---------------------------------------------------------------------
def begin_warmup() -> None:
    """Re-open the warmup window (daemon startup warmup; each daemon
    start in an in-process test cluster re-opens it)."""
    global _steady
    with _lock:
        _steady = False


def mark_steady() -> None:
    """Warmup complete: from here on every backend compile counts as a
    steady-state recompile (shape churn)."""
    global _steady
    with _lock:
        _steady = True
        _recent_steady_compiles.clear()


def is_steady() -> bool:
    return _steady


# ---------------------------------------------------------------------
# Read side
# ---------------------------------------------------------------------
def compile_count() -> int:
    with _lock:
        return sum(st[0] for st in _compiles.values())


def steady_recompile_count() -> int:
    with _lock:
        return sum(_steady_recompiles.values())


def compile_snapshot() -> Dict[str, dict]:
    with _lock:
        return {
            label: {
                "count": st[0],
                "total_s": round(st[1], 6),
                "max_s": round(st[2], 6),
                "steady_recompiles": _steady_recompiles.get(label, 0),
            }
            for label, st in sorted(_compiles.items())
        }


def take_exec_stats() -> Dict[str, tuple]:
    """Drain per-program execution aggregates accumulated since the
    last call: {label: (count, total_s, max_s)}."""
    with _lock:
        out = {k: tuple(v) for k, v in _exec_stats.items()}
        _exec_stats.clear()
    return out


def snapshot() -> dict:
    """The GET /debug/device document (minus live device stats, which
    device_snapshot() adds — they cost a live-buffer walk)."""
    with _lock:
        exec_view = {
            label: {
                "count": st[0],
                "total_s": round(st[1], 6),
                "max_s": round(st[2], 6),
            }
            for label, st in sorted(_exec_stats.items())
        }
        created = dict(sorted(_programs_created.items()))
        storms = _storms
    return {
        "enabled": _ENABLED,
        "steady": _steady,
        "compiles": compile_snapshot(),
        "compileTotal": compile_count(),
        "steadyRecompiles": steady_recompile_count(),
        "recompileStorms": storms,
        "stormThreshold": STORM_THRESHOLD,
        "stormWindowS": STORM_WINDOW_S,
        "programRuns": exec_view,
        "programsCreated": created,
        "startup": startup_snapshot(),
    }


def device_snapshot() -> List[dict]:
    """Per-device memory / live-buffer stats.  `memory_stats()` is
    backend-reported (TPU/GPU; None on CPU); the live-array walk is the
    universal fallback — both are read on scrape / debug request only."""
    if not _ENABLED:
        return []
    try:
        import jax
    except Exception:  # noqa: BLE001 — no jax, no devices
        return []
    per_dev: Dict[str, dict] = {}
    try:
        for d in jax.local_devices():
            row = {
                "device": str(d), "platform": d.platform,
                "device_kind": d.device_kind,
            }
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 — backend without stats
                stats = None
            if stats:
                for k in ("bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit", "num_allocs"):
                    if k in stats:
                        row[k] = int(stats[k])
            row["live_buffers"] = 0
            row["live_bytes"] = 0
            per_dev[str(d)] = row
        for arr in jax.live_arrays():
            try:
                devs = arr.devices()
                nbytes = int(arr.nbytes) // max(len(devs), 1)
                for d in devs:
                    row = per_dev.get(str(d))
                    if row is not None:
                        row["live_buffers"] += 1
                        row["live_bytes"] += nbytes
            except Exception:  # noqa: BLE001 — deleted/donated mid-walk
                continue
    except Exception as e:  # noqa: BLE001 — diagnostics must never raise
        logger.warning("device snapshot failed: %s", e)
    return list(per_dev.values())


def reset(steady: bool = False) -> None:
    """Test hook: clear every aggregate (mirrors tracing.reset)."""
    global _steady, _storms
    with _lock:
        _compiles.clear()
        _steady_recompiles.clear()
        _exec_stats.clear()
        _programs_created.clear()
        _recent_steady_compiles.clear()
        _startup_parts.clear()
        _startup_programs.clear()
        _startup_listening_s[0] = None
        _steady = steady
        _storms = 0
        _last_storm[0] = -float("inf")
    _tls.program = None
    _tls.program_lazy = False


# Module init: honor the environment; the listener registers lazily on
# first enable so disabled library embeddings never touch jax.
if _ENABLED:
    _ensure_listener()
