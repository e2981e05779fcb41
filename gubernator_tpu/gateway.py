"""HTTP/JSON gateway — the client-facing edge.

Parity with the reference's grpc-gateway mux + metrics endpoint
(daemon.go:194-239): POST /v1/GetRateLimits, GET /v1/HealthCheck,
GET /metrics, plus the peer data plane (PeersV1) as
POST /v1/peer.GetPeerRateLimits and POST /v1/peer.UpdatePeerGlobals.
Errors render grpc-gateway style: {"code": N, "message": "..."}.
TLS (including mTLS client auth) wraps the listener when configured
(tls.go:118-263 equivalent via ssl.SSLContext).
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import ssl
import threading
import time
from functools import partial
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlsplit

import numpy as np

from . import audit as audit_mod
from . import native as _native
from . import profiling
from . import saturation
from .saturation import phase
from . import telemetry
from . import tracing
from . import wire
from .config import (
    INGRESS_COLUMNS_MAX_LANES,
    MAX_BATCH_SIZE,
    PEER_COLUMNS_MAX_LANES,
)
from .models.shard import greg_lanes, resolve_greg_columns
from .service import ApiError, ColumnarResult, IngressColumns, V1Service
from .types import (
    Algorithm,
    Behavior,
    RateLimitRequest,
    UpdatePeerGlobal,
    _parse_behavior,
)
from .utils.logging import category_logger

logger = category_logger("gateway")



_GRPC_CODES = {"InvalidArgument": 3, "OutOfRange": 11, "Internal": 13,
               "FailedPrecondition": 9}

_STATUS_NAMES = ("UNDER_LIMIT", "OVER_LIMIT")


class LazyIngressColumns:
    """IngressColumns twin built from the native JSON parse
    (native.parse_json_batch): kernel-ready columns + PACKED hash keys
    + per-lane validation codes, with name/unique_key strings
    materialized lazily — the hot path never creates 2n string objects
    per batch."""

    __slots__ = ("_pj", "algorithm", "behavior", "hits", "limit",
                 "duration", "_names", "_uks")

    def __init__(self, pj):
        self._pj = pj
        self.algorithm = pj.algo
        self.behavior = pj.behavior
        self.hits = pj.hits
        self.limit = pj.limit
        self.duration = pj.duration
        self._names = None
        self._uks = None

    def __len__(self) -> int:
        return self._pj.n

    @property
    def prevalidated(self):
        """(PackedKeys hash keys, err codes u8[n]: 1 empty unique_key,
        2 empty name) — lets the service skip its per-lane validation
        and hash-key loop (service.py _route_columns)."""
        return self._pj.hash_keys, self._pj.err

    @property
    def names(self):
        if self._names is None:
            self._names = [self._pj.name_at(i) for i in range(self._pj.n)]
        return self._names

    @property
    def unique_keys(self):
        if self._uks is None:
            self._uks = [
                self._pj.unique_key_at(i) for i in range(self._pj.n)
            ]
        return self._uks

    def request_at(self, i: int) -> RateLimitRequest:
        return RateLimitRequest(
            name=self._pj.name_at(i),
            unique_key=self._pj.unique_key_at(i),
            hits=int(self.hits[i]),
            limit=int(self.limit[i]),
            duration=int(self.duration[i]),
            algorithm=int(self.algorithm[i]),
            behavior=int(self.behavior[i]),
        )


def parse_body_native(raw: bytes):
    """Native fast path for a /v1/GetRateLimits body; None falls back
    to json.loads + parse_columns (exotic JSON, bad enum values — the
    Python path reproduces the exact historical error behavior)."""
    pj = _native.parse_json_batch(raw)
    if pj is None or (pj.err >= 3).any():
        return None
    return LazyIngressColumns(pj)


def render_result_native(result: ColumnarResult):
    """Native response rendering; overrides pre-render in Python (they
    carry metadata/errors), forwarded lanes pre-render their
    metadata.owner straight from the arrays (no per-lane dataclass).
    None when the native runtime is absent."""
    ov = None
    if result.overrides:
        ov = {
            i: json.dumps(r.to_json(), separators=(",", ":")).encode("utf-8")
            for i, r in result.overrides.items()
        }
    if result.owner_of is not None:
        ov = ov or {}
        owner_json = [json.dumps(a) for a in result.owner_addrs]
        status, limit = result.status, result.limit
        remaining, reset = result.remaining, result.reset_time
        for i in np.nonzero(result.owner_of >= 0)[0]:
            i = int(i)
            if i in ov:
                continue
            ov[i] = (
                '{"status":"%s","limit":"%d","remaining":"%d",'
                '"resetTime":"%d","metadata":{"owner":%s}}'
                % (
                    _STATUS_NAMES[status[i]], limit[i], remaining[i],
                    reset[i], owner_json[result.owner_of[i]],
                )
            ).encode("utf-8")
    return _native.render_json(
        result.status, result.limit, result.remaining, result.reset_time,
        ov or {},
    )


def parse_columns(items: list) -> IngressColumns:
    """Parse a JSON `requests` array straight into ingress columns (no
    per-request dataclasses — the gateway's half of the zero-dataclass
    hot path)."""
    n = len(items)
    names: list = [""] * n
    uks: list = [""] * n
    algo = np.zeros(n, dtype=np.int32)
    behavior = np.zeros(n, dtype=np.int32)
    hits = np.zeros(n, dtype=np.int64)
    limit = np.zeros(n, dtype=np.int64)
    duration = np.zeros(n, dtype=np.int64)
    for i, d in enumerate(items):
        names[i] = d.get("name", "")
        uks[i] = d.get("uniqueKey") or d.get("unique_key") or ""
        v = d.get("hits")
        if v:
            hits[i] = int(v)
        v = d.get("limit")
        if v:
            limit[i] = int(v)
        v = d.get("duration")
        if v:
            duration[i] = int(v)
        v = d.get("algorithm")
        if v:
            # Same validation as the dataclass path (_parse_enum): an
            # out-of-range value must fail identically at every batch size.
            if isinstance(v, str) and v in Algorithm.__members__:
                algo[i] = int(Algorithm[v])
            else:
                algo[i] = int(Algorithm(int(v)))
        v = d.get("behavior")
        if v:
            behavior[i] = v if isinstance(v, int) else _parse_behavior(v)
    return IngressColumns(
        names=names, unique_keys=uks, algorithm=algo, behavior=behavior,
        hits=hits, limit=limit, duration=duration,
    )


def render_columns(result: ColumnarResult) -> dict:
    """Serialize a ColumnarResult to the gateway JSON payload directly
    from the arrays."""
    status = result.status
    limit = result.limit
    remaining = result.remaining
    reset = result.reset_time
    ov = result.overrides
    owner_of = result.owner_of
    out = []
    for i in range(result.n):
        r = ov.get(i)
        if r is not None:
            out.append(r.to_json())
        else:
            d = {
                "status": _STATUS_NAMES[status[i]],
                "limit": str(limit[i]),
                "remaining": str(remaining[i]),
                "resetTime": str(reset[i]),
            }
            if owner_of is not None and owner_of[i] >= 0:
                d["metadata"] = {"owner": result.owner_addrs[owner_of[i]]}
            out.append(d)
    return {"responses": out}


def handle_request(service: V1Service, method: str, path: str, raw: bytes,
                   headers=None):
    """Transport-independent request handler: the single routing +
    metrics + error surface behind BOTH edges (the stdlib ThreadingHTTP
    server below and the native epoll edge, NativeGatewayServer).
    Returns (http_status, content_type, body_bytes).  `headers` (any
    mapping with .get, or None) feeds traceparent extraction and
    /metrics content negotiation; the native edge passes None — its
    requests root fresh traces."""
    # Per-service flight recorder + incident black box: bind this
    # daemon's recorder for the handler's duration (co-resident daemons
    # stop interleaving their rings), and tap every GUBC frame at the
    # gateway edge, both directions (bb.tap sniffs the frame magic, so
    # JSON bodies cost one length/prefix check each way).
    tracing.bind_recorder(getattr(service, "recorder", None))
    bb = getattr(service, "blackbox", None)
    if bb is not None and raw:
        bb.tap("in", "", raw)
    status, ctype, body = _handle_request(service, method, path, raw, headers)
    if bb is not None and body:
        bb.tap("out", "", body)
    return status, ctype, body


def _handle_request(service: V1Service, method: str, path: str, raw: bytes,
                    headers=None):
    try:
        if method == "GET":
            # /healthz is an alias so stock k8s liveness/readiness
            # probes work without a rewrite rule; the payload includes
            # breakerOpenCount (peers currently fast-failed by their
            # circuit breaker, faults.py).
            if path in ("/v1/HealthCheck", "/healthz"):
                with service.metrics.observe_rpc("/pb.gubernator.V1/HealthCheck"):
                    hc = service.health_check()
                return 200, "application/json", _json_bytes(hc.to_json())
            if path == "/metrics":
                # Collect-on-scrape: refresh the cache gauges from the
                # store (the reference's prometheus Collector pattern,
                # cache.go:205-218) and the per-peer circuit-breaker
                # state gauges from the live PeerClients.  The WHOLE
                # refresh+render runs under the scrape lock: two racing
                # scrapers must not interleave a take_pipeline_stats
                # drain with the other's clear()/set() — an unlucky
                # interleaving would render a per-scrape sample as if
                # it never happened.
                with service.metrics.scrape_lock:
                    service.metrics.observe_cache(service.store)
                    service.metrics.observe_dispatch(service.store)
                    service.metrics.observe_saturation(service)
                    service.metrics.observe_telemetry()
                    service.metrics.observe_audit(service)
                    service.metrics.observe_cost(service)
                    service.metrics.observe_native_ingress(service)
                    service.metrics.observe_blackbox(service)
                    service.metrics.observe_peers(
                        service.get_peer_list()
                        + list(service.get_region_picker().peers())
                    )
                    ctype, payload = service.metrics.render_negotiated(
                        headers.get("Accept", "") if headers else ""
                    )
                return 200, ctype, payload
            qpath = urlsplit(path).path
            if qpath in ("/debug/traces", "/debug/events"):
                return _debug_dump(service, path)
            if qpath == "/debug/status":
                # The cluster-status surface: one JSON doc per daemon
                # (scripts/cluster_status.py polls these).
                return 200, "application/json", _json_bytes(
                    service.debug_status()
                )
            if qpath == "/debug/latency":
                # Live per-phase percentile snapshots from the always-on
                # attribution reservoirs (saturation.py).  `express` is
                # the express-vs-batched split: per-path lane counts +
                # hit rate, with the bypass's own submit wall under
                # phases["express.submit"] beside the windowed path's
                # batch.window/queue.wait.
                return 200, "application/json", _json_bytes({
                    "phases": saturation.phase_snapshot(),
                    "waterfall": [
                        {"phase": p, "depth": d} for p, d in saturation.WATERFALL
                    ],
                    "express": saturation.express_snapshot(),
                    "slo": service.slo.snapshot(),
                })
            if qpath == "/debug/hotkeys":
                return 200, "application/json", _json_bytes(
                    service.hotkeys.snapshot()
                )
            if qpath == "/debug/device":
                # XLA/device telemetry (telemetry.py): compile table,
                # steady-state recompiles, per-program timings, device
                # memory / live-buffer samples.
                doc = telemetry.snapshot()
                doc["devices"] = telemetry.device_snapshot()
                doc["mesh"] = saturation.mesh_tally.snapshot()
                return 200, "application/json", _json_bytes(doc)
            if qpath == "/debug/audit":
                # Conservation audit (audit.py): ledger deltas +
                # invariant verdicts; the soak harness's pass/fail gate.
                return 200, "application/json", _json_bytes(
                    service.auditor.snapshot()
                )
            if qpath == "/debug/tenants":
                # Cost observatory (profiling.py): per-tenant cost
                # ledger — top-K exact rows + the `other` rollup;
                # scripts/cluster_status.py --tenants aggregates these
                # fleet-wide.
                return 200, "application/json", _json_bytes(
                    service.tenants.snapshot()
                )
            if qpath == "/debug/pprof":
                return _debug_pprof(path)
            return 404, "application/json", _json_bytes(
                {"code": 5, "message": f"no handler for {path}"}
            )
        if method != "POST":
            return 404, "application/json", _json_bytes(
                {"code": 5, "message": f"no handler for {method} {path}"}
            )
        tp = headers.get("traceparent") if headers else None
        if path == "/v1/GetRateLimits":
            # Span OUTSIDE the metrics timer: observe_rpc's exit hook
            # attaches a trace exemplar from the still-active context.
            with tracing.ingress_span("http", path, tp):
                with service.metrics.observe_rpc("/pb.gubernator.V1/GetRateLimits"):
                    if service.serves_ingress_columns and wire.is_ingress_frame(raw):
                        # Columnar front door: GUBC kind-5 frame in,
                        # kind-6 frame out (no JSON either way).  With
                        # the knob off this branch is never reached —
                        # the frame falls into json.loads below and
                        # 400s exactly like a pre-columns build, which
                        # is the client's version probe.
                        with phase("ingress.parse"):
                            cols = _decode_ingress_frame_or_400(raw)
                        result = service.get_rate_limits_columns(
                            cols, max_lanes=INGRESS_COLUMNS_MAX_LANES
                        )
                        with phase("response.encode"):
                            rendered = wire.encode_ingress_result_frame(result)
                        service.metrics.ingress_columns_batches.labels(
                            encoding="frame"
                        ).inc()
                        return 200, wire.COLUMNS_CONTENT_TYPE, rendered
                    with phase("ingress.parse"):
                        cols = parse_body_native(raw) if raw else None
                        native = cols is not None
                        if not native:
                            body = json.loads(raw) if raw else {}
                            cols = parse_columns(body.get("requests", []))
                    result = service.get_rate_limits_columns(cols)
                    with phase("response.encode"):
                        rendered = (
                            render_result_native(result) if native else None
                        )
                        if rendered is None:
                            rendered = _json_bytes(render_columns(result))
            return 200, "application/json", rendered
        if path == "/v1/peer.GetPeerRateLimits":
            # Body parsing happens INSIDE the metrics span on BOTH
            # gateway paths: a malformed peer body counts as a
            # status="1" request in request_counts here exactly like on
            # the async edge (architecture.md "Columnar pipeline: the
            # peer hop" documents the parity rule).
            with tracing.ingress_span("http", path, tp):
                with service.metrics.observe_rpc(
                    "/pb.gubernator.PeersV1/GetPeerRateLimits"
                ):
                    if service.serves_peer_columns and wire.is_columns_frame(raw):
                        # Columnar peer hop: binary frame in, frame out.
                        result = service.get_peer_rate_limits_columns(
                            _decode_frame_or_400(raw),
                            max_lanes=PEER_COLUMNS_MAX_LANES,
                        )
                        return (200, wire.COLUMNS_CONTENT_TYPE,
                                wire.encode_result_frame(result))
                    body = json.loads(raw) if raw else {}
                    cols = parse_columns(body.get("requests", []))
                    result = service.get_peer_rate_limits_columns(cols)
            # PeersV1 response field is rate_limits (peers.proto:42-45).
            return 200, "application/json", _json_bytes(
                {"rateLimits": render_columns(result)["responses"]}
            )
        if path == "/debug/profile":
            return _debug_profile(raw)
        if path == "/debug/incident":
            return _debug_incident(service, raw)
        if (path == "/v1/peer.UpdateRegionColumns"
                and service.serves_region_columns):
            # Cross-region federation receive (federation.py): GUBC
            # region frame in, ONE columnar apply.  A daemon with the
            # plane off (GUBER_REGION_COLUMNS=0) never reaches here —
            # it falls through to the 404 below, exactly what a
            # pre-federation build answers, which is the sender's
            # version probe (sticky classic fallback to the per-item
            # GetPeerRateLimits path).
            with service.metrics.observe_rpc(
                "/pb.gubernator.PeersV1/UpdateRegionColumns"
            ):
                if not wire.is_region_frame(raw):
                    raise ApiError(
                        "InvalidArgument",
                        "UpdateRegionColumns expects a GUBC region frame",
                    )
                try:
                    cols = wire.decode_region_frame(raw)
                except ValueError as e:
                    raise ApiError(
                        "InvalidArgument", f"invalid region frame: {e}"
                    ) from e
                applied = service.update_region_columns(cols)
            return 200, "application/json", _json_bytes(
                {"applied": applied}
            )
        if path == "/v1/peer.TransferOwnership" and service.serves_reshard:
            # Ownership-transfer receive (elastic membership): GUBC
            # transfer frame in, ONE batched merge-commit.  A daemon
            # with the plane off (GUBER_RESHARD=0) never reaches here —
            # it falls through to the 404 below, exactly what a
            # pre-reshard build answers, which is the sender's version
            # probe (sticky classic fallback).
            with service.metrics.observe_rpc(
                "/pb.gubernator.PeersV1/TransferOwnership"
            ):
                if not wire.is_transfer_frame(raw):
                    raise ApiError(
                        "InvalidArgument",
                        "TransferOwnership expects a GUBC transfer frame",
                    )
                try:
                    cols = wire.decode_transfer_frame(raw)
                except ValueError as e:
                    raise ApiError(
                        "InvalidArgument", f"invalid transfer frame: {e}"
                    ) from e
                committed, rejected = service.transfer_ownership(cols)
            return 200, "application/json", _json_bytes(
                {"committed": committed, "rejected": rejected}
            )
        if path == "/v1/peer.UpdatePeerGlobals":
            with service.metrics.observe_rpc(
                "/pb.gubernator.PeersV1/UpdatePeerGlobals"
            ):
                if service.serves_global_columns and wire.is_globals_frame(raw):
                    # Columnar GLOBAL broadcast: GUBC globals frame in,
                    # ONE batched replica commit.  A daemon with the
                    # plane off never reaches here — the json.loads
                    # below rejects the frame exactly like a
                    # pre-columns build (the sender's version answer).
                    try:
                        cols = wire.decode_globals_frame(raw)
                    except ValueError as e:
                        raise ApiError(
                            "InvalidArgument", f"invalid globals frame: {e}"
                        ) from e
                    service.update_peer_globals_columns(cols)
                    return 200, "application/json", b"{}"
                body = json.loads(raw) if raw else {}
                updates = [
                    UpdatePeerGlobal.from_json(u)
                    for u in body.get("globals", [])
                ]
                service.update_peer_globals(updates)
            return 200, "application/json", b"{}"
        return 404, "application/json", _json_bytes(
            {"code": 5, "message": f"no handler for {path}"}
        )
    except Exception as e:  # noqa: BLE001
        return _error_triplet(e)


def _json_bytes(payload) -> bytes:
    return json.dumps(payload).encode("utf-8")


def _debug_dump(service, path: str):
    """GET /debug/traces[?trace_id=<32-hex>][&since=<wall-ns>]
    [&limit=<n>] and GET /debug/events: dump the flight recorder
    (tracing.py).  The trace filter matches a span's own trace id OR
    its links — the batch span-link rule, so a lane's trace finds the
    coalesced window/stage spans it rode.  `since` filters on each
    span's wall-clock end stamp (wall_ns) so a stitcher
    (scripts/trace_collect.py) can poll incrementally instead of
    re-reading the whole ring; `limit` keeps the OLDEST N after the
    filter (pagination order — the poller's next `since` cursor picks
    up exactly where this page ended).  Reads across EVERY live
    recorder: per-service recorders exist so incident bundles stay
    attributable per daemon (blackbox.py snapshots only its service's
    ring), but the debug READ surface keeps the one-ring view — a
    cross-daemon trace in a co-resident cluster must be visible from
    ANY daemon's debug port (the two-daemon trace-stitching contract)."""
    recorders = None
    parts = urlsplit(path)
    if parts.path == "/debug/events":
        return 200, "application/json", _json_bytes(
            {"events": tracing.events_snapshot(recorders=recorders)}
        )
    q = parse_qs(parts.query)
    trace_id = (q.get("trace_id") or [""])[0]

    def _int_q(name: str) -> int:
        try:
            return max(int((q.get(name) or ["0"])[0]), 0)
        except ValueError:
            return 0

    return 200, "application/json", _json_bytes(
        {
            "sampleRate": tracing.sample_rate(),
            "spans": tracing.spans_snapshot(
                trace_id, since_ns=_int_q("since"), limit=_int_q("limit"),
                recorders=recorders,
            ),
        }
    )


def _debug_pprof(path: str):
    """GET /debug/pprof?seconds=N[&format=collapsed|json][&top=N]: the
    continuous host profiler's window (profiling.py).  Default output
    is flamegraph collapsed text ('phase;frame;...;frame count' lines —
    pipe into flamegraph.pl / speedscope); format=json serves the
    top-N + phase/program attribution view the integration gate
    asserts against (>= 80% of samples on a loaded daemon must
    attribute to a named phase)."""
    q = parse_qs(urlsplit(path).query)

    def _int_q(name: str, default: int) -> int:
        try:
            return int((q.get(name) or [str(default)])[0])
        except ValueError:
            return default

    seconds = _int_q("seconds", 10)
    if (q.get("format") or ["collapsed"])[0] == "json":
        return 200, "application/json", _json_bytes(
            profiling.profile_snapshot(seconds, top=_int_q("top", 30))
        )
    return (200, "text/plain; charset=utf-8",
            profiling.collapsed(seconds).encode("utf-8"))


_profile_state = {"thread": None, "dirs": [], "run_id": "", "log_dir": ""}
_profile_seq = itertools.count(1)
_profile_lock = threading.Lock()
# Retention cap on profile dumps this daemon created: a client looping
# POST /debug/profile must not fill the temp filesystem of a long-lived
# daemon (each dump is a multi-MB TensorBoard trace).
PROFILE_KEEP = 5


def _debug_profile(raw: bytes):
    """POST /debug/profile {"durationMs": N}: run an on-demand
    jax.profiler device trace for N ms (default 1000, cap 60s) in the
    background, writing a TensorBoard-loadable dump to a fresh
    mkdtemp-created directory (mode 0700, unpredictable name — the
    caller must NOT choose the path, and a predictable fixed path in
    /tmp could be pre-planted by another local user).  Gated on tracing
    being enabled (GUBER_TRACE_SAMPLE > 0) — a daemon with
    observability off must not let callers start device-wide profiles.
    One at a time; answers 202 immediately (a profile must not park a
    gateway worker for its whole duration; the first call also pays
    jax.profiler's lazy tensorflow import, several seconds)."""
    if not tracing.enabled():
        raise ApiError(
            "InvalidArgument",
            "profiling requires tracing enabled (GUBER_TRACE_SAMPLE > 0)",
            http_status=403,
        )
    body = json.loads(raw) if raw else {}
    if not isinstance(body, dict):
        raise ApiError("InvalidArgument", "body must be a JSON object")
    try:
        duration_s = min(max(float(body.get("durationMs", 1000)) / 1000.0, 0.01), 60.0)
    except (TypeError, ValueError):
        raise ApiError("InvalidArgument", "durationMs must be a number") from None
    with _profile_lock:
        t = _profile_state["thread"]
        if t is not None and t.is_alive():
            # Concurrent-run guard: the second caller learns WHICH run
            # holds the device (its id + artifact path) instead of just
            # a refusal — two operators racing a profile can converge
            # on the same artifact.
            return 409, "application/json", _json_bytes(
                {
                    "code": 10,
                    "message": "a device profile is already running",
                    "runId": _profile_state["run_id"],
                    "logDir": _profile_state["log_dir"],
                }
            )
        import shutil
        import tempfile

        log_dir = tempfile.mkdtemp(prefix="gubernator-profile-")
        run_id = f"profile-{next(_profile_seq)}"
        _profile_state["run_id"] = run_id
        _profile_state["log_dir"] = log_dir
        _profile_state["dirs"].append(log_dir)
        while len(_profile_state["dirs"]) > PROFILE_KEEP:
            shutil.rmtree(_profile_state["dirs"].pop(0), ignore_errors=True)

        def run():
            import jax

            try:
                jax.profiler.start_trace(log_dir)
                time.sleep(duration_s)
            finally:
                try:
                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass
            # Cost-observatory pairing: the continuous host profiler's
            # window covering the SAME interval lands beside the device
            # trace, so one call yields device trace + host flamegraph
            # for the same seconds (collapsed text, flamegraph.pl /
            # speedscope ready).
            if profiling.enabled():
                try:
                    with open(
                        os.path.join(log_dir, "host_profile.collapsed"),
                        "w",
                    ) as f:
                        f.write(
                            profiling.collapsed(max(int(duration_s), 1))
                        )
                except OSError:
                    pass

        t = threading.Thread(target=run, daemon=True, name="debug-profile")
        _profile_state["thread"] = t
        t.start()
    host_seconds = max(int(duration_s), 1)
    return 202, "application/json", _json_bytes(
        {
            "runId": run_id, "logDir": log_dir,
            "durationMs": duration_s * 1000.0,
            # Written when the run completes (the 202 answers before the
            # trace finishes); the live equivalent is the pprof URL.
            "hostProfile": (
                f"{log_dir}/host_profile.collapsed"
                if profiling.enabled() else None
            ),
            "hostPprof": f"/debug/pprof?seconds={host_seconds}",
        }
    )


def _debug_incident(service, raw: bytes):
    """POST /debug/incident [{"reason": "..."}]: operator-requested
    incident bundle (blackbox.py) — freeze the wire rings + debug
    surfaces into an on-disk bundle exactly as an auto-dump trigger
    would, but exempt from the writer's rate limit.  403 when the
    black box is disabled (GUBER_BLACKBOX=0 must not let callers
    re-arm capture), 409 when no bundle directory is configured (the
    rings run but there is nowhere to freeze them), 202 otherwise —
    the write happens off-thread (the /debug/profile shape: evidence
    collection must not park a gateway worker)."""
    from . import blackbox as blackbox_mod

    bb = getattr(service, "blackbox", None)
    if bb is None or not (blackbox_mod.enabled() and bb._on):  # noqa: SLF001
        raise ApiError(
            "InvalidArgument",
            "incident capture requires the black box enabled "
            "(GUBER_BLACKBOX=1)",
            http_status=403,
        )
    if not bb.path:
        return 409, "application/json", _json_bytes(
            {
                "code": 9,
                "message": "no bundle directory configured "
                           "(GUBER_BLACKBOX_DIR)",
            }
        )
    body = json.loads(raw) if raw else {}
    if not isinstance(body, dict):
        raise ApiError("InvalidArgument", "body must be a JSON object")
    doc = bb.trigger_manual(str(body.get("reason", "")))
    return 202, "application/json", _json_bytes(doc)


def _decode_frame_or_400(raw: bytes):
    """Frame decode for the peer endpoint: a malformed/truncated frame
    is the CLIENT's fault — surface it as a 400 (ApiError), not a 500,
    on both gateway paths."""
    try:
        return wire.decode_columns_frame(raw)
    except ValueError as e:
        raise ApiError("InvalidArgument", f"invalid columns frame: {e}") from e


def _decode_ingress_frame_or_400(raw: bytes):
    """Public-ingress twin of _decode_frame_or_400 (kind-5 frames,
    untrusted-client validation inside the decode)."""
    try:
        return wire.decode_ingress_frame(raw)
    except ValueError as e:
        raise ApiError("InvalidArgument", f"invalid columns frame: {e}") from e


def _error_triplet(e: BaseException):
    """Map a handler exception to (status, content_type, body) — the
    same arms as handle_request's except clauses, shared with the async
    path so the two edges answer errors identically."""
    if isinstance(e, ApiError):
        return e.http_status, "application/json", _json_bytes(
            {"code": _GRPC_CODES.get(e.code, 2), "message": e.message}
        )
    if isinstance(e, (json.JSONDecodeError, UnicodeDecodeError)):
        # UnicodeDecodeError: json.loads auto-detects utf-16/32 from a
        # leading NUL and raises it for binary garbage — a malformed
        # REQUEST, not a server fault (and the columns-negotiation
        # probe relies on old peers answering 4xx to non-JSON bodies).
        return 400, "application/json", _json_bytes(
            {"code": 3, "message": f"invalid JSON: {e}"}
        )
    return 500, "application/json", _json_bytes(
        {"code": 13, "message": str(e)}
    )


def observe_edge_sends(edges) -> list:
    """Drain the native edges' answered requests and observe `edge.send`
    (the answer handed to the acceptor -> its last byte accepted by the
    kernel) once each.  Returns the [token, t_staged, t_last_byte]
    records, for a caller that also puts them into a trace."""
    sends = [rec for e in edges for rec in e.drain_sends()]
    if sends:
        saturation.observe_phases(
            "edge.send", [(last - staged) / 1e9 for _, staged, last in sends])
    return sends


def observe_edge_arrivals(stamps) -> None:
    """`edge.recv` and `edge.handoff` of requests by the C++ edge's
    stamps, rows of (t_first_byte, t_body, arrival): an observation a
    request, the lock of each phase taken once."""
    saturation.observe_phases(
        "edge.recv", [(body - first) / 1e9 for first, body, _ in stamps])
    saturation.observe_phases(
        "edge.handoff", [(arrival - body) / 1e9 for _, body, arrival in stamps])


def handle_request_async(service: V1Service, method: str, path: str,
                         raw: bytes, respond, headers=None) -> None:
    """Async twin of handle_request for the device-bound POST paths:
    parse + submit on the calling thread, deliver via
    respond(status, content_type, body) exactly once from a completion
    thread.  Everything else (GET, globals push, unknown paths) answers
    synchronously — those never wait on a device round.  Used by the
    native epoll edge so its workers return to the ingress queue
    instead of parking one thread per in-flight request."""
    if method != "POST" or path not in (
        "/v1/GetRateLimits", "/v1/peer.GetPeerRateLimits"
    ):
        respond(*handle_request(service, method, path, raw, headers))
        return
    # Recorder binding + black-box edge taps, the handle_request
    # discipline (the early branch above already taps inside
    # handle_request): request on the submitting worker here, response
    # in finish() where the rendered triplet exists.
    tracing.bind_recorder(getattr(service, "recorder", None))
    bb = getattr(service, "blackbox", None)
    if bb is not None and raw:
        bb.tap("in", "", raw)
    rpc = (
        "/pb.gubernator.V1/GetRateLimits"
        if path == "/v1/GetRateLimits"
        else "/pb.gubernator.PeersV1/GetPeerRateLimits"
    )
    metrics = service.metrics
    start = time.perf_counter()
    # Ingress span, async form: active on THIS thread only while the
    # request is parsed/submitted (that is where routing captures the
    # context into batch links and peer forwards); ended exactly once
    # by finish(), from whichever completion thread delivers.
    span = tracing.ingress_span(
        "http", path, headers.get("traceparent") if headers else None
    )
    span.activate()
    # Exactly-once guard: an inline callback that raised must not
    # re-enter through the outer except and answer the same token
    # twice (round-5 review finding).  The check-then-set is LOCKED: a
    # completion thread and the submitting thread can race into
    # finish() concurrently (e.g. a drainer callback firing while the
    # submit path converts a late exception), and an unlocked flag
    # would let both pass the check and double-respond / double-count.
    finished = [False]
    finished_lock = threading.Lock()

    def finish(status_label: str, triplet) -> None:
        with finished_lock:
            if finished[0]:
                return
            finished[0] = True
        # Manual observe_rpc: the span covers parse -> response-ready,
        # like the sync context manager covers parse -> render.
        dt = time.perf_counter() - start
        metrics.request_counts.labels(status=status_label, method=rpc).inc()
        metrics.request_duration.labels(method=rpc).observe(dt)
        metrics.observe_latency(rpc, dt, ctx=span.ctx if span else None)
        span.end(status=status_label)
        if bb is not None and triplet[2]:
            bb.tap("out", "", triplet[2])
        respond(*triplet)

    try:
        if path == "/v1/GetRateLimits":
            ingress_frame = (
                service.serves_ingress_columns and wire.is_ingress_frame(raw)
            )
            # ingress.parse on the async edge.  For the columnar front
            # door the native worker hands ready column buffers
            # (gt_frame_parse ran with the GIL released) to the submit
            # path and returns to the ingress queue; the kind-6 response
            # renders on the completion thread straight from the result
            # arrays.
            with phase("ingress.parse"):
                native = False
                if ingress_frame:
                    cols = _decode_ingress_frame_or_400(raw)
                else:
                    cols = parse_body_native(raw) if raw else None
                    native = cols is not None
                    if cols is None:
                        body = json.loads(raw) if raw else {}
                        cols = parse_columns(body.get("requests", []))

            def cb(result, exc):
                # Guarded like the sync catch-all: a render failure on a
                # completion thread must become a 500, not a swallowed
                # exception that leaves the client hanging.
                try:
                    if exc is not None:
                        finish("1", _error_triplet(exc))
                        return
                    if ingress_frame:
                        with phase("response.encode"):
                            rendered = wire.encode_ingress_result_frame(result)
                        metrics.ingress_columns_batches.labels(
                            encoding="frame"
                        ).inc()
                        finish("0", (200, wire.COLUMNS_CONTENT_TYPE, rendered))
                        return
                    with phase("response.encode"):
                        rendered = (
                            render_result_native(result) if native else None
                        )
                        if rendered is None:  # native render unavailable/cap
                            rendered = _json_bytes(render_columns(result))
                    finish("0", (200, "application/json", rendered))
                except Exception as e:  # noqa: BLE001
                    finish("1", _error_triplet(e))

            service.get_rate_limits_columns_async(
                cols, cb,
                max_lanes=(
                    INGRESS_COLUMNS_MAX_LANES if ingress_frame
                    else MAX_BATCH_SIZE
                ),
            )
        else:
            frame = service.serves_peer_columns and wire.is_columns_frame(raw)
            if frame:
                cols = _decode_frame_or_400(raw)
            else:
                body = json.loads(raw) if raw else {}
                cols = parse_columns(body.get("requests", []))

            def cb(result, exc):
                try:
                    if exc is not None:
                        finish("1", _error_triplet(exc))
                        return
                    if frame:
                        finish("0", (200, wire.COLUMNS_CONTENT_TYPE,
                                     wire.encode_result_frame(result)))
                        return
                    finish("0", (200, "application/json", _json_bytes(
                        {"rateLimits": render_columns(result)["responses"]}
                    )))
                except Exception as e:  # noqa: BLE001
                    finish("1", _error_triplet(e))

            service.get_peer_rate_limits_columns_async(
                cols, cb,
                max_lanes=PEER_COLUMNS_MAX_LANES if frame else MAX_BATCH_SIZE,
            )
    except Exception as e:  # noqa: BLE001 — parse/submit errors, before
        finish("1", _error_triplet(e))  # any callback was registered
    finally:
        # Submit done: drop the context from this worker thread (the
        # span itself stays open until finish()).
        span.deactivate()


_HTTP_REASONS = {200: "OK", 400: "Bad Request", 404: "Not Found",
                 500: "Internal Server Error"}


class NativeIngressPump:
    """Batch-granularity control of the native ingress service loop
    (host_runtime.cpp gt_ingress_*, architecture.md "Native service
    loop").

    Gateway workers feed kind-5 frames and, since PR 47, classic JSON
    calls (parsed in C++ into the frame of their checks) into the native
    ring without ever copying their bytes into Python
    (HttpEdge.next(ingress=...));
    this pump is the ONLY Python in the steady-state hot path: one
    take per coalesced batch (zero-copy column views), the
    batch-granularity observability folds (audit ledger, tenant
    ledger, hot-key sketch, phase attribution — the PR 6/9/12 planes
    stay honest), one store dispatch, and one complete that hands the
    result arrays back to C++ for the per-frame response fill (kind-6
    for a frame, JSON for a call) and socket write.

    Lanes needing Python semantics never reach here — the native
    submit falls back to the ordinary gateway path for them (GLOBAL or
    MULTI_REGION lanes in a ring of more than one node, validation
    errors, remote owners, sampled traces, malformed frames, JSON the
    native parser refuses), so
    correctness is identical with the pump on or off; the pump only
    removes interpreter time from the already-columnar common case."""

    # Behavior bits that make the native submit hand a frame to the
    # Python router whole (`_push` picks the mask).  GLOBAL and
    # MULTI_REGION (OWNER_BEHAVIOR) do so only in a ring of more than
    # one node: there a GLOBAL lane may be owned elsewhere and needs
    # the replica path.  Where every vnode is this daemon's (the
    # snapshot's all_self) their lanes are the owner's own, change no
    # answer and stay: `_submit` queues the MULTI_REGION hits and the
    # store's plan does the GLOBAL owner's book-keeping, in the take's
    # one dispatch.  NO_BATCHING falls back only when the express lane
    # is off (direct dispatch is then the Python router's); with
    # GUBER_EXPRESS on it flags its frame for the native EXPRESS queue
    # instead (frames jump the ring — the bit means "skip coalescing
    # waits", which the native loop satisfies directly).
    # DURATION_IS_GREGORIAN (4) is in no mask: a calendar lane stays on
    # this lane and `_submit` resolves it; the native submit hands a
    # frame over whole only when such a lane's duration is not an
    # interval upstream resolves (weeks, or outside 0-5), because the
    # Python path owns the per-lane error wording.
    OWNER_BEHAVIOR = int(Behavior.GLOBAL) | int(Behavior.MULTI_REGION)
    EXPRESS_MASK = int(Behavior.NO_BATCHING)
    #: The bits for which `_submit` does work of its own on a take's way
    #: to its launch (a calendar resolve, the owner's book-keeping); a
    #: take whose lanes carry none is a plain take (`plain_takes`).
    TAKE_BEHAVIOR = OWNER_BEHAVIOR | int(Behavior.DURATION_IS_GREGORIAN)

    @classmethod
    def fallback_mask(cls, all_self: bool, express: bool) -> int:
        """The bits that send a frame to the Python router whole."""
        return (0 if all_self else cls.OWNER_BEHAVIOR) | (
            0 if express else cls.EXPRESS_MASK
        )

    #: Lane ceiling of one coalesced take = the device dispatch
    #: ceiling (ColumnarBatcher.MAX_LANES).  A take is also kept inside
    #: what warm-up compiled (`take_bound`): queued frames coalesced
    #: past the widest warmed pad bucket would pad into a brand-new XLA
    #: bucket and compile it inside a client's request.
    TAKE_LANES = 64_000
    #: Overlapping dispatches in flight (the PR 3 pipeline overlaps
    #: host work behind device compute underneath this bound; 6 keeps
    #: the device fed through a host-side hiccup without queueing work
    #: past any useful deadline — the native ring's shed bound still
    #: caps total admitted lanes).
    DEPTH = 6
    #: Take/dispatch threads.  Two: the PREPARE of take N+1 (the C++
    #: mesh plan, under `_plan_lock`) overlaps take N's STAGE/LAUNCH
    #: (store lock) — on one thread the
    #: two stages serialize and the ~equal-cost halves each idle while
    #: the other runs (measured ~1.6x at 60k-lane takes on the 2-core
    #: dev box).
    N_PUMPS = 2

    def __init__(self, service: V1Service, take_lanes: "Optional[int]" = None):
        from concurrent.futures import ThreadPoolExecutor

        from . import native as _nat

        self.service = service
        self.batcher = _nat.IngressBatcher()
        self.take_lanes = take_lanes or self.take_bound(service.store)
        self._sem = threading.Semaphore(self.DEPTH)
        self._in_flight = 0  # takes admitted and not yet committed
        # Takes that held no calendar and no owner lane, so did no numpy
        # of the pump's own (/debug/status ingress.plainTakes).
        self.plain_takes = 0
        self._flight_lock = threading.Lock()
        self._stopped = threading.Event()
        self._threads: list = []
        self._done_pool = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="native-ingress-done",
            initializer=tracing.bind_recorder,
            initargs=(getattr(service, "recorder", None),),
        )
        self._ring_lock = threading.Lock()
        self._ring = None
        self._eligible = False
        self._enable_at = 0.0
        self._stats_lock = threading.Lock()
        self._shed_seen = 0
        self._express_seen = 0
        self._lanes_seen = 0
        # The set_peers hook: the service pushes ring snapshots here.
        service.native_ingress = self

    @classmethod
    def take_bound(cls, store) -> int:
        """The most lanes one take may hold: every shard's share of the
        widest pad bucket warm-up compiled for the shapes it was given
        (`store.warm_bucket`), `TAKE_LANES` at the most, and
        `TAKE_LANES` where warm-up was given no shape.  The first frame
        of a take always fits (gt_ingress_take), so a frame wider than
        every warmed bucket is still served, alone."""
        if not store.warm_bucket:
            return cls.TAKE_LANES
        return min(cls.TAKE_LANES, store.n_shards * store.warm_bucket)

    @property
    def active(self) -> bool:
        """Whether workers should offer frames to the native lane.
        Sampled tracing (GUBER_TRACE_SAMPLE > 0) does not change it: a
        traced daemon serves on the same path, and a sampled take gets
        a batch trace of its own in `_run`."""
        return (
            not self._stopped.is_set()
            and not getattr(self.service, "_closed", False)
        )

    def stats(self) -> dict:
        return self.batcher.stats()

    # -- ring push (service.set_peers -> update_ring) ------------------
    def update_ring(self) -> None:
        """Recompute and push the native route snapshot: sorted vnode
        hashes + per-vnode self bits off the live picker (the
        ownership-code pass of hash_ring.get_batch_codes reduced to
        the one question the fast lane asks).  During a reshard
        double-dispatch window the lane DISABLES — moved keys owe the
        old owner a peek only the Python router performs — and
        re-enables when the window closes."""
        from .parallel import hash_ring as _hr

        svc = self.service
        with svc._peer_mutex:
            picker = svc.local_picker
            handoff_until = (
                svc._handoff_deadline if svc._prev_picker is not None else 0.0
            )
            vh = np.array(picker._vnode_hashes, dtype=np.uint64, copy=True)
            codes = np.array(picker._vnode_code, dtype=np.int32, copy=True)
            ids = list(picker._code_ids)
            self_codes = []
            for c, pid in enumerate(ids):
                peer = picker.get_by_peer_id(pid)
                info = getattr(peer, "info", None)
                if info is not None and info.is_owner:
                    self_codes.append(c)
            hash_fn = picker.hash_fn
        if hash_fn is _hr._fnv1a_str:
            variant = 1
        elif hash_fn is _hr._fnv1_str:
            variant = 0
        else:
            variant = -1  # custom hash: the native route cannot mirror it
        vself = (
            np.isin(codes, np.asarray(self_codes, dtype=np.int32))
            .astype(np.uint8)
            if codes.size else np.zeros(0, np.uint8)
        )
        now = time.monotonic()
        enabled = (
            variant >= 0
            and bool(ids)
            and handoff_until <= now
            and not self._stopped.is_set()
        )
        with self._ring_lock:
            self._ring = (vh, vself, bool(ids) and len(self_codes) == len(ids),
                          max(variant, 0))
            # Eligibility WITHOUT the window: what the deadline re-push
            # may enable (a custom hash_fn or empty ring stays off).
            self._eligible = variant >= 0 and bool(ids)
            self._enable_at = handoff_until if handoff_until > now else 0.0
            self._push(enabled)

    def _push(self, enabled: bool) -> None:
        # _ring_lock held.
        vh, vself, all_self, variant = self._ring
        b = self.service.conf.behaviors
        express = bool(getattr(b, "express", False))
        self.batcher.set_ring(
            vh, vself, all_self=all_self, enabled=enabled,
            cap_lanes=getattr(b, "ingress_queue_lanes", 0),
            max_frame_lanes=INGRESS_COLUMNS_MAX_LANES,
            behavior_mask=self.fallback_mask(all_self, express),
            hash_variant=variant,
            express_mask=self.EXPRESS_MASK if express else 0,
        )

    # -- pump loop ------------------------------------------------------
    def start(self) -> "NativeIngressPump":
        for i in range(self.N_PUMPS):
            t = threading.Thread(
                target=self._run, daemon=True, name=f"native-ingress-pump-{i}"
            )
            t.start()
            self._threads.append(t)
        return self

    def _run(self) -> None:
        batcher = self.batcher
        tracing.bind_recorder(getattr(self.service, "recorder", None))
        while not self._stopped.is_set():
            with self._ring_lock:
                # Check-and-push under ONE lock hold: a set_peers that
                # opens a NEW window between a read and the push must
                # not be re-enabled over; and the re-push honors the
                # SAME eligibility update_ring derived (a custom
                # hash_fn or empty ring stays disabled).
                if self._enable_at and time.monotonic() >= self._enable_at:
                    self._enable_at = 0.0
                    self._push(
                        self._eligible and not self._stopped.is_set()
                    )
            with phase("pump.take"):
                tb = batcher.take(self.take_lanes, timeout_ms=200)
            if tb is None:
                self._surface_stats()
                self._edge_sends()  # the last take's answer, once idle
                if batcher.stopped:
                    return
                continue
            # A sampled take's trace: its frames were served in C++ and
            # carry no context of their own, so the dice are rolled here.
            bt = tracing.new_batch(roll=True)
            with phase("pump.depth_wait", bt):
                self._sem.acquire()
            with self._flight_lock:
                self._in_flight += 1
                in_flight = self._in_flight
                self.plain_takes += not tb.beh_or & self.TAKE_BEHAVIOR
            saturation.mesh_tally.add_take(tb.n_frames, in_flight)
            try:
                args = self._submit(tb, bt)
            except BaseException as e:  # noqa: BLE001
                self._release_slot()
                # The incident frame is what the black box is for: a take
                # whose dispatch raised is tapped and folded all the same,
                # before its clients are answered.
                try:
                    self._observe(tb, bt)
                except Exception:  # noqa: BLE001
                    logger.exception("native pump: observers of a failed take")
                self._fail(tb, e)
                continue
            self._done_pool.submit(self._complete, *args, time.perf_counter())

    def _release_slot(self) -> None:
        """A take's slot of the pipeline's depth, given back."""
        with self._flight_lock:
            self._in_flight -= 1
        self._sem.release()

    def _surface_stats(self) -> None:
        """Overload-signal parity with the Python gate: native sheds
        happen entirely in C++, so the pump surfaces them into the
        flight recorder (the automatic-dump trigger shedding exists
        for) and samples the ring depth for /debug/status."""
        (_, lanes, _, _, shed, _, _, pending, _, express_lanes, _, _
         ) = self.batcher.counters()
        saturation.observe_queue_depth(pending)
        # The last-seen values are shared by every caller (a done-pool
        # worker a take, an idle pump thread): a delta is noted once.
        with self._stats_lock:
            d_express = express_lanes - self._express_seen
            d_bulk = (lanes - self._lanes_seen) - d_express
            d_shed = shed - self._shed_seen
            self._express_seen = max(express_lanes, self._express_seen)
            self._lanes_seen = max(lanes, self._lanes_seen)
            self._shed_seen = max(shed, self._shed_seen)
        # Express-lane attribution: NO_BATCHING frames served by
        # the native express queue (counted in C++ at submit), and
        # the ring's BULK lanes into the batched denominator — the
        # hit-rate gauge must reflect the native edge's coalesced
        # traffic, not just the batchers' windows.
        if d_express > 0:
            saturation.note_express("native", d_express)
        if d_bulk > 0:
            saturation.note_express("windowed", d_bulk)
        if d_shed > 0:
            tracing.record_event(
                "shed", lanes=d_shed, queued=pending,
                cap=getattr(
                    self.service.conf.behaviors,
                    "ingress_queue_lanes", 0,
                ),
            )

    def _submit(self, tb, bt):
        """A take's way to its launch: only what the answer needs.  The
        conservation ledger's note (the take summed its hits in C++; its
        order against `dispatched_hits` stays), the edge's stamps of a
        take that is being traced, ONE clock reading, the calendar
        resolve and the MULTI_REGION queueing where the take holds such
        lanes (`tb.beh_or`, the OR of its behaviour words: a plain take
        does no numpy here), then ONE columnar dispatch.  Whatever only
        observes the take runs once it has launched (`_observe`)."""
        svc = self.service
        with phase("pump.admit", bt, frames=tb.n_frames, lanes=tb.n) as ph:
            # The anchor that ties the C++ edge's stamps to the trace's
            # clock is read beside the event's start (_trace_edge).
            anchor_ns = time.monotonic_ns() if ph.traced else 0
            audit_mod.note("ingress_hits", tb.hits_total)
            # The C++ edge's stamps are observed after the answers have
            # left (`pump.account`); only a take that is being traced
            # reads them here, where its event is open.
            if bt is not None or ph.traced:
                stamps = tb.frame_stamps.tolist()
                take_ns = stamps[0][3] + int(tb.frame_age_us[0]) * 1000
                self._trace_edge(ph, bt, anchor_ns, take_ns, stamps)
            if bt is not None:
                now = time.monotonic_ns()
                tracing.record_span(
                    "batch.window", bt.ctx,
                    start_ns=now - int(tb.frame_age_us.max()) * 1000,
                    end_ns=now, lanes=tb.n, submissions=tb.n_frames,
                    lane="native",
                )
        t0 = time.perf_counter()
        # ONE clock reading for the calendar resolve and the store: the
        # expiry and the kernel's `greg_expire - now` cannot straddle a
        # boundary.
        now_ms = svc.clock.now_ms()
        beh_or = tb.beh_or
        greg_expire = greg_duration = None
        # Both phases are entered once a take, whatever it holds (their
        # counts are the per-dispatch metrics' divisors); a take without
        # the bit holds a flag test.
        with phase("calendar.resolve", bt) as ph:
            lanes = distinct = 0
            if beh_or & int(Behavior.DURATION_IS_GREGORIAN):
                greg = greg_lanes(tb.behavior)
                lanes = int(np.count_nonzero(greg))
                greg_expire, greg_duration, errors, distinct = (
                    resolve_greg_columns(greg, tb.duration, now_ms)
                )
                if errors:
                    # gt_ingress_submit hands such a frame to Python whole.
                    raise ValueError(errors[0][1])
            ph.note(lanes=lanes, durations=distinct)
        # The callers' behaviour bits.  Every lane of a take is owned
        # here (gt_ingress_submit keeps a frame with a GLOBAL or
        # MULTI_REGION lane only in an all-self ring), so none changes
        # an answer and all ride the one dispatch: a MULTI_REGION lane's
        # hits are queued for the other regions here, a GLOBAL lane's
        # owner book-keeping is the store's, inside the plan
        # (`dispatch.global_note`); NO_BATCHING chose the take's queue in
        # C++ and has nothing left to ask.
        with phase("behavior.handle", bt) as ph:
            if beh_or & self.OWNER_BEHAVIOR:
                owed = tb.behavior & self.OWNER_BEHAVIOR
                mr = ()
                if beh_or & int(Behavior.MULTI_REGION):
                    mr = np.flatnonzero(owed & int(Behavior.MULTI_REGION)).tolist()
                    svc.multi_region_mgr.queue_columns(
                        mr, tb.hash_keys, tb.hits, tb.request_at
                    )
                ph.note(lanes=int(np.count_nonzero(owed)), multi_region=len(mr))
        tracing.stage_batch_trace(bt)
        try:
            handle = svc.store.apply_columns_async(
                tb.hash_keys, tb.algorithm, tb.behavior, tb.hits, tb.limit,
                tb.duration, now_ms, greg_expire, greg_duration,
            )
        finally:
            # A store that raised before consuming the staged trace must
            # not leak it into this thread's next dispatch.
            tracing.take_batch_trace()
        return tb, handle, t0, bt

    def _observe(self, tb, bt):
        """What only OBSERVES a take, run once it has launched and before
        its answer is waited for (the head of `_complete`, on a done-pool
        worker: the device computes meanwhile), or before `_fail` answers
        a take whose dispatch raised: the ring's counters, the black-box
        tap (the frames' own bytes, which live with the batch's views
        until complete()/fail(); express-lane singles answered entirely
        in C++ never surface here — documented capture slack,
        architecture.md "Incident black box"), the tenant fold and the
        hot-key sketch (both native passes over the take's own columns,
        the sketch riding the hashes the native route already computed),
        and the attribution of what C++ timed.  By the time a client has
        its answer its take has been tapped, folded and sketched.  Booked
        under `pump.account`: no request waits on it.  Returns what the
        outcome needs of it: the tenant fold's context and the frames'
        ages in seconds."""
        svc = self.service
        with phase("pump.account", bt):
            self._surface_stats()
            bb = getattr(svc, "blackbox", None)
            if bb is not None:
                bb.tap_taken(tb)
            tenant_ctx = svc.tenants.fold_admit(tb)
            svc.hotkeys.update(tb.hashes, tb.hash_keys)
            # Measured in C++ (parse by the worker, a frame's age from its
            # arrival to this take), so observed, not timed, here.
            saturation.observe_phase(
                "ingress.parse", tb.parse_ns_total / 1e9 / max(tb.n_frames, 1)
            )
            ages_s = (tb.frame_age_us / 1e6).tolist()
            saturation.observe_phases("batch.window", ages_s)
        return tenant_ctx, ages_s

    @staticmethod
    def _trace_edge(ph, bt, anchor_ns, take_ns, stamps) -> None:
        """The edge's way in of a take that is being traced.  Sampled
        (`bt`): a span each under the take's trace, carrying the frame's
        token, which the take's `pump.admit` span lists beside the ids
        its dispatch spans share.  In a profiler session: the stamps as
        metadata of the `pump.admit` event (saturation.edge_trace_note),
        for a reader to rebuild on the trace's clock."""
        if bt is not None:
            ph.note(tokens=";".join(str(s[0]) for s in stamps))
            for token, t_first_byte, t_body, t_arrival in stamps:
                tracing.batch_span("edge.recv", bt, t_first_byte, t_body, token=token)
                tracing.batch_span("edge.handoff", bt, t_body, t_arrival, token=token)
        if ph.traced:
            ph.note(**saturation.edge_trace_note(anchor_ns, take_ns, stamps))

    def _edge_sends(self, ph=None, bt=None, anchor_ns=0) -> None:
        """The edge's way out: `edge.send` of the answers whose last byte
        has left since the last drain by any thread (earlier takes', as a
        rule; the token says whose).  Inside a take's `pump.account`
        (`ph`) that is being traced they also become spans under `bt` and
        metadata of the event, as in `_trace_edge`."""
        sends = observe_edge_sends(getattr(self.service, "native_edges", ()))
        if not sends or ph is None:
            return
        if bt is not None:
            for token, t_staged, t_last_byte in sends:
                tracing.batch_span("edge.send", bt, t_staged, t_last_byte, token=token)
        if ph.traced:
            ph.note(**saturation.edge_trace_note(anchor_ns, sends=sends))

    def _complete(self, tb, handle, t0, bt, t_handoff) -> None:
        # pump.handoff: queued behind the done pool's two workers.  It
        # crosses threads, so it is read from the pump's stamp.
        saturation.observe_phase("pump.handoff", time.perf_counter() - t_handoff)
        svc = self.service
        m = svc.metrics
        rpc = "/pb.gubernator.V1/GetRateLimits"
        try:
            try:
                # The take has launched: its observers run here, while
                # the device computes, with the copies of everything
                # needed past complete() (the batch's views die inside it).
                tenant_ctx, ages_s = self._observe(tb, bt)
                edge_stamps = tb.frame_stamps[:, 1:].tolist()
                nf, n_calls = tb.n_frames, tb.n_calls
                out = handle.result()
                with phase("pump.outcome", bt):
                    result = ColumnarResult(
                        n=tb.n,
                        status=np.asarray(out["status"], dtype=np.int32),
                        limit=np.asarray(out["limit"], dtype=np.int64),
                        remaining=np.asarray(out["remaining"], dtype=np.int64),
                        reset_time=np.asarray(out["reset_time"], dtype=np.int64),
                        overrides={},
                    )
                    svc.tenants.fold_outcome(tenant_ctx, result)
                # One observation a take, of the whole encode (the sum
                # over takes divided by the frames answered is a frame's).
                with phase("response.encode", bt, frames=nf):
                    self.batcher.complete(
                        tb, result.status, result.limit, result.remaining,
                        result.reset_time,
                    )
                # pump.account: the answers have left; what follows only
                # holds this take's slot of the pipeline-depth semaphore.
                with phase("pump.account", bt) as ph:
                    anchor_ns = time.monotonic_ns() if ph.traced else 0
                    dt_disp = time.perf_counter() - t0
                    # A take's entries are requests, kind-5 frames and
                    # classic calls alike; only its frames are columnar.
                    m.ingress_columns_batches.labels(encoding="frame").inc(nf - n_calls)
                    m.request_counts.labels(status="0", method=rpc).inc(nf)
                    duration = m.request_duration.labels(method=rpc)
                    for age in ages_s:
                        dt = age + dt_disp
                        duration.observe(dt)
                        m.observe_latency(rpc, dt)
                    # The C++ edge's stamps of each frame: its socket
                    # reads, and its way from the acceptor to the worker's
                    # submit (`arrival`, where batch.window starts; the
                    # native parse lies inside that window); and the
                    # answers whose last byte has left, earlier takes' as
                    # a rule.  Here, off the frame's way through the pump.
                    observe_edge_arrivals(edge_stamps)
                    self._edge_sends(ph, bt, anchor_ns)
            except BaseException as e:  # noqa: BLE001
                self._fail(tb, e)
        finally:
            self._release_slot()

    def _fail(self, tb, exc: BaseException) -> None:
        nf = tb.n_frames
        status, ctype, body = _error_triplet(exc)
        self.batcher.fail(
            tb, status, _HTTP_REASONS.get(status, "Error"), ctype, body
        )
        self.service.metrics.request_counts.labels(
            status="1", method="/pb.gubernator.V1/GetRateLimits"
        ).inc(nf)

    def stop(self) -> None:
        if self._stopped.is_set():
            return
        self._stopped.set()
        # Detach from the scrape surface FIRST: a /metrics scrape must
        # not read batcher stats across the free below.
        if getattr(self.service, "native_ingress", None) is self:
            self.service.native_ingress = None
        # Wake the pump + 503 queued frames; in-flight dispatches
        # complete through the done pool.  The batcher is NOT freed
        # here: gateway workers may still be blocked in
        # edge.next(ingress=...) and a submit against freed memory is a
        # use-after-free — a stopped batcher answers every submit with
        # the fallback code instead.  NativeGatewayServer.close calls
        # release() once its workers are joined.
        self.batcher.stop()
        for t in self._threads:
            t.join(timeout=15.0)
        self._done_pool.shutdown(wait=True)

    def release(self) -> None:
        """Free the native batcher.  Only safe after every thread that
        could submit into it (the gateway workers) has exited."""
        if all(not t.is_alive() for t in self._threads):
            self.batcher.free()


class NativeGatewayServer:
    """The C++ epoll edge (host_runtime.cpp gt_http_*): one native
    thread owns accept/read/frame/write for every connection; N Python
    workers pull parsed requests (GIL released while blocked) and run
    the same handle_request path as the stdlib gateway.  Replaces the
    Python HTTP layer and the thread-per-connection model that convoys
    at 100-way concurrency.  No TLS — the daemon selects the stdlib
    gateway when TLS is configured."""

    # Workers only parse + SUBMIT (handle_request_async): the device
    # round completes through the service's drainer pool and responds
    # from there, so in-flight requests are bounded by the native
    # ingress queue, not this pool — a handful of workers keeps the
    # submit path fed even on a 1-core host.
    N_WORKERS = 4
    # JSON requests a worker gathers before it observes their edge
    # phases together (_flush_edge); an idle worker flushes at once.
    EDGE_FLUSH = 32

    def __init__(self, service: V1Service, listen_address: str = "127.0.0.1:0",
                 n_workers: "Optional[int]" = None, acceptors: int = 1,
                 uds_path: str = ""):
        from . import native as _nat

        self.service = service
        if n_workers is not None and n_workers < 1:
            # Fail at startup: 0/negative would accept-but-never-serve.
            raise ValueError(
                f"native_workers must be >= 1, got {n_workers}"
            )
        self.n_workers = self.N_WORKERS if n_workers is None else n_workers
        self._edge = _nat.HttpEdge(  # raises if unavailable
            listen_address, acceptors=acceptors, uds_path=uds_path,
        )
        self._host = listen_address.partition(":")[0] or "127.0.0.1"
        self._threads: list = []
        self._stopped = threading.Event()
        # The native ingress service loop (NativeIngressPump): attached
        # by the daemon when the fast lane is on.  Workers hand kind-5
        # tokens to its batcher via edge.next(ingress=...); close()
        # stops it BEFORE the edge so staged responses never touch a
        # freed server.
        self.pump: "Optional[NativeIngressPump]" = None
        # Per-service scrape surface (metrics.observe_native_ingress).
        service.native_edges = getattr(service, "native_edges", [])
        service.native_edges.append(self._edge)
        # Responses not yet handed back to the C++ edge: free() must
        # wait for this to reach zero — async completions outlive the
        # worker threads, and edge.respond on freed memory is a
        # use-after-free (shutdown() alone is safe: respond after
        # shutdown is an explicit no-op C++-side).
        self._pending = 0
        self._pending_cv = threading.Condition()

    @property
    def address(self) -> str:
        return f"{self._host}:{self._edge.port}"

    def start(self) -> None:
        for i in range(self.n_workers):
            t = threading.Thread(target=self._worker, daemon=True,
                                 name=f"native-gw-{i}")
            t.start()
            self._threads.append(t)

    def _worker(self) -> None:
        from .native import FAST_LANE

        edge, service = self._edge, self.service
        # The C++ edge's stamps of the requests this worker has taken on
        # the JSON path, rows of (t_first_byte, t_body, arrival), observed
        # together once EDGE_FLUSH have gathered or the worker idles: on
        # this path the interpreter is the limit, and an observation a
        # phase a request, taken singly, is interpreter time.
        arrivals: list = []
        while not self._stopped.is_set():
            # The native fast lane: when the pump is attached, a kind-5
            # ingress frame or a classic JSON call is parsed, validated,
            # hashed, routed and enqueued INSIDE edge.next and this
            # worker never sees its bytes.  That is
            # two GIL-released native calls with the interpreter between
            # them (gt_http_next, the body's sniff in Python, then
            # gt_ingress_submit): Python's per-frame cost is the token
            # round trip and that wake-up, which `edge.handoff` measures.
            # Fallback reasons fall through to the unchanged path below.
            pump = self.pump
            ingress = pump.batcher if pump is not None and pump.active else None
            # Time blocked in the native queue pull (the GIL is released
            # inside edge.next) is epoll.wait — the "GIL-idle in epoll"
            # answer, distinct from parse work: this worker has no request.
            # (The socket's reads and writes are the acceptor threads':
            # edge.recv and edge.send.)
            with phase("epoll.wait"):
                got = edge.next(timeout_ms=200, ingress=ingress)
            if got is None:
                self._flush_edge(arrivals)
                if edge.stopped:
                    return
                continue
            if got is FAST_LANE:
                continue
            token, method, path, body, (t_first_byte, t_body) = got
            # `arrival` on this path: a worker holds the request, here.
            arrivals.append((t_first_byte, t_body, time.monotonic_ns()))
            if len(arrivals) >= self.EDGE_FLUSH:
                self._flush_edge(arrivals)
            if getattr(service, "_closed", False):
                edge.respond(token, 503, b'{"code": 14, "message": "shutting down"}')
                continue
            with self._pending_cv:
                self._pending += 1
            handle_request_async(
                service, method, path, body, partial(self._respond, token)
            )
        self._flush_edge(arrivals)

    def _flush_edge(self, arrivals: list) -> None:
        """Observe `edge.recv` and `edge.handoff` of the gathered
        requests and empty the list; and, as the JSON path has no take
        to drain the edge's send ring in, `edge.send` of the answers that
        have left.  A worker that gathered nothing leaves the ring alone:
        its records are the native pump's to read (and to trace)."""
        if arrivals:
            observe_edge_arrivals(arrivals)
            arrivals.clear()
            observe_edge_sends((self._edge,))

    def _respond(self, token: int, status: int, ctype: str,
                 payload: bytes) -> None:
        try:
            self._edge.respond(token, status, payload,
                               reason=_HTTP_REASONS.get(status, "Error"),
                               content_type=ctype)
        finally:
            with self._pending_cv:
                self._pending -= 1
                if self._pending == 0:
                    self._pending_cv.notify_all()

    def close(self) -> None:
        # Teardown order matters (round-5 review: use-after-free):
        # shutdown stops traffic but keeps the native server allocated;
        # the workers — possibly mid-device-round, about to respond() —
        # are joined BEFORE free() releases it.  A worker stuck past the
        # join timeout leaks the server instead of crashing into freed
        # memory.  The pump stops FIRST: its completions stage
        # responses into the edge, so it must drain while the server is
        # still allocated (respond-after-shutdown is a C++-side no-op).
        self._stopped.set()
        if self.pump is not None:
            self.pump.stop()
        self._edge.shutdown()
        deadline = time.monotonic() + 30.0
        for t in self._threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.1))
        # Async completions (service drainer / forward pool) may still
        # owe edge.respond calls after the workers exit; free() only
        # when none remain (a stuck completion leaks the edge instead
        # of crashing into freed memory, same policy as a stuck worker).
        with self._pending_cv:
            self._pending_cv.wait_for(
                lambda: self._pending == 0,
                timeout=max(deadline - time.monotonic(), 0.1),
            )
            drained = self._pending == 0
        workers_done = all(not t.is_alive() for t in self._threads)
        if self.pump is not None and workers_done:
            # Workers are out of edge.next: no submit can reach the
            # batcher anymore.
            self.pump.release()
        # Off the scrape surface before the native server frees: a
        # /metrics scrape must never reach a freed edge.
        edges = getattr(self.service, "native_edges", None)
        if edges is not None and self._edge in edges:
            edges.remove(self._edge)
        if drained and workers_done:
            self._edge.free()


class _GatewayHTTPServer(ThreadingHTTPServer):
    # socketserver's default listen backlog of 5 resets connections under
    # a concurrent client burst; the reference edge accepts thousands of
    # in-flight requests and bounds load at the request level instead
    # (1000-item cap, gubernator.go:118-121).
    request_queue_size = 128


class GatewayServer:
    def __init__(
        self,
        service: V1Service,
        listen_address: str = "127.0.0.1:0",
        tls_context: Optional[ssl.SSLContext] = None,
    ):
        self.service = service
        host, _, port = listen_address.partition(":")
        handler = _make_handler(service)
        self.httpd = _GatewayHTTPServer((host or "127.0.0.1", int(port or 0)), handler)
        self.httpd.daemon_threads = True
        if tls_context is not None:
            self.httpd.socket = tls_context.wrap_socket(self.httpd.socket, server_side=True)
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> str:
        host, port = self.httpd.server_address[:2]
        return f"{host}:{port}"

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._thread.start()

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)


def _make_handler(service: V1Service):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # noqa: N802 — silence stdlib logging
            pass

        def _send_bytes(self, status: int, content_type: str, body: bytes,
                        traceparent: "Optional[str]" = None) -> None:
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            if traceparent:
                # W3C trace-context emission: the client learns the
                # trace id its request was sampled under.
                self.send_header("traceparent", traceparent)
            self.end_headers()
            self.wfile.write(body)

        def _refuse_if_closed(self) -> bool:
            """A closed daemon must refuse — keep-alive handler threads
            outlive server shutdown, but the reference's gRPC server
            kills streams on Close (daemon.go:254-274)."""
            if getattr(service, "_closed", False):
                self.close_connection = True
                try:
                    self.connection.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                return True
            return False

        def _read_raw(self) -> bytes:
            length = int(self.headers.get("Content-Length", "0"))
            return self.rfile.read(length) if length else b""

        def do_GET(self):  # noqa: N802
            if self._refuse_if_closed():
                return
            status, ctype, body = handle_request(
                service, "GET", self.path, b"", self.headers
            )
            self._send_bytes(status, ctype, body)

        def do_POST(self):  # noqa: N802
            if self._refuse_if_closed():
                return
            status, ctype, body = handle_request(
                service, "POST", self.path, self._read_raw(), self.headers
            )
            self._send_bytes(
                status, ctype, body,
                traceparent=tracing.take_emitted_traceparent(),
            )

    return Handler
