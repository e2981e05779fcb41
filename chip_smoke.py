#!/usr/bin/env python3
"""Serve from the chip: start the daemon as a user would, load a million
buckets into its device table through the front door, and check every
answer against the line-faithful oracle.

    python3 chip_smoke.py             # one TPU chip; what the driver runs
    python3 chip_smoke.py --chips 4   # one daemon whose mesh spans four chips
    python3 chip_smoke.py --rehearse  # 20,000 keys on any backend; never a pass

One parent (this process) that never initialises a jax backend, and one
daemon child at a time that holds the chip.  The last line of standard
output is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

There is no path on which this exits 0 without a TPU: `--rehearse` always
ends `"ok": false` with exit status 3.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback

import numpy as np

from gubernator_tpu import native, wire
from gubernator_tpu.client import V1Client, dial_v1_server
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    GetRateLimitsRequest,
    RateLimitRequest,
    Status,
)
from gubernator_tpu.utils import hashing
from tests import oracle

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")

# Every columnar frame of a run is exactly this wide — one warmed pad bucket —
# and the width is the run's one entry in GUBER_WARMUP_SHAPES.  On a mesh of S
# shards a frame pads to pad_size(lanes/S), a hot key to pad_size(lanes), and
# warmup compiles both: above 1024 lanes those are two buckets, eight programs
# instead of four, on the call that costs four chips a second.  1024 is the
# widest frame for which they are one.
LANES_BY_CHIPS = {1: 4096, 4: 1024}
CACHE_SIZE = 1 << 20
N_KEYS = 1_000_000
REHEARSE_KEYS = 20_000
N_DRAINED = 1000  # of one frame of sampled resident keys, these take a hit each round
DRAIN_ROUNDS = 10  # token limits are 3..8, so round 9 is OVER_LIMIT for all
N_SMALL = 42  # 40 plain JSON singles and the two requests of the gRPC call
HOUR_MS = 3_600_000
# Warmup compiles four programs for every shape (dict wire, narrow wire,
# fused K=2 and K=4) beside its base programs (classic apply, GLOBAL sync,
# replica commit).  On the v5e host (PR 23) those took 69 + 44 + 106 + 207 s a
# shape and 69 + 249 + 0.3 s for the base: 745 s of a 776 s cold start with the
# bulk shape alone.  `1` beside it for the singles would add some 400 s — more
# than the 1200 s the driver allows the whole script.  So only the bulk shape
# is warmed; the plain singles come last, after the no-recompile evidence is
# taken, and say what they compiled lazily.
COLUMNS_CT = "application/x-gubernator-columns"
NAME = "smoke"
# One chip: whatever starts later than this cannot finish inside the driver's
# 1200 s anyway (load, queries, the lazy single and life 2 take ~130 s).  Four
# chips: the builder's own call, sized from one chip's compile seconds, doubled.
LISTEN_LIMIT_S = {1: 1050.0, 4: 2000.0}
STOP_LIMIT_S = 60.0
# Loading a program of this deployment from the compile cache took at most 7 s
# on the v5e host (fused K=4), compiling one at least 44 s (PR 23).
CACHE_LOAD_LIMIT_S = 20.0


class SmokeFailure(Exception):
    """A phase did not hold; the run ends non-zero."""


def say(msg: str) -> None:
    print(msg, flush=True)


def now_ms_floor() -> int:
    return time.time_ns() // 1_000_000


def now_ms_ceil() -> int:
    return -(-time.time_ns() // 1_000_000)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ----------------------------------------------------------------------
# The daemon child
# ----------------------------------------------------------------------
class DaemonProc:
    """`python -m gubernator_tpu.cmd.server` as a user would start it.  The
    environment is the caller's plus the GUBER_* settings of the deployment;
    nothing here names a jax platform."""

    def __init__(self, name: str, lanes: int, extra_env: dict):
        self.name = name
        self.http = f"127.0.0.1:{free_port()}"
        self.grpc = f"127.0.0.1:{free_port()}"
        env = dict(os.environ)
        env.update(
            GUBER_HTTP_ADDRESS=self.http,
            GUBER_GRPC_ADDRESS=self.grpc,
            GUBER_CACHE_SIZE=str(CACHE_SIZE),
            GUBER_WARMUP_SHAPES=str(lanes),
        )
        env.update(extra_env)
        env.setdefault("JAX_LOG_COMPILES", "1")  # each program's seconds, into the stderr file
        os.makedirs(OUT_DIR, exist_ok=True)
        self.stderr_path = os.path.join(OUT_DIR, f"chip_smoke_{name}.stderr")
        self._stderr = open(self.stderr_path, "wb")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "gubernator_tpu.cmd.server"],
            stdout=subprocess.PIPE, stderr=self._stderr, env=env, cwd=REPO,
            text=True,
        )
        self.cold_start_s = float("nan")

    def stderr_tail(self, n: int = 2000) -> str:
        self._stderr.flush()
        with open(self.stderr_path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")

    def wait_listening(self, limit_s: float) -> None:
        line: list = []
        reader = threading.Thread(
            target=lambda: line.append(self.proc.stdout.readline()), daemon=True
        )
        reader.start()
        reader.join(limit_s)
        waited = time.monotonic() - self.started
        if not line or "listening" not in line[0]:
            why = (
                f"exited with status {self.proc.poll()}"
                if self.proc.poll() is not None
                else "still starting"
            )
            raise SmokeFailure(
                f"{self.name}: no 'listening' line after {waited:.1f} s "
                f"(limit {limit_s:.0f} s; daemon {why}); stderr tail:\n"
                f"{self.stderr_tail()}"
            )
        self.cold_start_s = waited

    def stop(self) -> None:
        """SIGTERM and insist on a clean exit."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(STOP_LIMIT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name}: still running {STOP_LIMIT_S:.0f} s after SIGTERM; "
                f"stderr tail:\n{self.stderr_tail()}"
            ) from None
        if rc != 0:
            raise SmokeFailure(
                f"{self.name}: exit status {rc} after SIGTERM; stderr tail:\n"
                f"{self.stderr_tail()}"
            )

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._stderr.close()


# ----------------------------------------------------------------------
# Traffic: seeded keys, frames, expectations
# ----------------------------------------------------------------------
class Population:
    """`n` distinct keys from `seed`, token and leaky buckets 50/50, every
    duration an hour.  Token limits 3..8; leaky limits 2..3, so a token
    leaks back every 20-30 minutes — longer than the run, which makes the
    leaky answers as exact as the token ones while the comparison still
    allows for the instants it cannot know."""

    def __init__(self, n: int, seed: int, lanes: int):
        self.lanes = lanes  # the width of every frame sent
        rng = np.random.default_rng(seed)
        salt = rng.integers(0, 1 << 32, size=n, dtype=np.uint64)
        # The index leads the string: FNV clusters suffix-varying keys.
        self.unique_keys = [f"{i}k{s:08x}" for i, s in enumerate(salt.tolist())]
        self.algo = rng.integers(0, 2, size=n).astype(np.int32)
        token_limit = 3 + rng.integers(0, 6, size=n)
        leaky_limit = 2 + rng.integers(0, 2, size=n)
        self.limit = np.where(self.algo == 0, token_limit, leaky_limit).astype(np.int64)
        self.n = n

    def request(self, i: int, hits: int) -> RateLimitRequest:
        return RateLimitRequest(
            name=NAME, unique_key=self.unique_keys[i], hits=hits,
            limit=int(self.limit[i]), duration=HOUR_MS, algorithm=int(self.algo[i]),
        )


def frame_of(requests) -> bytes:
    """A GUBC kind-5 frame: the bytes ColumnsV1Client puts on the wire."""
    return wire.encode_ingress_frame((
        [r.name for r in requests],
        [r.unique_key for r in requests],
        np.array([r.algorithm for r in requests], np.int32),
        np.array([r.behavior for r in requests], np.int32),
        np.array([r.hits for r in requests], np.int64),
        np.array([r.limit for r in requests], np.int64),
        np.array([r.duration for r in requests], np.int64),
    ))


def post_frame(http: V1Client, frame: bytes):
    """One frame in flight at a time: the daemon's coalescer then never
    merges two frames into a lane count nobody warmed."""
    t_send = now_ms_floor()
    status, raw = http._roundtrip("POST", "/v1/GetRateLimits", frame, COLUMNS_CT)
    t_recv = now_ms_ceil()
    if status != 200:
        raise SmokeFailure(f"columnar frame answered HTTP {status}: {raw[:300]!r}")
    result = wire.decode_ingress_result_frame(raw)
    if result.overrides:
        raise SmokeFailure(f"columnar frame carried per-lane errors: {result.overrides}")
    return result, t_send, t_recv


class TwoOracles:
    """The daemon stamps each request at an instant this process cannot see,
    between sending and receiving.  So the same sequence runs through two
    oracles, one at the send instants and one at the receive instants, and
    an answer must lie between theirs.  Where they agree — every status and
    `remaining` of a bucket whose duration is an hour — that is equality."""

    def __init__(self):
        self.at_send = oracle.OracleCache()
        self.at_recv = oracle.OracleCache()
        self.checked = 0
        self.mismatches: list = []

    def expect(self, req: RateLimitRequest, t_send: int, t_recv: int):
        return (
            oracle.apply(self.at_send, req, t_send),
            oracle.apply(self.at_recv, req, t_recv),
        )

    def check(self, what: str, req, t_send, t_recv, status, limit, remaining, reset):
        lo, hi = self.expect(req, t_send, t_recv)
        self.checked += 1
        ok = (
            status in (int(lo.status), int(hi.status))
            and limit == lo.limit
            and min(lo.remaining, hi.remaining) <= remaining <= max(lo.remaining, hi.remaining)
            and min(lo.reset_time, hi.reset_time) <= reset <= max(lo.reset_time, hi.reset_time)
        )
        if req.algorithm == Algorithm.TOKEN_BUCKET:
            ok = ok and lo.status == hi.status and lo.remaining == hi.remaining
        if not ok:
            self.mismatches.append(
                f"{what} {req.hash_key()} hits={req.hits}: got "
                f"(status={status}, limit={limit}, remaining={remaining}, reset={reset}) "
                f"oracle@send={lo} oracle@recv={hi}"
            )

    def check_frame(self, what, http, requests):
        result, t_send, t_recv = post_frame(http, frame_of(requests))
        if result.n != len(requests):
            raise SmokeFailure(f"{what}: {result.n} lanes answered, {len(requests)} sent")
        for j, req in enumerate(requests):
            self.check(
                what, req, t_send, t_recv, int(result.status[j]), int(result.limit[j]),
                int(result.remaining[j]), int(result.reset_time[j]),
            )
        return result

    def check_responses(self, what, requests, responses, t_send, t_recv):
        if len(responses) != len(requests):
            raise SmokeFailure(f"{what}: {len(responses)} answers for {len(requests)} requests")
        for req, resp in zip(requests, responses):
            if resp.error:
                raise SmokeFailure(f"{what}: {req.hash_key()} answered error {resp.error!r}")
            self.check(
                what, req, t_send, t_recv, int(resp.status), int(resp.limit),
                int(resp.remaining), int(resp.reset_time),
            )

    def require_clean(self, what: str) -> None:
        if self.mismatches:
            raise SmokeFailure(
                f"{what}: {len(self.mismatches)} of {self.checked} answers differ "
                f"from the oracle; first:\n" + "\n".join(self.mismatches[:5])
            )


# ----------------------------------------------------------------------
# Reading the daemon's own account of itself
# ----------------------------------------------------------------------
def get_json(http: V1Client, path: str) -> dict:
    status, raw = http._roundtrip("GET", path, None)
    if status != 200:
        raise SmokeFailure(f"GET {path} answered HTTP {status}: {raw[:300]!r}")
    return json.loads(raw)


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def scrape(http: V1Client) -> list:
    """`/metrics` as (name, labels-text, value) rows."""
    rows = []
    for line in http.metrics_text().splitlines():
        m = _SAMPLE.match(line)
        if m:  # comment lines start with '#', which no metric name does
            rows.append((m.group(1), m.group(2) or "", float(m.group(3))))
    return rows


def metric_sum(rows: list, name: str, label_has: str = "") -> float:
    return sum(v for n, labels, v in rows if n == name and label_has in labels)


def device_of(doc: dict) -> dict:
    """What the process that holds the chip says it holds (`/debug/device`)."""
    devices = doc.get("devices") or []
    if not devices:
        raise SmokeFailure("/debug/device lists no device")
    return {
        "platform": devices[0]["platform"],
        "kind": devices[0]["device_kind"],
        "count": len(devices),
    }


def compile_report(doc: dict) -> "tuple[int, float, float]":
    """Print the compile table of a `/debug/device` document; return
    (programs compiled, seconds in the backend compiler, the slowest one's)."""
    compiles = doc.get("compiles") or {}
    slowest_s = max((float(row.get("max_s", 0.0)) for row in compiles.values()), default=0.0)
    total_n = 0
    total_s = 0.0
    for label in sorted(compiles):
        row = compiles[label]
        n = int(row.get("count", 0))
        s = float(row.get("total_s", 0.0))
        total_n += n
        total_s += s
        say(f"    compile {label}: {n} programs, {s:.2f} s")
    return total_n, total_s, slowest_s


# ----------------------------------------------------------------------
# Phases
# ----------------------------------------------------------------------
def phase_load(http: V1Client, pop: Population) -> list:
    """Every key once, hits=1, in frames of exactly `pop.lanes` lanes.  Returns
    the (t_send, t_recv) of each frame so the oracles can replay the
    sampled keys' creation.  The tail frame is filled with re-reads
    (hits=0) of the first keys, whose answers are checked too."""
    LANES = pop.lanes
    spans = []
    bad = 0
    first_bad = ""
    t0 = time.monotonic()
    for lo in range(0, pop.n, LANES):
        hi = min(lo + LANES, pop.n)
        idx = np.arange(lo, hi)
        fill = np.arange(0, LANES - (hi - lo))  # loaded by frame 0 when lo > 0
        lanes = np.concatenate([idx, fill])
        hits = np.concatenate([np.ones(hi - lo, np.int64), np.zeros(len(fill), np.int64)])
        frame = wire.encode_ingress_frame((
            [NAME] * LANES,
            [pop.unique_keys[i] for i in lanes.tolist()],
            pop.algo[lanes], np.zeros(LANES, np.int32), hits, pop.limit[lanes],
            np.full(LANES, HOUR_MS, np.int64),
        ))
        result, t_send, t_recv = post_frame(http, frame)
        spans.append((t_send, t_recv))
        if result.n != LANES:
            raise SmokeFailure(f"load: {result.n} lanes answered, {LANES} sent")
        # The first-hit answer in closed form (oracle.token_bucket /
        # leaky_bucket, the create branch; the fill lanes re-read it).
        limit = pop.limit[lanes]
        token = pop.algo[lanes] == 0
        step = np.where(token, HOUR_MS, HOUR_MS // limit)
        fresh = hits == 1
        span_lo = np.where(fresh | ~token, t_send, spans[0][0])
        span_hi = np.where(fresh | ~token, t_recv, spans[0][1])
        reset = np.asarray(result.reset_time)
        wrong = (
            (np.asarray(result.status) != Status.UNDER_LIMIT)
            | (np.asarray(result.limit) != limit)
            | (np.asarray(result.remaining) != limit - 1)
            | (reset < span_lo + step)
            | (reset > span_hi + step)
        )
        if wrong.any():
            j = int(np.flatnonzero(wrong)[0])
            bad += int(wrong.sum())
            first_bad = first_bad or (
                f"key {pop.unique_keys[int(lanes[j])]} hits={int(hits[j])}: status "
                f"{int(result.status[j])} limit {int(result.limit[j])} remaining "
                f"{int(result.remaining[j])} reset {int(reset[j])}; expected UNDER_LIMIT "
                f"{int(limit[j])} {int(limit[j]) - 1} within "
                f"[{int(span_lo[j] + step[j])}, {int(span_hi[j] + step[j])}]"
            )
    took = time.monotonic() - t0
    say(
        f"  load: {pop.n} keys in {len(spans)} frames of {LANES} lanes, {took:.1f} s "
        f"({pop.n / took:.0f} keys/s from one sequential client), {bad} answers wrong"
    )
    if bad:
        raise SmokeFailure(f"load: {bad} lanes differ from the first-hit answer; first: {first_bad}")
    return spans


def timed_singles(http: V1Client, both: "TwoOracles", what: str, requests) -> list:
    """Classic JSON requests of one item, one at a time; every answer is
    checked, and the client-side milliseconds of each are returned."""
    lat_ms = []
    for req in requests:
        t_send, t0 = now_ms_floor(), time.perf_counter()
        resp = http.get_rate_limits(GetRateLimitsRequest(requests=[req]))
        lat_ms.append((time.perf_counter() - t0) * 1e3)
        both.check_responses(what, [req], resp.responses, t_send, now_ms_ceil())
    both.require_clean(what)
    return lat_ms


def p50(values: list) -> float:
    return sorted(values)[len(values) // 2]


def phase_resident(http: V1Client, pop: Population, spans: list, seed: int,
                   n_shards: int) -> "tuple[TwoOracles, np.ndarray]":
    """Queries on resident state that only warmed programs serve: drains,
    duplicates, GLOBAL singles, health.  Returns the oracles (which now
    hold the sampled keys' state) and the sampled key indices."""
    rng = np.random.default_rng(seed + 1)
    sampled = rng.choice(pop.n, size=pop.lanes, replace=False)
    both = TwoOracles()
    for i in sampled.tolist():
        both.expect(pop.request(i, 1), *spans[i // pop.lanes])

    # Drain: the first N_DRAINED sampled keys take one hit a round until
    # every one of them is OVER_LIMIT; the others are re-read beside them.
    t0 = time.monotonic()
    over = 0
    for rnd in range(DRAIN_ROUNDS):
        reqs = [
            pop.request(i, 1 if j < N_DRAINED else 0)
            for j, i in enumerate(sampled.tolist())
        ]
        result = both.check_frame(f"drain round {rnd}", http, reqs)
        over = int((np.asarray(result.status[:N_DRAINED]) == Status.OVER_LIMIT).sum())
    both.require_clean("drain")
    if over != N_DRAINED:
        raise SmokeFailure(f"drain: {over} of {N_DRAINED} keys OVER_LIMIT after {DRAIN_ROUNDS} rounds")
    say(
        f"  drain: {N_DRAINED} keys to OVER_LIMIT in {DRAIN_ROUNDS} rounds, "
        f"{both.checked} answers equal to the oracle, {time.monotonic() - t0:.1f} s"
    )

    # One frame with duplicated keys: lanes of one key apply in lane order.
    dup_token = RateLimitRequest(
        name=NAME, unique_key="dup-token", hits=1, limit=3, duration=HOUR_MS,
    )
    dup_leaky = RateLimitRequest(
        name=NAME, unique_key="dup-leaky", hits=1, limit=2, duration=HOUR_MS,
        algorithm=Algorithm.LEAKY_BUCKET,
    )
    reqs = [dup_token] * 5 + [dup_leaky] * 4
    reqs += [pop.request(int(i), 0) for i in sampled[: pop.lanes - len(reqs)].tolist()]
    before = both.checked
    result = both.check_frame("duplicates", http, reqs)
    both.require_clean("duplicates")
    if [int(x) for x in result.status[:5]] != [0, 0, 0, 1, 1]:
        raise SmokeFailure(f"duplicates: token lanes answered {list(result.status[:5])}")
    say(f"  duplicates: one key in 5 lanes and one in 4, {both.checked - before} answers equal")

    # GLOBAL keys, one owned by each shard of the mesh, so that the sync
    # collective's owner-apply and broadcast cross every chip.  (The served
    # path never sets `home_shard`: in one daemon a GLOBAL hit always lands
    # on its owner shard, so "a hit on a shard that is not the owner" exists
    # only between daemons.)  They go as classic JSON singles through
    # `store.apply`, whose 64-lane program the base warmup compiles.
    sync_runs(http)  # the gauge counts runs since the previous scrape: start from zero
    owners: dict = {}
    k = 0
    while len(owners) < n_shards:
        uk = f"global-{k}"
        owners.setdefault(hashing.hash_string_64(f"{NAME}_{uk}") % n_shards, uk)
        k += 1
    reqs = [
        RateLimitRequest(name=NAME, unique_key=uk, hits=1, limit=7, duration=HOUR_MS,
                         behavior=Behavior.GLOBAL)
        for _, uk in sorted(owners.items())
    ] * 6
    lat_ms = timed_singles(http, both, "global single", reqs)
    deadline = time.monotonic() + 30.0
    ran = sync_runs(http)
    while not ran:
        if time.monotonic() > deadline:
            raise SmokeFailure("global: the sync collective did not run within 30 s of a GLOBAL hit")
        time.sleep(0.1)
        ran = sync_runs(http)
    say(
        f"  global: {len(owners)} keys (owner shards {sorted(owners)}) x 6 JSON singles equal to "
        f"the oracle; the first took {lat_ms[0]:.1f} ms; the rest p50 {p50(lat_ms[1:]):.3f} ms, "
        f"min {min(lat_ms[1:]):.3f} ms, max {max(lat_ms[1:]):.3f} ms (host clock, client side); "
        f"the sync collective ran {ran:.0f} times since the first"
    )

    health = http.health_check()
    if health.status != "healthy" or health.peer_count != 1:
        raise SmokeFailure(f"health: {health}")
    say(f"  health: {health.status}, {health.peer_count} peer")
    return both, sampled


def phase_small(daemon: DaemonProc, http: V1Client, both: TwoOracles, requests: list) -> None:
    """Plain JSON singles and one gRPC GetRateLimits — the stock-client
    wires.  They dispatch the 64-lane columnar program, which no warmup of
    this script compiles: the first of these requests compiles it, or loads
    it from the compile cache in a later life."""
    singles, grpc_reqs = requests[:-2], requests[-2:]
    first_ms = timed_singles(http, both, "json single (first)", singles[:1])[0]
    lat_ms = timed_singles(http, both, "json single", singles[1:])
    say(
        f"  json singles: {len(singles)} sequential requests equal to the oracle; the first "
        f"took {first_ms:.1f} ms; the rest p50 {p50(lat_ms):.3f} ms, min {min(lat_ms):.3f} ms, "
        f"max {max(lat_ms):.3f} ms (host clock, client side)"
    )
    client = dial_v1_server(daemon.grpc, timeout_s=120.0)
    try:
        t_send = now_ms_floor()
        resp = client.get_rate_limits(GetRateLimitsRequest(requests=grpc_reqs))
        both.check_responses("grpc", grpc_reqs, resp.responses, t_send, now_ms_ceil())
    finally:
        client.close()
    both.require_clean("grpc")
    say(f"  grpc: GetRateLimits with {len(grpc_reqs)} requests equal to the oracle")


def sync_runs(http: V1Client) -> float:
    return metric_sum(
        scrape(http), "gubernator_xla_program_runs", 'program="mesh:global_sync",stat="count"'
    )


def steady_recompiles(http: V1Client) -> dict:
    compiles = get_json(http, "/debug/device").get("compiles") or {}
    return {k: v["steady_recompiles"] for k, v in compiles.items() if v["steady_recompiles"]}


def phase_evidence(http: V1Client, n_sent_keys: int, size_at_start: float,
                   native_edge: bool) -> None:
    """The fast path ran, nothing fell back, nothing was evicted, and no
    program was compiled inside a request."""
    rows = scrape(http)
    size = metric_sum(rows, "gubernator_cache_size")
    say(f"  gubernator_cache_size {size:.0f} ({n_sent_keys} keys sent; {size_at_start:.0f} after warmup)")
    if not n_sent_keys <= size <= n_sent_keys + size_at_start:
        raise SmokeFailure(
            f"cache holds {size:.0f} rows, not the {n_sent_keys} sent (+ at most "
            f"{size_at_start:.0f} of warmup): something was evicted or never stored"
        )
    build = [labels for n, labels, _ in rows if n == "gubernator_build_info"]
    say(f"  gubernator_build_info{build[0] if build else ' missing'}")
    ingress = metric_sum(rows, "gubernator_native_ingress_batches_total", 'stat="batches"')
    say(f"  gubernator_native_ingress_batches_total{{stat=\"batches\"}} {ingress:.0f}")
    if native_edge and ingress <= 0:
        raise SmokeFailure("the native ingress loop took no batch: the frames fell back to Python")
    lazy = steady_recompiles(http)
    say(f"  gubernator_xla_steady_recompiles {sum(lazy.values())}")
    if lazy:
        raise SmokeFailure(f"programs compiled inside requests, after warmup: {lazy}")
    require_audit_clean(http)


def require_audit_clean(http: V1Client) -> None:
    audit = get_json(http, "/debug/audit")
    say(f"  /debug/audit violationTotal {audit.get('violationTotal')}")
    if audit.get("violationTotal") != 0:
        raise SmokeFailure(f"audit: {json.dumps(audit)[:1500]}")


def check_platform(device: dict, rehearse: bool, want_count: int) -> None:
    say(
        f"  /debug/device: platform {device['platform']}, kind {device['kind']}, "
        f"{device['count']} devices"
    )
    if device["platform"] != "tpu" and not rehearse:
        raise SmokeFailure(f"the daemon holds a {device['platform']} device, not a TPU")
    if device["count"] != want_count:
        raise SmokeFailure(f"{device['count']} devices in the daemon, {want_count} asked for")


def check_spread(http: V1Client, want_count: int) -> None:
    """Every device of the mesh holds live buffers, and like amounts of
    memory: code that never ran on more than one chip may have put everything
    on the first.  Judged on the backend's own `bytes_in_use` where it reports
    one (a TPU does): the live-array walk misses whatever array a dispatch has
    donated at that instant, and the first device also holds the unsharded
    host-to-device scraps."""
    rows = get_json(http, "/debug/device")["devices"]
    for r in rows:
        say(
            f"    {r['device']}: {r['live_buffers']} live buffers, {r['live_bytes']} bytes"
            + (f", {r['bytes_in_use']} in use, peak {r.get('peak_bytes_in_use')}"
               if "bytes_in_use" in r else "")
        )
    stat = "bytes_in_use" if all("bytes_in_use" in r for r in rows) else "live_bytes"
    sizes = [r[stat] for r in rows]
    table_bytes = CACHE_SIZE * 64 // want_count
    if (
        len(rows) != want_count
        or min(r["live_buffers"] for r in rows) <= 0
        or max(sizes) > 1.25 * min(sizes)
        or min(sizes) < table_bytes
    ):
        raise SmokeFailure(
            f"device state is not spread over {want_count} devices in like amounts of at "
            f"least {table_bytes} bytes ({stat}): {sizes}"
        )


def life(name: str, extra_env: dict, pop: Population, seed: int, chips: int,
         rehearse: bool, full: bool, device_out: dict) -> "tuple[float, float]":
    """One daemon from start to SIGTERM.  `full` loads the whole population
    and runs every query phase; otherwise one frame and the small requests.
    Fills `device_out` as soon as the daemon says what it holds; returns the
    seconds warmup spent in the compiler, and those of its slowest program."""
    say(f"== {name}: GUBER_WARMUP_SHAPES={pop.lanes} GUBER_CACHE_SIZE={CACHE_SIZE} {extra_env}")
    daemon = DaemonProc(f"rehearse_{name}" if rehearse else name, pop.lanes, extra_env)
    try:
        daemon.wait_listening(LISTEN_LIMIT_S[chips])
        http = V1Client(daemon.http, timeout_s=300.0)
        doc = get_json(http, "/debug/device")
        device_out.update(device_of(doc))
        check_platform(device_out, rehearse, chips)
        n_programs, compile_s, slowest_s = compile_report(doc)
        say(
            f"  cold start {daemon.cold_start_s:.1f} s to 'listening': {n_programs} programs, "
            f"{compile_s:.2f} s in the compiler, {slowest_s:.2f} s the slowest"
        )
        size_at_start = metric_sum(scrape(http), "gubernator_cache_size")
        native_edge = extra_env.get("GUBER_NATIVE_HTTP") == "1"
        if full:
            spans = phase_load(http, pop)
            both, sampled = phase_resident(http, pop, spans, seed, chips)
            small = [pop.request(i, 1) for i in sampled[-N_SMALL:].tolist()]
            n_sent = pop.n + 2 + chips  # + the two duplicate keys + the GLOBAL keys
            check_spread(http, chips)
        else:
            both = TwoOracles()
            both.check_frame("frame", http, [pop.request(i, 1) for i in range(pop.lanes)])
            both.require_clean(name)
            say(f"  one frame of {pop.lanes} lanes: {both.checked} answers equal to the oracle")
            small = [pop.request(i, 1) for i in range(N_SMALL)]
            n_sent = pop.lanes
        phase_evidence(http, n_sent, size_at_start, native_edge)
        if chips == 1:  # the four-chip call pays for the mesh, not for the small wires
            phase_small(daemon, http, both, small)
            lazy = steady_recompiles(http)
            say(f"  programs compiled inside those requests: {lazy or 'none'}")
            if sum(lazy.values()) > 2:
                raise SmokeFailure(f"more programs than the 64-lane pair compiled lazily: {lazy}")
            require_audit_clean(http)
        daemon.stop()
        say("  SIGTERM: exit status 0")
        return compile_s, slowest_s
    finally:
        daemon.kill()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--rehearse", action="store_true",
                    help=f"{REHEARSE_KEYS} keys on any backend; always ends ok=false, status 3")
    args = ap.parse_args(argv)

    from jax._src import xla_bridge

    lib = native.lib_path()
    had_lib = os.path.exists(lib)
    say(f"native runtime {native.source_digest()}: {'present' if had_lib else 'absent, the daemon builds it'}")
    pop = Population(REHEARSE_KEYS if args.rehearse else N_KEYS, args.seed, LANES_BY_CHIPS[args.chips])
    device = {"platform": "none", "kind": "none", "count": 0}
    ok = False
    try:
        t0 = time.monotonic()
        if args.chips == 4:
            life("mesh4", {"GUBER_NATIVE_HTTP": "1"}, pop, args.seed, 4, args.rehearse,
                 True, device)
        else:
            compile_1, slowest_1 = life("life1", {"GUBER_NATIVE_HTTP": "1"}, pop, args.seed, 1,
                                        args.rehearse, True, device)
            compile_2, slowest_2 = life("life2", {}, pop, args.seed, 1, args.rehearse, False, {})
            say(
                f"compile seconds during warmup: life 1 {compile_1:.2f} (slowest program "
                f"{slowest_1:.2f}), life 2 {compile_2:.2f} (slowest {slowest_2:.2f}); one "
                f"persistent cache, "
                f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or '.jax_cache/ in the checkout'}"
            )
            # Life 2 must have loaded every program, not compiled it.  Where life 1
            # compiled (an empty cache) the totals show it; where the cache came
            # with the machine both lives load alike, and only the slowest program
            # can tell.  (Not in a rehearsal: the CPU's compiles take under the
            # second below which jax caches nothing.)
            if not args.rehearse and not (
                slowest_2 < CACHE_LOAD_LIMIT_S
                and (slowest_1 < CACHE_LOAD_LIMIT_S or compile_2 < 0.25 * compile_1)
            ):
                raise SmokeFailure(
                    f"life 2 compiled for {compile_2:.2f} s (slowest program {slowest_2:.2f} s) "
                    f"against life 1's {compile_1:.2f} s: it did not load its programs from "
                    f"the compile cache the two lives share"
                )
        if not os.path.exists(lib):
            raise SmokeFailure(f"the daemon did not build {lib}")
        say(f"native runtime {'was already built' if had_lib else 'built by this run'}; "
            f"whole run {time.monotonic() - t0:.1f} s")
        ok = not args.rehearse
    except SmokeFailure as e:
        say(f"FAILED: {e}")
    except Exception:  # anything else is a failed phase too; the verdict line still follows
        say(f"FAILED:\n{traceback.format_exc()}")
    if xla_bridge.backends_are_initialized():
        say("FAILED: the parent initialised a jax backend")
        ok = False
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    if args.rehearse:
        return 3
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
