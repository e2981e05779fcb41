"""CPU-backend soak smoke (`make soak-smoke`): a short
scripts/long_soak.py-derived run that drives mixed-shape traffic at a
2-daemon cluster while POLLING GET /debug/status — the observability
backbone the ROADMAP item-5 soak harness will assert against — and
checks steady-state invariants on every poll:

  * health stays "healthy", zero breakers open,
  * zero ingress shed,
  * occupancy monotone-consistent (used <= capacity, eviction counters
    never go backwards),
  * queue depth bounded by the configured cap,
  * the SLO engine live (enabled, burn rates present) and the latency
    attribution phases populated,
  * the CONSERVATION AUDIT silent: zero invariant violations on every
    poll and on a final quiesced reconciliation pass (audit.py — a
    clean soak is the audit's no-false-positive contract).

Marked `slow` (excluded from tier-1); `make soak-smoke` runs it alone.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import pytest

from gubernator_tpu.client import V1Client
from gubernator_tpu.cluster import Cluster, fast_test_behaviors
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    GetRateLimitsRequest,
    RateLimitRequest,
)

SOAK_S = 20
POLL_EVERY_S = 2.0

SHAPES = [
    (1, 0), (1, int(Behavior.NO_BATCHING)), (50, 0),
    (200, 0), (4, int(Behavior.GLOBAL)),
]


def _fetch(addr: str, path: str) -> dict:
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=10) as r:
        return json.loads(r.read())


@pytest.mark.slow
def test_soak_smoke_status_invariants():
    beh = fast_test_behaviors()
    beh.batch_timeout_s = 30.0
    # SLO engine live for the soak: a generous CPU-box target — the
    # invariant checked is "the plane reports"; latency verdicts are
    # the chip benchmark's.
    beh.latency_target_ms = 30_000.0
    cl = Cluster().start_with(["", ""], behaviors=beh)
    stop = threading.Event()
    lock = threading.Lock()
    stats = {"requests": 0, "errors": []}

    def worker(wid: int) -> None:
        client = V1Client(cl.daemons[wid % 2].gateway.address, timeout_s=60.0)
        i = 0
        while not stop.is_set():
            lanes, b = SHAPES[(wid + i) % len(SHAPES)]
            reqs = [
                RateLimitRequest(
                    name="smoke", unique_key=f"w{wid % 3}k{(i + j) % 40}",
                    hits=1, limit=100_000_000, duration=120_000,
                    algorithm=(
                        Algorithm.TOKEN_BUCKET if j % 2 == 0
                        else Algorithm.LEAKY_BUCKET
                    ),
                    behavior=b,
                )
                for j in range(lanes)
            ]
            try:
                resp = client.get_rate_limits(
                    GetRateLimitsRequest(requests=reqs)
                )
                errs = [r.error for r in resp.responses if r.error]
                with lock:
                    stats["requests"] += 1
                    stats["errors"].extend(errs[:2])
            except Exception as e:  # noqa: BLE001
                with lock:
                    stats["errors"].append(f"{type(e).__name__}: {e}")
            i += 1

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(6)]
    for t in threads:
        t.start()

    polls = 0
    last_evictions = {}
    violations = []
    try:
        t0 = time.time()
        while time.time() - t0 < SOAK_S:
            time.sleep(POLL_EVERY_S)
            for d in cl.daemons:
                addr = d.gateway.address
                doc = _fetch(addr, "/debug/status")
                polls += 1
                h = doc["health"]
                if h["status"] != "healthy":
                    violations.append(f"{addr}: unhealthy: {h['message']}")
                if h["breakerOpenCount"]:
                    violations.append(
                        f"{addr}: {h['breakerOpenCount']} breakers open"
                    )
                ing = doc["ingress"]
                if ing["shedLanes"]:
                    violations.append(f"{addr}: shed {ing['shedLanes']} lanes")
                if ing["capLanes"] and ing["queuedLanes"] > ing["capLanes"]:
                    violations.append(
                        f"{addr}: queue {ing['queuedLanes']} > cap"
                    )
                occ = doc["occupancy"]
                if occ["used"] > occ["capacity"]:
                    violations.append(
                        f"{addr}: occupancy {occ['used']} > {occ['capacity']}"
                    )
                if occ["evictions"] < last_evictions.get(addr, 0):
                    violations.append(f"{addr}: eviction counter went back")
                last_evictions[addr] = occ["evictions"]
                assert doc["slo"]["enabled"] is True
                assert "burn_rate_5m" in doc["slo"]
                aud = doc["audit"]
                assert aud["enabled"] is True
                if aud["violationTotal"]:
                    violations.append(
                        f"{addr}: audit violations {aud['violations']}"
                    )
            if violations:
                break
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        alive = [t.name for t in threads if t.is_alive()]
        # Attribution phases populated by the soak traffic (the
        # /debug/latency half of the backbone).
        lat = _fetch(cl.daemons[0].gateway.address, "/debug/latency")
        cl.stop()

    assert not alive, f"threads deadlocked: {alive}"
    assert not violations, violations[:5]
    # Final quiesced reconciliation: with traffic drained the ledger
    # inequalities are at their tightest — still zero violations.  The
    # thread-liveness assert runs FIRST: check_now() below also bumps
    # `checks`, which would mask a checker thread that never started.
    for d in cl.daemons:
        assert d.service.auditor.checks > 0, "auditor thread never ran"
        assert d.service.auditor.check_now() == []
        assert d.service.auditor.violations == {}
    assert polls >= 4, "soak made too few status polls"
    assert stats["requests"] > 50, "soak made no progress"
    assert not stats["errors"], stats["errors"][:5]
    assert "dispatch.launch" in lat["phases"], lat["phases"].keys()
    assert "ingress.total" in lat["phases"]
