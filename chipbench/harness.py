"""One run of one cell: start the daemon, load the resident population through
the front door, drive the cell's traffic from the client's side, verify every
answer, stop the daemon, and report.  Driven by data: the cell names its
configuration and traffic files, the traffic file names its generator kind, and
each per-layer metric names its reader."""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import signal
import statistics
import sys
import time

import numpy as np

from . import gregorian, gubc, reference, trace_reduce
from .generators import frames
from .daemon import OUT_DIR, REPO, BenchFailure, DaemonProc, Http, metric_sum
from .loadgen import LoadGen, wall_ceil_ms, wall_floor_ms
from .readers import steady_recompiles
from .population import Population

BENCH_DIR = os.path.join(REPO, "chipbench")
LISTEN_LIMIT_S = 1100.0  # a cold start compiles; the driver allows a first run 1,200 s
# The trace takes the window's last seconds: writing it out keeps the daemon's
# host busy for a while (a minute on four chips), which must fall after the window.
TRACE_SECONDS = 3.0
TRACE_MARGIN_S = 0.5
REHEARSE_KEYS = 20_000
REHEARSE_SLOTS = 32_768
READBACK_SAMPLE = 65_536
READBACK_HOTTEST = 1_024
NATIVE_INGRESS = "gubernator_native_ingress_batches_total"  # {stat=...}: what the C++ ingress lane has counted
BOUNDARY_MARGIN_S = 2.0  # past a calendar boundary by this much before the first key is loaded


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def percentile(sorted_vals, q: float) -> float:
    """Nearest rank: the ceil(q*n)-th smallest."""
    n = len(sorted_vals)
    return float(sorted_vals[min(n - 1, max(0, math.ceil(q * n - 1e-9) - 1))])


def find_cell(bench: dict, name: str) -> "tuple[dict, dict, dict]":
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is not None:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    else:  # a cell that is written but not yet in BENCHMARK.json
        candidate = os.path.join(BENCH_DIR, "candidates", name + ".json")
        if not os.path.exists(candidate):
            raise BenchFailure(f"no workload {name!r} in BENCHMARK.json or chipbench/candidates/")
        cell, entry = (load_json(candidate)[k] for k in ("workload", "config"))
    config = load_json(REPO, entry["file"])
    traffic = load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


# ----------------------------------------------------------------------
# Phases outside the window
# ----------------------------------------------------------------------
def wait_past_boundary(pop: Population, clock=time.time, sleep=time.sleep) -> float:
    """A calendar bucket resets at its interval's end (midnight UTC for a daily
    quota), and the reference's closed forms hold for buckets that do not reset
    between their load and their read-back.  So no key is loaded while a
    boundary of a unit the population holds lies within `horizon_s`, the
    longest the load, ramp, window and read-back take together: the run waits
    until the boundary has passed.  It waits HERE, after `listening`, so that a
    cold start's compiles run meanwhile.  Returns the seconds waited."""
    def ahead_s() -> float:
        now_ms = int(clock() * 1000)
        return (int(gregorian.boundary_ms(now_ms, pop.calendar_units).min()) - now_ms) / 1e3

    if not pop.calendar_units or ahead_s() > pop.calendar_horizon_s:
        return 0.0
    pause = ahead_s() + BOUNDARY_MARGIN_S
    say(f"  calendar: a boundary of units {pop.calendar_units} lies {pause - BOUNDARY_MARGIN_S:.1f} s "
        f"ahead, inside the {pop.calendar_horizon_s:g} s a run's load, window and read-back may "
        f"take: waiting {pause:.1f} s (it counts in setup_s)")
    sleep(pause)
    if ahead_s() <= pop.calendar_horizon_s:
        raise BenchFailure(f"calendar units {pop.calendar_units} meet a boundary every "
                           f"{pop.calendar_horizon_s:g} s or less: no run fits between two")
    return pause


def boundary_crossed(pop: Population, from_ms: float, to_ms: float) -> bool:
    """Whether a boundary of a unit the population holds fell in [from_ms, to_ms]."""
    return bool(pop.calendar_units) and bool(
        (gregorian.boundary_ms(int(from_ms), pop.calendar_units) <= to_ms).any())


def load_population(http: Http, pop: Population, lanes: int, host: str) -> "tuple[np.ndarray, np.ndarray, int]":
    """Every key once with one hit, in frames of exactly `lanes` lanes, one in
    flight.  The tail frame is filled with re-reads (hits=0) of loaded token
    keys, whose level cannot have moved.  Returns each key's load bracket on the
    wall clock, and the number of wrong answers."""
    fill = np.flatnonzero(pop.algo[: 4 * lanes] == reference.TOKEN)[:lanes]
    sent_ms = np.empty(pop.n, np.float64)
    recv_ms = np.empty(pop.n, np.float64)
    wrong = 0
    for lo in range(0, pop.n, lanes):
        hi = min(lo + lanes, pop.n)
        idx = np.concatenate([np.arange(lo, hi), fill[: lanes - (hi - lo)]])
        hits = np.concatenate([np.ones(hi - lo, np.int64), np.zeros(lanes - (hi - lo), np.int64)])
        payload = frames.frame_payload(pop, idx, hits, host)
        t_send = wall_floor_ms()
        body = http.roundtrip(payload)
        t_recv = wall_ceil_ms()
        status, limit, remaining, _ = frames.decode(body, lanes)
        wrong += int((
            (status != reference.UNDER) | (limit != pop.limit[idx])
            | (remaining != pop.limit[idx] - 1)
        ).sum())
        sent_ms[lo:hi] = t_send
        recv_ms[lo:hi] = t_recv
    return sent_ms, recv_ms, wrong


def read_back(http: Http, pop: Population, sample: np.ndarray, lanes: int, host: str):
    """The sampled keys with hits=0, in frames of `lanes`, one in flight; the
    last frame is filled with repeats of the first sampled keys."""
    n = len(sample)
    cols = [np.empty(n, np.int64) for _ in range(4)]
    read_lo = np.empty(n, np.float64)
    read_hi = np.empty(n, np.float64)
    for lo in range(0, n, lanes):
        hi = min(lo + lanes, n)
        idx = np.concatenate([sample[lo:hi], np.resize(sample, lanes - (hi - lo))])
        payload = frames.frame_payload(pop, idx, 0, host)
        read_lo[lo:hi] = wall_floor_ms()
        body = http.roundtrip(payload)
        read_hi[lo:hi] = wall_ceil_ms()
        for col, got in zip(cols, frames.decode(body, lanes)):
            col[lo:hi] = got[: hi - lo]
    return (*cols, read_lo, read_hi)


def device_of(doc: dict) -> dict:
    devices = doc.get("devices") or []
    if not devices:
        raise BenchFailure("/debug/device lists no device")
    return {"platform": devices[0]["platform"], "kind": devices[0]["device_kind"],
            "count": len(devices)}


def snapshot(http: Http) -> dict:
    """What the daemon counts, for a reader to take differences of.  The
    /metrics scrape comes last: it drains the per-program run counts that
    /debug/device reports since the previous scrape."""
    return {
        "latency": http.get_json("/debug/latency"),
        "device": http.get_json("/debug/device"),
        "metrics": http.scrape(),
    }


# ----------------------------------------------------------------------
# The run
# ----------------------------------------------------------------------
def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, control: bool = False, daemon_argv=None,
             t_process_start: "float | None" = None, log_pads: bool = False) -> "tuple[dict, int]":
    """Returns (the result line, the exit status)."""
    t_begin = t_process_start if t_process_start is not None else time.perf_counter()
    cell, config, traffic = find_cell(bench, workload)
    chips = int(cell["chips"])
    label = f"{workload}.seed{seed}.trace{int(trace)}"
    n_keys = int(config["control"]["resident_keys"] if control
                 else config["population"]["resident_keys"])
    env = dict(config["env"])
    if rehearse:
        n_keys = 2 * REHEARSE_SLOTS if control else REHEARSE_KEYS
        env["GUBER_CACHE_SIZE"] = str(REHEARSE_SLOTS)
        env["GUBER_EXPRESS_SCALAR"] = "0"  # the CPU's host scalar slot would skip the device path
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    env["GUBER_WARMUP_SHAPES"] = ",".join(str(b) for b in traffic["warm_buckets"])
    trace_dir = os.path.join(OUT_DIR, f"{label}.trace")
    pads_path = os.path.join(OUT_DIR, f"{label}.pads")
    if log_pads:  # a rehearsal's aid: the pad of every columnar dispatch, warm-up's included
        env["CHIPBENCH_LOG_PADS"] = pads_path
        if os.path.exists(pads_path):
            os.remove(pads_path)
    if trace or log_pads:
        daemon_argv = daemon_argv or [sys.executable, os.path.join(BENCH_DIR, "traced_daemon.py")]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)  # an earlier run of this seed
        env["CHIPBENCH_TRACE_DIR"] = trace_dir
        trace_s = min(TRACE_SECONDS / chips, seconds / 3.0)  # the trace grows with the chips
        env["CHIPBENCH_TRACE_SECONDS"] = str(trace_s)

    say(f"== {workload} seed {seed} seconds {seconds:g} trace {int(trace)}"
        f"{' REHEARSAL' if rehearse else ''}{' CONTROL' if control else ''}: {n_keys} resident keys, "
        f"{traffic['kind']} x{traffic['connections']} {traffic['loop']} loop, warm buckets "
        f"{traffic['warm_buckets']}, daemon env {env}")
    pop = Population(config["population"], n_keys, seed)
    generator = importlib.import_module(f"chipbench.generators.{traffic['kind']}")
    daemon = DaemonProc(label, env, daemon_argv)
    http = None
    gen = None
    try:
        host = daemon.http
        pool = generator.build_pool(pop, traffic, np.random.default_rng([seed, 0x706F6F6C]), host)
        prepare_s = time.perf_counter() - t_begin
        daemon.wait_listening(LISTEN_LIMIT_S)
        http = Http(daemon.http)
        doc = http.get_json("/debug/device")
        device = device_of(doc)
        say(f"  device: platform {device['platform']}, kind {device['kind']}, count {device['count']}")
        if device["platform"] != "tpu" and not rehearse:
            raise BenchFailure(f"the daemon holds a {device['platform']} device, not a TPU")
        if device["count"] != chips:
            raise BenchFailure(f"{device['count']} devices in the daemon, the cell asks for {chips}")
        compiles = doc.get("compiles") or {}
        compile_s = sum(float(r.get("total_s", 0.0)) for r in compiles.values())
        at_start = http.scrape()
        size_at_start = metric_sum(at_start, "gubernator_cache_size")

        if wait_past_boundary(pop):  # the daemon may have dropped a connection that idled so long
            http.close()
            http = Http(daemon.http)
        load_begin_ms = wall_floor_ms()
        t = time.perf_counter()
        load_lo, load_hi, load_wrong = load_population(http, pop, int(traffic["load_lanes"]), host)
        load_s = time.perf_counter() - t
        before = snapshot(http)

        gen = LoadGen(daemon.http, pool, traffic, seed)
        hooks = []
        if trace:
            hooks.append((seconds - trace_s - TRACE_MARGIN_S, lambda: daemon.signal(signal.SIGUSR1)))
        t_ramp = time.perf_counter()
        t0, t1 = gen.run(float(traffic["ramp_s"]), seconds, hooks)
        setup_s = t0 - t_begin
        say("  set-up parts: " + json.dumps({
            "prepare_s": round(prepare_s, 3), "listening_s": round(daemon.listening_s, 3),
            "compile_s": round(compile_s, 3),
            "compile_s_by_program": {k: round(float(r.get("total_s", 0.0)), 3)
                                     for k, r in sorted(compiles.items())},
            "load_s": round(load_s, 3), "ramp_s": round(t0 - t_ramp, 3),
            "setup_s": round(setup_s, 3),
        }))
        after = snapshot(http)

        # ---- the window, from the client's side --------------------------
        done = gen.done
        in_window = [d for d in done if t0 <= d.t_done <= t1]
        lat_ms = sorted((d.t_done - d.t_start) * 1e3 for d in in_window)
        if not lat_ms:
            raise BenchFailure("no request completed inside the window")
        between = [c.between_s for c in gen.conns]
        say(f"  generator: {len(done)} requests answered, {len(in_window)} inside the window; "
            f"between an answer and the next request: mean {100 * sum(between) / len(between) / (t1 - t0 + float(traffic['ramp_s'])):.3f}% "
            f"of a connection's time, worst connection {100 * max(between) / (t1 - t0 + float(traffic['ramp_s'])):.3f}%"
            + (f"; open loop sent late by p50 {1e3 * statistics.median(gen.late_s):.3f} ms, "
               f"max {1e3 * max(gen.late_s):.3f} ms" if gen.late_s else ""))

        # ---- verify: every answer since the load -------------------------
        answers, failed_checks, ok_window_checks = flatten_answers(done, pool, generator, t0, t1)
        compared, asked = reference.token_accounting(pop, answers)
        compared.append(reference.Compared("load.first_hit_answers_wrong", load_wrong, 0))
        compared.append(reference.Compared("requests.failed_checks", failed_checks, 0))
        compared.append(reference.leaky_admissions(pop, answers, load_lo, float(answers.recv_ms.max())))

        rng = np.random.default_rng([seed, 0x72656164])
        touched = np.flatnonzero(asked > 0)
        hottest = np.argsort(-asked, kind="stable")[:READBACK_HOTTEST]
        sample = np.union1d(
            hottest, rng.choice(touched, size=min(READBACK_SAMPLE, len(touched)), replace=False)
        )
        t = time.perf_counter()
        rb = read_back(http, pop, sample, int(traffic["readback_lanes"]), host)
        compared += reference.readback(
            pop, asked, answers, sample, load_lo[sample], load_hi[sample], rb[4], rb[5], *rb[:4]
        )
        readback_s = time.perf_counter() - t
        if boundary_crossed(pop, load_begin_ms, wall_ceil_ms()):
            raise BenchFailure(
                f"a calendar boundary of units {pop.calendar_units} fell between the load and the "
                f"read-back, which took {(wall_ceil_ms() - load_begin_ms) / 1e3:.1f} s where the "
                f"configuration's calendar.horizon_s says at most {pop.calendar_horizon_s:g}: buckets "
                "reset inside the run, so it has no verdict")

        # ---- verify: what the daemon counts ------------------------------
        final = snapshot(http)
        rows = final["device"]["devices"]
        compared += daemon_counts(final, http.get_json("/debug/audit"), pop.n, size_at_start)
        ingress = "; ".join(
            f"{when}: " + ", ".join(f"{stat} {metric_sum(rows_at, NATIVE_INGRESS, label):g}"
                                   for stat, label in (("frames", '"frames"'), ("fallbacks", '"fallbacks"')))
            for when, rows_at in (("before the load", at_start), ("after the read-back", final["metrics"])))
        say(f"  native ingress lane: {ingress} (fallbacks: frames the C++ lane handed to the Python path whole)")
        recompiles = steady_recompiles.read({"before": before, "after": after}, {})
        say(f"  xla.compiles_in_window {recompiles:g} (programs compiled after warm-up, ramp and window)")

        if trace:
            trace_reduce.wait_for_span(trace_dir)
        http.close()
        http = None
        gen.close()
        gen = None
        daemon.stop()
        say("  SIGTERM: exit status 0")
    finally:
        if gen is not None:
            gen.close()
        if http is not None:
            http.close()
        daemon.kill()

    if log_pads:
        with open(pads_path) as f:
            pads = [tuple(int(x) for x in row.split()) for row in f]
        counts: dict = {}
        for lanes, padded, rounds in pads:
            row = counts.setdefault(padded, [0, lanes, lanes, 0])
            row[0] += 1
            row[1], row[2], row[3] = min(row[1], lanes), max(row[2], lanes), max(row[3], rounds)
        say("  pads of every dispatch (per shard): " + ", ".join(
            f"{p}: {c} dispatches of {lo}..{hi} lanes, at most {r} rounds"
            for p, (c, lo, hi, r) in sorted(counts.items())))
    for c in compared:
        say("  compared: " + c.line())
    checks_ok = all(c.ok for c in compared)
    say(f"  verified {len(answers.key)} answers and {len(sample)} keys read back "
        f"({readback_s:.1f} s); {len(touched)} keys touched")
    say(f"  req latency: {len(lat_ms)} samples in the window (req_p99_ms "
        f"{'stands' if len(lat_ms) >= 1000 else 'DOES NOT STAND: under 1,000 samples'}); ms: mean "
        f"{sum(lat_ms) / len(lat_ms):.3f}, " + ", ".join(
            f"p{int(q * 100)} {percentile(lat_ms, q):.3f}" for q in (0.1, 0.5, 0.9, 0.95, 0.99))
        + f", max {lat_ms[-1]:.3f}")
    fifths = np.histogram([d.t_done for d in in_window], bins=5, range=(t0, t1))[0]
    say("  requests completed in each fifth of the window: " + ", ".join(str(int(n)) for n in fifths))

    window_s = t1 - t0
    end_to_end = {
        "checks_per_s": {"value": ok_window_checks / window_s, "unit": "checks/s"},
        "req_p50_ms": {"value": percentile(lat_ms, 0.50), "unit": "ms"},
        "req_p99_ms": {"value": percentile(lat_ms, 0.99), "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    end_to_end = {m["name"]: end_to_end[m["name"]] for m in bench["end_to_end"]
                  if metric_applies(m, cell, bench)}
    peak = max(r.get("peak_bytes_in_use", r.get("bytes_in_use", r["live_bytes"])) for r in rows)
    device_line = dict(device, memory_peak_bytes=int(peak))
    wrong = sum(int(c.value) for c in compared if not c.ok and c.limit == 0)
    line = {
        "correct": bool(checks_ok and not rehearse),
        "attempted": int(len(answers.key) + failed_checks + len(sample)),
        "failed": int(failed_checks + (0 if checks_ok else max(wrong, 1))),
        "metrics": end_to_end,
        "device": device_line,
    }
    if rehearse or control:
        line["rehearsal"], line["control"], line["checks_ok"] = rehearse, control, checks_ok
    if trace:
        say("  end to end in this traced run (not the cell's numbers): "
            + json.dumps({k: round(v["value"], 4) for k, v in end_to_end.items()}))
        line["metrics"], extra = per_layer_metrics(
            bench, cell, config, traffic, before, after, in_window, lat_ms, done, pool, trace_dir,
            device, rehearse,
        )
        line["device"].update(extra["device"])
        line["breakdown"] = extra["breakdown"]
    # Every number compared beside its limit, last in the line: what the driver's
    # record keeps of a run that is not correct.
    line["compared"] = {c.name: {"value": c.value, "limit": c.limit, "ok": c.ok} for c in compared}
    if "jax" in sys.modules:  # the trace reader imports it; no backend may have come up
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise BenchFailure("the parent initialised a JAX backend")
    return line, (3 if rehearse else 0)


def metric_applies(metric: dict, cell: dict, bench: dict) -> bool:
    """A metric that lists `workloads` exists only in those cells.  (A candidate
    cell, in no list yet, reports whatever its readers find.)"""
    return ("workloads" not in metric or cell["name"] in metric["workloads"]
            or cell not in bench["workloads"])


def flatten_answers(done: list, pool: list, generator, t0: float, t1: float):
    """(every check answered since the load as `reference.Answers`, the checks
    of failed requests, the checks answered inside the window)."""
    keys, cols, sent, recv = [], [[], [], []], [], []
    failed_checks = 0
    ok_window_checks = 0
    for d in done:
        req = pool[d.pool_index]
        try:
            if d.status != 200:
                raise gubc.WireError(f"HTTP {d.status}: {d.body[:200]!r}")
            status, limit, remaining, _ = generator.decode(d.body, len(req.keys))
        except gubc.WireError as e:  # refused (429), failed or malformed: a failed request
            if failed_checks == 0:
                say(f"  FAILED request: {e!r}"[:400])
            failed_checks += len(req.keys)
            continue
        keys.append(req.keys)
        for col, got in zip(cols, (status, limit, remaining)):
            col.append(got)
        sent.append(np.full(len(req.keys), d.wall_send_ms, np.float64))
        recv.append(np.full(len(req.keys), d.wall_recv_ms, np.float64))
        if t0 <= d.t_done <= t1:
            ok_window_checks += len(req.keys)
    if not keys:
        raise BenchFailure("no request was answered")
    answers = reference.Answers(
        np.concatenate(keys), *(np.concatenate(c) for c in cols),
        np.concatenate(sent), np.concatenate(recv),
    )
    return answers, failed_checks, ok_window_checks


def daemon_counts(final: dict, audit: dict, n_keys: int, size_at_start: float) -> list:
    """(c): nothing evicted, the audit silent, the table spread over the devices."""
    size = metric_sum(final["metrics"], "gubernator_cache_size")
    rows = final["device"]["devices"]
    in_use = [r.get("bytes_in_use", r["live_bytes"]) for r in rows]
    violations = audit.get("violationTotal")
    return [
        reference.Compared("daemon.cache_rows_missing", max(0.0, n_keys - size), 0),
        reference.Compared("daemon.cache_rows_beyond_sent", max(0.0, size - n_keys - size_at_start), 0),
        reference.Compared("daemon.audit_violations", 1.0 if violations is None else float(violations), 0),
        reference.Compared("daemon.device_bytes_max_over_min", max(in_use) / max(min(in_use), 1), 1.25),
    ]


def per_layer_metrics(bench, cell, config, traffic, before, after, in_window, lat_ms, done, pool,
                      trace_dir, device, rehearse) -> "tuple[dict, dict]":
    reduced = trace_reduce.read_and_reduce(trace_dir, int(cell["chips"]), cpu_stand_in=rehearse)
    # Tracing overhead: the same window's requests inside and outside the traced span.
    span = reduced["span_perf"]  # (start, stop) on this process's perf_counter, from wall clock
    inside = sorted((d.t_done - d.t_start) * 1e3 for d in in_window if span[0] <= d.t_start and d.t_done <= span[1])
    outside = sorted((d.t_done - d.t_start) * 1e3 for d in in_window if d.t_done < span[0] or d.t_start > span[1])
    if inside and outside:
        say(f"  tracing overhead: req p50 {percentile(inside, 0.5):.4f} ms over {len(inside)} requests "
            f"inside the traced {span[1] - span[0]:.2f} s, {percentile(outside, 0.5):.4f} ms over "
            f"{len(outside)} outside it, in the same window "
            f"({100 * (percentile(inside, 0.5) / percentile(outside, 0.5) - 1):+.2f}%)")
    ctx = {
        "before": before, "after": after, "cell": cell, "config": config, "traffic": traffic,
        "requests": len(done), "checks": sum(len(pool[d.pool_index].keys) for d in done),
        "unique_keys_per_request": float(np.mean([len(np.unique(r.keys)) for r in pool])),
        "checks_per_request": float(np.mean([len(r.keys) for r in pool])),
        "trace": reduced, "device": device, "window_latencies_ms": lat_ms,
    }
    metrics = {}
    for m in bench["per_layer"]:
        if not metric_applies(m, cell, bench):
            continue
        spec = load_json(BENCH_DIR, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
        value = reader.read(ctx, spec.get("params") or {})
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, {
        "device": {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]},
        "breakdown": reduced["breakdown"],
    }
