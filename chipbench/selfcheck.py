#!/usr/bin/env python3
"""The benchmark checks itself, on the CPU, in seconds:

    python3 chipbench/selfcheck.py

BENCHMARK.json keeps to the contract's names and limits; every cell's
configuration, traffic, metric and reader files are found by name; the
accounting check passes a sound stream and fails one with a lost and one with a
doubled hit; the leaky bracket holds the sequential oracle; calendar quotas pass
sound and fail, each by its own comparison alone, with a token bucket that
ignores the bit and a daily leaky bucket that leaks by the hour; `trace_reduce`
gives the known busy share of the recorded trace; the bytes function matches
the program's shapes.  Exit status 0 when all hold."""

from __future__ import annotations

import importlib
import json
import os
import re
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import gregorian, oracle, reference, roofline, trace_reduce  # noqa: E402
from chipbench.population import Population  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
FAILURES: list = []


def check(ok: bool, what: str) -> None:
    print(("ok    " if ok else "WRONG ") + what, flush=True)
    if not ok:
        FAILURES.append(what)


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def check_benchmark_json() -> dict:
    path = os.path.join(REPO, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    check(os.path.getsize(path) <= 64 * 1024, "BENCHMARK.json is at most 64 KiB")
    check(set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}, "BENCHMARK.json has exactly the contract's keys")
    check(isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51,
          "run_seconds is a whole number from 1 to 51")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    check(len(names) == len(set(names)), "no two metrics share a name")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[kind]:
            check(bool(NAME.match(entry["name"])), f"{kind} name {entry['name']!r} uses the allowed characters")
    for m in bench["end_to_end"] + bench["per_layer"]:
        check(bool(UNIT.match(m["unit"])) and m["better"] in ("lower", "higher")
              and m["source"] in SOURCES, f"metric {m['name']}: unit, better and source are allowed values")
    for m in bench["end_to_end"]:
        check(m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
              and set(m) <= {"name", "unit", "better", "bound", "source", "workloads"},
              f"end-to-end {m['name']}: source, bound and keys")
    check(any(m["name"] == "setup_s" for m in bench["end_to_end"]), "setup_s is an end-to-end metric")
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        check(m["moves"] in e2e and one_line(m["layer"]) and set(m.get("workloads", [])) <= cells
              and set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"},
              f"per-layer {m['name']}: moves an end-to-end metric, names a layer, lists known cells")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    check(four <= max(1, len(bench["workloads"]) // 2), "at most half the cells (or one) ask for 4 chips")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    check(len(pairs) == len(set(pairs)), "a pair of configuration and traffic appears once")
    for w in bench["workloads"]:
        check(one_line(w["why"]) and w["chips"] in (1, 4) and bool(NAME.match(w["traffic"])),
              f"cell {w['name']}: why on one line of at most 200 characters, 1 or 4 chips")
    for c in bench["configs"]:
        check(one_line(c["source"]) and one_line(c["why"]) and c["file"].startswith("chipbench/")
              and any(w["config"] == c["name"] for w in bench["workloads"]),
              f"configuration {c['name']}: source and why on one line, file under paths, used by a cell")
    return bench


def check_files(bench: dict) -> None:
    from chipbench import harness

    for w in bench["workloads"]:
        _, config, traffic = harness.find_cell(bench, w["name"])
        check(config["name"] == w["config"] and config["chips"] == w["chips"],
              f"cell {w['name']}: its configuration file is found and gives {w['chips']} chips")
        gen = importlib.import_module(f"chipbench.generators.{traffic['kind']}")
        check(callable(gen.build_pool) and callable(gen.decode),
              f"cell {w['name']}: traffic {traffic['name']!r} names the generator kind {traffic['kind']!r}")
        in_flight = traffic["connections"] * traffic.get("lanes_per_request", traffic.get("checks_per_request"))
        check(traffic["lanes_in_flight"] == in_flight and traffic["warm_buckets"] and traffic["warm_buckets_why"],
              f"traffic {traffic['name']}: states its {in_flight} lanes in flight and its warm buckets")
    for m in bench["per_layer"]:
        spec = harness.load_json(harness.BENCH_DIR, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
        check(all(spec[k] == m[k] for k in ("unit", "better", "source", "layer", "moves"))
              and callable(reader.read), f"per-layer {m['name']}: its file agrees and its reader {spec['reader']!r} is found")


# ----------------------------------------------------------------------
# The accounting check against the sequential oracle
# ----------------------------------------------------------------------
POP_SPEC = {"name": "t", "leaky_share": 0.5, "duration_ms": 3_600_000, "limit_min": 3,
            "limit_max": 40, "limit_tiers": 6, "zipf_theta": 0.99}


ROOMY = dict(limit_min=2000, limit_max=4000)  # no bucket runs dry, and a daily one leaks 2-3 tokens in a stream
CALENDAR_SPEC = dict(POP_SPEC, calendar={"share": 0.7, "units": {"days": 0.5, "months": 0.5},
                                        "horizon_s": 300})
T0_MS = 1_700_000_000_000  # 2023-11-14 22:13:20 UTC: no calendar boundary within a stream


def drive_oracle(pop, rng, fault=None, start_ms=T0_MS, pace=1.0):
    """A daemon that is the sequential oracle: load every key, then 60 frames
    of 64 Zipfian checks in time order, then read every key back (about 70 s of
    the oracle's clock from `start_ms`; `pace` scales every step).  Returns the
    numbers compared and the milliseconds the stream took.  `fault`
    "lost" or "doubled" drops or repeats the effect of one admitted token hit;
    "leaky" takes four tokens too many from a leaky bucket, once;
    "calendar_ignored" reads a calendar token bucket back with creation plus
    `duration_ms` for its reset time, as a program that ignores the bit would;
    "daily_by_the_hour" leaks a daily leaky bucket at an hour's rate."""
    orc = oracle.Oracle()
    now = start_ms
    key = [pop.unique_key(i) for i in range(pop.n)]
    quota = pop.behavior == gregorian.GREGORIAN
    hourly = quota & (pop.algo == reference.LEAKY) & (pop.duration == gregorian.UNITS["days"]) \
        if fault == "daily_by_the_hour" else np.zeros(pop.n, bool)

    def step(lo, hi):  # a step that cannot be 0 stays at least 1 ms at any pace
        return max(min(lo, 1), round(int(rng.integers(lo, hi)) * pace))

    def apply(i, hits, t):
        duration, behavior = (pop.duration_ms, 0) if hourly[i] else (int(pop.duration[i]), int(pop.behavior[i]))
        a = orc.apply(key[i], int(pop.algo[i]), hits, int(pop.limit[i]), duration, t, behavior)
        return a.status, a.limit, a.remaining, a.reset_time

    load_lo = np.empty(pop.n)
    load_hi = np.empty(pop.n)
    for i in range(pop.n):
        now += step(0, 3)
        apply(i, 1, now)
        load_lo[i], load_hi[i] = now - 2, now + 3
    loaded_at = load_lo + 2
    rows = []
    faulted = fault not in ("lost", "doubled", "leaky")
    kind = reference.LEAKY if fault == "leaky" else reference.TOKEN
    hot = next(int(k) for k in pop.key_of_rank if pop.algo[k] == kind)
    for _ in range(60):
        now += step(1, 2000)
        for i in pop.draw(rng, 64).tolist():
            t = now + int(rng.integers(0, 5))
            got = apply(i, 1, t)
            item = orc.items[key[i]]
            if not faulted and i == hot and got[0] == reference.UNDER and item.remaining > 0:
                item.remaining += {"lost": 1, "doubled": -1, "leaky": -4.0}[fault]
                faulted = True
            rows.append((i, got[0], got[1], got[2], t - 3, t + 4))
        now += 5
    r = np.array(rows, dtype=np.int64)
    answers = reference.Answers(r[:, 0].astype(np.int32), r[:, 1], r[:, 2], r[:, 3],
                                r[:, 4].astype(np.float64), r[:, 5].astype(np.float64))
    now += step(500, 501)
    sample = np.arange(pop.n)
    back = np.array([apply(i, 0, now) for i in range(pop.n)], dtype=np.int64)
    if fault == "calendar_ignored":
        ignored = quota & (pop.algo == reference.TOKEN)
        back[ignored, 3] = loaded_at[ignored] + pop.duration_ms
    compared, asked = reference.token_accounting(pop, answers)
    compared += reference.readback(
        pop, asked, answers, sample, load_lo, load_hi, np.full(pop.n, now - 3.0),
        np.full(pop.n, now + 4.0), back[:, 0], back[:, 1], back[:, 2], back[:, 3])
    compared.append(reference.leaky_admissions(pop, answers, load_lo, float(now)))
    return compared, now - start_ms


def check_accounting() -> None:
    worst_leaky = 0.0
    for seed in range(8):
        pop = Population(POP_SPEC, 200, seed)
        compared, _ = drive_oracle(pop, np.random.default_rng(seed))
        bad = [c.line() for c in compared if not c.ok]
        worst_leaky = max(worst_leaky, next(
            c.value for c in compared if c.name == "readback.leaky_tokens_outside_bracket"))
        check(not bad, f"seed {seed}: the sequential oracle's answers pass every comparison {bad}")
    print(f"      (largest leaky reading outside the continuous bracket, 8 seeds: {worst_leaky:g} tokens)")
    roomy = dict(POP_SPEC, **ROOMY)  # no bucket runs dry: the read-back sees it too
    for fault in ("lost", "doubled"):
        pop = Population(roomy, 200, 99)
        failing = failing_of(pop, fault)
        check(any(n.startswith("accounting.token") for n in failing)
              and "readback.token_keys_wrong" in failing,
              f"one {fault} hit fails the accounting and the read-back: {failing}")
    failing = failing_of(Population(roomy, 200, 99), "leaky")
    check(failing == ["readback.leaky_tokens_outside_bracket"],
          f"four tokens taken too many from a leaky bucket fail its bracket, and only it: {failing}")


def failing_of(pop, fault, seed=99) -> list:
    return [c.name for c in drive_oracle(pop, np.random.default_rng(seed), fault)[0] if not c.ok]


def check_calendar() -> None:
    """Calendar quotas (`population.calendar`): daily and monthly keys among
    plain ones, through the oracle's Gregorian branch."""
    roomy = dict(CALENDAR_SPEC, **ROOMY)
    bad = []
    for seed in range(16):
        pop = Population(CALENDAR_SPEC if seed < 8 else roomy, 200, seed)
        bad += [f"seed {seed}: {c.line()}" for c in drive_oracle(pop, np.random.default_rng(seed))[0]
                if not c.ok]
    check(not bad, f"16 seeds: a sound stream of daily and monthly quotas passes every comparison {bad}")
    failing = failing_of(Population(roomy, 200, 99), "calendar_ignored")
    check(failing == ["readback.token_keys_born_outside_load"],
          "calendar token buckets that reset at creation + duration_ms (the bit ignored) fail "
          f"the reset time, and only it: {failing}")
    failing = failing_of(Population(roomy, 200, 99), "daily_by_the_hour")
    check(failing == ["readback.leaky_tokens_outside_bracket"],
          f"daily leaky buckets that leak at an hour's rate fail their bracket, and only it: {failing}")


# ----------------------------------------------------------------------
# The trace reduction and the bytes function
# ----------------------------------------------------------------------
def check_trace() -> None:
    path = os.path.join(REPO, "chipbench", "data", "recorded_trace.json")
    if not os.path.exists(path):
        check(False, "data/recorded_trace.json is there")
        return
    with open(path) as f:
        rec = json.load(f)
    got = trace_reduce.reduce(rec["rows"], rec["chips"])
    # The known answer, worked out another way: paint the first device's
    # operations onto a grid of 100 ns and count the painted cells.
    ops = [(s, s + d) for p, line, _, s, d in rec["rows"]
           if p == rec["first_device"] and line == trace_reduce.OPS_LINE]
    lo = min(s for _, _, _, s, _ in rec["rows"])
    hi = max(s + d for _, _, _, s, d in rec["rows"])
    grid = np.zeros(int((hi - lo) / 100) + 2, bool)
    for a, b in ops:
        grid[int((a - lo) / 100):int(np.ceil((b - lo) / 100))] = True
    painted = grid.sum() * 100 / (hi - lo)
    share = got["busy_s"] / got["window_s"]
    check(rec["chips"] == 1 and abs(share - painted) < 0.002 and abs(share - rec["busy_share"]) < 1e-9,
          f"trace_reduce gives the recorded trace's busy share {rec['busy_share']:.6f} "
          f"(got {share:.6f}; painted on a 100 ns grid {painted:.6f})")
    check(bool(got["breakdown"]["device_ops"]) and bool(got["breakdown"]["idle_gaps"])
          and set(got["program"]) == set(rec["programs"]),
          f"the recorded trace reduces to its programs {sorted(rec['programs'])} and a breakdown")


def check_bytes() -> None:
    from gubernator_tpu.ops import buckets

    state = buckets.init_state(8)
    row_bytes = state.hot.shape[1] * state.hot.dtype.itemsize
    check(row_bytes == roofline.ROW_BYTES == state.cold.shape[1] * state.cold.dtype.itemsize,
          f"a table row is {roofline.ROW_BYTES} bytes in each of the hot and the cold array")
    p = 64
    z = np.zeros((1, p), np.int32)
    table = tuple(np.zeros(buckets.DICT_TABLE_ROWS, np.int64) for _ in range(7))
    wire = np.asarray(buckets.pack_dict_wire(z, z, z, z.astype(np.uint8), z, z, table))
    check(wire.dtype == np.int32
          and wire.shape[1] - buckets.DICT_WIRE_TABLE_WORDS - buckets.WIRE_HEADER_WORDS
          == roofline.WIRE_WORDS_IN_PER_LANE * p,
          f"the dictionary wire carries {roofline.WIRE_WORDS_IN_PER_LANE} i32 words a lane beside its "
          "table and its header")
    want = 4 * (3 + 4) * 4096 + 3 * 32 * 3000
    check(roofline.dict_wire_dispatch_bytes(4096, 3000) == want,
          f"a 4096-lane dispatch over 3,000 distinct keys must move {want} bytes")
    try:
        roofline.peak("TPU v9 imaginary")
        check(False, "an unknown device kind is an error")
    except KeyError:
        check(True, "an unknown device kind is an error")


def main() -> int:
    bench = check_benchmark_json()
    check_files(bench)
    check_accounting()
    check_calendar()
    check_trace()
    check_bytes()
    print(f"{len(FAILURES)} wrong" if FAILURES else "all hold")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    raise SystemExit(main())
