"""The per-layer readers PR 25 added to `chipbench/`, each held to the known
answer of `chipbench/data/recorded_phases.json` (two cuts of a traced chip
run) or of a document written out here; and each returning None, not raising,
against a program from before the phases existed."""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.readers import (  # noqa: E402
    idle_by_phase, phase_ms_seen, scope_us_per_dispatch, startup_s, unattributed_ms,
)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(REPO, "chipbench", "data", "recorded_phases.json")) as f:
        return json.load(f)


def _overlap(a, b):
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


# ---------------------------------------------------------------------
# idle_by_phase
# ---------------------------------------------------------------------
def test_innermost_names_each_instant_by_the_deepest_open_event():
    events = [("a", 0, 100), ("b", 10, 30), ("c", 15, 20), ("d", 50, 60), ("e", 200, 300)]
    assert idle_by_phase.innermost(events) == [
        ("a", 0, 10), ("b", 10, 15), ("c", 15, 20), ("b", 20, 30), ("a", 30, 50),
        ("d", 50, 60), ("a", 60, 100), ("e", 200, 300)]


@pytest.mark.parametrize("cut", [0, 1])
def test_idle_by_phase_gives_the_recorded_answer(recorded, cut):
    c = recorded["cuts"][cut]
    got = idle_by_phase.attribute(
        [tuple(g) for g in c["gaps"]], c["thread_rows"], recorded["order"], recorded["no_request"])
    want = c["idle_seconds_by_phase"]
    assert set(got) == set(want)
    for phase, seconds in want.items():
        assert got[phase] == pytest.approx(seconds, abs=1e-9), phase
    assert sum(got.values()) == pytest.approx(sum(hi - lo for lo, hi in c["gaps"]) / 1e9, abs=1e-9)


def test_a_gap_under_two_threads_phases_goes_to_the_deeper_one(recorded):
    """Cut 0: a take waits for the plan lock (`dispatch.plan_wait` on t0)
    while the GLOBAL tick holds it (`global.sync` on t4).  Those idle
    instants are the tick's, not the waiter's."""
    c = recorded["cuts"][0]
    (wait,) = [r for r in c["thread_rows"] if r[1] == "dispatch.plan_wait" and r[3] - r[2] > 20e6]
    (sync,) = [r for r in c["thread_rows"] if r[1] == "global.sync"]
    assert wait[0] != sync[0]  # two threads
    both = (max(wait[2], sync[2]), min(wait[3], sync[3]))
    idle_under_both = sum(_overlap(g, both) for g in c["gaps"]) / 1e9
    assert idle_under_both > 0.015
    got = c["idle_seconds_by_phase"]
    assert got["global.sync"] >= idle_under_both
    assert got["dispatch.plan_wait"] < 0.001
    assert got["unattributed"] == 0.0


def test_a_gap_under_no_phase_is_unattributed(recorded):
    """Cut 1: the profiler stopped with phases still open, so the trace's
    last gaps lie under no event at all."""
    c = recorded["cuts"][1]
    covered = [(r[2], r[3]) for r in c["thread_rows"]]
    bare = sum(
        (hi - lo) - sum(_overlap((lo, hi), ev) for ev in covered) for lo, hi in c["gaps"]) / 1e9
    assert bare > 0.02
    assert c["idle_seconds_by_phase"]["unattributed"] == pytest.approx(bare, rel=0.02)


def test_no_request_phases_count_only_when_nothing_else_is_under_way():
    rows = [["t0", "pump.take", 0, 100], ["t1", "dispatch.fetch", 40, 60]]
    got = idle_by_phase.attribute([(0, 100)], rows, ["pump.take", "dispatch.fetch"], ["pump.take"])
    assert got == {"unattributed": 0.0, "pump.take": pytest.approx(80e-9), "dispatch.fetch": pytest.approx(20e-9)}


def test_idle_by_phase_reads_nothing_from_a_program_without_phases():
    ctx = {"after": {"latency": {"phases": {}}}, "trace": {"xplane": "/nonexistent"},
           "device": {"platform": "tpu"}}
    params = {"share": "unattributed", "no_request": ["pump.take"]}
    assert idle_by_phase.read(ctx, params) is None


# ---------------------------------------------------------------------
# scope_us_per_dispatch
# ---------------------------------------------------------------------
def test_rounds_scope_gives_the_recorded_microseconds(recorded):
    c = recorded["cuts"][0]
    runs = scope_us_per_dispatch.bucket_runs(c["device_events"], recorded["exclude"])
    ops = scope_us_per_dispatch.scoped(c["device_events"], recorded["scope"])
    assert len(runs) == c["bucket_runs"] == 2
    assert scope_us_per_dispatch.under_scope_us(ops, runs) == pytest.approx(c["rounds_us_per_dispatch"], abs=1e-6)
    assert 700 < c["rounds_us_per_dispatch"] < 800  # of a ~1,085 us kernel
    decode = scope_us_per_dispatch.scoped(c["device_events"], "wire_decode")
    assert 0 < scope_us_per_dispatch.under_scope_us(decode, runs) < c["rounds_us_per_dispatch"]
    none = scope_us_per_dispatch.scoped(c["device_events"], "round")
    assert scope_us_per_dispatch.under_scope_us(none, runs) is None  # a prefix is not the scope


def test_executables_from_before_the_scope_are_read_by_the_while_loop(recorded):
    """The compile cache's key leaves metadata out: a parent's executable,
    whose op_names read `jit(f)/vmap()/while/body/...`, serves the change too."""
    c = recorded["cuts"][0]
    stale = {line: [[n, op.replace("vmap(rounds)", "vmap()").replace("/rounds/", "/"), lo, hi]
                    for n, op, lo, hi in rows] for line, rows in c["device_events"].items()}
    assert scope_us_per_dispatch.scoped(stale, "rounds") == []
    spec = json.load(open(os.path.join(REPO, "chipbench", "layer_metrics", "kernel.rounds_us_per_dispatch.json")))
    ops = scope_us_per_dispatch.ops_where(stale, lambda op_name: spec["params"]["unscoped"] in op_name)
    runs = scope_us_per_dispatch.bucket_runs(stale, recorded["exclude"])
    # (all but the loop's zero-filled carry, 0.05 us, which the scope holds and the loop does not)
    assert scope_us_per_dispatch.under_scope_us(ops, runs) == pytest.approx(c["rounds_us_per_dispatch"], rel=1e-3)


def test_nested_operations_under_a_scope_count_once():
    ops = [(0, 1000), (100, 200), (300, 400), (5000, 5100)]  # a loop, two of its body's, one outside any run
    assert scope_us_per_dispatch.under_scope_us(ops, [(0, 2000), (3000, 4000)]) == pytest.approx(0.5)


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint((number << 3) | 2) + _varint(len(value)) + value
    return out


def test_the_wire_reader_finds_op_names_in_the_event_metadata(tmp_path):
    """An XSpace written field by field: a host plane to skip, and a device
    plane whose operations keep their `op_name` as the `tf_op` stat of the
    event metadata, once as a string and once as a reference to a stat name."""
    stat_meta = lambda i, name: _msg((5, _msg((1, i), (2, _msg((1, i), (2, name))))))  # noqa: E731
    event_meta = lambda i, name, stat: _msg((4, _msg((1, i), (2, _msg((1, i), (2, name), (5, stat))))))  # noqa: E731
    line = lambda name, t0, events: _msg((3, _msg((2, name), (3, t0), *[(4, e) for e in events])))  # noqa: E731
    device = (
        _msg((2, "/device:TPU:0"))
        + stat_meta(7, "tf_op") + stat_meta(9, "jit(f)/vmap(rounds)/while/body/add:")
        + event_meta(1, "%fusion.1 = s32[8] fusion(...)", _msg((1, 7), (5, "jit(f)/vmap(wire_decode)/gather:")))
        + event_meta(2, "%add.2 = s32[8] add(...)", _msg((1, 7), (7, 9)))
        + event_meta(3, "jit_f(123)", b"")
        + line("XLA Ops", 1000, [_msg((1, 1), (2, 5_000), (3, 2_000_000)), _msg((1, 2), (2, 3_000_000), (3, 1_000_000)),
                                 _msg((1, 2), (2, 9_000_000), (3, 0))])
        + line("XLA Modules", 1000, [_msg((1, 3), (2, 0), (3, 5_000_000))])
        + line("Steps", 1000, [_msg((1, 3), (2, 0), (3, 5_000_000))])
    )
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, _msg((2, "/host:CPU"))), (1, device)))
    events = scope_us_per_dispatch.device_events(scope_us_per_dispatch.first_device_plane(str(path)))
    assert set(events) == {"XLA Ops", "XLA Modules"}
    assert events["XLA Modules"] == [("jit_f(123)", "", 1000.0, 6000.0)]
    assert [(op, lo, hi) for _, op, lo, hi in events["XLA Ops"]] == [
        ("jit(f)/vmap(wire_decode)/gather:", 1005.0, 3005.0),
        ("jit(f)/vmap(rounds)/while/body/add:", 4000.0, 5000.0)]  # the zero-length event is dropped
    ctx = {"trace": {"xplane": str(path)}}
    assert scope_us_per_dispatch.read(ctx, {"scope": "rounds", "exclude": ["sync_body"]}) == pytest.approx(1.0)
    assert scope_us_per_dispatch.read(ctx, {"scope": "commit", "exclude": []}) is None
    host_only = tmp_path / "h.xplane.pb"
    host_only.write_bytes(_msg((1, _msg((2, "/host:CPU")))))
    assert scope_us_per_dispatch.read({"trace": {"xplane": str(host_only)}}, {"scope": "rounds"}) is None


# ---------------------------------------------------------------------
# unattributed_ms, startup_s, and phase_ms_per over the new phases
# ---------------------------------------------------------------------
def _snap(phases, waterfall=None, startup=None):
    latency = {"phases": {k: {"count": c, "sum_ms": s} for k, (c, s) in phases.items()}}
    if waterfall is not None:
        latency["waterfall"] = [{"phase": p, "depth": d} for p, d in waterfall]
    device = {} if startup is None else {"startup": startup}
    return {"latency": latency, "device": device}


WATERFALL = [("pump.take", 0), ("ingress.parse", 0), ("dispatch.prepare", 0), ("dispatch.plan_wait", 1),
             ("dispatch.fetch", 0), ("ingress.total", 0)]


def test_unattributed_is_the_round_trip_less_the_top_level_phases():
    before = _snap({"ingress.parse": (10, 5.0), "dispatch.prepare": (10, 50.0), "dispatch.plan_wait": (10, 20.0),
                    "dispatch.fetch": (10, 10.0), "pump.take": (10, 900.0), "ingress.total": (10, 100.0)})
    after = _snap({"ingress.parse": (110, 55.0), "dispatch.prepare": (110, 650.0), "dispatch.plan_wait": (110, 320.0),
                   "dispatch.fetch": (110, 110.0), "pump.take": (110, 9900.0), "ingress.total": (110, 1500.0)},
                  waterfall=WATERFALL)
    ctx = {"before": before, "after": after, "requests": 100, "window_latencies_ms": [10.0, 12.0, 14.0]}
    params = {"off_request": ["pump.take", "ingress.total"]}
    # 12 ms mean; parse 0.5 + prepare 6.0 (its plan_wait 3.0 is inside it) + fetch 1.0
    assert unattributed_ms.read(ctx, params) == pytest.approx(12.0 - 7.5)
    after["latency"].pop("waterfall")  # a program from before the phases
    assert unattributed_ms.read(ctx, params) is None


def test_startup_seconds_sum_the_programs_loads_compiles_and_first_runs():
    startup = {"parts_s": {"warmup": 30.0}, "listening_s": 41.0, "programs": {
        "mesh:dispatch:solo:narrow": {"trace_s": 0.5, "lower_s": 0.2, "cache_load_s": 1.5, "compile_s": 0.25, "run_s": 0.125},
        "mesh:global_sync": {"trace_s": 0.1, "lower_s": 0.1, "cache_load_s": 0.0, "compile_s": 8.0, "run_s": 0.5}}}
    params = {"fields": ["cache_load_s", "compile_s", "run_s"]}
    assert startup_s.read({"after": _snap({}, startup=startup)}, params) == pytest.approx(10.375)
    assert startup_s.read({"after": _snap({})}, params) is None


def test_lock_wait_metrics_divide_by_the_enclosing_phases_count():
    before = _snap({"dispatch.plan_wait": (5, 1.0), "dispatch.prepare": (5, 9.0)})
    after = _snap({"dispatch.plan_wait": (25, 41.0), "dispatch.prepare": (25, 109.0)})
    spec = json.load(open(os.path.join(REPO, "chipbench", "layer_metrics", "plan.lock_wait_ms.json")))
    assert spec["reader"] == "phase_ms_seen"
    assert phase_ms_seen.read({"before": before, "after": after}, spec["params"]) == pytest.approx(2.0)
    # A program without the phase: nothing, not a 0 per counted prepare.
    bare = _snap({"dispatch.prepare": (25, 109.0)})
    assert phase_ms_seen.read({"before": _snap({"dispatch.prepare": (5, 9.0)}), "after": bare}, spec["params"]) is None
    stall = json.load(open(os.path.join(REPO, "chipbench", "layer_metrics", "launch.sync_stall_ms.json")))
    assert phase_ms_seen.read({"before": before, "after": after}, stall["params"]) is None  # no tick ran
