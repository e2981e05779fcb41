"""A launch uploads nothing: the round count and the clock ride the wire's
header (`buckets.set_wire_header` on the host, `buckets.wire_header` inside
the jitted program), so the dispatch programs take `(state, wire...)` and a
launch hands the runtime device arrays alone.

Held here: what the host writes the device reads, whole (a clock above
2**31, a low word whose bit 31 is set); the rounds the planner counted run
and the times the program computes are the sequential oracle's, on either
wire, at 1, 255 and 300 rounds, on one device and on four shards; and JAX
itself refuses a host->device transfer inside `_launch_group` for every
wire, the fused program among them."""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu import native
from gubernator_tpu.ops import buckets
from gubernator_tpu.parallel import mesh as mesh_mod
from gubernator_tpu.types import Algorithm, RateLimitRequest

from . import oracle as orc
from .conftest import _store_over

SEED = 37
NAME = "wh"
DURATION = 60_000
# Two clocks as a daemon reads them (ms since the epoch, far above 2**31):
# the low word of the first has bit 31 set, of the second not.
NOW_HIGH_BIT = 1_790_000_000_000
NOW_LOW_BIT = NOW_HIGH_BIT + 2**31
assert (NOW_HIGH_BIT & 0xFFFFFFFF) >> 31 == 1 and (NOW_LOW_BIT & 0xFFFFFFFF) >> 31 == 0

needs_native = pytest.mark.skipif(
    not native.available(), reason="the columnar path needs the native host runtime")


def _sharding(shards: int) -> NamedSharding:
    return NamedSharding(Mesh(np.array(jax.devices()[:shards]), ("shard",)), P("shard"))


@pytest.mark.parametrize("now_ms", [
    0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32, NOW_HIGH_BIT, NOW_LOW_BIT, 2**63 - 1],
    ids=lambda v: f"now{v}")
@pytest.mark.parametrize("shards", [1, 4])
def test_what_the_host_writes_in_the_header_the_program_reads(shards, now_ms):
    """Either wire ends in the same four words; the program reads one
    unbatched pair whatever the shard count, and touches nothing else."""
    n_rounds = {0: 0, 1: 1, 2**31 - 1: 255, 2**31: 300}.get(now_ms, 2**31 - 1)
    width = 3 * 64 + buckets.DICT_WIRE_TABLE_WORDS + buckets.WIRE_HEADER_WORDS
    rng = np.random.default_rng([SEED, shards])
    wire = rng.integers(-2**31, 2**31, (shards, width)).astype(np.int32)
    before = wire.copy()
    buckets.set_wire_header(wire, n_rounds, now_ms)
    body = width - buckets.WIRE_HEADER_WORDS
    assert (wire[:, :body] == before[:, :body]).all()
    assert (wire[:, -1] == before[:, -1]).all()  # the spare word: the packers zero it
    assert (wire[:, body:-1] == wire[0, body:-1]).all()
    got_rounds, got_now = jax.jit(buckets.wire_header)(jax.device_put(wire, _sharding(shards)))
    assert got_rounds.shape == got_now.shape == ()
    assert (got_rounds.dtype, got_now.dtype) == (jnp.int32, jnp.int64)
    assert (int(got_rounds), int(got_now)) == (n_rounds, now_ms)


@pytest.mark.parametrize("body", ["_rounds_packed_mesh", "_rounds_lanes_mesh", "fused2"])
def test_no_device_waits_for_another_inside_a_dispatch(body):
    """On four shards each device reads its own row's header: the compiled
    program holds no collective (read as "shard 0's word" under a plain
    `jit`, the partitioner makes it an all-reduce)."""
    mesh = mesh_mod.make_mesh(jax.devices()[:4])
    rows = lambda *shape, dtype=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        (4, *shape), dtype, sharding=_sharding(4))
    state = jax.tree.map(lambda a: rows(*a.shape, dtype=a.dtype),
                         jax.eval_shape(lambda: buckets.init_state(256)))
    header = buckets.WIRE_HEADER_WORDS
    if body == "fused2":
        wire = rows(3 * 64 + buckets.DICT_WIRE_TABLE_WORDS + header)
        lowered = mesh_mod._mesh_fused_packed_jit(mesh, 2, False, donate_wires=False).lower(state, wire, wire)
    else:
        words = 3 * 64 + buckets.DICT_WIRE_TABLE_WORDS if "packed" in body else buckets.LANE_WIRE_WORDS * 64
        lowered = mesh_mod._dispatch_jit(mesh, getattr(mesh_mod, body)).lower(state, rows(words + header))
    text = lowered.compile().as_text()
    assert " while(" in text  # the rounds loop is there to be looked at
    for op in ("all-reduce", "all-gather", "collective-permute", "all-to-all"):
        assert f" {op}(" not in text and f" {op}-start(" not in text, op


@pytest.mark.parametrize("body", [
    "_rounds_packed_mesh", "_rounds_packed_wide_mesh", "_rounds_lanes_mesh",
    "_rounds_lanes_wide_mesh", "fused2-narrow", "fused2-wide"])
@pytest.mark.parametrize("shards", [1, 4])
def test_no_64_bit_array_leaves_the_device_on_the_dispatch_path(shards, body):
    """What a launch hands the host to read back is 32-bit words at either
    answer width, on either wire, solo and fused: the narrow answer's
    [S, 4, P] deltas, the wide answer's [S, 8, P] lo/hi planes.  (A TPU
    keeps an s64 array as two u32 halves and its runtime rebuilds the
    64-bit array on the host at every fetch.)"""
    mesh = mesh_mod.make_mesh(jax.devices()[:shards])
    rows = lambda *shape, dtype=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        (shards, *shape), dtype, sharding=_sharding(shards))
    state = jax.tree.map(lambda a: rows(*a.shape, dtype=a.dtype),
                         jax.eval_shape(lambda: buckets.init_state(256)))
    header, lanes, wide = buckets.WIRE_HEADER_WORDS, 64, "wide" in body
    if body.startswith("fused2"):
        wire = rows(3 * lanes + buckets.DICT_WIRE_TABLE_WORDS + header)
        program = mesh_mod._mesh_fused_packed_jit(mesh, 2, wide, donate_wires=False)
        _, answer = jax.eval_shape(program, state, wire, wire)
        lead = (2, shards)
    else:
        per_lane = buckets.LANE_WIRE_WORDS_WIDE if wide else buckets.LANE_WIRE_WORDS
        words = 3 * lanes + buckets.DICT_WIRE_TABLE_WORDS if "packed" in body else per_lane * lanes
        program = mesh_mod._dispatch_jit(mesh, getattr(mesh_mod, body))
        _, answer = jax.eval_shape(program, state, rows(words + header))
        lead = (shards,)
    assert answer.dtype == jnp.int32
    assert answer.shape == (*lead, buckets.WIDE_ANSWER_ROWS if wide else 4, lanes)


def test_a_packed_wire_nobody_stamped_runs_no_round():
    """The packers leave the header zero, and zero rounds answer nothing: a
    caller that forgets `set_wire_header` gets an inert wire, not a stale
    clock."""
    z = np.zeros((1, 64), np.int32)
    table = tuple(np.zeros(buckets.DICT_TABLE_ROWS, np.int64) for _ in range(7))
    dict_wire = buckets.pack_dict_wire(z, z, z, z.astype(np.uint8), z, z, table)
    lane_wire = buckets.pack_lane_wire(
        z, z, z, z, z, np.arange(64), tuple(np.zeros(64, np.int64) for _ in range(7)), wide=False)
    for wire, words in ((dict_wire, 3 * 64 + buckets.DICT_WIRE_TABLE_WORDS),
                        (lane_wire, buckets.LANE_WIRE_WORDS * 64)):
        assert wire.shape == (1, words + buckets.WIRE_HEADER_WORDS)
        assert not wire[:, words:].any()
    assert buckets.dict_wire_lanes(dict_wire.shape[1]) == 64


def _frame(rng, lanes: int, rounds: int, configurations: int, big: bool):
    """One frame's request columns: `rounds - 1` requests of one hot key
    whose hits alternate (so no two neighbours are a uniform group, and
    each takes a round of its own), the rest distinct keys, each met
    once.  `configurations` distinct limits; `big` puts them past int32
    (the wide answer)."""
    hot = rounds - 1
    uk = np.concatenate([np.zeros(hot, np.int64), 1 + np.arange(lanes - hot)])
    hits = np.concatenate([1 + np.arange(hot) % 2, rng.integers(0, 4, lanes - hot)])
    order = rng.permutation(lanes)
    uk, hits = uk[order], hits[order].astype(np.int64)
    limit = 300 + uk % configurations + (2**40 if big else 0)
    algo = (uk % 2).astype(np.int32)
    return uk, algo, hits, limit


def _apply(store, frame, now_ms, force_wire=None):
    uk, algo, hits, limit = frame
    n = len(uk)
    return store.apply_columns(
        [f"{NAME}_{k}" for k in uk.tolist()], algo, np.zeros(n, np.int32), hits, limit,
        np.full(n, DURATION, np.int64), now_ms, force_wire=force_wire)


def _oracle(cache, frame, now_ms):
    uk, algo, hits, limit = frame
    rows = np.empty((len(uk), 4), np.int64)
    for lane, (k, a, h, lim) in enumerate(zip(uk.tolist(), algo.tolist(), hits.tolist(), limit.tolist())):
        r = orc.apply(cache, RateLimitRequest(
            name=NAME, unique_key=str(k), hits=h, limit=lim, duration=DURATION,
            algorithm=Algorithm(a)), now_ms)
        rows[lane] = (int(r.status), r.limit, r.remaining, r.reset_time)
    return rows


def _assert_answers(got, want):
    for col, name in enumerate(("status", "limit", "remaining", "reset_time")):
        assert (np.asarray(got[name]) == want[:, col]).all(), name


@needs_native
@pytest.mark.parametrize("now_ms", [NOW_HIGH_BIT, NOW_LOW_BIT], ids=["bit31-set", "bit31-clear"])
@pytest.mark.parametrize("wire,rounds", [
    ("dictionary", 1), ("dictionary", 255), ("lanes", 300), ("lanes", 1)])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_rounds_run_and_the_times_are_the_oracles(shards, wire, rounds, now_ms):
    """Pack -> program -> answer: the planner's round count reaches the
    loop (the hot key's last request sees every one before it) and the
    clock reaches the arithmetic (reset times, the leak) whole.  255 is the
    dictionary wire's last round count, 300 takes the per-lane wire by
    itself; a second frame later in time meets the first one's buckets."""
    store = _store_over(shards, 2048)
    staged = []
    real_stage = store._stage_columns

    def stage(prep):
        staged.append((prep.n_rounds, real_stage(prep)))
        return staged[-1][1]

    store._stage_columns = stage
    rng = np.random.default_rng([SEED, rounds])
    force = "narrow" if (wire, rounds) == ("lanes", 1) else None
    cache = orc.OracleCache()
    for step, later in enumerate((0, 7_001)):
        frame = _frame(rng, 512, rounds, 16, big=False)
        got = _apply(store, frame, now_ms + later, force)
        want = _oracle(cache, frame, now_ms + later)
        _assert_answers(got, want)
        n_rounds, st = staged[step]
        assert n_rounds == rounds and st.lane_wire == (wire == "lanes")
    assert (want[:, 3] > now_ms).any() and (want[:, 0] == 1).any() == (rounds > 1)


def test_the_guard_refuses_a_scalar_argument_on_this_backend():
    """What the next test leans on: under the guard a jitted call with a
    Python or numpy argument raises here too, not on a TPU alone."""
    add = jax.jit(lambda a, b: a + b)
    x = jnp.ones(4, jnp.int32)
    add(x, 3)
    for scalar in (3, np.int64(3), np.ones(4, np.int32)):
        with jax.transfer_guard_host_to_device("disallow"), pytest.raises(Exception, match="[Dd]isallow"):
            add(x, scalar)
    with jax.transfer_guard_host_to_device("disallow"):
        add(x, x)


def _fused_pair(store, frames, now_ms):
    """Send two frames through the pipeline so that they launch as ONE fused
    program: the older batch holds its turn at the end of its stage until
    the younger one is parked at the launch gate."""
    first = store._next_ticket
    real_stage = store._stage_columns
    older = []

    def stage(prep):
        st = real_stage(prep)
        if threading.current_thread() in older:
            deadline = time.monotonic() + 60
            while first + 1 not in store._launch_gate:
                assert time.monotonic() < deadline, "the younger batch never reached the gate"
                time.sleep(0.001)
        return st

    def send(frame, is_older):
        if is_older:
            older.append(threading.current_thread())
        return _apply(store, frame, now_ms)

    store._stage_columns = stage
    with ThreadPoolExecutor(2) as pool:
        a = pool.submit(send, frames[0], True)
        deadline = time.monotonic() + 60
        while store._next_ticket == first:
            assert time.monotonic() < deadline and not a.done(), "the older batch took no ticket"
            time.sleep(0.001)
        b = pool.submit(send, frames[1], False)
        return a.result(timeout=120), b.result(timeout=120)


@needs_native
@pytest.mark.parametrize("wire", [
    "dictionary-narrow", "dictionary-wide", "lanes-narrow", "lanes-wide", "fused2"])
@pytest.mark.parametrize("shards", [1, 4])
def test_a_launch_makes_no_host_to_device_transfer(shards, wire):
    """`_launch_group` under `jax.transfer_guard_host_to_device("disallow")`:
    a Python or numpy argument to the dispatch program would raise.  The
    first launch of each program is the guarded one, so tracing and
    compiling upload nothing either."""
    store = _store_over(shards, 2048)
    groups = []
    real_launch = store._launch_group

    def guarded(group):
        groups.append([st for st, _ in group])
        with jax.transfer_guard_host_to_device("disallow"):
            return real_launch(group)

    store._launch_group = guarded
    rng = np.random.default_rng([SEED, shards, len(wire)])
    lanes = wire.startswith("lanes")
    big = wire.endswith("wide")
    frames = [_frame(rng, 512, 3, 300 if lanes else 16, big) for _ in range(2)]
    cache = orc.OracleCache()
    if wire == "fused2":
        got = _fused_pair(store, frames, NOW_HIGH_BIT)
        assert [len(g) for g in groups] == [2]
    else:
        got = [_apply(store, f, NOW_HIGH_BIT) for f in frames]
        assert [len(g) for g in groups] == [1, 1]
    for g, frame in zip(got, frames):
        _assert_answers(g, _oracle(cache, frame, NOW_HIGH_BIT))
    for st in (st for g in groups for st in g):
        assert (st.lane_wire, st.wide) == (lanes, big)
    assert store.device_dispatches == len(groups)
