"""The per-lane wire as ONE buffer (`buckets.pack_lane_wire` on the host,
`buckets.unpack_lane_wire` inside the jitted program): what the host packs
the device unpacks bit for bit, on one device and on four shards, at the
smallest pad bucket and at the cell's, for the i32 and the i64 answer; the
program behind it answers what the rounds kernel answers over the same
columns handed to it directly; the wide answer of either wire, and of a
fused group of two, leaves the device as ONE 32-bit array of lo/hi planes
that decodes bit for bit to the kernel's i64 answer; and a staged batch
makes the transfer calls `_Staged.uploads` says it made, counted from JAX
and not by hand."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu import native
from gubernator_tpu.ops import buckets
from gubernator_tpu.parallel import mesh as mesh_mod
from gubernator_tpu.types import Behavior

from .conftest import _store_over

SEED = 34
NOW = 1_790_000_000_000
I32_MAX = 2**31 - 1
VALUE_NAMES = ("algorithm", "behavior", "hits", "limit", "duration", "greg_expire", "greg_duration")


def _plan(shards: int, pad: int, wide: bool):
    """A plan's arrays as NativeMeshPlanner fills them ([S, P]; slot -1
    where no request lies) and the seven value columns in REQUEST order,
    with the values this wire exists for among the lanes."""
    rng = np.random.default_rng([SEED, shards, pad, wide])
    n = (shards * pad * 3) // 4
    pos = rng.permutation(shards * pad)[:n].astype(np.int64)
    slot = np.full((shards, pad), -1, np.int32)
    slot.reshape(-1)[pos] = rng.integers(0, 1 << 20, n)
    exists = np.zeros((shards, pad), np.uint8)
    exists.reshape(-1)[pos] = rng.integers(0, 2, n)
    write = np.zeros((shards, pad), np.uint8)
    write.reshape(-1)[pos] = rng.integers(0, 2, n)
    occ = np.zeros((shards, pad), np.int32)
    occ.reshape(-1)[pos] = rng.integers(0, 1000, n)
    rid = np.zeros((shards, pad), np.int32)
    rid.reshape(-1)[pos] = rng.integers(0, 256, n)
    occ.reshape(-1)[pos[0]] = 70_000  # past the dictionary wire's u16
    rid.reshape(-1)[pos[1]] = 300  # past its u8
    top = 2**62 if wide else I32_MAX
    values = {
        "algorithm": rng.integers(0, 2, n).astype(np.int32),
        "behavior": rng.integers(0, 64, n).astype(np.int32),
        "hits": rng.integers(0, top, n),
        "limit": rng.integers(0, top, n),
        "duration": rng.integers(0, top, n),
        "greg_expire": rng.integers(0, top, n),
        "greg_duration": rng.integers(0, top, n),
    }
    values["limit"][2] = I32_MAX
    values["greg_expire"][3] = -5  # a negative delta rides as it is
    values["behavior"][4] = -(2**31)  # a whole word, not a few bits of one
    if wide:
        values["hits"][5] = 2**63 - 1
        values["duration"][6] = -(2**63)
        values["limit"][7] = 2**32  # lo word all zero
        values["greg_duration"][8] = 2**31  # lo word's sign bit alone
    return dict(slot=slot, exists=exists, write=write, occ=occ, rid=rid, pos=pos, values=values)


def _sharding(shards: int) -> NamedSharding:
    return NamedSharding(Mesh(np.array(jax.devices()[:shards]), ("shard",)), P("shard"))


def _placed(shape, pos, col, dtype):
    """A request-order column where the plan puts it: [S, P], zero elsewhere."""
    a = np.zeros(shape, dtype)
    a.reshape(-1)[pos] = col
    return a


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("pad", [64, 4096])
@pytest.mark.parametrize("shards", [1, 4])
def test_what_the_host_packs_the_device_unpacks_bit_for_bit(shards, pad, wide):
    plan = _plan(shards, pad, wide)
    wire = buckets.pack_lane_wire(
        plan["slot"], plan["exists"], plan["write"], plan["occ"], plan["rid"], plan["pos"],
        tuple(plan["values"][k] for k in VALUE_NAMES), wide=wide)
    words = buckets.LANE_WIRE_WORDS_WIDE if wide else buckets.LANE_WIRE_WORDS
    assert (words, buckets.LANE_WIRE_WORDS) == (16 if wide else 11, 11)
    assert wire.dtype == np.int32
    assert wire.shape == (shards, words * pad + buckets.WIRE_HEADER_WORDS)
    assert not wire[:, words * pad:].any()  # the header: the stage's to fill

    sharding = _sharding(shards)
    unpack = jax.jit(jax.vmap(lambda w: buckets.unpack_lane_wire(w, wide)))
    req, rid = unpack(jax.device_put(wire, sharding))
    assert type(req) is (buckets.RequestBatch if wide else buckets.RequestBatch32)

    vdt = np.int64 if wide else np.int32
    place = lambda k, dtype: _placed((shards, pad), plan["pos"], plan["values"][k], dtype)  # noqa: E731
    want = {
        "slot": plan["slot"], "exists": plan["exists"].astype(bool),
        "write": plan["write"].astype(bool), "occ": plan["occ"],
        "algorithm": place("algorithm", np.int32), "behavior": place("behavior", np.int32),
        **{k: place(k, vdt) for k in VALUE_NAMES[2:]},
    }
    if not wide:
        want["greg_expire_delta"] = want.pop("greg_expire")
    assert set(want) == set(req._fields)
    for name, col in want.items():
        got = np.asarray(getattr(req, name))
        assert got.dtype == col.dtype and got.shape == col.shape, name
        assert (got == col).all(), name
    assert (np.asarray(rid) == plan["rid"]).all() and np.asarray(rid).dtype == np.int32
    flat = lambda a: a.reshape(-1)[plan["pos"]]  # noqa: E731
    assert flat(want["occ"])[0] == 70_000 and flat(plan["rid"])[1] == 300
    assert flat(want["limit"])[2] == I32_MAX
    assert flat(np.asarray(getattr(req, "greg_expire" if wide else "greg_expire_delta")))[3] == -5


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_program_behind_the_buffer_answers_as_the_kernel_over_the_columns(shards, wide):
    """`_rounds_lanes_mesh` (pack, one buffer, unpack inside the jit) against
    `apply_rounds32` / `apply_rounds` handed the same columns as device
    arrays, a column a value: the same answers and the same table, over
    three rounds of duplicates."""
    pad, slots = 64, 256
    rng = np.random.default_rng([SEED, shards, wide, 1])
    n = shards * pad - 7
    pos = np.sort(rng.permutation(shards * pad)[:n]).astype(np.int64)
    place = lambda col, dtype: _placed((shards, pad), pos, col, dtype)  # noqa: E731
    # Slots distinct within a round: round r of a shard owns slots r, r+3, ...
    rid_req = rng.integers(0, 3, n)
    slot_req = np.empty(n, np.int64)
    for s in range(shards):
        for r in range(3):
            mine = np.flatnonzero((pos // pad == s) & (rid_req == r))
            slot_req[mine] = r + 3 * rng.permutation(slots // 3)[: len(mine)]
    slot = np.full((shards, pad), -1, np.int32)
    slot.reshape(-1)[pos] = slot_req
    rid = place(rid_req, np.int32)
    zeros8 = np.zeros((shards, pad), np.uint8)
    write = place(np.ones(n), np.uint8)
    occ = np.zeros((shards, pad), np.int32)
    big = 2**40 if wide else 1
    values = (
        rng.integers(0, 2, n).astype(np.int32), np.zeros(n, np.int32),
        rng.integers(0, 5, n), rng.integers(1, 50, n) * big,
        np.full(n, 60_000, np.int64), np.zeros(n, np.int64), np.zeros(n, np.int64),
    )
    wire = buckets.pack_lane_wire(slot, zeros8, write, occ, rid, pos, values, wide=wide)
    buckets.set_wire_header(wire, 3, NOW)

    vdt = np.int64 if wide else np.int32
    make = buckets.RequestBatch if wide else buckets.RequestBatch32
    req = make(
        slot, zeros8.astype(bool), place(values[0], np.int32), place(values[1], np.int32),
        *(place(v, vdt) for v in values[2:]), occ=occ, write=write.astype(bool))
    rounds = buckets.apply_rounds if wide else buckets.apply_rounds32
    direct = jax.jit(jax.vmap(
        lambda st, rq, rd: rounds(st, rq, rd, 3, NOW, cold_cond=False)))

    sharding = _sharding(shards)
    put = lambda tree: jax.device_put(tree, sharding)  # noqa: E731
    fresh = lambda: put(jax.vmap(lambda _: buckets.init_state(slots))(jnp.arange(shards)))  # noqa: E731
    want_state, want = direct(fresh(), put(req), put(rid))
    program = mesh_mod._dispatch_jit(
        sharding.mesh, mesh_mod._rounds_lanes_wide_mesh if wide else mesh_mod._rounds_lanes_mesh)
    got_state, got = program(fresh(), put(wire))
    assert want.dtype == vdt and want.shape == (shards, 4, pad)
    # Either answer crosses to the host as 32-bit words: the wide one as
    # eight planes (the four rows' lo words, then their hi words).
    assert got.dtype == np.int32 and got.shape == (shards, 8 if wide else 4, pad)
    got = buckets.compose_wide_answer(np.asarray(got)) if wide else np.asarray(got)
    assert (got == np.asarray(want)).all()
    assert got[:, 1].max() > (2**32 if wide else 0)  # `remaining`: real answers
    for a, b in zip(jax.tree.leaves(got_state), jax.tree.leaves(want_state)):
        assert (np.asarray(a) == np.asarray(b)).all()


# A clock near 1.8e12 whose low word puts bit 31 of `now + a month` and of
# `now + a year` at one: a decode that shifts the lo word signed shows.
NOW_WIDE = (419 << 32) + 1_000_000_000
MONTH, YEAR = 31 * 86_400_000, 365 * 86_400_000
WIDE_LIMITS = (2**31 - 1, 2**31, 2**32, 2**53 + 1, 2**62)
RESET_REMAINING = int(Behavior.RESET_REMAINING)


def _wide_lanes():
    """Request columns whose answers fill both planes: every limit of
    WIDE_LIMITS under a month's and a year's duration, met with no hit
    (`remaining` is the limit itself) and with one; an OVER_LIMIT lane;
    and a key met twice, whose second request removes its bucket."""
    lanes = [(f"wl_{lim}_{dur}_{hits}", 0, 0, hits, lim, dur)
             for lim in WIDE_LIMITS for dur in (MONTH, YEAR) for hits in (0, 1)]
    lanes.append(("wl_leaky", 1, 0, 1, 2**32, MONTH))
    lanes.append(("wl_over", 0, 0, 2**32 + 5, 2**32, YEAR))
    lanes.append(("wl_gone", 0, 0, 1, 2**31, MONTH))
    lanes.append(("wl_gone", 0, RESET_REMAINING, 0, 2**31, MONTH))
    keys = [lane[0] for lane in lanes]
    algo, behavior = (np.array([lane[k] for lane in lanes], np.int32) for k in (1, 2))
    hits, limit, duration = (np.array([lane[k] for lane in lanes], np.int64) for k in (3, 4, 5))
    return keys, algo, behavior, hits, limit, duration


@pytest.mark.skipif(not native.available(), reason="the columnar path needs the native host runtime")
@pytest.mark.parametrize("wire", ["dictionary", "lanes", "fused2"])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_wide_answer_leaves_the_device_as_lo_hi_planes(shards, wire):
    """The program a wide batch launches (the dictionary wire's, the
    per-lane wire's, a fused group of two) answers i32[S, 8, P] /
    [2, S, 8, P], which decodes bit for bit to the i64[S, 4, P] of
    `buckets.apply_rounds` over the same plan and columns: absolute times,
    exact `remaining`, nothing clipped or turned into a delta."""
    from gubernator_tpu.models.shard import make_columns

    store = _store_over(shards, 2048)
    keys, algo, behavior, hits, limit, duration = _wide_lanes()
    n = len(keys)
    cols = make_columns(algo, behavior, hits, limit, duration, n)
    prep = store._prepare_columns(keys, cols, NOW_WIDE, "wide" if wire == "lanes" else None)
    staged = store._stage_columns(prep)
    assert staged.wide and staged.lane_wire == (wire == "lanes") and prep.n_rounds >= 2
    mp, pad = prep.mp, prep.padded
    place = lambda col: _placed((shards, pad), prep.pos, col, col.dtype)  # noqa: E731
    req = buckets.RequestBatch(
        mp.slot, mp.exists.astype(bool), place(algo), place(behavior), place(hits), place(limit),
        place(duration), np.zeros((shards, pad), np.int64), np.zeros((shards, pad), np.int64),
        occ=mp.occ, write=mp.write.astype(bool))
    direct = jax.jit(jax.vmap(
        lambda st, rq, rd: buckets.apply_rounds(st, rq, rd, prep.n_rounds, NOW_WIDE, cold_cond=False)))
    sharding = _sharding(shards)
    put = lambda tree: jax.device_put(tree, sharding)  # noqa: E731
    ref_state = put(jax.tree.map(np.asarray, store.state))
    k = 2 if wire == "fused2" else 1
    want = []
    for _ in range(k):
        ref_state, packed = direct(ref_state, put(req), put(mp.rid))
        assert packed.dtype == np.int64 and packed.shape == (shards, 4, pad)
        want.append(np.asarray(packed))

    if wire == "fused2":
        assert staged.fuse_key is not None
        _, got = store._fused_launch_fn(2, True)(store.state, staged.wire_dev, staged.wire_dev)
        assert got.shape == (2, shards, 8, pad)
    else:
        _, got = staged.solo(store.state)
        assert got.shape == (shards, 8, pad)
    assert got.dtype == np.int32
    planes = np.asarray(got).reshape(k, shards, 8, pad)
    for i in range(k):
        assert (buckets.compose_wide_answer(planes[i]) == want[i]).all(), i
        assert (buckets.split_wide_answer(want[i]) == planes[i]).all(), i

    # The values are the ones this container exists for, lane by lane.
    lane = lambda row: want[0][:, row].reshape(-1)[prep.pos]  # noqa: E731
    status, remaining, reset, expire = lane(0) & 1, lane(1), lane(2), lane(3)
    tokens = 4 * len(WIDE_LIMITS)
    assert (remaining[:tokens] == limit[:tokens] - hits[:tokens]).all()
    assert set(WIDE_LIMITS) <= set(remaining.tolist())
    assert (reset[:tokens] == NOW_WIDE + duration[:tokens]).all()
    assert status[keys.index("wl_over")] == 1 and remaining[keys.index("wl_over")] == 2**32
    assert (lane(0)[-1] >> 1) & 1 and expire[-1] == 0  # the removed lane
    lo, hi = planes[0][:, :4], planes[0][:, 4:]
    assert (lo[:, 2] < 0).any() and (hi[:, 2] > 0).any()  # `reset_time`: lo's bit 31, a hi word
    assert (hi[:, 1] == 2**30).any() and (lo[:, 1] == -(2**31)).any()  # `remaining` 2**62, 2**31
    assert not hi[:, 0].any()


def _frame(lanes: int, configurations: int):
    keys = [f"lw_{i}" for i in range(lanes)]
    limit = 100 + np.arange(lanes, dtype=np.int64) % configurations
    return keys, limit


@pytest.mark.skipif(not native.available(), reason="the columnar path needs the native host runtime")
@pytest.mark.parametrize("wire", ["narrow", "wide", "too-many-rows", "dictionary"])
@pytest.mark.parametrize("shards", [1, 4])
def test_a_staged_batch_makes_the_transfer_calls_it_counts(shards, wire, monkeypatch):
    """`_Staged.uploads` feeds `wire.uploads_per_dispatch`; it is held here to
    the calls the stage really makes: every `jax.device_put` and every
    `jnp.asarray` between the plan and the launch."""
    store = _store_over(shards, 2048)
    lanes = 512
    keys, limit = _frame(lanes, 16 if wire == "dictionary" else 300)
    force = wire if wire in ("narrow", "wide") else None
    staged, calls = [], []
    real_stage, real_put, real_asarray = store._stage_columns, jax.device_put, jnp.asarray

    def counting(name, real):
        def call(*a, **kw):
            calls.append(name)
            return real(*a, **kw)
        return call

    def stage(prep):
        with monkeypatch.context() as m:
            m.setattr(jax, "device_put", counting("device_put", real_put))
            m.setattr(jnp, "asarray", counting("asarray", real_asarray))
            staged.append(real_stage(prep))
        return staged[-1]

    monkeypatch.setattr(store, "_stage_columns", stage)
    got = store.apply_columns(
        keys, np.zeros(lanes, np.int32), np.zeros(lanes, np.int32), np.ones(lanes, np.int64),
        limit, np.full(lanes, 60_000, np.int64), NOW, force_wire=force)
    assert (got["remaining"] == limit - 1).all()  # the real stage ran, and answered
    (st,) = staged
    assert st.lane_wire == (wire != "dictionary")
    assert st.wide == (wire == "wide")
    assert st.config_rows == {"too-many-rows": 300, "dictionary": 16}.get(wire, 0)
    assert calls == ["device_put"]
    assert st.uploads == len(calls)


@pytest.mark.skipif(not native.available(), reason="the columnar path needs the native host runtime")
@pytest.mark.parametrize("wire", ["dictionary", "dictionary-wide", "lanes", "lanes-wide"])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_served_path_has_one_encoder_and_it_is_not_numpys(shards, wire, monkeypatch):
    """`buckets.build_config_dict`, `pack_dict_wire` and `pack_lane_wire` stay
    as the reference the native encode is held to (tests/test_native_encode.py)
    and nothing a dispatch runs calls them: with all three raising, two staged
    frames of each wire and answer width, keys met twice in a frame and again
    in the next, answer lane by lane as the sequential oracle does."""
    from gubernator_tpu.types import Algorithm, RateLimitRequest

    from . import oracle as orc

    def numpy_encoder(name):
        def raises(*a, **kw):
            raise AssertionError(f"buckets.{name} ran inside a dispatch")
        return raises

    for name in ("build_config_dict", "pack_dict_wire", "pack_lane_wire"):
        monkeypatch.setattr(buckets, name, numpy_encoder(name))
    store = _store_over(shards, 2048)
    staged, real_stage = [], store._stage_columns
    monkeypatch.setattr(store, "_stage_columns", lambda prep: staged.append(real_stage(prep)) or staged[-1])

    lanes, distinct = 512, 400
    wide, configurations = wire.endswith("wide"), 300 if wire.startswith("lanes") else 16
    rng = np.random.default_rng([SEED, shards, wide, configurations])
    cache = orc.OracleCache()
    for frame in range(2):
        now = NOW + 7_000 * frame
        key = rng.integers(0, distinct, lanes)
        keys = [f"one_encoder_{k}" for k in key.tolist()]
        algo = (np.zeros(lanes) if wide else key % 2).astype(np.int32)
        hits = rng.integers(0, 3, lanes)
        limit = 5 + key % configurations + (2**40 if wide else 0)
        duration = np.full(lanes, 60_000, np.int64)
        got = store.apply_columns(keys, algo, np.zeros(lanes, np.int32), hits, limit, duration, now)
        want = np.array([
            (int(r.status), r.limit, r.remaining, r.reset_time) for r in (
                orc.apply(cache, RateLimitRequest(
                    name="one_encoder", unique_key=str(k), hits=int(h), limit=int(lim), duration=60_000,
                    algorithm=Algorithm(int(a))), now)
                for k, h, lim, a in zip(key.tolist(), hits.tolist(), limit.tolist(), algo.tolist()))], np.int64)
        for col, name in enumerate(("status", "limit", "remaining", "reset_time")):
            assert (np.asarray(got[name]) == want[:, col]).all(), (frame, name)
    assert len(staged) == 2
    for st in staged:
        assert st.lane_wire == wire.startswith("lanes") and st.wide == wide and st.uploads == 1
        assert st.config_rows >= (257 if st.lane_wire else 2)
