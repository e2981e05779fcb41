"""The GLOBAL sync pass over the TOUCHED gslots against the pass over every
row it replaced.

`MeshBucketStore._sync_globals_locked` gathers the gslots touched since the
last pass into launches of one fixed width and reads back those rows alone.
The benchmark cannot see a wrong pass (in a one-node cell no client reads a
replica row), so this file keeps the plain reference: `_full_width_sync` is
the program of before, `global_sync` over all `g_capacity` rows, fed the whole
tables, and `_expected` its host decode.  After every pass of seeded random
traffic the store's bucket state, every `gcols` row of every shard, the host
mirror, the slot tables' commits and the `SyncResult` must be what that gives
on the same input.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from gubernator_tpu import saturation
from gubernator_tpu.models.shard import resolve_greg_columns
from gubernator_tpu.ops import buckets, global_ops
from gubernator_tpu.ops.buckets import RequestBatch
from gubernator_tpu.ops.global_ops import GlobalColumns, SyncConfig
from gubernator_tpu.parallel import mesh as mesh_mod
from gubernator_tpu.parallel.mesh import MeshBucketStore, shard_of_key
from gubernator_tpu.store import MockStore
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

T0 = 1_700_000_000_000
GLOBAL = int(Behavior.GLOBAL)
RESET = int(Behavior.RESET_REMAINING)
GREG = int(Behavior.DURATION_IS_GREGORIAN)
NAME = "cs"
CONFIG_COLUMNS = (
    "owner_slot", "owner_shard", "algorithm", "behavior", "limit", "duration",
    "greg_expire", "greg_duration",
)


# ---------------------------------------------------------------------
# The reference: the full-width pass as it was before the compact one
# ---------------------------------------------------------------------
def _global_sync_every_row(state, gcols, cfg, dirty, now_ms, *, axis):
    now = jnp.asarray(now_ms, jnp.int64)
    my = jax.lax.axis_index(axis).astype(jnp.int32)
    total = jax.lax.psum(gcols.ghits, axis)
    mine = cfg.owner_shard == my
    any_dirty = jax.lax.psum(jnp.where(mine & dirty, 1, 0).astype(jnp.int32), axis) > 0
    active = (total > 0) | any_dirty
    apply_mask = mine & active & (cfg.owner_slot >= 0)
    batch = RequestBatch(
        slot=jnp.where(apply_mask, cfg.owner_slot, -1), exists=apply_mask,
        algorithm=cfg.algorithm, behavior=cfg.behavior, hits=total, limit=cfg.limit,
        duration=cfg.duration, greg_expire=cfg.greg_expire, greg_duration=cfg.greg_duration,
    )
    new_state, out = buckets.apply_batch(state, batch, now)

    def bcast(v):
        return jax.lax.psum(jnp.where(apply_mask, v, 0), axis)

    b_status = bcast(out.status.astype(jnp.int32))
    b_limit, b_remaining, b_reset = bcast(out.limit), bcast(out.remaining), bcast(out.reset_time)
    applied = jax.lax.psum(apply_mask.astype(jnp.int32), axis) > 0
    new_gcols = GlobalColumns(
        rep_status=jnp.where(applied, b_status, gcols.rep_status),
        rep_limit=jnp.where(applied, b_limit, gcols.rep_limit),
        rep_remaining=jnp.where(applied, b_remaining, gcols.rep_remaining),
        rep_reset=jnp.where(applied, b_reset, gcols.rep_reset),
        rep_expire=jnp.where(applied, b_reset, gcols.rep_expire),
        ghits=jnp.zeros_like(gcols.ghits),
    )
    return new_state, new_gcols, out, applied, total


@functools.lru_cache(maxsize=None)
def _full_width_program(mesh, axis):
    def body(state, gcols, cfg, dirty, now):
        sq = lambda t: jax.tree.map(lambda a: a[0], t)  # noqa: E731
        ns, ngc, out, applied, total = _global_sync_every_row(
            sq(state), sq(gcols), cfg, dirty[0], now, axis=axis
        )
        ex = lambda t: jax.tree.map(lambda a: a[None], t)  # noqa: E731
        return ex(ns), ex(ngc), out.removed[None], out.new_expire[None], applied, total

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P(), P()),
    ))


def _full_width_sync(store, before):
    """The reference pass over `before` (a `_Recorder` snapshot): the new
    state and gcols, and per shard removed / new_expire, then applied and
    totals, all over every gslot row."""
    fn = _full_width_program(store.mesh, store.axis)
    put = lambda t: jax.tree.map(lambda a: jax.device_put(a, store._sharding), t)  # noqa: E731
    cfg = SyncConfig(*(jnp.asarray(before["table"][c]) for c in CONFIG_COLUMNS))
    out = fn(put(before["state"]), put(before["gcols"]), cfg,
             jax.device_put(before["dirty"], store._sharding), before["now"])
    return jax.tree.map(np.asarray, out)


class _Recorder:
    """Stands in front of `store._sync_fn`: at a pass's FIRST launch (owner
    slots resolved, nothing run yet) it copies everything the program reads,
    and holds every resolved slot of the pass against the slot table itself
    (the reference is fed the store's `owner_slot`, so it cannot see a stale
    one)."""

    def __init__(self, store):
        self.store, self.fn = store, store._sync_fn
        self.before, self.launches, self.now = None, 0, None
        store._sync_fn = self

    def __call__(self, state, gcols, wire):
        store = self.store
        if self.before is None:
            table = store.gtable
            self.before = {
                "state": jax.tree.map(np.array, state),
                "gcols": jax.tree.map(np.array, gcols),
                "table": {c: getattr(table, c).copy() for c in CONFIG_COLUMNS},
                "dirty": store.dirty.copy(),
                "touched": np.flatnonzero(store._gtouched),
                "now": self.now,
            }
            for g in self.before["touched"].tolist():
                o, slot = int(table.owner_shard[g]), int(table.owner_slot[g])
                if o >= 0 and slot >= 0:
                    assert store.tables[o].get_slot(table.key_of(g)) == slot, (g, o, slot)
        self.launches += 1
        assert wire.shape == (
            1, global_ops.SYNC_WIRE_COLUMNS * store._sync_width + buckets.WIRE_HEADER_WORDS)
        return self.fn(state, gcols, wire)

    def sync(self, now):
        self.before, self.launches, self.now = None, 0, now
        return self.store.sync_globals(now)


def _expected(store, before, ref):
    """The host decode of the full-width pass: what the pass broadcasts and
    forwards, key by key, and what it commits to the slot tables."""
    _, ref_gcols, removed, new_expire, applied, totals = ref
    table = store.gtable
    owner, slot = before["table"]["owner_shard"], before["table"]["owner_slot"]
    broadcasts, remote_hits, commits = {}, {}, {}
    for g in table.active_gslots():
        key = table.key_of(g)
        if owner[g] < 0:
            if totals[g] > 0 and table.names[g] is not None:
                remote_hits[(table.names[g], table.unique_keys[g])] = (
                    int(totals[g]), int(table.algorithm[g]), int(table.behavior[g]) | GLOBAL,
                    int(table.limit[g]), int(table.duration[g]))
        elif applied[g] and slot[g] >= 0:
            broadcasts[key] = (
                int(table.algorithm[g]), int(ref_gcols.rep_status[0, g]),
                int(ref_gcols.rep_limit[0, g]), int(ref_gcols.rep_remaining[0, g]),
                int(ref_gcols.rep_reset[0, g]))
            o = int(owner[g])
            commits[key] = (o, int(slot[g]), bool(removed[o, g]), int(new_expire[o, g]))
    return broadcasts, remote_hits, commits


def _check_pass(store, rec, res):
    """One pass of the store against the reference on the same input."""
    before = rec.before
    assert before is not None and rec.launches == max(
        -(-len(before["touched"]) // store._sync_width), 1)
    ref = _full_width_sync(store, before)
    ref_state, ref_gcols = ref[0], ref[1]
    for got, want in zip(jax.tree.leaves(store.state), jax.tree.leaves(ref_state)):
        np.testing.assert_array_equal(np.asarray(got), want)
    for name, got, want in zip(GlobalColumns._fields, store.gcols, ref_gcols):
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=name)
    assert not np.asarray(store.gcols.ghits).any()
    # Every row the reference changed was one the host had marked.
    changed = np.zeros(store.g_capacity, bool)
    for old, new in zip(before["gcols"], ref_gcols):
        changed |= (old != new).any(axis=0)
    assert set(np.flatnonzero(changed)) <= set(before["touched"])
    np.testing.assert_array_equal(store.gtable.rep_expire, ref_gcols.rep_expire[0])

    broadcasts, remote_hits, commits = _expected(store, before, ref)
    cols = res.broadcast_cols
    got = {} if cols is None else {
        k: (int(cols.algorithm[i]), int(cols.status[i]), int(cols.limit[i]),
            int(cols.remaining[i]), int(cols.reset_time[i]))
        for i, k in enumerate(cols.keys)}
    assert got == broadcasts and (cols is None or len(cols.keys) == len(got))
    hits = res.remote_hit_cols
    got = {} if hits is None else {
        (hits.names[i], hits.unique_keys[i]): (
            int(hits.hits[i]), int(hits.algorithm[i]), int(hits.behavior[i]),
            int(hits.limit[i]), int(hits.duration[i]))
        for i in range(len(hits))}
    assert got == remote_hits
    for key, (o, slot, removed, new_expire) in commits.items():
        if removed:
            assert store.tables[o].get_slot(key) is None, key
        else:
            assert store.tables[o].get_slot(key) == slot, key
            if store._native:
                assert int(store.tables[o].get_expire_bulk([slot])[0]) == new_expire, key
    assert not store.dirty.any() and not store._gtouched.any() and not store._global_pending
    return broadcasts, remote_hits


# ---------------------------------------------------------------------
# Seeded traffic
# ---------------------------------------------------------------------
def _request(rng, key, now, remote=False):
    leaky = rng.random() < 0.4
    behavior = GLOBAL | (RESET if rng.random() < 0.1 else 0)
    duration = int(rng.choice([40, 400, 60_000]))
    if not remote and rng.random() < 0.15:
        behavior |= GREG
        duration = int(rng.choice([0, 1, 2]))  # a minute's, an hour's, a day's quota
    return RateLimitRequest(
        name=NAME, unique_key=key, hits=int(rng.integers(0, 4)),
        limit=int(rng.choice([5, 20, 1_000_000_000_000])), duration=duration,
        algorithm=Algorithm.LEAKY_BUCKET if leaky else Algorithm.TOKEN_BUCKET,
        behavior=behavior,
    )


def _apply_columns(store, rng, keys, now):
    n = len(keys)
    algo = rng.integers(0, 2, n).astype(np.int32)
    behavior = np.full(n, GLOBAL, np.int32)
    behavior[rng.random(n) < 0.1] |= RESET
    duration = rng.choice([40, 400, 60_000], n).astype(np.int64)
    greg = rng.random(n) < 0.15
    behavior[greg] |= GREG
    duration[greg] = rng.choice([0, 1, 2], int(greg.sum()))
    greg_expire, greg_duration, errors, _ = resolve_greg_columns(greg, duration, now)
    assert not errors
    store.apply_columns(
        [f"{NAME}_{k}" for k in keys], algo, behavior,
        rng.integers(0, 4, n).astype(np.int64),
        rng.choice([5, 20, 1_000_000_000_000], n).astype(np.int64), duration, now,
        greg_expire, greg_duration,
    )


def _drive(store, seed, steps, columnar=True):
    """Random GLOBAL traffic on every way into the touched set, table churn
    between, a pass every step or few, each held against the reference.
    Local keys outnumber the gslots (the gslot table evicts between
    passes), plain keys outnumber a shard's slots (owner slots are lost
    while their gslot waits untouched)."""
    rng = np.random.default_rng(seed)
    rec = _Recorder(store)
    S = store.n_shards
    local = [f"l{i}" for i in range(store.g_capacity + store.g_capacity // 2)]
    remote = [f"r{i}" for i in range(6)]
    now = T0
    seen = {"passes": 0, "chunked": 0, "broadcasts": 0, "remote_hits": 0, "idle": 0}
    for _ in range(steps):
        now += int(rng.choice([1, 7, 60, 500]))
        for _ in range(int(rng.integers(1, 4))):
            way = rng.integers(0, 6 if columnar else 5)
            if way == 0:  # owner lanes
                keys = rng.choice(local, int(rng.integers(1, 6)), replace=True)
                store.apply([_request(rng, k, now) for k in keys], now)
            elif way == 1:  # the same keys entering at another shard than their owner's
                keys = rng.choice(local[:12], int(rng.integers(1, 6)), replace=True)
                store.apply([_request(rng, k, now) for k in keys], now,
                            home_shard=int(rng.integers(0, S)))
            elif way == 2:  # keys another daemon owns
                keys = rng.choice(remote, int(rng.integers(1, 4)), replace=True)
                store.apply([_request(rng, k, now, remote=True) for k in keys], now,
                            remote_global=True)
            elif way in (3, 4):  # churn: plain keys take the shards' slots
                store.apply([
                    RateLimitRequest(name=NAME, unique_key=f"p{rng.integers(0, 400)}", hits=1,
                                     limit=9, duration=60_000)
                    for _ in range(int(rng.integers(4, 24)))], now)
            else:  # the columnar path: GLOBAL lanes it owns, duplicates and all
                _apply_columns(store, rng, rng.choice(local, int(rng.integers(2, 30))), now)
        if rng.random() < 0.6:
            pending = store._global_pending
            idle_before = (saturation.phase_snapshot().get("global.tick_idle") or {"count": 0})["count"]
            res = rec.sync(now)
            if not pending:
                # Nothing planned a GLOBAL lane: the tick is idle, as it was.
                assert res.did_work is False and rec.launches == 0
                assert saturation.phase_snapshot()["global.tick_idle"]["count"] == idle_before + 1
                seen["idle"] += 1
                continue
            broadcasts, remote_hits = _check_pass(store, rec, res)
            seen["passes"] += 1
            seen["chunked"] += rec.launches > 1
            seen["broadcasts"] += len(broadcasts)
            seen["remote_hits"] += len(remote_hits)
    store.check_consistency()
    return seen


@pytest.fixture
def narrow(monkeypatch):
    """The program's one width cut to 8 gslots for stores made inside the
    test: a pass over 9 touched gslots launches it twice."""
    monkeypatch.setattr(mesh_mod, "SYNC_WIDTH", 8)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("shards", [4, 8])
def test_every_pass_is_the_full_width_pass(narrow, shards, seed):
    store = MeshBucketStore(
        capacity_per_shard=24, g_capacity=40, devices=jax.devices()[:shards])
    assert store._sync_width == 8
    seen = _drive(store, 1000 * shards + seed, steps=36)
    assert seen["passes"] >= 8 and seen["chunked"] >= 3, seen
    assert seen["broadcasts"] > 20 and seen["remote_hits"] > 0, seen


@pytest.mark.parametrize("seed", range(3))
def test_every_pass_is_the_full_width_pass_under_the_store_spi(narrow, seed):
    """A Store SPI takes the object path alone and the per-key commit of a
    pass: the same pass, and the callbacks the owner-side apply owes."""
    spi = MockStore()
    store = MeshBucketStore(
        capacity_per_shard=24, g_capacity=40, devices=jax.devices()[:4], store=spi)
    seen = _drive(store, 77 + seed, steps=30, columnar=False)
    assert seen["passes"] >= 6 and seen["chunked"] >= 1, seen
    assert spi.called["OnChange()"] > seen["broadcasts"] > 0


def test_a_burst_past_the_real_width_launches_the_one_program_again():
    """No cut width: two launches' worth of GLOBAL keys and 404 more are
    touched at once by one frame on a table of 8,192 gslots, so the pass
    launches the program at its real width three times and the third launch
    carries 404 live lanes."""
    store = MeshBucketStore(
        capacity_per_shard=2048, g_capacity=8192, devices=jax.devices()[:4])
    width = store._sync_width
    assert width == mesh_mod.SYNC_WIDTH < 4096
    burst = 2 * width + 404
    rec = _Recorder(store)
    rng = np.random.default_rng(5)
    _apply_columns(store, rng, [f"b{i}" for i in range(burst)], T0)
    res = rec.sync(T0 + 1)
    assert rec.launches == 3 and len(rec.before["touched"]) == burst
    broadcasts, _ = _check_pass(store, rec, res)
    assert len(broadcasts) == burst
    # And a pass of a few keys afterwards is one launch.
    _apply_columns(store, rng, ["b7", f"b{burst - 1}", "fresh"], T0 + 2)
    res = rec.sync(T0 + 3)
    assert rec.launches == 1
    assert sorted(_check_pass(store, rec, res)[0]) == sorted(
        f"{NAME}_{k}" for k in ("b7", f"b{burst - 1}", "fresh"))


def test_a_gslot_left_alone_while_its_shard_churns_is_resolved_anew(narrow):
    """The stale `owner_slot` case.  A key's slot is confirmed in one pass;
    three passes go by that take other gslots while plain keys push the key out
    of its owner's table and another key into its slot; then hits for it arrive
    at a non-owner shard.  The pass that takes them must not apply them to the
    slot it remembers: that slot is another key's now."""
    store = MeshBucketStore(capacity_per_shard=8, g_capacity=40, devices=jax.devices()[:4])
    rec = _Recorder(store)
    key = f"{NAME}_held"
    owner = shard_of_key(key, 4)
    other_shard = (owner + 1) % 4

    def req(k, hits=1, behavior=GLOBAL):
        return RateLimitRequest(name=NAME, unique_key=k, hits=hits, limit=50,
                                duration=60_000, behavior=behavior)

    store.apply([req("held", 2)], T0)
    _check_pass(store, rec, rec.sync(T0))
    g = store.gtable.get(key)
    slot = int(store.gtable.owner_slot[g])
    assert slot >= 0 and store.tables[owner].get_slot(key) == slot

    bystanders = [k for k in (f"w{i}" for i in range(200))
                  if shard_of_key(f"{NAME}_{k}", 4) != owner][:3]
    fill = [k for k in (f"f{i}" for i in range(400)) if shard_of_key(f"{NAME}_{k}", 4) == owner]
    for i, bystander in enumerate(bystanders):
        now = T0 + 10 * (i + 1)
        store.apply([req(k, behavior=0) for k in fill[8 * i:8 * i + 8]], now)
        store.apply([req(bystander)], now)
        _check_pass(store, rec, rec.sync(now))
        assert g not in rec.before["touched"]
    assert store.tables[owner].get_slot(key) is None  # pushed out
    assert int(store.gtable.owner_slot[g]) == slot  # and the gslot still remembers
    squatter = next(k for k in store.tables[owner].keys()
                    if store.tables[owner].get_slot(k) == slot)
    rows_before = jax.tree.map(lambda a: np.array(a[owner, slot]), store.state)

    store.apply([req("held", 3)], T0 + 100, home_shard=other_shard)
    res = rec.sync(T0 + 101)
    assert g in rec.before["touched"]
    broadcasts, _ = _check_pass(store, rec, res)
    fresh = store.tables[owner].get_slot(key)
    assert fresh is not None and int(store.gtable.owner_slot[g]) == fresh
    if store.tables[owner].get_slot(squatter) == slot:
        # The squatter kept its slot: the pass left its row alone.
        for got, want in zip(jax.tree.leaves(jax.tree.map(lambda a: np.asarray(a[owner, slot]), store.state)),
                             jax.tree.leaves(rows_before)):
            np.testing.assert_array_equal(got, want)
    assert key in broadcasts


def test_the_wire_and_the_answer_carry_every_bit():
    """pack_sync_wire / global_sync's unpack and the lo/hi planes of the
    answer, at the ends of the 64-bit range."""
    big = np.array([0, 1, -1, 2**31, 2**32 + 5, 2**62, -(2**62)], np.int64)
    n = len(big)
    cfg = SyncConfig(
        np.arange(n, dtype=np.int32), np.full(n, 3, np.int32), np.ones(n, np.int32),
        np.full(n, 8, np.int32), big, big[::-1], big + 1, big - 1)
    wire = global_ops.pack_sync_wire(16, 40, np.arange(n) * 2, cfg, np.arange(n) % 2 == 0, T0 + 7)
    K = 16
    col = lambda k: wire[0, k * K:(k + 1) * K]  # noqa: E731
    assert (col(0)[:n] == np.arange(n) * 2).all() and (col(0)[n:] == 40).all()
    assert (col(5)[:n] == (np.arange(n) % 2 == 0)).all()
    for j, want in enumerate((big, big[::-1], big + 1, big - 1)):
        lo, hi = col(6 + 2 * j)[:n], col(7 + 2 * j)[:n]
        assert ((hi.astype(np.int64) << 32) | (lo.astype(np.int64) & 0xFFFFFFFF) == want).all()
    assert int(buckets.wire_header(jnp.asarray(wire))[1]) == T0 + 7
    rows = np.stack([big, big[::-1], big + 1, big - 1, big])
    answer = np.concatenate((
        np.array([[1, 2, 7, 0, 5, 6, 3]], np.int32),
        rows.astype(np.int32), (rows >> 32).astype(np.int32)))
    applied, removed, status, *values = global_ops.unpack_sync_answer(answer)
    assert applied.tolist() == [True, False, True, False, True, False, True]
    assert removed.tolist() == [False, True, True, False, False, True, True]
    assert status.tolist() == [0, 0, 1, 0, 1, 1, 0]
    for got, want in zip(values, rows):
        assert (got == want).all()
