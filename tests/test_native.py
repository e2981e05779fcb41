"""Native host runtime (C++ slot table / planner / fnv) parity tests.

The C++ twin must agree operation-for-operation with the Python
SlotTable (models/slot_table.py) — both mirror cache.go semantics — and
the batch planner must reproduce RoundPlanner's round splits.
"""

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.models.slot_table import SlotTable
from gubernator_tpu.ops import buckets
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, SECOND
from gubernator_tpu.utils import hashing

from .conftest import one_device_store, take_moves

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native runtime unavailable: {native.build_error()}"
)


def test_fnv_matches_python():
    keys = ["", "a", "foobar", "test_health_hc_0", "账户:1234"]
    for variant in (False, True):
        got = native.fnv1_batch(keys, variant_1a=variant)
        py = [
            (hashing.fnv1a_64 if variant else hashing.fnv1_64)(k.encode("utf-8"))
            for k in keys
        ]
        assert list(got) == py


def test_table_parity_random_ops():
    """Drive both tables with the same randomized op sequence and
    compare every observable output."""
    rng = np.random.RandomState(7)
    py = SlotTable(32)
    nat = native.NativeSlotTable(32)
    keys = [f"k{i}" for i in range(100)]
    now = 1000
    for step in range(3000):
        op = rng.randint(0, 10)
        key = keys[rng.randint(0, len(keys))]
        if op < 6:
            a = py.lookup_or_assign(key, now)
            b = nat.lookup_or_assign(key, now)
            assert a == b, (step, key, a, b)
        elif op < 8:
            slot = py.get_slot(key)
            assert slot == nat.get_slot(key), (step, key)
            if slot is not None:
                exp = now + int(rng.randint(0, 500))
                py.commit([slot], [exp], [False])
                nat.commit([slot], [exp], [False])
        elif op == 8:
            py.remove(key)
            nat.remove(key)
        else:
            now += int(rng.randint(0, 200))
    assert len(py) == len(nat)
    assert sorted(py.keys()) == sorted(nat.keys())
    assert (py.hits, py.misses, py.evictions) == (nat.hits, nat.misses, nat.evictions)


def test_commit_staleness_guard():
    """A lane whose slot was remapped (eviction mid-batch) must not
    touch the slot's new owner when committed with keys."""
    t = native.NativeSlotTable(2)
    s_a, _ = t.lookup_or_assign("A", 100)
    t.lookup_or_assign("B", 100)
    s_c, _ = t.lookup_or_assign("C", 100)  # evicts LRU (= A)
    assert s_c == s_a
    t.commit([s_a], [999], [False], keys=["A"])  # stale: dropped
    assert t.lookup_or_assign("C", 500) == (s_c, False)  # expire untouched
    t.commit([s_c], [999], [True], keys=["A"])  # stale removal: dropped
    assert t.get_slot("C") == s_c
    t.commit([s_c], [999], [False], keys=["C"])  # valid
    assert t.lookup_or_assign("C", 500) == (s_c, True)


def test_planner_rounds_duplicates():
    t = native.NativeSlotTable(16)
    keys = ["a", "b", "a", "a", "c", "b"]
    p = native.NativeBatchPlanner(t, keys, 100)
    rounds = []
    while True:
        r = p.next_round()
        if r is None:
            break
        lane, slots, exists = r
        rounds.append(list(lane))
        p.commit_round(np.full(len(lane), 500, np.int64), np.zeros(len(lane), np.uint8))
    # Skip-and-defer: duplicates wait for the next round, unique keys
    # keep flowing; the k-th request for a key always sees the (k-1)-th's
    # committed state, and round count = max key multiplicity.
    assert rounds == [[0, 1, 4], [2, 5], [3]]


def test_planner_exists_reflects_commits():
    t = native.NativeSlotTable(16)
    p = native.NativeBatchPlanner(t, ["x", "x"], 100)
    lane, slots, exists = p.next_round()
    assert list(exists) == [False]
    p.commit_round(np.array([500], np.int64), np.array([0], np.uint8))
    lane, slots, exists = p.next_round()
    assert list(exists) == [True]  # round 1's commit is visible
    p.commit_round(np.array([500], np.int64), np.array([0], np.uint8))


def _req(key, hits=1, limit=10, duration=9 * SECOND, algo=Algorithm.TOKEN_BUCKET, behavior=0):
    return RateLimitRequest(
        name="nat", unique_key=key, hits=hits, limit=limit,
        duration=duration, algorithm=algo, behavior=behavior,
    )


def test_store_native_vs_python_sequences():
    """Same request stream through the native fast path and the Python
    fallback gives byte-identical responses."""
    now = 1_700_000_000_000
    a = one_device_store(64, use_native=True)
    b = one_device_store(64, use_native=False)
    assert a._native and not b._native
    rng = np.random.RandomState(3)
    for t in range(20):
        reqs = [
            _req(
                f"k{rng.randint(0, 12)}",
                hits=int(rng.randint(0, 4)),
                limit=5,
                algo=Algorithm(int(rng.randint(0, 2))),
            )
            for _ in range(16)
        ]
        ra = a.apply(reqs, now + t * 250)
        rb = b.apply(reqs, now + t * 250)
        assert ra == rb, t


def test_apply_columns_matches_apply():
    now = 1_700_000_000_000
    st = one_device_store(128)
    reqs = [_req(f"c{i % 7}", hits=1, limit=100) for i in range(32)]
    expect = one_device_store(128).apply(reqs, now)
    out = st.apply_columns(
        keys=[r.hash_key() for r in reqs],
        algorithm=[int(r.algorithm) for r in reqs],
        behavior=[0] * len(reqs),
        hits=[r.hits for r in reqs],
        limit=[r.limit for r in reqs],
        duration=[r.duration for r in reqs],
        now_ms=now,
    )
    for i, e in enumerate(expect):
        assert int(out["status"][i]) == e.status
        assert int(out["remaining"][i]) == e.remaining
        assert int(out["reset_time"][i]) == e.reset_time


def test_native_store_capacity_eviction_parity():
    """Under capacity pressure both paths evict LRU and keep working."""
    now = 1_700_000_000_000
    a = one_device_store(8, use_native=True)
    b = one_device_store(8, use_native=False)
    for t in range(40):
        reqs = [_req(f"e{(t + j) % 20}", limit=1000) for j in range(6)]
        assert a.apply(reqs, now + t) == b.apply(reqs, now + t)
    assert sorted(a.tables[0].keys()) == sorted(b.tables[0].keys())


def test_plan_single_dispatch_round_ids():
    """gt_batch_plan assigns the same rounds as the interleaved planner
    without needing per-round commits."""
    t = native.NativeSlotTable(16)
    keys = ["a", "b", "a", "a", "c", "b"]
    p = native.NativeBatchPlanner(t, keys, 100)
    round_id, slots, exists, n_rounds = p.plan()
    assert n_rounds == 3
    assert list(round_id) == [0, 0, 1, 2, 0, 1]
    # First occurrences are misses; chained occurrences trust the device.
    assert list(exists) == [False, False, True, True, False, True]
    assert slots[0] == slots[2] == slots[3]
    assert slots[1] == slots[5]
    # commit_plan folds the last write per key into the table.
    exp = np.arange(100, 106, dtype=np.int64) + 1000
    p.commit_plan(exp, np.zeros(6, np.uint8))
    assert t.lookup_or_assign("a", 1100) == (int(slots[3]), True)  # expire 1103


def test_reset_remaining_then_hit_same_batch():
    """Token RESET_REMAINING followed by hits on the same key in ONE
    batch: the reset removes the bucket, the next hit recreates it, and
    the recreated bucket must survive into the next batch (the remove-
    then-recreate commit chain)."""
    now = 1_700_000_000_000
    a = one_device_store(32, use_native=True)
    b = one_device_store(32, use_native=False)
    warm = [_req("rr", hits=4, limit=10)]
    batch = [
        _req("rr", hits=0, behavior=int(Behavior.RESET_REMAINING), limit=10),
        _req("rr", hits=3, limit=10),
    ]
    after = [_req("rr", hits=1, limit=10)]
    for st in (a, b):
        st.apply(warm, now)
        st.apply(batch, now + 1)
        (r,) = st.apply(after, now + 2)
        assert r.remaining == 6, r  # 10 - 3 - 1: recreation persisted
    assert a.tables[0].get_slot("nat_rr") is not None


def test_plan_path_overlimit_chain():
    """Duplicate chain crossing the limit: k-th request sees (k-1)-th's
    state exactly as the mutex-serialized reference would."""
    now = 1_700_000_000_000
    a = one_device_store(32, use_native=True)
    b = one_device_store(32, use_native=False)
    # remaining=5: [hits=7 OVER no-mutate, hits=3 UNDER ->2, hits=3 OVER, hits=2 UNDER ->0]
    reqs = [_req("ol", hits=h, limit=5) for h in (7, 3, 3, 2)]
    ra, rb = a.apply(reqs, now), b.apply(reqs, now)
    assert ra == rb
    assert [r.status for r in ra] == [1, 0, 1, 0]
    assert [r.remaining for r in ra] == [5, 2, 2, 0]


def test_plan_path_random_stress_vs_python():
    """Randomized mixed workload (dups, resets, algo switches, expiry,
    capacity pressure) through the single-dispatch path vs the Python
    twin."""
    now = 1_700_000_000_000
    a = one_device_store(16, use_native=True)
    b = one_device_store(16, use_native=False)
    rng = np.random.RandomState(11)
    for t in range(30):
        reqs = []
        for _ in range(24):
            behavior = int(Behavior.RESET_REMAINING) if rng.random() < 0.1 else 0
            reqs.append(
                _req(
                    f"s{rng.randint(0, 10)}",
                    hits=int(rng.randint(0, 4)),
                    limit=6,
                    duration=int(rng.choice([200, 5000])),
                    algo=Algorithm(int(rng.randint(0, 2))),
                    behavior=behavior,
                )
            )
        step = now + t * 150
        ra, rb = a.apply(reqs, step), b.apply(reqs, step)
        assert ra == rb, t
    assert sorted(a.tables[0].keys()) == sorted(b.tables[0].keys())


def test_eviction_skips_pending_write_slots():
    """Under capacity pressure, LRU eviction must not steal a slot whose
    device write from an earlier un-resolved (pipelined) batch is still
    in flight — doing so silently drops that batch's device state
    (advisor finding, host_runtime.cpp lookup_or_assign)."""
    from gubernator_tpu.models.shard import _Columns

    nat = native.NativeSlotTable(4)
    now = 1000

    # Batch A plans k0,k1: their slots carry pending writes until commit.
    cols = _Columns(2)
    cols.algo[:] = 0
    cols.behavior[:] = 0
    cols.hits[:] = 1
    cols.limit[:] = 10
    cols.duration[:] = 60_000
    planner = native.NativeBatchPlanner(nat, ["k0", "k1"], now)
    _, slots_a, _, _, _, _ = planner.plan_grouped(cols, int(Behavior.RESET_REMAINING))
    pending = set(int(s) for s in slots_a)

    # Fill the rest of the capacity with committed keys.
    s2, _ = nat.lookup_or_assign("k2", now)
    s3, _ = nat.lookup_or_assign("k3", now)
    nat.set_expire(s2, now + 60_000)
    nat.set_expire(s3, now + 60_000)

    # Table full; a new key must evict — but NOT a pending slot, even
    # though k0/k1 are the LRU-coldest entries.
    s4, _ = nat.lookup_or_assign("k4", now)
    assert s4 not in pending
    assert s4 == s2  # first non-pending in LRU order
    assert nat.get_slot("k0") is not None and nat.get_slot("k1") is not None

    # After commit the claims are released: next eviction takes k0.
    planner.commit_plan(
        np.full(2, now + 60_000, dtype=np.int64), np.zeros(2, dtype=np.uint8)
    )
    s5, _ = nat.lookup_or_assign("k5", now)
    assert s5 in pending
    assert nat.get_slot("k0") is None


def test_eviction_falls_back_when_all_pending():
    """When every slot has an in-flight write, eviction degrades to the
    raw LRU head instead of failing."""
    from gubernator_tpu.models.shard import _Columns

    nat = native.NativeSlotTable(2)
    now = 1000
    cols = _Columns(2)
    cols.algo[:] = 0
    cols.behavior[:] = 0
    cols.hits[:] = 1
    cols.limit[:] = 10
    cols.duration[:] = 60_000
    planner = native.NativeBatchPlanner(nat, ["k0", "k1"], now)
    planner.plan_grouped(cols, int(Behavior.RESET_REMAINING))

    s, exists = nat.lookup_or_assign("k2", now)
    assert not exists
    assert 0 <= s < 2  # evicted the LRU head despite the pending claim


def test_passthrough_reset_survives_pipelined_eviction():
    """The narrow-wire keep-sentinel (-2) reconstructs an unchanged
    reset_time from the host expiry bookkeeping; that value must be the
    PLAN-time snapshot once a later pipelined batch's planning has
    evicted and reassigned the slot before the earlier batch resolves,
    and the live table value while the slot still maps the lane's key.

    The sentinel itself only fires for far-future expiries the i32 wire
    can't carry, so instead of driving the kernel there this hands
    `finish_narrow` a packed result that says "unchanged" directly."""
    from gubernator_tpu.models.shard import make_columns

    now = 1_700_000_000_000
    cols = make_columns([0], [0], [0], [10], [60_000], 1)
    padded = 64
    keep = np.zeros((1, 4, padded), np.int32)
    keep[0, 1, 0] = 9
    keep[0, 2, 0] = keep[0, 3, 0] = -2  # reset_time, new_expire: unchanged

    def planned(table):
        slot, _ = table.lookup_or_assign("a", now)
        table.set_expire(slot, now + 60_000)  # "a" committed earlier
        mp = native.NativeMeshPlanner([table], ["a"], now + 1)
        mp.plan_grouped(cols, int(Behavior.RESET_REMAINING), padded)
        return slot, mp

    # Stolen: with every slot pending, "b" takes the LRU head (the
    # all-pending fallback) and the slot's expiry is zeroed.
    t = native.NativeSlotTable(1)
    slot, mp = planned(t)
    assert t.lookup_or_assign("b", now + 2) == (slot, False)
    assert t.get_slot("a") is None
    status, remaining, reset = mp.finish_narrow(keep, now + 1)
    assert (int(status[0]), int(remaining[0])) == (0, 9)
    assert int(reset[0]) == now + 60_000  # the snapshot, not the table's 0

    # Still mapped: the live value wins (older commits have folded in).
    t = native.NativeSlotTable(4)
    slot, mp = planned(t)
    t.set_expire(slot, now + 90_000)
    _, _, reset = mp.finish_narrow(keep, now + 1)
    assert int(reset[0]) == now + 90_000


# ---------------------------------------------------------------------
# The flat key index (KeyIndex) and the one-record-a-slot table, held to
# the Python twin step by step
# ---------------------------------------------------------------------
ALL_BITS = (1 << 64) - 1


class _TwoTierTwin:
    """models/slot_table.SlotTable with a back tier that never wraps: a
    live key evicted from the front is parked with its expiry, and a
    lookup of a parked, still-live key is a hit that gets it back (what
    Table::assign does when `back_capacity` holds every key)."""

    def __init__(self, capacity: int):
        self.t = SlotTable(capacity)
        self.back = {}

    def __getattr__(self, name):
        return getattr(self.t, name)

    def __len__(self):
        return len(self.t)

    def lookup_or_assign(self, key, now):
        t = self.t
        if t.get_slot(key) is not None:
            return t.lookup_or_assign(key, now)
        parked = self.back.pop(key, None)
        victim = None
        if not t._free:
            vs = next(iter(t._lru))
            victim = (t.key_of(vs), int(t.expire_ms[vs]))
        slot, _ = t.lookup_or_assign(key, now)
        if victim is not None and victim[1] >= now:
            self.back[victim[0]] = victim[1]
        if parked is not None and parked >= now:
            t.expire_ms[slot] = parked
            t.misses -= 1
            t.hits += 1
            return slot, True
        return slot, False

    def remove(self, key):
        self.t.remove(key)
        self.back.pop(key, None)


def _plan_then_commit(rng, nat, py, batches, now):
    """Pipelined: every batch is planned before the first is committed
    (the native side holds their pending writes meanwhile); the twin
    resolves each batch's distinct keys in first-appearance order."""
    planned = []
    for keys in batches:
        n = len(keys)
        cols = _Cols(n)
        p = native.NativeBatchPlanner(nat, keys, now)
        rid, slots, exists, occ, write, n_rounds = p.plan_grouped(cols, 8)
        first, ev = {}, py.evictions
        for k in keys:
            if k not in first:
                first[k] = py.lookup_or_assign(k, now)
        got = [(int(slots[i]), bool(exists[i])) for i in range(n)]
        want = [first[k] for k in keys]
        assert got == want, next((i, keys[i], g, w) for i, (g, w) in enumerate(zip(got, want)) if g != w)
        if nat.move_counts() != (0, 0):
            take_moves(nat)  # the launch drains a plan's tier moves before the next plan's evictions
        # A group whose assign evicted goes to the rounds, after round 0.
        assert int(rid.max()) == n_rounds - 1 and (n_rounds == 1 or py.evictions > ev)
        last = {k: i for i, k in enumerate(keys)}
        assert [bool(w) for w in write] == [last[k] == i for i, k in enumerate(keys)]
        nth = {}
        for i, k in enumerate(keys):  # a lane's occurrence index within its key's group
            assert occ[i] == nth.get(k, 0), (i, k)
            nth[k] = nth.get(k, 0) + 1
        # The plan's commit order: round 0's groups as they first appear,
        # then the rounds' lanes.
        order = sorted(last.values(), key=lambda i: (int(rid[i]), keys.index(keys[i])))
        planned.append((p, keys, slots, order))
    for p, keys, slots, lanes in planned:
        n = len(keys)
        exp = now + rng.randint(-50, 400, size=n).astype(np.int64)
        rm = (rng.random_sample(n) < 0.15).astype(np.uint8)
        p.commit_plan(exp, rm)
        py.commit([int(slots[i]) for i in lanes], [int(exp[i]) for i in lanes],
                  [bool(rm[i]) for i in lanes], keys=[keys[i] for i in lanes])


class _Cols:
    """One uniform configuration a lane: every duplicate group collapses."""

    def __init__(self, n):
        self.algo = np.zeros(n, np.int32)
        self.behavior = np.zeros(n, np.int32)
        self.hits = np.ones(n, np.int64)
        self.limit = np.full(n, 10, np.int64)
        self.duration = np.full(n, 1000, np.int64)
        self.greg_expire = np.zeros(n, np.int64)
        self.greg_duration = np.zeros(n, np.int64)


INDEX_CASES = {
    # capacity, key bytes, (and, or) over the index hash, two-tier
    "chains_wrap_the_index": (3, 5, (ALL_BITS ^ 7, 7), False),  # 8 entries, every chain starts at the last
    "all_keys_on_the_same_hash_bits": (24, 9, (0, 0), False),
    "same_hash_bits_two_tier": (6, 9, (0, 0), True),
    "keys_of_1_byte": (12, 1, None, False),
    "keys_of_15_bytes": (12, 15, None, False),
    "keys_of_16_bytes": (12, 16, None, True),
    "keys_of_22_bytes": (16, 22, None, False),
    "keys_of_24_bytes_the_last_inline": (12, 24, None, True),
    "keys_of_25_bytes_the_first_on_the_heap": (12, 25, None, False),
    "keys_of_200_bytes": (12, 200, None, True),
    "two_tier_small": (4, 22, None, True),
}


@pytest.mark.parametrize("case", sorted(INDEX_CASES))
def test_native_table_equals_its_twin_after_every_step(case):
    """Random lookups, removals, expiry, eviction at capacity, the
    remove-then-recreate remap and pipelined plan-then-commit through
    NativeSlotTable and the Python twin: the same slot and `exists` at
    every step, the same hits / misses / evictions, the mapping
    generation moving in the same steps (the native table counts an
    evicting assign twice, so the values differ), and at the end the same
    eviction order slot by slot."""
    capacity, width, bits, two_tier = INDEX_CASES[case]
    rng = np.random.RandomState(len(case) * 31 + width)
    alphabet = "abcdefghijklmnopqrstuvwxyz"[: max(2, min(26, capacity * 3))]
    if width == 1:
        keys = list(alphabet)
    else:
        keys = [(f"{i:03d}{c}" * width)[:width] for i, c in enumerate(alphabet)]
    assert len(set(keys)) == len(keys) and all(len(k) == width for k in keys)
    nat = native.NativeSlotTable(capacity, _hash_bits=bits)
    py = SlotTable(capacity)
    if two_tier:
        nat.enable_back(4 * len(keys))
        py = _TwoTierTwin(capacity)
    now = 1000
    for step in range(1500):
        gen = (nat.generation, py.generation)
        op = rng.randint(0, 12)
        key = keys[rng.randint(0, len(keys))]
        if op < 5:
            assert nat.lookup_or_assign(key, now) == py.lookup_or_assign(key, now), (step, key)
        elif op < 7:
            slot = py.get_slot(key)
            assert slot == nat.get_slot(key), (step, key)
            if slot is not None:
                exp = now + int(rng.randint(-100, 500))
                py.commit([slot], [exp], [False])
                nat.commit([slot], [exp], [False])
        elif op == 7:
            py.remove(key)
            nat.remove(key)
        elif op == 8:
            # Remove through a keyed commit, then the keyed commit of a
            # later lane of the same key: the slot is re-mapped.
            slot = py.get_slot(key)
            if slot is not None:
                for t in (py, nat):
                    t.commit([slot], [0], [True], keys=[key])
                    t.commit([slot], [now + 300], [False], keys=[key])
                assert nat.get_slot(key) == py.get_slot(key) == slot
        elif op == 9:
            now += int(rng.randint(0, 200))
        else:
            # Two batches in flight together.  They share no key and fit
            # the table (the twin has no pending writes: it agrees only
            # while no plan must evict around one), and a batch repeats
            # keys only while no eviction can send a group to the rounds.
            distinct = list(rng.permutation(keys)[: max(2, min(capacity, len(keys)) // 2 * 2)])
            half = len(distinct) // 2
            batches = []
            for part in (distinct[:half], distinct[half:]):
                part = [str(k) for k in part]
                if len(py.t._free if two_tier else py._free) >= len(distinct):
                    part = part + [part[i] for i in rng.randint(0, len(part), size=len(part))]
                batches.append(part)
            _plan_then_commit(rng, nat, py, batches, now)
        if two_tier:
            take_moves(nat)  # as every dispatch does: a queued promotion shields its slot from eviction
        assert len(nat) == len(py), step
        assert (nat.hits, nat.misses) == (py.hits, py.misses), step
        if not two_tier:  # a back row dropped for room counts as an eviction too
            assert nat.evictions == py.evictions, step
        assert (nat.generation != gen[0]) == (py.generation != gen[1]), step
    assert sorted(nat.keys()) == sorted(py.keys())
    assert py.evictions > 0 and py.hits > 0  # the sequence did reach capacity
    if two_tier:
        total, back_keys, demotions, promotions, lost = nat.tier_stats
        assert demotions > 0 and promotions > 0 and lost == 0
        assert back_keys == len(py.back) and sorted(nat.back_entries()[0]) == sorted(py.back)
    stats = nat.index_stats
    assert stats["lookups"] > 0 and stats["probes"] >= stats["lookups"]
    if bits == (0, 0):
        assert stats["refused"] > 0  # distinct keys on the same 64 bits: the key compare told them apart
    elif bits is None:
        assert stats["refused"] == 0
    # The eviction order, slot by slot: fresh keys push everything out.
    for i in range(capacity):
        fresh = f"fresh-{i}"
        assert nat.lookup_or_assign(fresh, now) == py.lookup_or_assign(fresh, now), i


# ---------------------------------------------------------------------
# The grouped plan on the benchmark cells' own shapes, held lane for lane
# to the Python plan
# ---------------------------------------------------------------------
PLAN_CASES = {
    "4096_lanes_1_shard": (4096, 1),  # v5e1-1m.frames
    "1028_lanes_4_shards": (1028, 4),  # v5e4-mesh-1m.frames
    "4096_lanes_4_shards": (4096, 4),
    "1028_lanes_1_shard": (1028, 1),
}
PLAN_KEYS = 20_000  # the harness's rehearsal size
PLAN_T0 = 1_790_000_000_000
HOUR = 3_600_000


@pytest.fixture(scope="module")
def cell_population():
    import json
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from chipbench.population import Population

    with open(os.path.join(repo, "chipbench", "configs", "v5e1-1m.json")) as f:
        return Population(json.load(f)["population"], PLAN_KEYS, 28)


def _cell_frames(pop, lanes):
    """[(keys, algo, behavior, limit, now)]: the load, Zipfian-0.99 frames
    (hot keys repeat), a frame with RESET_REMAINING lanes (one alone, one in
    the middle of a hot key's group), a frame half of fresh keys that the full
    table must evict for, more Zipfian frames, and one after every bucket
    has expired."""
    rng = np.random.default_rng([28, lanes])
    resident = [f"bench_{pop.unique_key(i)}" for i in range(pop.n)]
    frames, now = [], PLAN_T0

    def frame(idx, fresh=()):
        keys = [resident[i] for i in idx]
        algo = pop.algo[idx].astype(np.int32)
        limit = pop.limit[idx].astype(np.int64)
        if len(fresh):
            at = np.sort(rng.choice(len(keys) + len(fresh), size=len(fresh), replace=False))
            for a, k in zip(at, fresh):
                keys.insert(int(a), k)
            algo = np.insert(algo, at - np.arange(len(at)), 0).astype(np.int32)
            limit = np.insert(limit, at - np.arange(len(at)), 100).astype(np.int64)
        return [keys, algo, np.zeros(len(keys), np.int32), limit, now]

    for lo in range(0, pop.n, lanes):
        frames.append(frame(np.arange(lo, min(lo + lanes, pop.n))))
        now += 7
    for _ in range(3):
        frames.append(frame(pop.draw(rng, lanes)))
        now += 900
    f = frame(pop.draw(rng, lanes))
    counts = {}
    for k in f[0]:
        counts[k] = counts.get(k, 0) + 1
    hot = max(counts, key=counts.get)
    alone = next(i for i, k in enumerate(f[0]) if counts[k] == 1)
    second = [i for i, k in enumerate(f[0]) if k == hot][1]
    assert counts[hot] >= 3
    f[2][[alone, second]] = int(Behavior.RESET_REMAINING)
    frames.append(f)
    now += 900
    fresh = [f"bench_fresh{i:07d}kffff" for i in range(lanes // 2)]
    frames.append(frame(pop.draw(rng, lanes - len(fresh)), fresh))
    now += 900
    for _ in range(2):
        frames.append(frame(pop.draw(rng, lanes)))
        now += 900
    now += 2 * HOUR
    frames.append(frame(pop.draw(rng, lanes)))
    return frames


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_mesh_plan_gives_the_python_plans_arrays_lane_for_lane(case, cell_population):
    """`gt_mesh_begin` + `gt_mesh_plan_grouped` over loaded tables against
    `plan_grouped_python` over the twin's: `slot`, `rid`, `exists`, `occ`,
    `write`, `pos` and the round count, frame after frame, each committed
    on both sides with the same results."""
    from gubernator_tpu.models.shard import _Prepared, pad_size, plan_grouped_python
    from gubernator_tpu.parallel.mesh import shard_of_key

    lanes, S = PLAN_CASES[case]
    pop = cell_population
    cap = int(PLAN_KEYS / S * 1.005)  # full after the load: the fresh keys evict
    nat = [native.NativeSlotTable(cap) for _ in range(S)]
    py = [SlotTable(cap) for _ in range(S)]
    saw = {"rounds": 0, "evictions": 0, "groups": 0, "expired": 0}
    frames = _cell_frames(pop, lanes)
    for f, (keys, algo, behavior, limit, now) in enumerate(frames):
        n = len(keys)
        cols = _Cols(n)
        cols.algo, cols.behavior, cols.limit = algo, behavior, limit
        cols.duration = np.full(n, HOUR, np.int64)
        mp = native.NativeMeshPlanner(nat, keys, now)
        P = pad_size(max(int(mp.counts.max()), 1))
        n_rounds = mp.plan_grouped(cols, int(Behavior.RESET_REMAINING), P)

        by_shard = [[] for _ in range(S)]
        for i, k in enumerate(keys):
            req = RateLimitRequest(
                name="bench", unique_key=k[6:], hits=1, limit=int(limit[i]), duration=HOUR,
                algorithm=Algorithm(int(algo[i])), behavior=int(behavior[i]))
            by_shard[shard_of_key(k, S)].append(_Prepared(pos=i, slot=-1, exists=False, req=req, key=k))
        ev = sum(t.evictions for t in py)
        want_rounds = 1
        packed = np.zeros((S, 4, P), np.int64)
        for s, chunk in enumerate(by_shard):
            m = len(chunk)
            assert m == mp.counts[s]
            rid, occ, write, nr = plan_grouped_python(py[s], chunk, now)
            want_rounds = max(want_rounds, nr)
            for name, got, want in (
                ("slot", mp.slot[s, :m], [p.slot for p in chunk]),
                ("rid", mp.rid[s, :m], rid),
                ("exists", mp.exists[s, :m], [p.exists for p in chunk]),
                ("occ", mp.occ[s, :m], occ),
                ("write", mp.write[s, :m], write),
                ("pos", mp.pos[[p.pos for p in chunk]], s * P + np.arange(m)),
            ):
                bad = np.flatnonzero(np.asarray(got).astype(np.int64) != np.asarray(want).astype(np.int64))
                assert not len(bad), (f, s, name, int(bad[0]), chunk[int(bad[0])].key)
            assert (mp.slot[s, m:] == -1).all() and not mp.write[s, m:].any()
            # The same results on both sides: a RESET_REMAINING lane removes
            # its bucket, every other lane's lasts an hour from now.
            removed = np.array([bool(p.req.behavior) for p in chunk])
            packed[s, 0, :m] = removed.astype(np.int64) << 1
            packed[s, 3, :m] = np.where(removed, 0, now + HOUR)
            commits = [j for j in range(m) if write[j]]
            py[s].commit([chunk[j].slot for j in commits], [int(packed[s, 3, j]) for j in commits],
                         [bool(removed[j]) for j in commits], keys=[chunk[j].key for j in commits])
            saw["groups"] += int((np.asarray(occ) > 0).sum())
            if f == len(frames) - 1:
                saw["expired"] += sum(1 for p in chunk if not p.exists)
        assert n_rounds == want_rounds, f
        mp.finish_wide(buckets.split_wide_answer(packed))
        saw["rounds"] = max(saw["rounds"], n_rounds)
        saw["evictions"] += sum(t.evictions for t in py) - ev
        for s in range(S):
            assert len(nat[s]) == len(py[s]) and nat[s].evictions == py[s].evictions, (f, s)
    # The frames held what they are meant to hold.
    assert saw["rounds"] >= 3 and saw["evictions"] >= lanes // 4 and saw["groups"] > 0, saw
    assert saw["expired"] > 0.9 * lanes, saw  # two hours on, a resident key is a recycled slot
    for s in range(S):
        assert sorted(nat[s].keys()) == sorted(py[s].keys())
        stats = nat[s].index_stats
        assert stats["refused"] == 0 and stats["probes"] < 2 * stats["lookups"]


# What a wide answer holds, a value a lane: both planes of a row at work.
# A time a month and a year past a clock near 1.8e12 whose lo word has bit
# 31 set (a signed lo would borrow from the hi word), 0 (a removed lane).
_WIDE_NOW = (419 << 32) + 1_000_000_000
_WIDE_VALUES = (
    0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32, 2**53 + 1, 2**62, 2**63 - 1, -1, -(2**63),
    _WIDE_NOW, _WIDE_NOW + 31 * 86_400_000, _WIDE_NOW + 365 * 86_400_000,
)


@pytest.mark.parametrize("shards", [1, 4])
def test_finish_wide_puts_the_planes_together_as_numpy_does(shards):
    """`gt_mesh_finish_wide` reads the wide answer as it leaves the device,
    i32[S, 8, P] lo planes then hi planes, and composes a lane's 64 bits in
    its loop: held to `buckets.compose_wide_answer` (numpy, the path with
    no compiler) on `remaining`, `reset_time`, the status bit, and on what
    the slot table keeps of `new_expire` and of the removed bit."""
    from gubernator_tpu.models.shard import pad_size

    rng = np.random.default_rng([40, shards])
    n = 6 * len(_WIDE_VALUES)
    keys = [f"fw{i:04d}" for i in range(n)]
    nat = [native.NativeSlotTable(4 * n) for _ in range(shards)]
    mp = native.NativeMeshPlanner(nat, keys, _WIDE_NOW)
    P = pad_size(int(mp.counts.max()))
    assert mp.plan_grouped(_Cols(n), int(Behavior.RESET_REMAINING), P) == 1
    lane_rows = np.stack([
        rng.integers(0, 4, n),  # status | removed << 1
        *(rng.permutation(np.resize(np.array(_WIDE_VALUES, np.int64), n)) for _ in range(2)),
        np.resize(np.array(_WIDE_VALUES[-3:], np.int64), n),  # new_expire: live times
    ])
    flat = np.zeros((4, shards * P), np.int64)
    flat[:, mp.pos[:n]] = lane_rows
    packed = np.ascontiguousarray(flat.reshape(4, shards, P).transpose(1, 0, 2))
    planes = buckets.split_wide_answer(packed)
    assert planes.dtype == np.int32 and planes.shape == (shards, 8, P)
    assert (planes[:, 1:4] < 0).any() and (planes[:, 5:] != 0).any() and not planes[:, 4].any()
    assert (buckets.compose_wide_answer(planes) == packed).all()
    with pytest.raises(TypeError, match="i32 planes"):
        mp.finish_wide(packed)

    status, remaining, reset = mp.finish_wide(planes)
    want = buckets.compose_wide_answer(planes).transpose(1, 0, 2).reshape(4, -1)[:, mp.pos[:n]]
    assert (status == (want[0] & 1)).all()
    assert (remaining == want[1]).all() and set(_WIDE_VALUES) <= set(remaining.tolist())
    assert (reset == want[2]).all()
    removed = (want[0] >> 1) & 1
    for i, key in enumerate(keys):
        table = nat[int(mp.pos[i]) // P]
        slot = table.get_slot(key)
        assert (slot is None) == bool(removed[i]), key
        if slot is not None:
            assert table.get_expire_bulk([slot])[0] == want[3][i], key


def test_occupancy_rows_serve_the_key_indexs_health():
    """Each shard's row of `occupancy_stats()` (the `occupancy.shards` of
    GET /debug/status) carries the native index's lookups, probes, refused
    hash hits and size; the Python table has no index and no such key."""
    import json

    st = one_device_store(64, use_native=True)
    st.apply([_req(f"o{i % 9}") for i in range(30)], 1_700_000_000_000)
    (row,) = st.occupancy_stats()
    assert row["used"] == 9
    assert set(row["index"]) == {"lookups", "probes", "refused", "entries"}
    assert row["index"]["lookups"] >= 9 and row["index"]["probes"] >= row["index"]["lookups"]
    assert row["index"]["entries"] == 128 and row["index"]["refused"] == 0
    json.dumps(row)
    (row,) = one_device_store(64, use_native=False).occupancy_stats()
    assert "index" not in row
