"""SlotTable unit tests (cache.go semantics: expiry, LRU, accounting)."""

from gubernator_tpu.models.slot_table import SlotTable

from .conftest import one_device_store


def test_assign_and_hit():
    t = SlotTable(4)
    s, exists = t.lookup_or_assign("a", 100)
    assert not exists
    t.commit([s], [200], [False])
    s2, exists = t.lookup_or_assign("a", 150)
    assert s2 == s and exists
    assert t.hits == 1 and t.misses == 1


def test_expired_recycles_same_slot():
    t = SlotTable(4)
    s, _ = t.lookup_or_assign("a", 100)
    t.commit([s], [200], [False])
    # Strict expiry boundary: at exactly ExpireAt the item is still live
    # (cache.go:151 `ExpireAt < now`).
    s2, exists = t.lookup_or_assign("a", 200)
    assert s2 == s and exists
    s2, exists = t.lookup_or_assign("a", 201)  # past expiry
    assert s2 == s and not exists


def test_lru_eviction_order():
    t = SlotTable(2)
    sa, _ = t.lookup_or_assign("a", 0)
    sb, _ = t.lookup_or_assign("b", 0)
    t.commit([sa, sb], [10**15, 10**15], [False, False])
    t.lookup_or_assign("a", 1)  # touch a; b becomes LRU
    sc, _ = t.lookup_or_assign("c", 2)
    assert sc == sb  # b evicted
    assert t.get_slot("b") is None
    assert t.get_slot("a") == sa
    assert t.evictions == 1


def test_removed_slot_freed():
    t = SlotTable(2)
    s, _ = t.lookup_or_assign("a", 0)
    t.commit([s], [0], [True])
    assert len(t) == 0
    s2, exists = t.lookup_or_assign("b", 0)
    assert not exists
    assert s2 == s  # freed slot reused




class TestColumnarNarrowAndPipelined:
    """The int32 wire (buckets.apply_rounds32) and the pipelined
    apply_columns_async must be semantically identical to the wide
    synchronous path."""

    def _cols(self, n, rng, now, greg=False):
        import numpy as np

        key_ids = rng.randint(0, max(n // 2, 1), size=n)
        keys = [f"nw:{k}" for k in key_ids]
        return keys, dict(
            algorithm=(key_ids % 2).astype(np.int32),
            behavior=np.zeros(n, np.int32),
            hits=np.ones(n, np.int64),
            limit=np.full(n, 7, np.int64),
            duration=np.full(n, 60_000, np.int64),
        )

    def test_narrow_matches_wide(self):
        import numpy as np


        rng = np.random.RandomState(7)
        now = 1_700_000_000_000
        n = 257
        keys, cols = self._cols(n, rng, now)
        narrow = one_device_store(1024)
        wide = one_device_store(1024)
        # Force the wide path by pushing one value over int32.
        wide_cols = dict(cols)
        for step in range(3):
            r1 = narrow.apply_columns(keys, now_ms=now + step, **cols)
            big = dict(wide_cols)
            big["limit"] = cols["limit"].copy()
            r2 = wide.apply_columns(
                keys, now_ms=now + step,
                algorithm=cols["algorithm"], behavior=cols["behavior"],
                hits=cols["hits"].astype(np.int64),
                limit=np.where(np.arange(n) == n - 1, 2**32, cols["limit"]),
                duration=cols["duration"],
            )
            # all lanes except the int64-limit one must agree
            for f in ("status", "remaining", "reset_time"):
                assert (np.asarray(r1[f])[:-1] == np.asarray(r2[f])[:-1]).all(), (
                    step, f)

    def test_narrow_predicate(self):
        import numpy as np

        from gubernator_tpu.models.shard import _Columns, narrow_ok

        now = 1_700_000_000_000
        c = _Columns(4)
        c.hits[:] = 1
        c.limit[:] = 10
        c.duration[:] = 1000
        c.greg_expire[:] = 0
        c.greg_duration[:] = 0
        assert narrow_ok(c, now)
        c.limit[2] = 2**31
        assert not narrow_ok(c, now)
        c.limit[2] = 10
        # Gregorian monthly: delta exceeds int32 only for huge spans
        c.greg_duration[1] = 3_000_000_000
        c.greg_expire[1] = now + 1000
        assert not narrow_ok(c, now)

    def test_dict_wire_parity_and_fallback(self):
        """The config-dictionary wire (few distinct configs) must match
        the per-lane narrow wire exactly; >256 distinct configs fall
        back; the lane->config mapping is exact."""
        import numpy as np

        from gubernator_tpu.models.shard import make_columns
        from gubernator_tpu.ops import buckets

        rng = np.random.RandomState(11)
        now = 1_700_000_000_000
        n = 400
        key_ids = rng.randint(0, 200, size=n)
        keys = [f"dw:{k}" for k in key_ids]
        few = dict(
            algorithm=(key_ids % 2).astype(np.int32),
            behavior=np.zeros(n, np.int32),
            hits=(1 + key_ids % 3).astype(np.int64),
            limit=np.full(n, 50, np.int64),
            duration=(60_000 + (key_ids % 4) * 1000).astype(np.int64),
        )
        # few-configs batch dict-encodes: 2 algos x 3 hits x 4 durations
        cols = make_columns(few["algorithm"], few["behavior"], few["hits"],
                            few["limit"], few["duration"], n)
        rows, enc = buckets.build_config_dict(cols, now)
        assert enc is not None and 0 < rows <= 24
        cfg_idx, table = enc
        for j in range(0, n, 37):  # spot-check exact lane->config mapping
            k = cfg_idx[j]
            assert table[0][k] == few["algorithm"][j]
            assert table[2][k] == few["hits"][j]
            assert table[4][k] == few["duration"][j]

        # >256 distinct configs: fallback to per-lane wire
        many = dict(few)
        many["limit"] = (10 + np.arange(n)).astype(np.int64)
        cols_many = make_columns(many["algorithm"], many["behavior"],
                                 many["hits"], many["limit"],
                                 many["duration"], n)
        assert buckets.build_config_dict(cols_many, now) == (n, None)

        # End-to-end: the dict wire must match the WIDE path lane for
        # lane on identical values (wide forced by one int64 lane,
        # which is excluded from the comparison).
        a = one_device_store(1024)
        b = one_device_store(1024)
        wide_keys = keys + ["dw:wide"]
        for step in range(3):
            r1 = a.apply_columns(keys, now_ms=now + step, **few)
            r2 = b.apply_columns(
                wide_keys, now_ms=now + step,
                algorithm=np.append(few["algorithm"], 0).astype(np.int32),
                behavior=np.append(few["behavior"], 0).astype(np.int32),
                hits=np.append(few["hits"], 1),
                limit=np.append(few["limit"], 2**32),  # forces wide
                duration=np.append(few["duration"], 60_000),
            )
            for f in ("status", "remaining", "reset_time"):
                assert (np.asarray(r1[f]) == np.asarray(r2[f])[:-1]).all(), (step, f)

    def test_pipelined_matches_sync_with_duplicates(self):
        import numpy as np


        rng = np.random.RandomState(3)
        now = 1_700_000_000_000
        n = 128
        keys, cols = self._cols(n, rng, now)
        sync = one_device_store(512)
        pipe = one_device_store(512)
        sync_res = [sync.apply_columns(keys, now_ms=now + i, **cols) for i in range(4)]
        handles = [pipe.apply_columns_async(keys, now_ms=now + i, **cols) for i in range(4)]
        pipe_res = [h.result() for h in handles]
        # resolving out of order must also be safe (FIFO enforced inside)
        assert handles[2].done
        for a, b in zip(sync_res, pipe_res):
            for f in ("status", "remaining", "reset_time"):
                assert (np.asarray(a[f]) == np.asarray(b[f])).all()


class TestGroupedDuplicates:
    """The analytic duplicate-group path (gt_batch_plan_grouped +
    occurrence math in ops/buckets.py) must match applying the same
    requests ONE AT A TIME in request order — the reference's
    mutex-serialized semantics (gubernator.go:336-337)."""

    def _differential(self, make_req, steps=60, seed=0):
        import numpy as np

        from gubernator_tpu.types import RateLimitRequest

        rng = np.random.RandomState(seed)
        grouped = one_device_store(256)
        serial = one_device_store(256)
        now = 1_700_000_000_000
        for step in range(steps):
            reqs = make_req(rng, step)
            now += rng.randint(0, 400)
            got = grouped.apply(reqs, now)
            want = [serial.apply([r], now)[0] for r in reqs]
            for i, (g, w) in enumerate(zip(got, want)):
                assert (g.status, g.remaining, g.reset_time) == (
                    w.status, w.remaining, w.reset_time,
                ), (step, i, reqs[i], g, w)

    def test_hot_key_token(self):
        from gubernator_tpu.types import Algorithm, RateLimitRequest

        def make(rng, step):
            # one hot key hammered 1-30x per batch + a few cold keys
            n_hot = rng.randint(1, 30)
            hits = int(rng.choice([0, 1, 1, 2, 5]))
            return [
                RateLimitRequest(
                    name="grp", unique_key="hot", hits=hits, limit=17,
                    duration=5_000, algorithm=Algorithm.TOKEN_BUCKET,
                )
                for _ in range(n_hot)
            ] + [
                RateLimitRequest(
                    name="grp", unique_key=f"cold{rng.randint(5)}", hits=1,
                    limit=3, duration=2_000, algorithm=Algorithm.TOKEN_BUCKET,
                )
                for _ in range(rng.randint(0, 4))
            ]

        self._differential(make, seed=11)

    def test_hot_key_leaky(self):
        from gubernator_tpu.types import Algorithm, RateLimitRequest

        def make(rng, step):
            n = rng.randint(1, 25)
            hits = int(rng.choice([0, 1, 2, 7]))
            return [
                RateLimitRequest(
                    name="grp", unique_key="lk", hits=hits, limit=21,
                    duration=3_000, algorithm=Algorithm.LEAKY_BUCKET,
                )
                for _ in range(n)
            ]

        self._differential(make, seed=22)

    def test_non_uniform_falls_back(self):
        """Varying hits/limit per duplicate forces the round path; the
        mix of grouped and round lanes in one batch must still match."""
        from gubernator_tpu.types import Algorithm, RateLimitRequest

        def make(rng, step):
            out = []
            for _ in range(rng.randint(2, 12)):
                out.append(
                    RateLimitRequest(
                        name="grp", unique_key="mix",
                        hits=int(rng.choice([1, 2])),   # non-uniform
                        limit=int(rng.choice([9, 9, 11])),
                        duration=4_000,
                        algorithm=Algorithm.TOKEN_BUCKET,
                    )
                )
            for _ in range(rng.randint(1, 10)):
                out.append(
                    RateLimitRequest(  # uniform group alongside
                        name="grp", unique_key="uni", hits=1, limit=6,
                        duration=4_000, algorithm=Algorithm.LEAKY_BUCKET,
                    )
                )
            rng.shuffle(out)
            return out

        self._differential(make, seed=33)

    def test_reset_remaining_group_is_sequential(self):
        from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

        def make(rng, step):
            return [
                RateLimitRequest(
                    name="grp", unique_key="rr", hits=1, limit=4,
                    duration=3_000, algorithm=Algorithm.TOKEN_BUCKET,
                    behavior=(Behavior.RESET_REMAINING if rng.random() < 0.3 else 0),
                )
                for _ in range(rng.randint(1, 10))
            ]

        self._differential(make, seed=44)

    def test_grouped_over_limit_create(self):
        """Thundering herd on a cold key with hits > limit (the leaky
        over-create stores 0, token keeps limit)."""
        from gubernator_tpu.types import Algorithm, RateLimitRequest

        def make(rng, step):
            algo = Algorithm.TOKEN_BUCKET if step % 2 else Algorithm.LEAKY_BUCKET
            return [
                RateLimitRequest(
                    name="grp", unique_key=f"burst{step}", hits=9, limit=5,
                    duration=1_000, algorithm=algo,
                )
                for _ in range(rng.randint(2, 8))
            ]

        self._differential(make, steps=20, seed=55)


def test_narrow_batch_preserves_wide_expiry():
    """A leaky bucket created with a >int32-ms duration (wide path)
    keeps its exact far-future expiry bookkeeping when a later NARROW
    batch passes it through unchanged (hits=0 status query with a small
    config): the -2 sentinel reconstructs the absolute value instead of
    clipping the delta to ~24.8 days."""
    import numpy as np

    from gubernator_tpu.types import Algorithm

    now = 1_700_000_000_000
    thirty_days = 30 * 24 * 3600 * 1000  # > 2**31 ms
    store = one_device_store(64)
    store.apply_columns(
        ["long_k"],
        algorithm=np.array([Algorithm.LEAKY_BUCKET], np.int32),
        behavior=np.zeros(1, np.int32),
        hits=np.ones(1, np.int64),
        limit=np.array([10], np.int64),
        duration=np.array([thirty_days], np.int64),
        now_ms=now,
    )
    slot = store.tables[0].get_slot("long_k")
    assert int(store.tables[0].get_expire_bulk([slot])[0]) == now + thirty_days

    # Narrow batch (every column fits int32): a status query on the
    # long-lived key.  hits=0 on a leaky bucket mutates nothing — the
    # kernel passes the stored expiry straight through.
    later = now + 1000
    r = store.apply_columns(
        ["long_k", "other_k"],
        algorithm=np.array([Algorithm.LEAKY_BUCKET] * 2, np.int32),
        behavior=np.zeros(2, np.int32),
        hits=np.array([0, 1], np.int64),
        limit=np.array([10, 5], np.int64),
        duration=np.array([60_000, 1000], np.int64),
        now_ms=later,
    )
    assert int(np.asarray(r["remaining"])[0]) == 9
    # The regression: a clipped delta would have rewritten this to
    # later + ~2**31 ms (~24.8 days), silently shortening the bucket's
    # life by ~5 days.
    assert int(store.tables[0].get_expire_bulk([slot])[0]) == now + thirty_days
