"""The numpy batch folds the package ran until PR 32, kept as the
reference the native folds (`native.name_groups`, `native.cms_fold`)
are held to: `ref_fold_batch` is `TenantLedger._fold_batch` and
`ref_sketch_update` is `HotKeySketch.update`, body for body, as
functions of the object they fold into.  They share the objects' own
bookkeeping (`_promote_locked`, the top-K dictionary), so a ledger or a
sketch folded by the reference and one folded by the package must come
out equal: every count-min cell, totals, `other`, rows, the context.
Only which of several candidates with EQUAL estimates the top-K keeps
may differ (numpy's argsort and the native selection break ties their
own way)."""

import numpy as np

from gubernator_tpu import native
from gubernator_tpu.profiling import (
    NUMERIC_LANE_BYTES,
    _name_columns,
    _TenantCtx,
)


def ref_fold_batch(led, cols):
    """TenantLedger._fold_batch as numpy wrote it."""
    names, name_at, name_lens, uk_lens = _name_columns(cols)
    hashes = native.fnv1_batch(names)
    uh, first, inv = np.unique(
        hashes, return_index=True, return_inverse=True
    )
    ctx = _TenantCtx(inv, uh, first, name_at)
    lanes_u = np.bincount(inv, minlength=ctx.m).astype(np.int64)
    hits_u = np.bincount(
        inv, weights=np.asarray(cols.hits, dtype=np.float64),
        minlength=ctx.m,
    ).astype(np.int64)
    lane_bytes = name_lens + uk_lens + NUMERIC_LANE_BYTES
    bytes_u = np.bincount(
        inv, weights=lane_bytes.astype(np.float64), minlength=ctx.m
    ).astype(np.int64)
    with led._lock:
        led.batches += 1
        idx = (
            (uh[None, :] * led._salts[:, None]) >> np.uint64(17)
        ) % np.uint64(led.width)
        for r in range(led.depth):
            np.add.at(led._tab[r], idx[r].astype(np.intp), hits_u)
        est = led._tab[
            np.arange(led.depth)[:, None], idx.astype(np.intp)
        ].min(axis=0)
        led._totals["hits"] += int(hits_u.sum())
        led._totals["lanes"] += int(lanes_u.sum())
        led._totals["ingress_bytes"] += int(bytes_u.sum())
        tracked = np.isin(uh, led._row_hashes)
        for j in np.nonzero(tracked)[0]:
            row = led._rows[int(uh[j])]
            row.est = int(est[j])
            row.hits += int(hits_u[j])
            row.lanes += int(lanes_u[j])
            row.ingress_bytes += int(bytes_u[j])
        un = np.nonzero(~tracked)[0]
        if un.size:
            led._other["hits"] += int(hits_u[un].sum())
            led._other["lanes"] += int(lanes_u[un].sum())
            led._other["ingress_bytes"] += int(bytes_u[un].sum())
            led._promote_locked(
                un, est, uh, first, name_at,
                hits_u, lanes_u, bytes_u,
            )
    return ctx


def ref_sketch_update(sk, hashes, keys) -> None:
    """HotKeySketch.update as numpy wrote it."""
    n = len(hashes)
    if n == 0:
        return
    hs = np.ascontiguousarray(hashes, dtype=np.uint64)
    with sk._lock:
        now = sk._time()
        if now - sk._last_decay >= sk.decay_s:
            sk._last_decay = now
            sk._tab >>= 1
            for rec in sk._top.values():
                rec[0] >>= 1
        uh, first, counts = np.unique(
            hs, return_index=True, return_counts=True
        )
        idx = ((uh[None, :] * sk._salts[:, None])
               >> np.uint64(17)) % np.uint64(sk.width)
        for r in range(sk.depth):
            np.add.at(sk._tab[r], idx[r].astype(np.intp), counts)
        est = sk._tab[
            np.arange(sk.depth)[:, None], idx.astype(np.intp)
        ].min(axis=0)
        sk.total_lanes += n
        sk.batches += 1
        if len(sk._top) >= sk.topk:
            floor = min(rec[0] for rec in sk._top.values())
            cand = np.nonzero(est >= floor)[0]
            if cand.size > sk.topk:
                cand = cand[np.argsort(est[cand])[-sk.topk:]]
        else:
            cand = np.argsort(est)[max(0, est.size - sk.topk):]
        for j in cand:
            h = int(uh[j])
            rec = sk._top.get(h)
            if rec is not None:
                rec[0] = int(est[j])
            else:
                sk._top[h] = [int(est[j]), str(keys[int(first[j])])]
        if len(sk._top) > sk.topk:
            keep = sorted(
                sk._top.items(), key=lambda kv: kv[1][0], reverse=True
            )[: sk.topk]
            sk._top = dict(keep)


# ---------------------------------------------------------------------
# The cells' own shapes, for both files that hold the folds to the
# reference (tests/test_profiling.py, tests/test_observability.py).
# ---------------------------------------------------------------------
_ZIPF_CDF = {}


def zipf_ids(rng, n, universe=1_000_000, s=0.99):
    """`n` key ids drawn Zipfian(s) over `universe` keys, as the
    benchmark's frames draw them."""
    cdf = _ZIPF_CDF.get((universe, s))
    if cdf is None:
        w = np.arange(1, universe + 1, dtype=np.float64) ** -s
        cdf = _ZIPF_CDF[(universe, s)] = np.cumsum(w) / w.sum()
    return np.searchsorted(cdf, rng.random_sample(n))


def frame_cols(names, uks, hits):
    """One decoded GUBC frame: the column shape the native pump's take
    has (name blob + offsets)."""
    from gubernator_tpu import wire

    n = len(names)
    return wire.decode_ingress_frame(wire.encode_ingress_frame((
        list(names), list(uks), np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.asarray(hits, np.int64), np.full(n, 1_000_000, np.int64),
        np.full(n, 3_600_000, np.int64),
    )))


def takes(shape, seed=0):
    """The batches of one shape, each (names, unique_keys, hits)."""
    rng = np.random.RandomState(seed)

    def zipf_take(n, name="requests_per_sec", hits=None):
        uks = [f"account:{i:012d}" for i in zipf_ids(rng, n)]
        return [name] * n, uks, np.ones(n, np.int64) if hits is None else hits

    if shape == "zipf-4096":  # v5e1-1m.frames / ycsb-f-32m.frames
        return [zipf_take(4096) for _ in range(6)]
    if shape == "zipf-1028":  # v5e4-mesh-1m.frames
        return [zipf_take(1028) for _ in range(8)]
    if shape == "load-64":  # a population load's frame: 64 fresh keys
        return [
            (["requests_per_sec"] * 64,
             [f"account:{64 * t + i:012d}" for i in range(64)],
             np.zeros(64, np.int64))
            for t in range(12)
        ]
    if shape == "coalesced":  # one take of several callers' frames
        out = []
        for _ in range(5):
            parts = [
                zipf_take(int(rng.randint(20, 400)), name=f"api-{k % 3}")
                for k in range(int(rng.randint(2, 7)))
            ]
            out.append((
                [nm for p in parts for nm in p[0]],
                [uk for p in parts for uk in p[1]],
                np.concatenate([p[2] for p in parts]),
            ))
        return out
    if shape == "names-10k":  # the cardinality test's shape
        return [
            ([f"n{i}" for i in range(lo, lo + 500)],
             [f"k{i}" for i in range(500)], np.ones(500, np.int64))
            for lo in range(0, 10_000, 500)
        ]
    if shape == "hits-0-and-many":
        return [
            zipf_take(700, name=f"t{t % 4}",
                      hits=rng.randint(0, 2, 700) * rng.randint(1, 900, 700))
            for t in range(8)
        ]
    raise ValueError(shape)
