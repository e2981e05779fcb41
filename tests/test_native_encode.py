"""The wire's encode in one native call (`NativeMeshPlanner.encode_wire`,
`gt_mesh_encode_wire`) held to the numpy reference it replaced on the
served path: `buckets.build_config_dict` + `pack_dict_wire` /
`pack_lane_wire` + `set_wire_header`, put together here as the stage put
them together before.  The per-lane wire is compared byte for byte; the
dictionary wire through `unpack_dict_wire`, the device's own decode, lane
by lane (the table's rows lie in order of first appearance, not of sorted
hash, and the program gathers by index), and against the request columns
themselves.  The rule that picks the wire is walked on both sides of each
of its limits: 256 and 257 configurations, 255 and 256 rounds, an `occ` of
65,535 and 65,536, `force_wire`, an empty frame."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.models.shard import make_columns, narrow_ok
from gubernator_tpu.ops import buckets

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the encode is the native host runtime's")

SEED = 43
# A clock whose low word has bit 31 set: a header or a delta that took
# the word signed would show.
NOW = (419 << 32) + 3_000_000_000
DAY, MONTH = 86_400_000, 31 * 86_400_000
assert DAY < 2**31 < MONTH


def _columns(n: int, configs: int, wide: bool, rng):
    """Request columns of `n` lanes over exactly `configs` distinct
    configurations, each met at least once: every third a calendar quota
    (`greg_duration != 0`; a day on the narrow answer, a month, whose
    expiry lies past 2**31 from now, on the wide one), the others plain,
    some of them with a stale `greg_expire` that no wire may carry as a
    delta.  The limit tells the configurations apart."""
    k = np.arange(configs, dtype=np.int64)
    calendar = k % 3 == 1
    span = MONTH if wide else DAY
    table = {
        "algo": (k % 2).astype(np.int32),
        "behavior": np.where(calendar, 4, (k // 2) % 2 * 8).astype(np.int32),
        "hits": k % 5,
        "limit": 100 + k + (2**40 if wide else 0) * (k % 4 != 2),
        "duration": np.where(calendar, k % 2, 60_000 + k % 7),
        "greg_expire": np.where(calendar, NOW + span - 1 - k, (k % 5 == 0) * 12_345),
        "greg_duration": np.where(calendar, span, 0),
    }
    pick = np.concatenate((k, rng.integers(0, configs, n - configs))) if n else k[:0]
    rng.shuffle(pick)
    cols = make_columns(
        *(table[c][pick] for c in ("algo", "behavior", "hits", "limit", "duration")),
        n, table["greg_expire"][pick], table["greg_duration"][pick])
    assert narrow_ok(cols, NOW) == (not wide)
    return cols


def _planner(shards: int, pad: int, n: int, rng, top_occ: int = 1000, top_rid: int = 200,
             span: int = 0):
    """A planner as `plan_grouped` leaves it, without tables: the encode
    reads its arrays alone.  `n` requests lie at random places of the
    [S, P] plan (of its first `span` places, if given); every other lane
    is padding (slot -1, zeros)."""
    pos = rng.permutation(span or shards * pad)[:n].astype(np.int64)

    def placed(values, dtype, fill=0):
        a = np.full((shards, pad), fill, dtype)
        a.reshape(-1)[pos] = values
        return a

    mp = native.NativeMeshPlanner.__new__(native.NativeMeshPlanner)
    mp._lib, mp._ptr, mp.n = native._get_lib(), None, n
    mp.slot = placed(rng.integers(0, 1 << 20, n), np.int32, -1)
    mp.exists = placed(rng.integers(0, 2, n), np.uint8)
    mp.write = placed(rng.integers(0, 2, n), np.uint8)
    mp.occ = placed(rng.integers(0, top_occ, n), np.int32)
    mp.rid = placed(rng.integers(0, top_rid, n), np.int32)
    mp.pos = np.zeros(max(n, 1), np.int64)
    mp.pos[:n] = pos
    if n:
        mp.occ.reshape(-1)[pos[0]] = top_occ
        mp.rid.reshape(-1)[pos[-1]] = top_rid
    return mp


def _reference(mp, cols, n_rounds: int, narrow: bool, force_wire):
    """The stage's encode as numpy made it (parent of PR 43,
    `_stage_columns`): (wire, lane_wire, config_rows)."""
    pos, (shards, pad) = mp.pos[:mp.n], mp.slot.shape
    rows, enc = 0, None
    if force_wire is None and n_rounds <= 255:
        rows, enc = buckets.build_config_dict(cols, NOW)
    if enc is not None and int(mp.occ.max()) <= 65535:
        cfg = np.zeros((shards, pad), np.uint8)
        cfg.reshape(-1)[pos] = enc[0]
        wire = buckets.pack_dict_wire(mp.slot, mp.exists, mp.write, cfg, mp.occ, mp.rid, enc[1])
    else:
        expire = cols.greg_expire
        if narrow:
            expire = np.where(cols.greg_duration != 0, cols.greg_expire - NOW, 0)
        wire = buckets.pack_lane_wire(
            mp.slot, mp.exists, mp.write, mp.occ, mp.rid, pos,
            (cols.algo, cols.behavior, cols.hits, cols.limit, cols.duration,
             expire, cols.greg_duration), wide=not narrow)
    buckets.set_wire_header(wire, n_rounds, NOW)
    return wire, enc is None or int(mp.occ.max()) > 65535, rows


def _encode(mp, cols, n_rounds: int, narrow: bool, force_wire):
    pad = mp.slot.shape[1]
    return mp.encode_wire(
        cols, NOW, n_rounds, narrow, force_wire is not None,
        buckets.dict_wire_words(pad), buckets.lane_wire_words(pad, wide=not narrow))


def _decoded(wire, pad: int):
    """A dictionary wire as the program reads it: `unpack_dict_wire` over
    every shard's row, the seven values gathered by each lane's index."""
    unpack = jax.jit(jax.vmap(lambda w: buckets.unpack_dict_wire(w, pad)))
    slot, flags, cfg, occ, rid, rows = jax.tree.map(np.asarray, unpack(wire))
    take = np.take_along_axis
    values = [take(r, cfg.astype(np.int64), axis=1) for r in rows]
    return dict(slot=slot, flags=flags, cfg=cfg, occ=occ, rid=rid, values=values, rows=rows)


def _hold(mp, cols, n_rounds: int, narrow: bool, force_wire, want_lane: bool, want_rows: int):
    """Encode natively and by the reference; hold the first to the second
    and to the request columns.  Returns the native wire."""
    shards, pad = mp.slot.shape
    pos = mp.pos[:mp.n]
    got, lane_wire, rows = _encode(mp, cols, n_rounds, narrow, force_wire)
    want, ref_lane, ref_rows = _reference(mp, cols, n_rounds, narrow, force_wire)
    assert (lane_wire, rows) == (want_lane, want_rows) == (ref_lane, ref_rows)
    assert got.dtype == np.int32 and got.shape == want.shape and got.flags.c_contiguous
    header = np.s_[:, got.shape[1] - buckets.WIRE_HEADER_WORDS:]
    assert (got[header] == want[header]).all()
    assert (got[header][:, 0] == n_rounds).all() and not got[header][:, 3].any()
    padding = mp.slot == -1
    assert padding.sum() == shards * pad - mp.n
    if lane_wire:
        assert got.tobytes() == want.tobytes()
        words = (got.shape[1] - buckets.WIRE_HEADER_WORDS) // pad
        assert words == (buckets.LANE_WIRE_WORDS if narrow else buckets.LANE_WIRE_WORDS_WIDE)
        lanes = got[header[0], :words * pad].reshape(shards, words, pad)
        assert not lanes[:, 1:].transpose(0, 2, 1)[padding].any()  # zeros beside slot -1
        return got
    g, w = _decoded(got, pad), _decoded(want, pad)
    for name in ("slot", "flags", "occ", "rid"):
        assert (g[name] == w[name]).all(), name
    assert (g["slot"] == mp.slot).all() and (g["occ"] == mp.occ).all()
    assert (g["flags"] == (mp.exists | (mp.write << 1))).all() and (g["rid"] == mp.rid).all()
    assert not g["cfg"][padding].any()
    delta = np.where(cols.greg_duration != 0, cols.greg_expire - NOW, 0)
    columns = (cols.algo, cols.behavior, cols.hits, cols.limit, cols.duration, delta, cols.greg_duration)
    for k, col in enumerate(columns):
        assert (g["values"][k].reshape(-1)[pos] == col).all(), k
        assert (w["values"][k].reshape(-1)[pos] == col).all(), k
    # The table: `rows` rows in use, each configuration once, zeros
    # after them, the same copy in every shard's row.
    table = np.stack([r[0] for r in g["rows"]], axis=1)
    assert len(np.unique(table[:rows], axis=0)) == rows and not table[rows:].any()
    assert int(g["cfg"].max()) == rows - 1
    assert all((r == r[:1]).all() for r in g["rows"])
    return got


@pytest.mark.parametrize("answer", ["narrow", "wide"])
@pytest.mark.parametrize("configs", [1, 32, 256, 257, 4096])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_native_encode_is_the_numpy_encode(shards, configs, answer):
    """Calendar and plain lanes mixed, padding lanes among them: up to 256
    configurations ride the dictionary wire, one more the per-lane wire,
    and the count is of ALL the frame's configurations either way."""
    wide = answer == "wide"
    rng = np.random.default_rng([SEED, shards, configs, wide])
    n = 5000 if configs == 4096 else 700
    cols = _columns(n, configs, wide, rng)
    mp = _planner(shards, (8192 if configs == 4096 else 1024) // shards, n, rng)
    _hold(mp, cols, 201, not wide, None, want_lane=configs > 256, want_rows=configs)


@pytest.mark.parametrize("answer", ["narrow", "wide"])
@pytest.mark.parametrize("shards", [1, 4])
def test_an_empty_frame_takes_the_per_lane_wire(shards, answer):
    """`n = 0`: nothing to intern, every lane padding, the header still
    written (the numpy rule: `build_config_dict` of no lane is None)."""
    rng = np.random.default_rng([SEED, shards])
    cols = _columns(0, 0, False, rng)
    mp = _planner(shards, 64, 0, rng)
    wire = _hold(mp, cols, 1, answer == "narrow", None, want_lane=True, want_rows=0)
    assert (wire[:, :64] == -1).all() and not wire[:, 64:-buckets.WIRE_HEADER_WORDS].any()


@pytest.mark.parametrize("top_occ", [65_535, 65_536])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_largest_occ_the_dictionary_wire_holds_is_65535(shards, top_occ):
    rng = np.random.default_rng([SEED, shards, top_occ])
    cols = _columns(300, 32, False, rng)
    mp = _planner(shards, 512 // shards, 300, rng, top_occ=top_occ)
    assert int(mp.occ.max()) == top_occ
    _hold(mp, cols, 3, True, None, want_lane=top_occ > 65_535, want_rows=32)


@pytest.mark.parametrize("n_rounds", [255, 256])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_most_rounds_the_dictionary_wire_holds_is_255(shards, n_rounds):
    """Past 255 the rule is decided before any count: nothing is interned
    and the tally reads 0 configurations, as it did."""
    rng = np.random.default_rng([SEED, shards, n_rounds])
    cols = _columns(300, 32, False, rng)
    mp = _planner(shards, 512 // shards, 300, rng, top_rid=n_rounds - 1)
    lane = n_rounds > 255
    _hold(mp, cols, n_rounds, True, None, want_lane=lane, want_rows=0 if lane else 32)


@pytest.mark.parametrize("force_wire", ["narrow", "wide"])
@pytest.mark.parametrize("shards", [1, 4])
def test_force_wire_pins_the_per_lane_wire_of_either_answer(shards, force_wire):
    """A frame the dictionary would hold, forced off it (warm-up and tests
    do): `narrow` as `_prepare_columns` derives it."""
    rng = np.random.default_rng([SEED, shards, force_wire == "wide"])
    cols = _columns(300, 32, False, rng)
    mp = _planner(shards, 512 // shards, 300, rng)
    narrow = narrow_ok(cols, NOW) and force_wire != "wide"
    assert narrow == (force_wire == "narrow")
    _hold(mp, cols, 2, narrow, force_wire, want_lane=True, want_rows=0)


@pytest.mark.parametrize("configs", [1, 300], ids=["dictionary", "lanes"])
@pytest.mark.parametrize("shards", [1, 4])
def test_padding_lanes_read_slot_minus_one_and_zeros(shards, configs):
    """A frame that fills few of its lanes (a flush of singles fills 16 of
    64; here one shard of four holds nothing at all)."""
    rng = np.random.default_rng([SEED, shards, configs])
    n = configs + 3
    cols = _columns(n, configs, False, rng)
    mp = _planner(shards, 1024, n, rng, span=1024)  # every request on shard 0
    wire = _hold(mp, cols, 201, True, None, want_lane=configs > 256, want_rows=configs)
    assert (wire[1:, :1024] == -1).all() and (wire[0, :1024] >= 0).sum() == n


@pytest.mark.parametrize("shards", [1, 4])
def test_two_configurations_of_one_polynomial_hash_keep_their_rows(shards):
    """`build_config_dict` groups lanes by h = ((algo * M + behavior) * M +
    hits) * M + limit ... with M = 1,000,003, verifies every lane against
    its group, and gives the dictionary up on a collision.  One more hit
    and M less of limit is such a pair.  The native table compares the
    seven values: the frame stays on the dictionary wire, two rows."""
    M = 1_000_003
    rng = np.random.default_rng([SEED, shards, M])
    n = 200
    twin = rng.integers(0, 2, n)
    twin[:2] = 0, 1
    cols = make_columns(
        np.zeros(n, np.int32), np.zeros(n, np.int32), 1 + twin, 2 * M - M * twin,
        np.full(n, 60_000, np.int64), n)
    assert buckets.build_config_dict(cols, NOW) == (1, None)  # one hash, no dictionary
    mp = _planner(shards, 256 // shards, n, rng)
    wire, lane_wire, rows = _encode(mp, cols, 1, True, None)
    assert (lane_wire, rows) == (False, 2)
    g = _decoded(wire, 256 // shards)
    pos = mp.pos[:n]
    assert (g["values"][2].reshape(-1)[pos] == cols.hits).all()
    assert (g["values"][3].reshape(-1)[pos] == cols.limit).all()
    assert set(g["rows"][3][0, :2].tolist()) == {2 * M, M} and not g["rows"][3][0, 2:].any()


def test_a_row_width_the_native_side_would_not_write_is_refused():
    """The layout is `buckets`'; the C++ side has the same constants and
    says so before it writes a word."""
    rng = np.random.default_rng(SEED)
    cols = _columns(10, 2, False, rng)
    mp = _planner(1, 64, 10, rng)
    lane = buckets.lane_wire_words(64, wide=False)
    with pytest.raises(ValueError, match="layout"):
        mp.encode_wire(cols, NOW, 1, True, False, buckets.dict_wire_words(64) + 1, lane)
    with pytest.raises(ValueError, match="layout"):
        mp.encode_wire(cols, NOW, 1, True, False, buckets.dict_wire_words(64),
                       buckets.lane_wire_words(64, wide=True))
