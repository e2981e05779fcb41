"""The served path's XLA programs compile for a TPU v5e at the size
chip_smoke.py serves: 1,048,576 slots and the 4096-lane wire.

No chip is needed: the TPU compiler is installed and compiles for a
*described* v5e:2x2 (the on-chip-measurement guide, section 2).  Nothing
runs, so these say nothing about answers or times — only that a later PR
has not made a program the chip's compiler refuses, or one that no
longer fits its memory.  Each compile takes the better part of a minute.

The topology is described inside a fixture, never at import: only one
process may hold libtpu, and every xdist worker imports this file.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.ops import buckets, global_ops
from gubernator_tpu.parallel import mesh as mesh_mod

SLOTS = 1 << 20  # GUBER_CACHE_SIZE of chip_smoke.py
LANES = 4096  # its frame width, and the bulk entry of GUBER_WARMUP_SHAPES
G_CAPACITY = 65536  # service.py: min(max(4096, cache_size), 65536)
NOW_MS = 1_790_000_000_000
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return Mesh(np.array(topo.devices[:1]), ("shard",))


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("shard",))


def _sharded(mesh, tree):
    """[S, ...] ShapeDtypeStructs laid out as MeshBucketStore lays out its
    arrays: a leading shard axis over the mesh."""
    n = mesh.devices.size
    sharding = NamedSharding(mesh, P("shard"))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n, *a.shape), a.dtype, sharding=sharding), tree
    )


def _state(mesh):
    per_shard = SLOTS // mesh.devices.size
    return _sharded(mesh, jax.eval_shape(lambda: buckets.init_state(per_shard)))


def _dict_wire(mesh, lanes_per_shard):
    words = 3 * lanes_per_shard + buckets.DICT_WIRE_TABLE_WORDS + buckets.WIRE_HEADER_WORDS
    assert buckets.dict_wire_lanes(words) == lanes_per_shard
    return _sharded(mesh, jax.ShapeDtypeStruct((words,), jnp.int32))


def _compile(what, lowered):
    t0 = time.monotonic()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    print(
        f"\n{what}: compiled in {time.monotonic() - t0:.1f} s; per device: "
        f"code {mem.generated_code_size_in_bytes}, arguments {mem.argument_size_in_bytes}, "
        f"outputs {mem.output_size_in_bytes}, temp {mem.temp_size_in_bytes} bytes"
    )
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES // 4
    return compiled


def test_packed_dict_wire_compiles_for_one_v5e(one_chip):
    """The program every 4096-lane columnar frame dispatches."""
    _compile(
        "dict wire, 1M slots x 4096 lanes, one chip",
        mesh_mod._dispatch_jit(
            one_chip, mesh_mod._rounds_packed_mesh, donate_wire=True
        ).lower(_state(one_chip), _dict_wire(one_chip, LANES)),
    )


def test_packed_dict_wire_compiles_for_four_v5e_without_a_collective(four_chips):
    """The four-chip cell's program (1028-lane frames pad to 1024 a shard):
    every chip reads the round count and the clock from its own row of the
    wire, so no chip waits for another inside a dispatch."""
    compiled = _compile(
        "dict wire, 1M slots x 4 x 1024 lanes, four chips",
        mesh_mod._dispatch_jit(
            four_chips, mesh_mod._rounds_packed_mesh, donate_wire=True
        ).lower(_state(four_chips), _dict_wire(four_chips, 1024)),
    )
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "collective-permute", "all-to-all"):
        assert f" {op}(" not in text and f" {op}-start(" not in text, op


def test_fused_k2_compiles_for_one_v5e(one_chip):
    """The launch-fusion program a backlogged coalescer dispatches."""
    wire = _dict_wire(one_chip, LANES)
    fused = mesh_mod._mesh_fused_packed_jit(one_chip, 2, False, donate_wires=True)
    _compile(
        "fused K=2, 1M slots x 4096 lanes, one chip",
        fused.lower(_state(one_chip), wire, wire),
    )


def test_narrow_wire_compiles_for_one_v5e(one_chip):
    """The per-lane wire with the narrow answer, one buffer of eleven words a
    lane: the fall-back of the dict wire (a limit a key), and what warmup
    compiles beside it."""
    wire = _sharded(
        one_chip,
        jax.ShapeDtypeStruct(
            (buckets.LANE_WIRE_WORDS * LANES + buckets.WIRE_HEADER_WORDS,), jnp.int32
        ),
    )
    _compile(
        "per-lane wire, 1M slots x 4096 lanes, one chip",
        mesh_mod._dispatch_jit(one_chip, mesh_mod._rounds_lanes_mesh).lower(
            _state(one_chip), wire
        ),
    )


def test_wide_dict_wire_answers_in_32_bit_words_for_one_v5e(one_chip):
    """The program every frame with a monthly calendar quota dispatches.  The
    TPU compiler keeps an s64 array as two u32 halves and hands them to the
    host through an `X64Combine`, which the runtime undoes and redoes at
    every fetch; the wide answer leaves as ONE s32[1, 8, 4096] instead, the
    halves it was carried as side by side."""
    compiled = _compile(
        "dict wire, wide answer, 1M slots x 4096 lanes, one chip",
        mesh_mod._dispatch_jit(
            one_chip, mesh_mod._rounds_packed_wide_mesh, donate_wire=True
        ).lower(_state(one_chip), _dict_wire(one_chip, LANES)),
    )
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    root = entry[entry.index("ROOT"):].splitlines()[0]
    assert f"s32[1,{buckets.WIDE_ANSWER_ROWS},{LANES}]" in root and "s64[" not in root, root
    assert "X64Combine" not in entry


def _sync_wire(mesh):
    words = global_ops.SYNC_WIRE_COLUMNS * mesh_mod.SYNC_WIDTH + buckets.WIRE_HEADER_WORDS
    return jax.ShapeDtypeStruct((1, words), jnp.int32, sharding=NamedSharding(mesh, P()))


def _gcols(mesh):
    return _sharded(mesh, jax.eval_shape(lambda: global_ops.init_global_columns(G_CAPACITY)))


def test_global_sync_compiles_for_four_v5e(four_chips):
    """The GLOBAL sync collective — the mesh's one cross-chip program —
    with the table sharded four ways: one launch over the SYNC_WIDTH gslots
    its wire names, of the 65,536 provisioned.  Two collectives (the hits to
    their owners, the owners' statuses to everyone), and the answer leaves
    replicated as 32-bit words."""
    compiled = _compile(
        f"GLOBAL sync, 4 x 262,144 slots, {mesh_mod.SYNC_WIDTH} of 65,536 gslots, four chips",
        mesh_mod._get_sync_fn(four_chips, "shard").lower(
            _state(four_chips), _gcols(four_chips), _sync_wire(four_chips)
        ),
    )
    text = compiled.as_text()
    assert 1 <= text.count(" all-reduce(") + text.count(" all-reduce-start(") <= 2
    # What the host fetches is 32-bit words (the gslot columns stay s64, and
    # stay on the device).
    entry = text[text.index("ENTRY"):]
    root = entry[entry.index("ROOT"):].splitlines()[0]
    assert f"s32[{global_ops.SYNC_ANSWER_ROWS},{mesh_mod.SYNC_WIDTH}]" in root, root


def test_global_sync_compiles_for_one_v5e_and_updates_its_columns_in_place(one_chip):
    """The same program as every one-chip daemon warms it.  State and gslot
    columns are donated: a launch may not copy either table (64 MB and
    2.9 MB) to change a launch's rows."""
    compiled = _compile(
        f"GLOBAL sync, 1M slots, {mesh_mod.SYNC_WIDTH} of 65,536 gslots, one chip",
        mesh_mod._get_sync_fn(one_chip, "shard").lower(
            _state(one_chip), _gcols(one_chip), _sync_wire(one_chip)
        ),
    )
    mem = compiled.memory_analysis()
    tables = 64 * SLOTS + G_CAPACITY * (4 + 5 * 8)
    assert mem.alias_size_in_bytes == tables
    assert mem.temp_size_in_bytes < tables // 16


def test_tier_moves_compile_for_one_v5e_and_copy_no_tier(one_chip):
    """The two-tier table's move program at the size PR 31 ran on the chip
    (32M keys; the benchmark's `ycsb-f-32m` is the one tier, `PERF.md` §6): a
    front of 1,048,576 slots, a back tier of 32,505,856 (2.08 GB) and one
    4096-record block.  Both tiers are donated and must be updated in place:
    a program that copied the back tier would move 2 GB a launch."""
    back = _sharded(one_chip, jax.eval_shape(lambda: buckets.init_back(32_505_856)))
    moves = _sharded(one_chip, jax.ShapeDtypeStruct((5, LANES), jnp.int32))
    compiled = _compile(
        "tier moves, 1M front + 32.5M back x 4096 records, one chip",
        mesh_mod._moves_mesh_jit.lower(_state(one_chip), back, moves),
    )
    mem = compiled.memory_analysis()
    tiers = 64 * (SLOTS + 32_505_856)
    assert mem.alias_size_in_bytes == tiers  # every row of both tiers in place
    assert mem.temp_size_in_bytes < tiers // 64
