"""What the benchmark sends in the accepted cells is, byte for byte, what it
sent before PR 38 taught the population calendar quotas: for each cell of
BENCHMARK.json as PR 37 left it and two seeds, at the rehearsal's 20,000 keys,
the pool's payloads with their key indices, and the population's `key_bytes`,
`algo`, `limit` and `key_of_rank`, hash to what the parent tree (8c8e2c8) gave
(SHA-256, first 16 hex digits; noted before any edit).  A cell added since is
not in the table: its payloads had no earlier form.  CPU, seconds."""

from __future__ import annotations

import hashlib
import importlib
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402
from chipbench.population import Population  # noqa: E402

HOST = "127.0.0.1:1"  # a payload's Host header; the parent's hashes were taken with this one
# (cell, seed): (pool, population)
PARENT = {
    ("v5e1-1m.frames", 7): ("c1f19df15bc38478", "c78aef735999f33e"),
    ("v5e1-1m.frames", 2147483653): ("8eb7fbfc0717d2a1", "c47211a5b4c076f3"),
    ("v5e1-1m.singles", 7): ("3298b1c6506f1ce3", "c78aef735999f33e"),
    ("v5e1-1m.singles", 2147483653): ("687854fcc288682a", "c47211a5b4c076f3"),
    ("v5e4-mesh-1m.frames", 7): ("eefcbafc23f11fa9", "c78aef735999f33e"),
    ("v5e4-mesh-1m.frames", 2147483653): ("12784cd9733e1ffc", "c47211a5b4c076f3"),
    ("ycsb-f-32m.frames", 7): ("98d6612ab7bec310", "c78aef735999f33e"),
    ("ycsb-f-32m.frames", 2147483653): ("8a3bc7614e25601b", "c47211a5b4c076f3"),
    ("v5e1-1m-keylimits.frames", 7): ("df3f9078725d90ef", "d884e0c665f5e7b4"),
    ("v5e1-1m-keylimits.frames", 2147483653): ("b58383c3a5754f22", "d4fe1b8f5af32694"),
}


def sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(a if isinstance(a, bytes) else np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("cell,seed", sorted(PARENT))
def test_an_accepted_cell_sends_the_bytes_it_sent_before_calendar_quotas(cell, seed):
    bench = harness.load_json(REPO, "BENCHMARK.json")
    _, config, traffic = harness.find_cell(bench, cell)
    generator = importlib.import_module(f"chipbench.generators.{traffic['kind']}")
    pop = Population(config["population"], harness.REHEARSE_KEYS, seed)
    pool = generator.build_pool(pop, traffic, np.random.default_rng([seed, 0x706F6F6C]), HOST)
    got = (sha(*(x for r in pool for x in (r.payload, r.keys))),
           sha(pop.key_bytes, pop.algo, pop.limit, pop.key_of_rank))
    assert got == PARENT[cell, seed]
    assert not pop.calendar_units and not pop.behavior.any() and (pop.duration == pop.duration_ms).all()


def test_the_candidate_sends_the_calendar_bit_in_every_lane_and_its_twin_s_keys():
    """`greg-10m`'s population is `v5e1-1m`'s but for the calendar: the same
    keys, algorithms, limits and ranks from the same seed, behaviour 4 in every
    lane, and a duration of 2 (days) or 4 (months), about half each."""
    bench = harness.load_json(REPO, "BENCHMARK.json")
    _, config, _ = harness.find_cell(bench, "greg-10m.frames")
    pop = Population(config["population"], harness.REHEARSE_KEYS, 7)
    assert sha(pop.key_bytes, pop.algo, pop.limit, pop.key_of_rank) == PARENT["v5e1-1m.frames", 7][1]
    assert (pop.behavior == 4).all() and pop.calendar_units == [2, 4]
    assert 0.47 < (pop.duration == 2).mean() < 0.53
