"""What `v5e1-1m-mixed.frames` sends, held to its bytes: the pool that
`generators/frames_mixed.py` builds for one seed at the rehearsal's 20,000 keys
hashes to what it gave when PR 41 wrote it (SHA-256, first 16 hex digits), its
frames hold the keys `frames` gives the same seed, and one lane in a hundred
carries exactly one of the bits 1, 2, 16; the load's and the read-back's frames
(the harness's own `frames.frame_payload`) carry behaviour 0 in every lane; and
the accepted cells still send what `test_payload_identity.py` pins (its table,
its hashes: none of them moved when this generator came).  CPU, seconds."""

from __future__ import annotations

import importlib
import os
import struct
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import gubc, harness  # noqa: E402
from chipbench.generators import frames, frames_mixed  # noqa: E402
from chipbench.population import Population  # noqa: E402
from test_payload_identity import HOST, PARENT, sha  # noqa: E402

CELL = "v5e1-1m-mixed.frames"
SEED = 2147483653
PINNED = "045361f1df1ded28"  # the pool's payloads and key indices, this seed


def behavior_column(payload: bytes, pop) -> np.ndarray:
    """The behaviour a lane of one request frame, read from the byte layout
    (`gubc.encode_frame`): magic, header, two fixed-width string columns,
    algorithm i32[n], behaviour i32[n]."""
    body = payload[payload.index(b"\r\n\r\n") + 4:]
    assert body[:4] == gubc.MAGIC
    _, kind, n = struct.unpack_from("<BBI", body, 4)
    assert kind == gubc.KIND_REQUEST
    at = 10
    for width in (len(pop.name), pop.key_width):
        (blob,) = struct.unpack_from("<I", body, at)
        assert blob == n * width
        at += 4 + 4 * (n + 1) + blob
    return np.frombuffer(body, np.int32, n, at + 4 * n)


@pytest.fixture(scope="module")
def cell():
    bench = harness.load_json(REPO, "BENCHMARK.json")
    _, config, traffic = harness.find_cell(bench, CELL)
    return config, traffic


def test_the_pool_is_pinned_and_holds_the_keys_frames_gives_the_seed(cell):
    config, traffic = cell
    assert traffic["kind"] == "frames_mixed"
    generator = importlib.import_module(f"chipbench.generators.{traffic['kind']}")
    pop = Population(config["population"], harness.REHEARSE_KEYS, SEED)
    pool = generator.build_pool(pop, traffic, np.random.default_rng([SEED, 0x706F6F6C]), HOST)
    assert sha(*(x for r in pool for x in (r.payload, r.keys))) == PINNED
    # The population is v5e1-1m's, and the key draws are `frames`' own.
    assert sha(pop.key_bytes, pop.algo, pop.limit, pop.key_of_rank) == PARENT["v5e1-1m.frames", SEED][1]
    plain = frames.build_pool(
        pop, harness.load_json(REPO, "chipbench", "traffic", "frames.json"),
        np.random.default_rng([SEED, 0x706F6F6C]), HOST)
    assert len(pool) == len(plain) == 256
    assert all((a.keys == b.keys).all() and a.hits == b.hits == 1 for a, b in zip(pool, plain))
    columns = np.stack([behavior_column(r.payload, pop) for r in pool])
    flagged = columns[columns != 0]
    assert set(flagged.tolist()) == {1, 2, 16}
    assert 0.009 < flagged.size / columns.size < 0.011
    # Where no lane of a frame is flagged the two payloads are the same bytes;
    # elsewhere they differ in the behaviour column alone.
    for a, b, column in zip(pool, plain, columns):
        assert (a.payload == b.payload) == (not column.any())
        assert len(a.payload) == len(b.payload)
    assert (behavior_column(plain[0].payload, pop) == 0).all()


class _Recorder:
    """An `Http` that keeps what it was sent and answers every lane
    UNDER_LIMIT with a limit of 1: a plain kind-6 frame."""

    def __init__(self):
        self.sent = []

    def roundtrip(self, payload: bytes) -> bytes:
        self.sent.append(payload)
        body = payload[payload.index(b"\r\n\r\n") + 4:]
        (n,) = struct.unpack_from("<I", body, 6)
        return b"".join((
            gubc.MAGIC, struct.pack("<BBI", gubc.VERSION, gubc.KIND_ANSWER, n),
            np.zeros(n, np.int32).tobytes(), np.ones(n, np.int64).tobytes(),
            np.zeros(2 * n, np.int64).tobytes(), struct.pack("<II", 0, 0)))


def test_the_load_and_the_read_back_send_behaviour_0(cell):
    config, traffic = cell
    pop = Population(config["population"], 3 * 512 + 100, SEED)
    http = _Recorder()
    harness.load_population(http, pop, 512, HOST)
    loads = len(http.sent)
    harness.read_back(http, pop, np.arange(0, pop.n, 3), 512, HOST)
    assert loads == 4 and len(http.sent) == loads + 2
    for payload in http.sent:
        column = behavior_column(payload, pop)
        assert len(column) == 512 and not column.any()
    assert int(traffic["load_lanes"]) == int(traffic["readback_lanes"]) == 4096


@pytest.mark.parametrize("accepted,seed", sorted(PARENT))
def test_an_accepted_cell_still_sends_the_bytes_pinned_before_this_generator(accepted, seed):
    bench = harness.load_json(REPO, "BENCHMARK.json")
    _, config, traffic = harness.find_cell(bench, accepted)
    assert traffic["kind"] in ("frames", "json_calls")  # none of them takes the new module
    generator = importlib.import_module(f"chipbench.generators.{traffic['kind']}")
    pop = Population(config["population"], harness.REHEARSE_KEYS, seed)
    pool = generator.build_pool(pop, traffic, np.random.default_rng([seed, 0x706F6F6C]), HOST)
    assert sha(*(x for r in pool for x in (r.payload, r.keys))) == PARENT[accepted, seed][0]
