"""`frames` whose callers mark their hottest limits GLOBAL: the same pool of
GUBC kind-5 frames (the same key draws from the same stream, so a seed's
frames hold the keys `frames` gives it), with `Behavior.GLOBAL`
(`proto/gubernator.proto`, `enum Behavior`) OR-ed into the behaviour of every
lane whose key is one of the population's `global_hot_keys` hottest ranks.
Upstream's `docs/architecture.md` ("Global Behavior") says what the bit is
for: the few limits so hot that one owner cannot take them.  A service marks
a LIMIT, so the bit is a key's, not a lane's (`frames_mixed` draws it a
lane): every lane of a hot key carries it, and a duplicate group stays
uniform.

GLOBAL changes no owner's answer, so the reference holds the cell as it holds
`frames` (`decode` is `frames`'; the load and the read-back are the harness's
own `frames.frame_payload`, behaviour 0)."""

from __future__ import annotations

import numpy as np

from . import Request, frames, frames_mixed

decode = frames.decode
GLOBAL = 2  # proto/gubernator.proto enum Behavior


def hot_keys(pop, params: dict) -> np.ndarray:
    """The key indices whose every check carries GLOBAL."""
    return pop.key_of_rank[: int(params["global_hot_keys"])]


def lane_behaviors(pop, params: dict, keys: list) -> list:
    """One behaviour column a frame of `keys`: the key's own word, and GLOBAL
    where the key is hot."""
    hot = np.zeros(pop.n, bool)
    hot[hot_keys(pop, params)] = True
    return [pop.behavior[idx] | np.where(hot[idx], GLOBAL, 0).astype(np.int32) for idx in keys]


def build_pool(pop, params: dict, rng, host: str) -> list:
    lanes = int(params["lanes_per_request"])
    hits = int(params["hits"])
    keys = [pop.draw(rng, lanes) for _ in range(int(params["pool_requests"]))]
    return [
        Request(frames_mixed.frame_payload(pop, idx, behavior, hits, host), idx, hits)
        for idx, behavior in zip(keys, lane_behaviors(pop, params, keys))
    ]
