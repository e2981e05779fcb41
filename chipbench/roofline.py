"""The table of peaks, and the bytes a dispatch must move.

Peaks are keyed by `device_kind` as JAX reports it; a kind that is not here is
an error, never a default.  The bucket programs are scatter/gather over the
bucket table with a few integer operations a lane, so memory bandwidth is the
bound that applies."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM2e at 819 GB/s a chip.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

# Shapes, from gubernator_tpu/ops/buckets.py: the table is two row-major
# i32[C, 8] arrays (hot, cold: 32 bytes a row each); the dictionary wire
# carries 3 i32 words a lane in; the narrow answer is i32[4, P] out.
WIRE_WORDS_IN_PER_LANE = 3
ANSWER_WORDS_OUT_PER_LANE = 4
ROW_BYTES = 32


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to roofline.PEAKS with its source")
    return PEAKS[device_kind]


def dict_wire_dispatch_bytes(lanes: float, unique_keys: float) -> float:
    """The least bytes one narrow dictionary-wire dispatch moves through HBM,
    each byte once: the request words of the real lanes in, the answer words
    out, and for each distinct key its hot row read, its cold row read (the
    stored limit and duration decide the answer) and its hot row written.
    Left out on purpose, so that the share errs low and never passes 100%:
    padding lanes, the 256-row configuration table, cold rows written on a
    create, and anything the compiler moves twice."""
    return (
        4.0 * (WIRE_WORDS_IN_PER_LANE + ANSWER_WORDS_OUT_PER_LANE) * lanes
        + 3.0 * ROW_BYTES * unique_keys
    )


def least_seconds(device_kind: str, nbytes: float) -> float:
    return nbytes / peak(device_kind)["hbm_bytes_per_s"]
