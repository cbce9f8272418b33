"""Operations and bytes the ants tick needs, counted from the model's shapes
(whatever implements the tick), and the chip's peaks.

Per lane and tick the model must at least:

- read the chemical field and write it back once (diffusion and
  evaporation touch every patch): 2 x W^2 x 4 bytes in float32;
- read and write every ant's position (2 x int32) and carrying flag
  (1 byte): 2 x P x 9 bytes;
- read the food under every ant once: P x 4 bytes.

Operations per lane and tick: diffusion and evaporation take, per patch, 8
neighbour additions, the share (1 multiply), what the patch keeps (1
multiply, 1 subtraction) and the evaporation (1 multiply): 12 W^2. Each ant
scores 8 neighbours (log1p, multiply, add: 3 each; the Gumbel draw and the
argmax are not counted): 24 P. The count is a floor; the roofline share
taken from it says how far the tick is from the least time the chip could
take.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def tick_bytes(model: dict) -> float:
    w, p = int(model["world_size"]), int(model["population"])
    return 2 * w * w * 4 + 2 * p * (2 * 4 + 1) + p * 4


def tick_flops(model: dict) -> float:
    w, p = int(model["world_size"]), int(model["population"])
    return 12 * w * w + 24 * p


def diffusion_bytes(model: dict, lanes: int) -> float:
    """Bytes one call of the diffusion kernel must move for ``lanes``
    lanes: each lane's float32 field read and written once, and its two
    rates read (lanes the kernel pads to its block are not counted)."""
    w = int(model["world_size"])
    return lanes * (2 * w * w * 4 + 2 * 4)


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from peaks.json; a kind that is not in
    the table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add the chip's published peaks")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float:
    """Percent of the chip's peak: the least time the work could take (the
    larger of operations at peak FLOP/s and bytes at peak bandwidth) over
    the time it took."""
    pk = peaks(device_kind)
    least = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
