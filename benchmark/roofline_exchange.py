"""Bytes the device exchange must move, and its least time on one chip.

The exchange (``tensor/exchange.py``) takes each heartbeat's game
update from the shard of its player to the shard of its game: it reads
every lane's row to classify it, and moves each crossing update (row,
score, count: 12 B, as ``roofline.PER_UNIT`` counts a game update) out
of HBM, over the interconnect, and into HBM on the other side.  Games
and players are placed on shards by key hash, so a game update crosses
with probability ``(chips - 1) / chips``: the expectation stands in for
the count, which the engine's ``route.cross_shard_msgs`` reports.

All work is the cell's, spread evenly over its chips; the least time is
one chip's share at one chip's peaks (the trace's per-module time is a
per-device mean).  Padding lanes and the local lanes' pass-through are
left out, so the share can only read low.
"""

from __future__ import annotations

import json
from typing import Dict, Optional

#: the exchange's programs, by the module name the device trace gives them
MODULES = (
    "jit__exchange_kernel",  # classify, bucket, all_to_all, receive
    "jit__exchange_probe",   # measure-only classification (disengaged)
)
#: one game update's lane: row, score, count
UPDATE_BYTES = 12
#: a classified lane's row
CLASSIFY_BYTES = 4


def crossing(work: Dict[str, float], chips: int) -> float:
    """Expected game updates that change shard under hash placement."""
    return work.get("game_updates", 0) * (chips - 1) / chips


def bytes_per_chip(work: Dict[str, float], chips: int) -> Dict[str, float]:
    """HBM and interconnect bytes one chip moves for the exchange."""
    cross = crossing(work, chips)
    lanes = work.get("game_updates", 0)
    return {"hbm": (2 * UPDATE_BYTES * cross + CLASSIFY_BYTES * lanes)
            / chips,
            "ici": UPDATE_BYTES * cross / chips}


def least_seconds(work: Dict[str, float], chips: int,
                  peaks: Dict[str, float]) -> float:
    b = bytes_per_chip(work, chips)
    return max(b["hbm"] / peaks["hbm_bytes_per_s"],
               b["ici"] / peaks["ici_bytes_per_s"])


def device_seconds(trace: dict) -> float:
    return float(sum(trace["by_module"].get(m, 0.0) for m in MODULES))


def ici_peaks(device_kind: str, root: Optional[str] = None
              ) -> Dict[str, float]:
    """The chip's interconnect bandwidth; a device missing from the
    table is an error, never a default."""
    import spec

    with open(spec.find(root or spec.REPO, "benchmark",
                        "peaks_ici.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no interconnect peak for device kind "
                       f"{device_kind!r} in benchmark/peaks_ici.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
