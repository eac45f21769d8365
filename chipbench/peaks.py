"""Per-chip peaks, keyed by JAX's ``device_kind``, from ``peaks.json``.
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def for_kind(kind: str) -> dict:
    doc = json.loads(TABLE.read_text())
    try:
        return doc["devices"][kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {kind!r} in {TABLE.name}; "
                       f"known: {sorted(doc['devices'])}") from None


def least_seconds(ops: float, n_bytes: float, peaks: dict) -> tuple:
    """The least time the chip could take for ``ops`` operations and
    ``n_bytes`` bytes of HBM traffic, and which bound sets it.  Operations
    are held to the highest compute peak the chip has, so that no
    implementation can read under the bound."""
    t_ops = ops / max(peaks["int8_ops_per_s"], peaks["bf16_flops_per_s"])
    t_mem = n_bytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
