"""Published peaks of the chips the benchmark runs on (`peaks.json`), keyed
by JAX's `device_kind`, for the kernel roofline shares a later metric
computes. A device that is not in the table is an error, never a
default."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = json.loads((Path(__file__).resolve().parent / "peaks.json")
                   .read_text())


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return TABLE["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: "
                       f"{sorted(TABLE['devices'])}") from None
