"""The chip's published peaks (``peaks.json``), keyed by ``device_kind``."""
from __future__ import annotations

import json
import pathlib

PATH = pathlib.Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; an unknown kind is an error, so
    that no roofline share is ever taken against another chip's peaks."""
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in {PATH.name} "
                       f"(known: {sorted(table['devices'])})")
    return {"device_kind": device_kind, "source": table["source"],
            **table["devices"][device_kind]}
