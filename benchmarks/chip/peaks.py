"""The table of chip peaks, keyed by ``device_kind`` as JAX reports it.

A device that is not in ``peaks.json`` is an error, never a default: a
roofline share against the wrong peak is a wrong number.
"""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, table: pathlib.Path = TABLE) -> dict:
    """``{"bf16_flops_per_s", "hbm_bytes_per_s", "source"}`` of one chip."""
    entries = json.loads(table.read_text())
    if device_kind not in entries:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{table.name}; known: {sorted(entries)}")
    return entries[device_kind]
