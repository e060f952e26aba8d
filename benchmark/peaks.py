"""The chip's published peaks, keyed by ``jax.Device.device_kind``
(``peaks.json``, with its source). A kind missing from the table is an
error: a guessed peak would change every roofline share silently."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind: str, path: str = PATH) -> dict:
    """``{"hbm_bytes_per_s", "flops_per_s", "hbm_bytes"}`` of a kind;
    raises ``KeyError`` for a kind the table does not hold."""
    with open(path) as fh:
        chips = json.load(fh)["chips"]
    if device_kind not in chips:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{path} (known: {sorted(chips)})")
    return chips[device_kind]


def ideal_seconds(n_bytes: float, n_flops: float, peak: dict) -> float:
    """The least time the chip could take: the larger of bytes over the
    HBM bandwidth and operations over the peak rate."""
    return max(n_bytes / peak["hbm_bytes_per_s"],
               n_flops / peak["flops_per_s"])
