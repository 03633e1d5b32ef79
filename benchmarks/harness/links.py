"""Published rates of the links between chips, keyed by the ``device_kind``
jax reports, beside ``harness/peaks.py`` (the chip's own peaks). A device that
is not here is an error, never a default."""

from __future__ import annotations

from typing import Dict

LINKS: Dict[str, Dict] = {
    # Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of chip-to-chip
    # interconnect (ICI) a chip, all its links together.
    "TPU v5 lite": {"ici_bytes_per_s": 1600e9 / 8,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def links_for(device_kind: str) -> Dict:
    if device_kind not in LINKS:
        raise KeyError(f"no published link rate for device kind "
                       f"{device_kind!r}; add it to benchmarks/harness/"
                       f"links.py with its source")
    return LINKS[device_kind]
