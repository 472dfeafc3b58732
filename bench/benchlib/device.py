"""The chip the run is on, and its published peaks.

A run that finds no TPU, or fewer chips than its cell asks for, fails
before it prints any result: there is no CPU fallback. A device kind
missing from :data:`PEAKS` is an error, never a default.
"""

from __future__ import annotations

import sys
from typing import Any, Dict

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture
#: page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(SystemExit):
    """Raised when the machine cannot run the cell; exits 2."""

    def __init__(self, msg: str):
        print(f"[bench] FAIL: {msg}", file=sys.stderr)
        super().__init__(2)


def describe(jax, chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    """The device record every result carries; checks the chip count."""
    devs = jax.devices()
    dev = devs[0]
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(devs)}
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU (JAX found {out}); the benchmark runs "
                         f"only on the chip")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
        peaks(dev.device_kind)
    return out


def peaks(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise NoChip(f"device kind {kind!r} is not in the peaks table "
                     f"({sorted(PEAKS)})")
    return PEAKS[kind]


def peak_bytes(jax) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    best = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_in_use", 0)))
    return best
