"""The table of peaks, and the functions that count the least bytes a kernel
must move. Both belong to the yardstick: a PR that claims a gain cannot
change them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# Keyed by ``jax.devices()[0].device_kind``. A device that is not here is an
# error, not a default. Source: Google Cloud documentation, "TPU v5e" (one
# chip: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
  if device_kind not in PEAKS:
    raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                   f"has {sorted(PEAKS)}")
  return PEAKS[device_kind]


def apply_rows_hbm_bytes(shapes: Dict[str, Any]) -> Optional[float]:
  """Least HBM bytes one step's sparse apply must move, mean over the chips.

  Counts, for every packed class and rank the apply kernel serves
  (``shapes["apply_classes"]``: ``occurrences`` = delta rows the step
  scatters into that rank's block, ``unique_rows`` = distinct physical rows
  among them, ``row_bytes`` = bytes of one physical row; ``shapes["ranks"]``
  = the chips the sum is spread over):

  - each distinct physical row read once and written once
    (``2 * unique_rows * row_bytes``): a cached read-modify-write can merge
    the duplicates of a row but cannot skip the row;
  - the delta stream read once (``occurrences * row_bytes``): every
    occurrence's delta row has to be looked at.

  It does not count the id stream (4 bytes a row against 512), nor anything
  the kernel's own staging adds: those are what a better kernel removes. The
  kernel is HBM-bound (an add per element moved), so this is its roofline.
  """
  classes = shapes.get("apply_classes")
  if not classes:
    return None
  return float(sum(
      2 * c["unique_rows"] * c["row_bytes"] + c["occurrences"] * c["row_bytes"]
      for c in classes)) / shapes["ranks"]


BYTES_FUNCTIONS = {"apply_rows_hbm_bytes": apply_rows_hbm_bytes}


def least_ms(bytes_function: str, ctx: Dict[str, Any]) -> Optional[float]:
  """Least milliseconds for the bytes ``bytes_function`` counts, at the HBM
  peak of the device the run is on."""
  n = BYTES_FUNCTIONS[bytes_function](ctx["shapes"])
  if n is None:
    return None
  return 1e3 * n / peaks(ctx["device_kind"])["hbm_bytes_per_s"]
