"""The work one ECDSA P-256 verification needs, as constants of the
algorithm — not of whichever tier (q8/q16 tables, XLA or Pallas tree,
host or fused SHA) happened to serve the lanes. PERF.md section 3
carries the derivation; `tests/benchmark` checks the arithmetic.
"""

from __future__ import annotations

import json
import os

SCALAR_BITS = 256
WINDOW_BITS = 16            # the widest table the program builds
FIELD_BYTES = 32
LIMBS = 20                  # ops/limb.py: 20 x 13-bit limbs, int32

# operand bytes a verify must bring to the chip: digest, r, r+n and
# s^-1 (32 bytes each), the key slot (int32) and one verdict byte back
OPERAND_BYTES = 4 * FIELD_BYTES + 4 + 1
# u1*G + u2*Q by fixed-window tables: one affine row (x, y) per window
# and base
TABLE_ROWS = 2 * (SCALAR_BITS // WINDOW_BITS)
TABLE_ROW_BYTES = 2 * FIELD_BYTES


def bytes_per_verify() -> int:
    return OPERAND_BYTES + TABLE_ROWS * TABLE_ROW_BYTES


def int32_macs_per_verify() -> int:
    """Adding TABLE_ROWS points takes TABLE_ROWS - 1 complete
    additions of 12 field multiplications, each LIMBS x LIMBS limb
    products, plus u1 = e*w and u2 = r*w. A count, never a share: the
    v5e publishes no int32 VPU peak."""
    return ((TABLE_ROWS - 1) * 12 + 2) * LIMBS * LIMBS


def load_peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; a device that is not in
    the table is an error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path}")
    return table[device_kind]


def hbm_floor_seconds(real_verifies: int, hbm_bytes_per_s: float) -> float:
    """The least time the chip could take for the work done."""
    return real_verifies * bytes_per_verify() / hbm_bytes_per_s
