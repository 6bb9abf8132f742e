"""Rounding to the precision below the one a configuration states, for
the control of ``correct`` (numpy only)."""

from __future__ import annotations

import numpy as np


def to_bf16(x) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even); returned as float32."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    bits = bits + (np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1)))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


LOWER = {"float32": to_bf16}
