"""Bytes the codecs need, from shapes alone.

(A model's parameters and the FLOPs of its training step are counted
by the reference module of its architecture, ``refmodels/<family>.py``.)

Codec bytes are those the format must move: every element read or
written once at its width, plus one float32 absmax per block. Padding
that an implementation adds is not counted, so it lowers the share.
"""
from __future__ import annotations

import math

BLOCK8 = 4096
BLOCK4 = 64


def codec_bytes(kind: str, elems: int) -> float:
    """Bytes one codec or fold call over ``elems`` elements must move.

    q8/d8: float32 <-> int8 + absmax per 4096; q4/d4: float32 <-> 4 bits
    + absmax per 64; fold8: read the float32 sum, the int8 codes and the
    absmaxes, write the sum back."""
    if kind in ("q8", "d8"):
        return 4.0 * elems + elems + 4.0 * math.ceil(elems / BLOCK8)
    if kind in ("q4", "d4"):
        return 4.0 * elems + elems / 2.0 + 4.0 * math.ceil(elems / BLOCK4)
    if kind == "fold8":
        return 9.0 * elems + 4.0 * math.ceil(elems / BLOCK8)
    raise ValueError(f"unknown codec kind {kind!r}")
