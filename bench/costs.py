"""Operations and least HBM bytes of the server's device passes, from the
sizes alone: the merge with its server optimizer, and the transport
codec.  (Local training's counts live with its model family.)

``n`` is the model's parameter count; rows, scales and bitmaps are f32,
int8 codes one byte.
"""
from __future__ import annotations

F32 = 4


def merge(n: int, updates: int, mixes_server: bool) -> tuple:
    """(operations, bytes) of one merge: the weighted sum of ``updates``
    rows (a multiply-add per element), plus the server term when the
    merge mixes it in (``alpha < 1``).  Bytes: each update row and the
    server model read once, the result written once."""
    rows = updates + (1 if mixes_server else 0)
    return 2 * rows * n, (rows + 1) * n * F32


def adam_step(n: int) -> tuple:
    """(operations, bytes) of FedAdam's step over the merge result:
    reads the previous model, the merge, m and v; writes the model, m and
    v.  About a dozen operations per element."""
    return 12 * n, 7 * n * F32


def codec_threshold(n: int) -> tuple:
    """(operations, bytes) of the top-k threshold of an f32 vector (and of
    its kept count and its int8 scale, each a reduction of the same
    shape): reads it once, one operation per element."""
    return n, n * F32


def codec_dequant(n: int) -> tuple:
    """(operations, bytes) of the sender's reconstruction of its int8
    codes: reads the codes, writes the f32 vector."""
    return n, n * (1 + F32)


def codec_encode(n: int) -> tuple:
    """(operations, bytes) of one top-k + int8 encode of an f32 vector
    whose threshold and scale are known: reads it once, writes the int8
    codes and the f32 residual."""
    return 4 * n, n * (F32 + 1 + F32)


def codec_decode(n: int) -> tuple:
    """(operations, bytes) of one dequantise-and-add: reads the codes and
    the base, writes the sum."""
    return 2 * n, n * (1 + F32 + F32)


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time: the larger of operations over the bf16
    peak and bytes over HBM bandwidth."""
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
