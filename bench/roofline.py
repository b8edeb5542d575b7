"""Work of the counting kernel and its share of the chip's roofline.

``cooc_gram`` computes C = Bᵢᵀ Bⱼ for 0/1 incidence tiles Bᵢ (d, m) and
Bⱼ (d, n). Whatever implements it, it does the same work:

* operations: 2·d·m·n multiply-adds for the call's unpadded shapes, held
  against the chip's int8 peak, the fastest unit that computes a 0/1 Gram
  exactly, so that no faithful kernel can read above 100%;
* bytes: both incidence tiles at 1 byte per element and the (m, n) result
  at 4 bytes, held against the chip's HBM bandwidth.

The least time the chip could take for a call is the larger of operations
over peak and bytes over bandwidth; a kernel's roofline share is the sum of
those least times over its calls, divided by the kernel time the device
trace shows for them.
"""

from __future__ import annotations

import common


def peaks(device_kind: str) -> dict:
    """The peak table's row for a device; an unknown device is an error."""
    table = common.load_json(common.BENCH, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def cooc_gram_ops(d: int, m: int, n: int) -> int:
    return 2 * d * m * n


def cooc_gram_bytes(d: int, m: int, n: int) -> int:
    return d * m + d * n + 4 * m * n


def least_time(calls, peak: dict) -> tuple[float, str]:
    """(seconds, bound) for the calls [(d, m, n), ...]: the sum of each
    call's least time, and "compute" or "memory" for whichever bound gave
    more of it."""
    comp = mem = total = 0.0
    for d, m, n in calls:
        c = cooc_gram_ops(d, m, n) / peak["int8_ops_per_s"]
        b = cooc_gram_bytes(d, m, n) / peak["hbm_bytes_per_s"]
        total += max(c, b)
        comp += c if c >= b else 0.0
        mem += b if b > c else 0.0
    return total, "compute" if comp >= mem else "memory"


def share(calls, kernel_s: float, peak: dict) -> tuple[float, str]:
    """(percent of roofline, bound) of a kernel that took ``kernel_s``."""
    t, bound = least_time(calls, peak)
    return 100.0 * t / kernel_s, bound
