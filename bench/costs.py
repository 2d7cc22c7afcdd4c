"""Operations and bytes of the main path's kernels, from their shapes.

Each function takes the kernel call's result and operand shapes as
`(dtype, dims)` pairs, in the order the kernel's `pallas_call` receives
them, and returns (flops, bytes): the multiply-adds the algorithm needs
(two operations each) and the bytes it must move at the least: every
operand read once and the result written once, with no padding. The
least time of a call on a chip is the larger of flops over the peak rate
and bytes over the memory bandwidth (`least_seconds`).
"""
from __future__ import annotations

import json
import os

ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
            "u8": 1, "pred": 1, "f64": 8, "s64": 8}
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def _size(shape) -> int:
    dtype, dims = shape
    n = ITEMSIZE[dtype]
    for d in dims:
        n *= d
    return n


def spmm_blocksparse(result, operands):
    """Y = A X over nb dense (bm, bn) blocks: operands (block_rows,
    block_cols, blocks, x), result (rows, k)."""
    _, (nb, bm, bn) = operands[2]
    _, (_, k) = operands[3]
    return 2 * nb * bm * bn * k, sum(map(_size, operands)) + _size(result)


def gram(result, operands):
    """G = alpha A'B: operands (a (n, m), b (n, c), alpha), result (m, c)."""
    _, (n, m) = operands[0]
    _, (_, c) = operands[1]
    return 2 * n * m * c, sum(map(_size, operands)) + _size(result)


def tsgemm(result, operands):
    """C = alpha A B + beta C0: operands (a (n, m), b (m, c), c0 (n, c),
    alpha, beta), result (n, c)."""
    _, (n, m) = operands[0]
    _, (_, c) = operands[1]
    return (2 * n * m * c + 3 * n * c,
            sum(map(_size, operands)) + _size(result))


KERNELS = {"spmm_blocksparse": spmm_blocksparse, "gram": gram,
           "tsgemm": tsgemm}


def peaks(device_kind: str) -> dict:
    """The chip's peaks from `peaks.json`; an unknown chip is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
