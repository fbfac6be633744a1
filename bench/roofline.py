"""Bytes a PDHG iteration must move, and the chip's peaks.

One iteration of diagonally preconditioned PDHG on an LP with n
columns, m rows and nnz nonzeros, in float32, reads at least:

  * the operator twice (A^T y, then A (2 x+ - x)): a 4-byte value and a
    4-byte index per nonzero each time, 16 B per nonzero;
  * in the primal step: x, c, tau, xmax read and x+ written (5 n x 4 B);
    x+ and x read again for the extrapolation (2 n x 4 B);
  * in the dual step: y read by A^T y, then y, sigma, q read and y+
    written (5 m x 4 B).

That is 16 nnz + 28 n + 20 m bytes, counted on the unpadded LP, so it is
the same whatever the backend, layout or padding.  At about 0.1 flop
per byte, far below any chip's ridge point, bandwidth is the bound.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def bytes_per_iteration(n: int, m: int, nnz: int) -> int:
    return 16 * nnz + 28 * n + 20 * m


def pdhg_bytes(lps) -> int:
    """Bytes over LPs given as (n, m, nnz, iterations)."""
    return sum(it * bytes_per_iteration(n, m, nnz) for n, m, nnz, it in lps)


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown kind is an error."""
    with open(PEAKS) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]
