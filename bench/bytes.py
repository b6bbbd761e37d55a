"""The bytes a tick of the query step has to read from device memory,
computed from the configuration's valid (unpadded) shapes.

A tick scores B queries against the whole index: it reads the n×d user
matrix, the n×τ thresholds and the n×τ table once (the batched engine
streams each row once for all B queries), plus the B×d queries. Under
int8 storage the per-row parameters count too: six (n, 1) f32 arrays
(the users' scale; the thresholds' and the table's scale and offset;
the thresholds' deviation from the code grid).
Padding, copies the implementation makes, and the (B, n) result arrays
are not necessary work and do not count, so the same work is counted
whatever implements it.
"""
from __future__ import annotations

WIDTH = {"f32": 4, "bf16": 2, "int8": 1}


def tick_bytes(n: int, d: int, tau: int, storage: str, batch: int) -> int:
    """Necessary HBM bytes of one tick of `batch` queries."""
    if storage not in WIDTH:
        raise ValueError(f"unknown storage {storage!r}; one of "
                         f"{sorted(WIDTH)}")
    w = WIDTH[storage]
    users = n * d * w
    table = 2 * n * tau * w
    scales = (6 * n * 4) if storage == "int8" else 0
    queries = batch * d * 4
    return users + table + scales + queries
