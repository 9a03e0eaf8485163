"""Plain-Python arithmetic spread over worker processes.

The plain sr25519 (reference/sr25519_plain.py) is Python integers: a
key or a signature is ~0.5 ms and a 10,000-validator commit holds 5,000
of each, in every run of its cells. The work is a pure function of its
arguments, so it is cut into fixed chunks and mapped over a few spawned
workers, which import nothing but the module of the function they are
handed (numpy and hashlib; never JAX, so the parent may hold the chip).
The pool is closed, and its processes have ended, when `map_chunks`
returns.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

WORKERS = 8  # a one-chip machine has 13 cores, a four-chip host 30
SERIAL_BELOW = 256  # not worth a process start-up


def map_chunks(fn, items: list, chunk: int) -> list:
    """`fn(items[a:b])` for consecutive chunks, concatenated in order.
    `fn` is a module-level function (it is pickled by its import path)
    that returns one result an item."""
    if len(items) < SERIAL_BELOW:
        return list(fn(items))
    chunks = [items[a : a + chunk] for a in range(0, len(items), chunk)]
    workers = max(1, min(WORKERS, len(chunks), (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as pool:
        out = []
        for part in pool.map(fn, chunks):
            out.extend(part)
    return out
