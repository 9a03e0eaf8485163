"""Measure the chip's int32 multiply-add ceiling for chipbench/peaks.json.

    python3 chipbench/calibrate.py          (on the chip; ~1 min)

Google publishes bf16, int8 and HBM peaks for a TPU v5e and no int32
vector rate. The signature kernels are int32 limb arithmetic on the
VPU, so their roofline needs an int32 ceiling, and this measures one:
`x = x * a + b` over an int32 array that stays on the device, `chain`
dependent steps unrolled in the body of a `fori_loop` that runs `loops`
times inside ONE program. XLA fuses the body into one elementwise pass
(a read, `chain` multiply-adds an element, a write), so HBM moves 16
bytes against `chain` multiply-adds an element and the pass is
compute-bound; the loop keeps a launch far longer than its dispatch
(a launch a step reads a fifth lower: the host's ~0.2 ms a launch
shows). The ceiling is the best rate over a few array sizes and chain
lengths, in int32 multiply-adds a second (one multiply and the add that
follows it count as ONE). Wall time is a host clock around one launch
that ends in block_until_ready and lasts over a quarter of a second.

Prints one JSON line; nothing here is read by run.py — the reading is
copied by hand into peaks.json with this command as its source.
"""

from __future__ import annotations

import json
import sys
import time


def program(chain: int, loops: int):
    import jax

    def body(_i, x_a_b):
        x, a, b = x_a_b
        for _ in range(chain):
            x = x * a + b
        return x, a, b

    def f(x, a, b):
        return jax.lax.fori_loop(0, loops, body, (x, a, b))[0]

    return jax.jit(f)


def measure(n: int, chain: int, min_seconds: float = 0.3) -> dict:
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(n + chain)
    ka, kb, kx = jax.random.split(key, 3)
    shape = (n // 1024, 1024)
    x = jax.random.randint(kx, shape, 1, 1 << 20, dtype=jnp.int32)
    a = jax.random.randint(ka, shape, 3, 1 << 12, dtype=jnp.int32) | 1
    b = jax.random.randint(kb, shape, 1, 1 << 20, dtype=jnp.int32)
    loops, wall = 8, 0.0
    while True:
        fn = program(chain, loops)
        fn(x, a, b).block_until_ready()  # compile
        t0 = time.perf_counter()
        fn(x, a, b).block_until_ready()
        wall = time.perf_counter() - t0
        if wall >= min_seconds or loops >= 1 << 20:
            break
        loops = int(loops * max(2.0, 1.3 * min_seconds / max(wall, 1e-6)))
    return {
        "elements": n,
        "chain": chain,
        "loops": loops,
        "wall_s": wall,
        "int32_madd_per_s": n * chain * loops / wall,
    }


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    rows = [
        measure(n, k)
        for n in (1 << 17, 1 << 20, 1 << 22)
        for k in (32, 128, 256)
    ]
    best = max(rows, key=lambda r: r["int32_madd_per_s"])
    print(
        json.dumps(
            {
                "device_kind": dev.device_kind,
                "int32_madd_per_s": best["int32_madd_per_s"],
                "best": best,
                "rows": rows,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
