"""The one place this package points JAX's persistent compile cache.

The device programs are large: on a TPU v5e the ed25519 and sr25519
tiles take about half a minute each to compile, per bucket, and a
process that starts without a cache pays that again at every start.
Every entry point that installs or drives the device path — the node's
device install (node/node.py), chip_smoke.py, bench.py, the test
suite — calls `enable()` before its first compile.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
this module sets nothing: whoever runs the program places the cache.
Otherwise the cache is `<checkout>/.jax_cache`, derived from this
file's own path. The path is part of the cache's key, so it is never
built from a temporary name, a pid or the time.

An executable read back from the cache keeps the metadata it was
compiled with, and by default JAX leaves metadata out of the cache's
key: a program whose stage names (`jax.named_scope` in ops/) changed
since the entry was written would show the old names, or none, in every
profile. So wherever the process may reach an accelerator — where
profiles are read — metadata is part of the key. A CPU-pinned process
(the test suite) keeps JAX's default, and with it the hits between
tests that reach one program from different call sites.
"""

from __future__ import annotations

import os

__all__ = ["enable"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable() -> str:
    """Turn the persistent compile cache on and return its directory.
    Idempotent; safe before or after the backend is initialized, but
    programs compiled before the call are not written."""
    import jax

    platforms = jax.config.jax_platforms  # a string: no backend starts
    if not platforms or set(platforms.split(",")) != {"cpu"}:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True
        )
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
