"""One compile cache, placeable from outside (ops/compile_cache.py).

A chip's programs take about half a minute each to compile, so every
entry point that drives the device path turns the persistent cache on —
through one helper, which leaves the directory alone wherever
JAX_COMPILATION_CACHE_DIR already places it and otherwise derives
`<checkout>/.jax_cache` from the package's own path: the path is part
of the cache key, so it may depend on nothing that differs between two
runs of the same checkout.
"""

import asyncio
import os
import re
import subprocess
import sys

import jax
import pytest

from tendermint_tpu.ops import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")

_CHILD = (
    "import jax\n"
    "from tendermint_tpu.ops import compile_cache\n"
    "got = compile_cache.enable()\n"
    "print(got)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _child(cwd: str, **env_overrides) -> list:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
        check=True,
    ).stdout
    return out.strip().splitlines()[-2:]


def test_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory
    in code: in this process the config keeps whatever it held, and a
    fresh process ends up on the directory the variable names."""
    held = jax.config.jax_compilation_cache_dir
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert compile_cache.enable() == placed
    assert jax.config.jax_compilation_cache_dir == held
    assert _child(REPO, JAX_COMPILATION_CACHE_DIR=placed) == [placed, placed]


def test_default_is_the_checkout_from_any_cwd_and_process(
    monkeypatch, tmp_path
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable() == DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    # two processes, two working directories, one path
    assert _child(REPO) == [DEFAULT_DIR, DEFAULT_DIR]
    assert _child(str(tmp_path)) == [DEFAULT_DIR, DEFAULT_DIR]


_CHILD_KEY = (
    "import jax\n"
    "jax.config.update('jax_platforms', {platforms!r})\n"
    "from tendermint_tpu.ops import compile_cache\n"
    "compile_cache.enable()\n"
    "print(jax.config.jax_compilation_cache_include_metadata_in_key)\n"
)


@pytest.mark.parametrize(
    "platforms,keyed", [("cpu", "False"), ("tpu,cpu", "True")]
)
def test_metadata_is_in_the_key_where_profiles_are_read(platforms, keyed):
    """Stage names are metadata: a process that may reach an
    accelerator keys its cache on them, so a profile never shows the
    names of an older compile; a CPU-pinned one keeps JAX's default.
    Nothing here starts a backend."""
    out = subprocess.run(
        [sys.executable, "-c", _CHILD_KEY.format(platforms=platforms)],
        cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout
    assert out.strip().splitlines()[-1] == keyed


def test_one_assignment_in_the_tree():
    """No other file sets the cache directory in code."""
    needle = re.compile(r"update\(\s*[\"']jax_compilation_" + "cache_dir")
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, encoding="utf-8") as f:
                    if needle.search(f.read()):
                        hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("tendermint_tpu", "ops", "compile_cache.py")]


@pytest.mark.parametrize("enable", [True, False])
def test_node_device_install_enables_the_cache(
    enable, tmp_path, monkeypatch
):
    """A Node with `[tpu] enable = true` turns the cache on before it
    installs the device verifier (and one with the device off leaves
    jax alone)."""
    from tendermint_tpu.cmd.commands import _load_home, main as cli
    from tendermint_tpu.crypto import tpu_verifier
    from tendermint_tpu.node import make_node
    from tendermint_tpu.ops import merkle_kernel

    # whatever an earlier test of this worker left installed
    tpu_verifier.uninstall()
    calls = []
    monkeypatch.setattr(
        compile_cache, "enable",
        lambda: calls.append(tpu_verifier.installed()),
    )
    home = str(tmp_path / "home")
    assert cli(["--home", home, "init", "validator"]) == 0
    cfg = _load_home(home)
    cfg.tpu.enable = enable
    cfg.rpc.laddr = ""
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    try:
        node = make_node(cfg)

        async def start_stop():
            await node.start()
            await node.stop()

        asyncio.run(start_stop())
        # called once, while nothing was installed yet
        assert calls == ([None] if enable else [])
    finally:
        tpu_verifier.uninstall()
        merkle_kernel.uninstall()
