"""Seeded sharding mismatch: the mesh declares only the `sig` axis
but one PartitionSpec names `model` — dispatch would raise on the
first sharded call, on the chips."""

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SIG_AXIS = "sig"


def make_mesh(devs):
    return Mesh(np.array(devs), (SIG_AXIS,))


def shard(mesh, fn):
    vec = NamedSharding(mesh, P(SIG_AXIS))  # declared: fine
    mat = NamedSharding(mesh, P(None, "sig"))  # literal, declared: fine
    bad = NamedSharding(mesh, P("model"))  # undeclared axis: flagged
    return jax.jit(fn, in_shardings=(mat,), out_shardings=vec), bad
