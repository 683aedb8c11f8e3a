"""Seeded weights, made by the benchmark and by nothing else.

The system under test and the plain references both get their weights from
here, by canonical leaf name, so neither takes anything the other has made.
A leaf's values depend only on (seed, name, shape, dtype): the reference can
make one layer at a time, and a sharded job can make its own shards
(threefry is sharding-invariant), and both read the numbers the program got.

Which leaves a configuration has, with their shapes and kinds, its FAMILY
says (``families/``): canonical name -> (shape, kind), ``kind`` being "norm"
(ones, float32), "router" (normal, float32) or "matrix" (normal, the
configuration's dtype). A leaf's bits follow from its name alone, so a new
family's leaves cost no edit here.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

from . import families

INIT_STD = 0.02


def leaf_shapes(model: dict) -> dict:
    """Canonical name -> (shape, kind), by the configuration's family."""
    return families.of(model).leaf_shapes(model)


def base_key(seed: int):
    """A key from any whole number up to 2**32 and beyond (the driver's seeds
    do not fit 32 signed bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key((seed >> 24) & 0x7FFFFFFF),
                              seed & 0xFFFFFF)


def _crc(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def make_leaf(key, crc, shape, kind: str, dtype):
    """One leaf from the key and its name's checksum. Traceable; ``crc`` may
    be traced, so that layers of one shape share one compiled program."""
    if kind == "norm":
        return jnp.ones(shape, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, crc), shape,
                          jnp.float32) * INIT_STD
    return w.astype(jnp.float32 if kind == "router" else dtype)


def make_all(seed: int, model: dict) -> dict:
    """Every leaf of a configuration, on the device, in ONE jitted call, in
    the dtype it is served or trained in."""
    shapes = leaf_shapes(model)
    dtype = jnp.dtype(model["dtype"])

    def build(key):
        return {n: make_leaf(key, _crc(n), s, kind, dtype)
                for n, (s, kind) in shapes.items()}

    return jax.jit(build)(base_key(seed))


@functools.lru_cache(maxsize=None)
def _some_builder(spec: tuple, dtype_name: str):
    dtype = jnp.dtype(dtype_name)

    def build(key, crcs):
        return [make_leaf(key, crcs[i], shape, kind, dtype).astype(jnp.float32)
                for i, (shape, kind) in enumerate(spec)]
    return jax.jit(build)


def make_some(seed: int, model: dict, names) -> dict:
    """A few leaves (one layer, say) upcast to float32: what a reference
    asks for while it walks the model a layer at a time."""
    shapes = leaf_shapes(model)
    names = list(names)
    build = _some_builder(tuple(shapes[n] for n in names), model["dtype"])
    crcs = jnp.asarray([_crc(n) for n in names], jnp.uint32)
    return dict(zip(names, build(base_key(seed), crcs)))
