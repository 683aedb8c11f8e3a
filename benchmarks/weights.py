"""Seeded weights, made by the benchmark and by nothing else.

The system under test and the plain references both get their weights from
here, by canonical leaf name, so neither takes anything the other has made.
A leaf's values depend only on (seed, name, shape, dtype): the reference can
make one layer at a time, and a sharded job can make its own shards
(threefry is sharding-invariant), and both read the numbers the program got.

Canonical leaves (shapes are [in, out], the fused layouts are part of the
benchmark's convention and the references split them the same way):

    embed [V, H]   head [H, V]   final_norm [H]
    layers.<i>.attn_norm [H]     layers.<i>.mlp_norm [H]
    layers.<i>.qkv [H, (n_q + 2 n_kv) * hd]    columns q | k | v
    layers.<i>.o [n_q * hd, H]
    dense:  layers.<i>.gate_up [H, 2 I]  (gate | up)   layers.<i>.down [I, H]
    routed: layers.<i>.router [H, E] (float32)
            layers.<i>.experts_gate_up [E, H, 2 F]      layers.<i>.experts_down [E, F, H]
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def leaf_shapes(model: dict) -> dict:
    """Canonical name -> (shape, kind) for a configuration's ``model`` group.
    ``kind`` is "norm" (ones, float32), "router" (normal, float32) or
    "matrix" (normal, the configuration's dtype)."""
    h, v = model["hidden_size"], model["vocab_size"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or h // n_q
    out = {"embed": ((v, h), "matrix"), "head": ((h, v), "matrix"),
           "final_norm": ((h,), "norm")}
    experts = model.get("num_experts", 0)
    for i in range(model["num_hidden_layers"]):
        p = f"layers.{i}."
        out[p + "attn_norm"] = ((h,), "norm")
        out[p + "mlp_norm"] = ((h,), "norm")
        out[p + "qkv"] = ((h, (n_q + 2 * n_kv) * hd), "matrix")
        out[p + "o"] = ((n_q * hd, h), "matrix")
        if experts:
            f = model["intermediate_size"]
            out[p + "router"] = ((h, experts), "router")
            out[p + "experts_gate_up"] = ((experts, h, 2 * f), "matrix")
            out[p + "experts_down"] = ((experts, f, h), "matrix")
        else:
            m = model["intermediate_size"]
            out[p + "gate_up"] = ((h, 2 * m), "matrix")
            out[p + "down"] = ((m, h), "matrix")
    return out


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
