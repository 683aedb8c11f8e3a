"""Run a Pallas kernel per shard under the active device mesh.

XLA's SPMD partitioner cannot split a Mosaic custom call: on a mesh of more
than one device, lowering a ``pallas_call`` traced under plain jit/GSPMD
raises ``NotImplementedError: Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map`` — and it asks for a
region that is manual over EVERY mesh axis, size-1 axes included. So each
kernel entry point states how the model code lays its operands out (batch
over the data axes, heads over "tp", everything else replicated) and
:func:`per_shard` runs the kernel on the local block of each device,
making manual whatever mesh axes an enclosing shard_map has not already.

``check_vma=False``: Pallas interpret mode — what CPU tests and
``chip_smoke.py --rehearse`` run — cannot trace under the varying-axes type
system in jax 0.9.0 (its internal loop carries are untyped), and one
semantics on both backends is worth more than typed transposes. The cost
is in the backward pass: the cotangent of an operand that does not mention
an axis is psum'd over it even where every rank already holds the same
value (an activation replicated over "tp" pays an all-reduce there).
"""

from __future__ import annotations

import math

import jax

#: mesh axes the batch dimension is split over (parallel/mesh.py: the data
#: ranks span dp × ep × fsdp)
BATCH_AXES = ("dp", "ep", "fsdp")


def active_axes(hm=None):
    """``(mesh, free, sizes)`` for the mesh kernels must be partitioned
    over — ``hm`` or the current HybridMesh — or None when there is
    nothing to partition (no mesh, one device, or already inside a
    fully-manual region). ``free`` are the axes still automatic here;
    ``sizes`` maps every axis name to its size."""
    if hm is None:
        from ...parallel.mesh import current_mesh
        hm = current_mesh()
    if hm is None or hm.mesh.size == 1:
        return None
    mesh = hm.mesh
    ctx = jax.sharding.get_abstract_mesh()
    manual = frozenset(ctx.manual_axes)
    free = tuple(a for a in mesh.axis_names if a not in manual)
    if not free:
        return None
    # nested in a partly-manual region, shard_map wants that region's mesh
    return (ctx if manual else mesh), free, dict(mesh.shape)


def batch_spec(free):
    """The spec entry of a batch dimension: the free data axes."""
    axes = tuple(a for a in BATCH_AXES if a in free)
    return axes or None


def shards(entry, sizes) -> int:
    """How many pieces a spec entry (None, a name or a tuple) cuts a
    dimension into."""
    if entry is None:
        return 1
    names = (entry,) if isinstance(entry, str) else entry
    return math.prod(sizes[a] for a in names)


def qkv_layout(free, sizes):
    """``(batch entry, head entry, batch shards, head shards)`` for
    ``[b, s, h, d]`` attention operands: batch over the data axes, heads
    over "tp"."""
    b_ax, h_ax = batch_spec(free), ("tp" if "tp" in free else None)
    return b_ax, h_ax, shards(b_ax, sizes), shards(h_ax, sizes)


def per_shard(fn, mesh, free, in_specs, out_specs):
    """``fn`` mapped over the local blocks of ``mesh``: manual over the
    ``free`` axes, operands cut by ``in_specs``, results reassembled by
    ``out_specs``."""
    return jax.shard_map(fn, mesh=mesh, axis_names=frozenset(free),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


__all__ = ["BATCH_AXES", "active_axes", "batch_spec", "shards", "qkv_layout",
           "per_shard"]
