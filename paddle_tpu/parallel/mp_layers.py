"""Tensor-parallel (Megatron-style) layers.

Reference: python/paddle/distributed/fleet/layers/mpu/mp_layers.py —
VocabParallelEmbedding:47, ColumnParallelLinear:333, RowParallelLinear:540,
ParallelCrossEntropy:741 — and mp_ops.py (_c_identity:83 fwd-identity/
bwd-allreduce, _mp_allreduce:285 fwd-allreduce/bwd-identity).

TPU-native design: the fwd/bwd collective pairs the reference implements as
custom PyLayers are exactly what GSPMD derives from sharding annotations, so
these layers are thin Layer subclasses that (a) annotate their weights with
("tp"-sharded) PartitionSpecs and (b) constrain their activations. The one
case where explicit collectives beat GSPMD — cross entropy over vocab-sharded
logits without materializing the gathered softmax (key memory saver for 128K
vocab) — uses shard_map + psum/pmax directly (see ParallelCrossEntropy /
parallel_cross_entropy).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..nn import initializer as I
from ..nn.layer import Layer
from .mesh import current_mesh


def _constrain_dim(x, dim: int, axis_name):
    """Constrain ONE tensor dim to a mesh axis (or replicate it when
    axis_name is None), leaving every other dim unconstrained so GSPMD keeps
    whatever batch/dp sharding it already derived — a full PartitionSpec of
    Nones would force an all-gather of the batch at every layer."""
    hm = current_mesh()
    if hm is None:
        return x
    if axis_name is not None and (axis_name not in hm.mesh.axis_names
                                  or hm.mesh.shape[axis_name] <= 1):
        return x
    dim = dim % x.ndim
    if isinstance(x, jax.core.Tracer):
        entries = [P.UNCONSTRAINED] * x.ndim
        entries[dim] = axis_name
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(hm.mesh, P(*entries)))
    # eager: merge with the array's existing spec
    cur = list(getattr(getattr(x, "sharding", None), "spec", ()) or ())
    cur += [None] * (x.ndim - len(cur))
    cur[dim] = axis_name
    return jax.device_put(x, NamedSharding(hm.mesh, P(*cur)))


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim sharded over "tp"
    (reference: mp_layers.py:47 — per-rank vocab range + allreduce)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 weight_attr=None, dtype=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        init_w = weight_attr if isinstance(weight_attr, I.Initializer) \
            else I.Normal(0.0, 0.02)
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], dtype=dtype, initializer=init_w,
            sharding=("tp", "fsdp"))
        self._parameters["weight"].is_distributed = True

    def forward(self, ids):
        # GSPMD turns the gather over a vocab-sharded table into
        # dynamic-slice + masked psum — the reference's mask-and-allreduce
        # without hand-written collectives.
        return jnp.take(self.weight, ids, axis=0)


class ColumnParallelLinear(Layer):
    """Linear with output dim sharded over "tp" (reference: mp_layers.py:333;
    fwd identity / bwd allreduce comes out of GSPMD's partitioning)."""

    def __init__(self, in_features: int, out_features: int, weight_attr=None,
                 has_bias: bool = True, gather_output: bool = False, dtype=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        init_w = weight_attr if isinstance(weight_attr, I.Initializer) \
            else I.XavierUniform()
        self.weight = self.create_parameter(
            [in_features, out_features], dtype=dtype, initializer=init_w,
            sharding=("fsdp", "tp"))
        self._parameters["weight"].is_distributed = True
        if has_bias:
            self.bias = self.create_parameter([out_features], dtype=dtype,
                                              is_bias=True, sharding=("tp",))
            self._parameters["bias"].is_distributed = True
        else:
            self.add_parameter("bias", None)

    def forward(self, x):
        y = jnp.matmul(x, self.weight.astype(x.dtype))
        if self.bias is not None:
            y = y + self.bias.astype(y.dtype)
        y = _constrain_dim(y, -1, None if self.gather_output else "tp")
        return y


class RowParallelLinear(Layer):
    """Linear with input dim sharded over "tp" (reference: mp_layers.py:540;
    the fwd allreduce is inserted by GSPMD when the contraction dim is
    sharded)."""

    def __init__(self, in_features: int, out_features: int, weight_attr=None,
                 has_bias: bool = True, input_is_parallel: bool = True, dtype=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        init_w = weight_attr if isinstance(weight_attr, I.Initializer) \
            else I.XavierUniform()
        self.weight = self.create_parameter(
            [in_features, out_features], dtype=dtype, initializer=init_w,
            sharding=("tp", "fsdp"))
        self._parameters["weight"].is_distributed = True
        if has_bias:
            # bias added after the reduce → replicated (reference semantics)
            self.bias = self.create_parameter([out_features], dtype=dtype,
                                              is_bias=True)
        else:
            self.add_parameter("bias", None)

    def forward(self, x):
        if self.input_is_parallel:
            x = _constrain_dim(x, -1, "tp")
        y = jnp.matmul(x, self.weight.astype(x.dtype))
        y = _constrain_dim(y, -1, None)
        if self.bias is not None:
            y = y + self.bias.astype(y.dtype)
        return y


# ---------------------------------------------------------------------------
# vocab-parallel cross entropy (explicit shard_map — the GSPMD exception)
# ---------------------------------------------------------------------------

def parallel_cross_entropy(logits, labels, mesh=None, axis: str = "tp",
                           ignore_index: int = -100):
    """CE over vocab-sharded logits without gathering them.

    Reference: ParallelCrossEntropy (mp_layers.py:741) backed by
    c_softmax_with_cross_entropy_op.cu — max-allreduce + sum-allreduce over
    the model-parallel group. Here: shard_map over the "tp" axis with
    lax.pmax/psum; each shard computes its local max / exp-sum / target
    logit, so the full softmax is never materialized (the memory saver for
    128K+ vocabularies).

    logits: [..., vocab] sharded on the last dim over ``axis``;
    labels: [...] global ids. Returns per-token loss [...].
    """
    hm = current_mesh() if mesh is None else mesh
    if hm is None or hm.axis_size(axis) <= 1:
        # single shard: plain stable CE
        logits32 = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits32, axis=-1)
        safe = jnp.where(labels == ignore_index, 0, labels)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return jnp.where(labels == ignore_index, 0.0, nll)

    mesh_ = hm.mesh
    n_shards = hm.axis_size(axis)
    vocab = logits.shape[-1]
    shard_size = vocab // n_shards
    batch_spec = P(*([None] * (logits.ndim - 1)))

    def local_ce(logits_l, labels_l):
        # logits_l: [..., vocab/n]; labels_l: [...]
        idx = jax.lax.axis_index(axis)
        lo = idx * shard_size
        logits32 = logits_l.astype(jnp.float32)
        local_max = jnp.max(logits32, axis=-1)
        # stability shift only — not differentiated (pmax has no VJP)
        gmax = jax.lax.stop_gradient(
            jax.lax.pmax(jax.lax.stop_gradient(local_max), axis))
        shifted = logits32 - gmax[..., None]
        local_sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
        gsumexp = jax.lax.psum(local_sumexp, axis)
        # target logit: only the owning shard contributes
        safe = jnp.where(labels_l == ignore_index, 0, labels_l)
        local_label = safe - lo
        in_range = (local_label >= 0) & (local_label < shard_size)
        gathered = jnp.take_along_axis(
            shifted, jnp.clip(local_label, 0, shard_size - 1)[..., None],
            axis=-1)[..., 0]
        target = jax.lax.psum(jnp.where(in_range, gathered, 0.0), axis)
        nll = jnp.log(gsumexp) - target
        return jnp.where(labels_l == ignore_index, 0.0, nll)

    # manual ONLY over the tp axis: other mesh axes (dp/fsdp/sep) stay
    # auto/GSPMD-managed so batch-dim shardings pass straight through —
    # no hidden all-gather of the global batch
    fn = shard_map(
        local_ce, mesh=mesh_, axis_names=frozenset({axis}),
        in_specs=(P(*([None] * (logits.ndim - 1)), axis), batch_spec),
        out_specs=batch_spec)
    return fn(logits, labels)


def parallel_fused_linear_cross_entropy(hidden, w, labels, mesh=None,
                                        axis: str = "tp",
                                        ignore_index: int = -100,
                                        block_n=None, block_v=None,
                                        impl=None, interpret: bool = False):
    """Fused CE(hidden @ w, labels) over a VOCAB-SHARDED w — the fused
    loss head's tensor-parallel composition: neither the full logits NOR a
    full vocab shard of them ever materializes.

    parallel_cross_entropy (above) still receives [..., vocab]-sharded
    logits, i.e. the projection has already been paid and stored. Here each
    tp shard runs the blockwise fused kernel (ops/pallas/fused_vocab_ce.py)
    over ITS [H, V/tp] weight shard — per-shard online log-sum-exp + local
    target gather in O(block_v) memory — and the shards combine with the
    same pmax/psum pattern the reference's c_softmax_with_cross_entropy
    uses: global lse via max-shifted psum of exp(local_lse), target logit
    via psum (only the owning shard contributes a nonzero tgt).

    hidden: [..., H] replicated over ``axis``; w: [H, V] sharded on its
    LAST dim over ``axis``; labels: [...] global ids. Returns per-token
    nll [...] (f32). Differentiable in hidden and w (the fused primitive's
    custom_vjp recomputes per-block logits; psum/pmax combine via jax AD —
    the pmax stability shift is stop_gradient'd, as in
    parallel_cross_entropy)."""
    from ..ops.pallas.fused_vocab_ce import (fused_linear_cross_entropy,
                                             lse_and_target, resolve_impl)
    from ..ops.pallas.per_shard import (active_axes, batch_spec, per_shard,
                                        shards)
    hm = current_mesh() if mesh is None else mesh
    if hm is None or hm.axis_size(axis) <= 1:
        return fused_linear_cross_entropy(
            hidden, w, labels, ignore_index=ignore_index, reduction="none",
            block_n=block_n, block_v=block_v, impl=impl, interpret=interpret)

    n_shards = hm.axis_size(axis)
    vocab = w.shape[-1]
    if vocab % n_shards:
        raise ValueError(f"vocab {vocab} not divisible by {axis} degree "
                         f"{n_shards}")
    # ONE region, manual over every mesh axis (a Mosaic kernel lowers only
    # there — ops/pallas/per_shard.py): W's vocab over ``axis``, the rows
    # over the data axes when they divide the batch (else every data rank
    # holds all rows; the region's transpose still sums dW correctly)
    mesh_, free, sizes = active_axes(hm)
    rows = batch_spec(tuple(a for a in free if a != axis))
    if not labels.ndim or labels.shape[0] % shards(rows, sizes):
        rows = None
    shard_size = vocab // n_shards
    hd = hidden.shape[-1]
    n_tok = int(np.prod(labels.shape)) // shards(rows, sizes)
    if block_n is None or block_v is None:
        from ..ops.pallas.autotune import fused_vocab_ce_config
        tn, tv = fused_vocab_ce_config(n_tok, hd, shard_size,
                                       str(hidden.dtype))
        block_n = block_n if block_n is not None else tn
        block_v = block_v if block_v is not None else tv
    # a block size that DIVIDES the per-shard vocab spares the kernels'
    # pad-W-to-a-block-multiple copy of the whole weight shard; one
    # shard-sized block (== parallel_cross_entropy's per-shard working
    # set) when nothing divides
    if shard_size % block_v:
        block_v = next((c for c in (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
                        if c <= shard_size and shard_size % c == 0),
                       shard_size)
    if impl is None:
        impl = resolve_impl(n_tok, hd, shard_size, hidden.dtype,
                            block_n, block_v, interpret)
    # each shard's vocab offset arrives as DATA: an axis-sharded [n_shards]
    # array -> [1] per shard
    offsets = jnp.arange(n_shards, dtype=jnp.int32) * shard_size

    def local_fn(h_l, w_l, labels_l, off_l):
        lo = off_l[0]
        lab = labels_l.reshape(-1).astype(jnp.int32)
        valid = lab != ignore_index
        # ignored rows map below every shard's range (-1 - lo <= -1)
        local = jnp.where(valid, lab, -1) - lo
        lse_l, tgt_l = lse_and_target(h_l.reshape(-1, hd), w_l, local,
                                      block_n, block_v, impl, interpret)
        gmax = jax.lax.stop_gradient(
            jax.lax.pmax(jax.lax.stop_gradient(lse_l), axis))
        gse = jax.lax.psum(jnp.exp(lse_l - gmax), axis)
        lse = gmax + jnp.log(gse)
        tgt = jax.lax.psum(tgt_l, axis)
        nll = jnp.where(valid, lse - tgt, 0.0)
        return nll.reshape(labels_l.shape)

    lab_spec = P(rows, *([None] * (labels.ndim - 1)))
    fn = per_shard(
        local_fn, mesh_, free,
        (P(rows, *([None] * (hidden.ndim - 1))), P(None, axis), lab_spec,
         P(axis)),
        lab_spec)
    return fn(hidden, w, labels, offsets)


class ParallelCrossEntropy(Layer):
    """Layer wrapper (reference: mp_layers.py:741)."""

    def __init__(self, mp_group=None, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        return parallel_cross_entropy(logits, labels,
                                      ignore_index=self.ignore_index)


# ---------------------------------------------------------------------------
# sequence-parallel utilities
# ---------------------------------------------------------------------------

def scatter_seq(x, axis_name: str = "sep", dim: int = 1):
    """Shard activations along the seq dim — reference ScatterOp
    (fleet/utils/sequence_parallel_utils.py:85): with GSPMD this is a
    sharding constraint; the reduce-scatter/allgather pairs appear in the
    compiled program."""
    return _constrain_dim(x, dim, axis_name)


def gather_seq(x, dim: int = 1):
    """Re-replicate the seq dim — reference GatherOp/AllGatherOp."""
    return _constrain_dim(x, dim, None)


class ColumnSequenceParallelLinear(ColumnParallelLinear):
    """Column-parallel linear whose input is seq-sharded (reference:
    sequence_parallel_utils.py:230 — allgather along seq before the matmul,
    emitted by GSPMD from the constraints)."""

    def forward(self, x):
        x = gather_seq(x)
        return super().forward(x)


class RowSequenceParallelLinear(RowParallelLinear):
    """Row-parallel linear whose output is seq-sharded (reference:
    sequence_parallel_utils.py:340 — reduce-scatter along seq)."""

    def forward(self, x):
        y = super().forward(x)
        return scatter_seq(y)
