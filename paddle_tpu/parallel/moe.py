"""Mixture-of-Experts layer with expert parallelism.

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
(MoELayer: gate → count_by_gate → MoEScatter(global_scatter all-to-all) →
per-expert FFN loop → MoEGather), gates under moe/gate/{naive,gshard,switch}
_gate.py, kernels paddle/fluid/operators/collective/global_scatter_op.cu.

TPU-native redesign, round 3 (SURVEY.md A.2 translation): the reference's
index-select + ragged all-to-all becomes a SORT-BASED dispatch — token
assignments are sorted by expert id (one XLA sort of t*k int32 keys), each
assignment's slot in the [experts, capacity, d] layout is its rank within
its expert's run, and dispatch/combine are pure GATHERS through a slot
index. Routing memory is O(t·k + e·c·d): the round-2 one-hot GShard
[t, e, c] dispatch/combine tensors (O(t·e·c) — OOM at DeepSeekMoE's 64+
experts) are gone. The experts still run as ONE batched einsum on the MXU.

Dropless mode (``capacity_factor=None``): no token is ever dropped — the
sorted assignments feed a grouped matmul over per-expert group sizes, the
TPU analogue of the reference's exact-count global_scatter path
(moe/utils.py count_by_gate). Round-5 on-chip A/B at DeepSeekMoE scale
(e=64, d=2048, f=1408, k=6, v5e): XLA's native ``lax.ragged_dot`` runs the
same grouped matmul 1.7x faster than the bundled megablox Pallas gmm with
bit-identical output. Since PR 28 ``lax.ragged_dot`` is the only
implementation of that product, forward and backward, on every platform
(``grouped_matmul`` below); the capacity-factor dense path is ~4x faster
still at this scale but DROPS overflow tokens — the measured trade is
recorded in ops/pallas/tune_db.json (moe_grouped_mm).

Around those two products a differentiated step moves its routed rows by
GATHERS alone (PR 30) and does its index work by SORTS alone (PR 43:
``sorted_assignments``): rows go out through ``dispatch_rows`` (a gather
from [t, d]) and come back through ``combine_rows`` (a gather and a sum
over k), which are each other's transposes. The router's weight is applied
to the SORTED rows before the down product (``weighted_hidden``: the
product is linear in its rows, so ``down(hidden * w) == w * down(hidden)``),
in float32 and rounded once; nothing of size [k, t, d] is kept for the
backward or built in it, and d weight of a row is the row sum of d hidden x
activation, remade from the saved pre-activation.

Expert parallelism (ISSUE 20): expert weights shard their expert dim over
the ("ep","dp","fsdp") submesh — "ep" is a REAL mesh axis carved out of
the data ranks (HybridMesh.build's ep degree; _clean_spec drops it on
ep==1 meshes so pre-EP plans stay byte-identical). On an ep>1 mesh the
dispatch/combine run as a shard_map'd ``lax.all_to_all`` over the "ep"
axis in both capacity and DROPLESS variants — dropless keeps the exact
per-expert counts as the (logical) a2a split sizes inside a statically
bounded slot buffer, since this jax ships no ragged_all_to_all. On an
ep==1 mesh the dispatched [e, c, d] tensor is sharding-constrained to
the expert axes and XLA materializes the global_scatter/global_gather
all-to-alls itself.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from .mesh import current_mesh


def _aux_loss(probs, e):
    """GShard eq.4 load-balance loss: e * sum_e(mean_t(gate) * mean_t(frac))."""
    top1 = jnp.argmax(probs, axis=-1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    return jnp.sum(me * ce) * e


def routing_stats(gate_logits, k: int):
    """(aux_loss, router_z, per-expert token counts) for one routing
    batch. The counts vector is the MEASURED histogram the planner's
    entropy-priced all-to-all consumes (``price_config(...,
    moe_histogram=counts)``); router_z is the ST-MoE z-loss
    ``mean(logsumexp(logits)^2)``."""
    t, e = gate_logits.shape
    logits = gate_logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(probs, k)
    counts = jnp.bincount(ids.reshape(-1), length=e).astype(jnp.int32)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return _aux_loss(probs, e), z, counts


def publish_moe_metrics(aux_loss=None, router_z=None, expert_counts=None):
    """Publish MoE routing health through the metrics registry (the PR 4
    vocabulary): ``pt_moe_*`` counters for routed token assignments per
    expert plus gauges for the aux loss, router z-loss and the
    load-balance factor (``e × max expert share``; 1.0 = balanced — the
    same bottleneck statistic the planner's a2a entropy pricing uses).

    Host-side only: traced values are skipped silently, so call it from
    the training loop with concrete step outputs (``routing_stats`` of a
    logged step), never from inside jit."""
    from ..observability.metrics import REGISTRY
    if not REGISTRY.enabled:
        return
    tracer = lambda v: isinstance(v, jax.core.Tracer)
    if aux_loss is not None and not tracer(aux_loss):
        REGISTRY.gauge("pt_moe_aux_loss",
                       "GShard load-balance aux loss").set(float(aux_loss))
    if router_z is not None and not tracer(router_z):
        REGISTRY.gauge("pt_moe_router_z",
                       "router z-loss mean(logsumexp^2)").set(
            float(router_z))
    if expert_counts is not None and not tracer(expert_counts):
        c = np.asarray(expert_counts, dtype=float).ravel()
        tot = float(c.sum())
        ctr = REGISTRY.counter("pt_moe_expert_tokens_total",
                               "routed token assignments per expert")
        for i, v in enumerate(c):
            ctr.inc(float(v), expert=str(i))
        REGISTRY.counter("pt_moe_dispatch_total",
                         "MoE routing batches published").inc()
        if tot > 0:
            REGISTRY.gauge(
                "pt_moe_load_imbalance",
                "e * max expert share (1.0 = perfectly balanced)").set(
                float(c.max() * c.size / tot))


def top_k_routing(gate_logits, k: int, capacity: int,
                  jitter_eps: float = 0.0, key=None):
    """Sort-based top-k routing with capacity.

    Returns (slot [t, k] int32, gates [t, k] f32, aux_loss scalar):
    ``slot[i, j]`` is the flat position of token i's j-th assignment in the
    [e * capacity] expert-slot space, or e*capacity when the assignment was
    dropped (its expert full). Capacity priority is choice-major (every
    token's 1st choice outranks any 2nd choice), token-ascending — the
    fill-counter semantics of the reference's limit_by_capacity
    (moe/utils.py:74) without materializing anything O(t·e).
    """
    t, e = gate_logits.shape
    gate_logits = gate_logits.astype(jnp.float32)
    if jitter_eps > 0.0 and key is not None:
        noise = jax.random.uniform(key, gate_logits.shape, jnp.float32,
                                   1.0 - jitter_eps, 1.0 + jitter_eps)
        gate_logits = gate_logits * noise
    probs = jax.nn.softmax(gate_logits, axis=-1)              # [t, e]
    gates, ids = jax.lax.top_k(probs, k)                      # [t, k]

    # choice-major assignment stream: all 1st choices (token asc), then all
    # 2nd choices, ... — the stable sort by expert then ranks assignments
    # within each expert in exactly that priority order
    flat_e = ids.T.reshape(-1)                                # [k*t]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))        # [e]
    pos = jnp.arange(k * t, dtype=jnp.int32) - starts[sorted_e]
    keep = pos < capacity
    slot_sorted = jnp.where(keep, sorted_e * capacity + pos,
                            e * capacity).astype(jnp.int32)
    # scatter slots back to choice-major stream order, then to [t, k]
    slot_cm = jnp.zeros((k * t,), jnp.int32).at[order].set(slot_sorted)
    slot = slot_cm.reshape(k, t).T                            # [t, k]
    return slot, gates, _aux_loss(probs, e)


def dispatch_tokens(flat, slot, num_experts: int, capacity: int):
    """Gather tokens into the dense [e, c, d] expert layout (empty slots
    zero). flat: [t, d]; slot: [t, k] from top_k_routing."""
    t, d = flat.shape
    k = slot.shape[1]
    ec = num_experts * capacity
    # slot -> token index (choice-major flatten matches top_k_routing)
    slot_token = jnp.full((ec + 1,), t, jnp.int32)
    slot_token = slot_token.at[slot.T.reshape(-1)].set(
        jnp.tile(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
    padded = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)])
    return padded[slot_token[:ec]].reshape(num_experts, capacity, d)


def combine_tokens(ye, slot, gates, renormalize: bool):
    """Weighted gather back to tokens. ye: [e, c, d]; slot/gates: [t, k].
    Dropped assignments (slot == e*c) contribute zero."""
    e, c, d = ye.shape
    padded = jnp.concatenate(
        [ye.reshape(e * c, d),
         jnp.zeros((1, d), ye.dtype)])                        # trash row
    y = padded[slot]                                          # [t, k, d]
    kept = (slot < e * c).astype(gates.dtype)
    g = gates * kept
    if renormalize:
        g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    return jnp.sum(g[..., None].astype(y.dtype) * y, axis=1)  # [t, d]


# -- legacy one-hot formulation kept as the parity oracle --------------------

def top_k_gating(gate_logits, k: int, capacity: int,
                 jitter_eps: float = 0.0, key=None):
    """GShard one-hot gating (dispatch [t,e,c] bool, combine [t,e,c] float,
    aux_loss). O(t·e·c) — superseded by top_k_routing for real configs;
    retained as the test oracle for the sort-based path."""
    t, e = gate_logits.shape
    gate_logits = gate_logits.astype(jnp.float32)
    if jitter_eps > 0.0 and key is not None:
        noise = jax.random.uniform(key, gate_logits.shape, jnp.float32,
                                   1.0 - jitter_eps, 1.0 + jitter_eps)
        gate_logits = gate_logits * noise
    probs = jax.nn.softmax(gate_logits, axis=-1)  # [t,e]
    aux_loss = _aux_loss(probs, e)

    combine = jnp.zeros((t, e, capacity), jnp.float32)
    dispatch = jnp.zeros((t, e, capacity), bool)
    remaining = probs
    fill = jnp.zeros((e,), jnp.int32)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                      # [t]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)          # [t,e]
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1 + fill) * onehot
        pos = jnp.sum(pos_in_expert, axis=-1)                     # [t]
        fits = pos < capacity
        gate_val = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
        pos_oh = jax.nn.one_hot(jnp.where(fits, pos, capacity), capacity,
                                dtype=jnp.float32)                # [t,c]
        contrib = (onehot.astype(jnp.float32)[:, :, None] * pos_oh[:, None, :])
        combine = combine + gate_val[:, None, None] * contrib * fits[:, None, None]
        dispatch = dispatch | (contrib > 0) & fits[:, None, None]
        fill = fill + jnp.sum(onehot * fits[:, None].astype(jnp.int32), axis=0)
        remaining = remaining * (1.0 - onehot.astype(jnp.float32))
    if k > 1:
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
    return dispatch, combine, aux_loss


EXPERT_ACTS = ("swiglu", "relu2")


def expert_ffn(x, w_in, w_down, act: str, up, down):
    """One expert feed-forward, whatever carries its two products:
    ``down(hidden(up(x, w_in)), w_down)``. ``act`` "swiglu" reads the first
    product as [gate | up] and gives ``silu(gate) * up``; "relu2" (non-gated:
    ``w_in`` is the up-projection alone) gives ``relu(.)^2``. ``up`` and
    ``down`` are the caller's products: a batched einsum over a dense
    [e, slots, d] layout, a grouped matmul over sorted rows."""
    pre = up(x, w_in)
    if act == "swiglu":
        g, u = jnp.split(pre, 2, axis=-1)
        return down(F.silu(g) * u, w_down)
    if act == "relu2":
        return down(jnp.square(jax.nn.relu(pre)), w_down)
    raise ValueError(f"expert_act must be one of {EXPERT_ACTS}, got {act!r}")


def _dense_up(x, w):
    return jnp.einsum("ecd,edf->ecf", x, w)


def _dense_down(h, w):
    return jnp.einsum("ecf,efd->ecd", h, w)


class MoEMLP(Layer):
    """Experts as batched weights [E, ...] — one einsum, not a python loop.
    ``act`` "swiglu": leaves ``w_gate_up`` [E, d, 2 f] and ``w_down``;
    "relu2" (non-gated): ``w_up`` [E, d, f] and ``w_down``."""

    def __init__(self, num_experts: int, hidden_size: int, ffn_size: int,
                 dtype=None, act: str = "swiglu"):
        super().__init__()
        if act not in EXPERT_ACTS:
            raise ValueError(f"expert_act must be one of {EXPERT_ACTS}, got "
                             f"{act!r}")
        self.act = act
        std = 0.02
        # the expert dim shards over ep first (real expert parallelism),
        # then the dp/fsdp data axes; _clean_spec drops "ep" on ep==1
        # meshes so pre-EP placements stay byte-identical
        setattr(self, "w_gate_up" if act == "swiglu" else "w_up",
                self.create_parameter(
                    [num_experts, hidden_size,
                     (2 if act == "swiglu" else 1) * ffn_size], dtype=dtype,
                    initializer=I.Normal(0.0, std),
                    sharding=(("ep", "dp", "fsdp"), None, "tp")))
        self.w_down = self.create_parameter(
            [num_experts, ffn_size, hidden_size], dtype=dtype,
            initializer=I.Normal(0.0, std),
            sharding=(("ep", "dp", "fsdp"), "tp", None))

    @property
    def w_in(self):
        """The first product's weights: [gate | up] or the up-projection."""
        return self.w_gate_up if self.act == "swiglu" else self.w_up

    def forward(self, x):
        # x: [e, c, d] -> [e, c, d]
        return expert_ffn(x, self.w_in.astype(x.dtype),
                          self.w_down.astype(x.dtype), self.act,
                          _dense_up, _dense_down)


def _constrain_experts(xe):
    """Shard the [e, c, d] dispatched tensor's expert dim over the
    ep×dp×fsdp submesh — this boundary is where GSPMD emits the
    global_scatter/global_gather all-to-alls."""
    hm = current_mesh()
    if hm is None or not isinstance(xe, jax.core.Tracer):
        return xe
    axes = tuple(a for a in ("ep", "dp", "fsdp") if hm.axis_size(a) > 1)
    if not axes:
        return xe
    if xe.shape[0] % int(np.prod([hm.axis_size(a) for a in axes])) != 0:
        return xe
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        xe, NamedSharding(hm.mesh, P(axes, *([P.UNCONSTRAINED] * (xe.ndim - 1)))))


def xla_grouped_matmul(xs, w, group_sizes):
    """Ragged grouped matmul: rows of ``xs`` [m, k] are split into runs
    by ``group_sizes`` [g] and run ``i`` multiplies its own ``w[i]``
    [k, n], through XLA's ``lax.ragged_dot``. Returns f32, the
    accumulator dtype; callers cast back to the activation dtype. What
    ``blocked_expert_rows`` is tested and timed against (no path of the
    program calls it: training goes through ``grouped_matmul``)."""
    return jax.lax.ragged_dot(xs, w, group_sizes,
                              preferred_element_type=jnp.float32)


# FLOPs the MXU does in the time HBM delivers one byte, on a v5e (197e12 /
# 819e9; ``MoELayer.DENSE_ROWS`` is this number read as rows)
RIDGE_FLOPS_PER_BYTE = 240
# what a step of ``blocked_expert_rows`` is a whole number of: every size the
# rule below can choose in bf16 (64, 128, 192, 256) was read on the chip
STEP_QUANTUM = 64


def expert_step_rows(t: int, k: int, e: int, dtype) -> int:
    """Rows a step of ``blocked_expert_rows`` takes in a call that routes
    ``t`` rows top-``k`` over a router ``e`` wide: TWICE the rows an expert
    is expected to get, ``t k / e``, rounded up to ``STEP_QUANTUM``, and
    never over the rows at which a step turns MXU-bound. The experts'
    widths do not enter: a [step, d] x [d, f] product does 2 step / itemsize
    FLOPs a byte of the expert's weights whatever d and f are, so it crosses
    the ridge at 120 x itemsize rows (256 in bf16), and over that a larger
    step only computes more rows that are not the expert's.

    Twice, because a step costs nearly the same whatever it holds and a
    router does not deal evenly (``tools/expert_path_probe.py`` on a v5e, PR
    37: at 2688 x 1856 a step of 32 / 64 / 128 / 192 / 256 rows takes 36.4
    / 37.6 / 41.7 / 45.5 / 50.6 us, two products that stream 10 MB of
    weights each at four fifths of HBM's rate, and the rows on top; at 2048
    x 1536 gated 34.1 / 35.1 / 37.7 / 40.6 / 44.2; at 2048 x 2048 gated
    42.3 / 43.5 / 47.0 / - / 51.8). A prompt's rows an expert spread wide
    on seeded weights: of Nemotron's 64 held experts at 1,536 tokens the
    median gets 47-71 rows, one in ten 119-177, the busiest 174-344 (even:
    72); GLM's and ZAYA's wider still. A step of the even share then needs
    104 steps where one of 256 rows needs 66, and the loop is longest
    there; of steps of 64 / 128 / 192 / 256 rows the layer is fastest at
    this rule's in seven of nine readings (three lengths of each cell's
    prompts, loads skewed as theirs: PERF.md section 6, PR 37)."""
    whole = lambda rows: max(-(-rows // STEP_QUANTUM), 1) * STEP_QUANTUM
    ridge = RIDGE_FLOPS_PER_BYTE * jnp.dtype(dtype).itemsize // 2
    return min(whole(-(-2 * t * k // e)), whole(ridge))


def blocked_expert_rows(xs, w_in, w_dn, act: str, load, block: int):
    """The expert feed-forward over rows SORTED by expert (``load`` [e]: the
    rows of each, in order; rows past their sum belong to none and come back
    0), as a loop over steps of ``block`` consecutive rows of ONE expert:
    each step slices that expert's two matrices out of ``w_in`` / ``w_dn``
    and runs plain products, so the MXU sees [block, d] x [d, f] whatever
    the widths. An expert with more rows than a step takes several and
    reads its weights in each. A step's rows that are not its expert's (the
    next one's behind them; at the array's end, where the step is moved up
    to end with the rows, the ones before) are computed and not written.
    xs [m, d] -> [m, d_out] float32. As many steps as the experts' rows
    need (a dynamic trip count: forward only)."""
    m, d = xs.shape
    e = load.shape[0]
    block = min(block, m)
    steps = -(-load // block)                                 # [e]
    ends = jnp.cumsum(steps)
    rows0 = jnp.cumsum(load) - load                           # first row of e
    i = jnp.arange(-(-m // block) + e, dtype=jnp.int32)       # every step
    owner = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), e - 1)
    within = (i - (ends - steps)[owner]) * block              # rows before it
    first = (rows0[owner] + within).astype(jnp.int32)         # its own rows:
    last = first + jnp.clip(load[owner] - within, 0, block).astype(jnp.int32)
    start = jnp.minimum(first, m - block)       # a slice never runs off
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)

    def step(j, out):
        w1 = jax.lax.dynamic_index_in_dim(w_in, owner[j], keepdims=False)
        w2 = jax.lax.dynamic_index_in_dim(w_dn, owner[j], keepdims=False)
        x = jax.lax.dynamic_slice_in_dim(xs, start[j], block)
        y = expert_ffn(x, w1, w2, act,
                       lambda a, w: dot(a, w).astype(xs.dtype), dot)
        old = jax.lax.dynamic_slice_in_dim(out, start[j], block)
        row = start[j] + jnp.arange(block)
        keep = ((row >= first[j]) & (row < last[j]))[:, None]
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(keep, y, old), start[j], 0)

    return jax.lax.fori_loop(
        0, ends[-1], step, jnp.zeros((m, w_dn.shape[-1]), jnp.float32))


def _int_zero(a):
    """The cotangent of an integer argument of a ``custom_vjp``."""
    return np.zeros(a.shape, dtype=jax.dtypes.float0)


@jax.custom_vjp
def grouped_matmul(xs, w, group_sizes):
    """The grouped matmul of a differentiated program: the one entry of
    every per-expert product of a training step (dropless routing, the
    EP shard_map body). XLA's ``ragged_dot`` forward and backward since
    PR 28 (the Pallas kernel that ran the forward on TPU took 8x its
    time on the chip and is gone).

    Returns ``xs.dtype``: the product's accumulator is rounded to the
    activation dtype by ``ragged_dot`` itself, not by a cast after it. A
    float32 result of OLMoE's 262,144 routed rows is 2 GB a product, and
    with it beside its bf16 copy the step program needs 17.49 GB of a
    v5e's 15.75 (the compiler's count, PR 28). The backward's products
    do the same since PR 30: the cotangent goes in as it arrives, ``dxs``
    comes back in ``xs.dtype`` and ``dw`` in ``w.dtype`` (all four of
    OLMoE's, at its size on the chip: bit for bit the float32 products
    cast after, in 19.1 + 37.9 ms against 32.5 + 59.8; chip run, PR 30).

    custom_vjp because jax's ragged_dot ad rules choke on symbolic-Zero
    tangents inside a shard_map transpose (the dropless-EP body):
    custom_vjp instantiates the cotangent before bwd runs, and the
    product is linear in each operand, so the vjp below is the exact
    gradient."""
    return jax.lax.ragged_dot(xs, w, group_sizes,
                              preferred_element_type=xs.dtype)


def _gmm_fwd(xs, w, group_sizes):
    return grouped_matmul(xs, w, group_sizes), (xs, w, group_sizes)


def _gmm_bwd(res, gy):
    xs, w, group_sizes = res
    # jax's transpose rule hands ``preferred_element_type`` on to both
    # products of the backward
    _, vjp = jax.vjp(
        lambda a, b: jax.lax.ragged_dot(
            a, b, group_sizes, preferred_element_type=xs.dtype), xs, w)
    dxs, dw = vjp(gy)
    return dxs, dw, _int_zero(group_sizes)


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def inverse_permutation(order):
    """``inv`` with ``inv[order[i]] == i`` for a permutation ``order`` of
    ``arange(n)``: an int32 scatter of n indices (1 MB at OLMoE's 262,144
    routed rows), told that no two of them collide."""
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)


# The routed rows of a differentiated program move by GATHERS alone (PR
# 30). ``order`` (the stable argsort of the rows' experts) is a permutation,
# which XLA cannot know: left to jax's own rules, ``zeros.at[order].set``
# compiles on TPU to passes over a u32 copy of the rows with a mask and an
# index sort, and the transpose of ``flat[order % t]`` to a scatter-add
# with another sort (140 ms of OLMoE's 532 ms step, chip run, PR 28).
# Stated as what they are, a permutation's transpose is the permutation by
# its inverse, and going out (``dispatch_rows``: a gather from [t, d]) and coming
# back (``combine_rows``: a gather and a sum over k) are each other's
# transposes. Since PR 43 nothing else happens on the way back: the router's
# weight rides the SORTED rows into the down product (``weighted_hidden``),
# so no [k, t, d] array is kept for the backward and none is built in it.

@jax.custom_vjp
def permute_scalars(x, idx, idx_inv):
    """``x[idx]`` of a VECTOR ``x`` for a permutation ``idx`` whose inverse
    is ``idx_inv``, by a SORT: position i of the pairs (idx_inv, x) sorted
    by key holds the j with idx_inv[j] == i, that is x[idx[i]]; the
    transpose is the same with the two permutations exchanged. A gather of
    scalars runs one index at a time on a TPU, as a scatter does: 2.26 and
    1.87 ms for the 262,144 router weights of OLMoE's step and their
    cotangents, where the two sorts take 0.32 and 0.30 (chip run, PR 43)."""
    return jax.lax.sort((idx_inv, x), num_keys=1)[1]


def _permute_scalars_fwd(x, idx, idx_inv):
    return permute_scalars(x, idx, idx_inv), (idx, idx_inv)


def _permute_scalars_bwd(res, g):
    idx, idx_inv = res
    return (permute_scalars(g, idx_inv, idx), _int_zero(idx),
            _int_zero(idx_inv))


permute_scalars.defvjp(_permute_scalars_fwd, _permute_scalars_bwd)


def _spread(rows, order, live):
    """A row of ``rows`` [t, d] for each of the k*t sorted assignments,
    ``rows[order % t]``; 0 where ``live`` (bool [k*t] in sorted order, or
    None: all) says the assignment belongs to no group."""
    out = rows[order % rows.shape[0]]
    return out if live is None else jnp.where(live[:, None], out, 0)


def _gather_sum(rows, inv, live, t: int):
    """A token's k rows of the sorted ``rows`` [k*t, d], summed in float32
    and rounded once: [t, d]. A row of no group (``live`` as in ``_spread``)
    is whatever ``ragged_dot`` left there: taken out by a ``where``, never
    by a product with 0."""
    back = rows[inv]
    if live is not None:
        back = jnp.where(live[inv][:, None], back, 0)
    return jnp.sum(back.reshape(-1, t, rows.shape[-1]), axis=0,
                   dtype=jnp.float32).astype(rows.dtype)


def _live_zero(live):
    return None if live is None else _int_zero(live)


@jax.custom_vjp
def dispatch_rows(flat, order, inv, live=None):
    """The rows of ``flat`` [t, d] in the order of their k*t choice-major
    assignments sorted by ``order``: ``flat[order % t]`` [k*t, d].
    ``inv`` is ``order``'s inverse; ``live`` (bool [k*t] in sorted order, or
    None: all) marks the rows that belong to a group, whose cotangents
    alone come back."""
    return _spread(flat, order, None)


def _dispatch_fwd(flat, order, inv, live):
    return (dispatch_rows(flat, order, inv, live),
            (order, inv, live, flat.shape[0]))


def _dispatch_bwd(res, g):
    order, inv, live, t = res
    return (_gather_sum(g, inv, live, t), _int_zero(order), _int_zero(inv),
            _live_zero(live))


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def combine_rows(ys, order, inv, live, t: int):
    """``dispatch_rows``' transpose as a forward: each of the ``t`` tokens'
    k rows of the sorted ``ys`` [k*t, d], gathered through ``inv`` and
    summed. ``live`` (bool [k*t] in sorted order, or None) marks the rows
    that belong to a group: the others add nothing and get no cotangent.
    Its own transpose is the dispatch's gather, from [t, d]."""
    return _gather_sum(ys, inv, live, t)


def _combine_fwd(ys, order, inv, live, t):
    return combine_rows(ys, order, inv, live, t), (order, inv, live)


def _combine_bwd(t, res, g):
    order, inv, live = res
    return (_spread(g, order, live), _int_zero(order), _int_zero(inv),
            _live_zero(live))


combine_rows.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def cotangents_together(rows, w):
    """``(rows, w)`` as they are; their cotangents leave the backward
    TOGETHER (an ``optimization_barrier``), so that nothing downstream of a
    product's d rows starts before its d weights exist."""
    return rows, w


def _together_fwd(rows, w):
    return (rows, w), None


def _together_bwd(_, g):
    return jax.lax.optimization_barrier(g)


cotangents_together.defvjp(_together_fwd, _together_bwd)


def _halves32(pre):
    """A SwiGLU pre-activation's [gate | up] halves, float32 (split first:
    XLA fuses a cast behind a slice, not a slice behind a cast)."""
    g, u = jnp.split(pre, 2, axis=-1)
    return g.astype(jnp.float32), u.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def weighted_hidden(pre, gs, act: str):
    """The hidden activation of the SORTED rows' pre-activation ``pre``
    [rows, (2) f], each row times its router weight ``gs`` [rows] float32:
    ``silu(g) * u * gs`` or ``relu(pre)^2 * gs``, computed in float32 and
    rounded once to ``pre.dtype``. The down product is linear in its rows,
    so ``down(hidden * gs) == gs * down(hidden)``: the weight applied here
    leaves the way back a gather and a sum. The backward keeps ``pre`` and
    ``gs`` alone and remakes the activation for d ``gs`` where it forms
    d ``pre``."""
    if act == "swiglu":
        g, u = _halves32(pre)
        hidden = jax.nn.silu(g) * u
    elif act == "relu2":
        hidden = jnp.square(jax.nn.relu(pre.astype(jnp.float32)))
    else:
        raise ValueError(f"expert_act must be one of {EXPERT_ACTS}, got "
                         f"{act!r}")
    return (hidden * gs[:, None]).astype(pre.dtype)


def _weighted_hidden_fwd(pre, gs, act):
    return weighted_hidden(pre, gs, act), (pre, gs)


def _weighted_hidden_bwd(act, res, dh):
    pre, gs = res
    dh = dh.astype(jnp.float32)
    da = dh * gs[:, None]                                 # d activation
    if act == "swiglu":
        g, u = _halves32(pre)
        s = jax.nn.sigmoid(g)
        dgs = jnp.sum(dh * (g * s * u), axis=-1)
        dpre = jnp.concatenate([da * u * s * (1 + g * (1 - s)), da * g * s],
                               axis=-1)
    else:
        r = jax.nn.relu(pre.astype(jnp.float32))
        dgs = jnp.sum(dh * r * r, axis=-1)
        dpre = da * 2 * r
    return dpre.astype(pre.dtype), dgs


weighted_hidden.defvjp(_weighted_hidden_fwd, _weighted_hidden_bwd)


def sorted_assignments(flat_e, groups: int):
    """(order, inv, group_sizes, live) of the assignments' experts
    ``flat_e`` [n] int32, by sorts alone: ``order`` their stable argsort,
    ``inv`` its inverse (the argsort of a permutation), ``group_sizes``
    [groups] int32 the rows of each expert 0 .. groups - 1 read off the
    SORTED keys' boundaries, ``live`` [n] bool the sorted rows that have a
    group (an id of ``groups`` or more has none and sorts behind them all).
    A scatter of n indices runs one index at a time on a TPU (``bincount``
    2.29 ms and ``inverse_permutation`` 1.21 at OLMoE's 262,144, where the
    sort of the same keys takes 0.23: chip run, PR 42)."""
    n = flat_e.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    sorted_e, order = jax.lax.sort((flat_e.astype(jnp.int32), iota),
                                   num_keys=1, is_stable=True)
    _, inv = jax.lax.sort((order, iota), num_keys=1)
    ends = jnp.searchsorted(sorted_e, jnp.arange(1, groups + 1,
                                                 dtype=jnp.int32),
                            method="compare_all").astype(jnp.int32)
    sizes = jnp.diff(ends, prepend=jnp.zeros((1,), jnp.int32))
    return order, inv, sizes, sorted_e < groups


def _expert_ffn(xe, w_gu, w_dn):
    """The per-expert SwiGLU on a dense [e_local, slots, d] layout —
    MoEMLP.forward's math on raw (shard_map-local) weight shards."""
    return expert_ffn(xe, w_gu, w_dn, "swiglu", _dense_up, _dense_down)


def _aux_loss_ep(probs, e):
    """GShard aux loss inside an ep shard_map body: the two token-means
    are pmean'd over the ranks BEFORE the product, which IS the global
    estimator (mean of equal-sized shard means = global mean), so ep>1
    training loss stays at parity with the replicated path — a
    pmean-of-per-rank-aux would average products of local means
    instead and drift by O(routing skew)."""
    top1 = jnp.argmax(probs, axis=-1)
    me = jax.lax.pmean(jnp.mean(probs, axis=0), "ep")
    ce = jax.lax.pmean(
        jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0), "ep")
    return jnp.sum(me * ce) * e


# the eps of the RMSNorm over the MLP router's state: the one family that
# has such a router (ZAYA) normalises everything at 1e-5, and MoEConfig
# refuses router="mlp" beside another rms_norm_eps
ROUTER_NORM_EPS = 1e-5


class MoELayer(Layer):
    """Top-k routed MoE block (reference: MoELayer, moe_layer.py:263).

    forward(x: [b, s, d]) -> (out [b, s, d], aux_loss scalar)

    ``capacity_factor=None`` selects DROPLESS routing via grouped matmul
    (``lax.ragged_dot``, forward and backward since PR 28): exact
    per-expert counts, no token ever dropped.

    The router's variants (the defaults are the GShard router this layer
    has always had, so a model that passes none runs the program it ran):
    ``scoring`` "softmax" | "sigmoid" (DeepSeek-V3's, arXiv:2412.19437:
    each expert scored on its own); ``select_bias`` adds a float32
    parameter ``gate_bias`` [e] to the scores for the CHOICE of experts
    only (the weights stay the unbiased scores: the auxiliary-loss-free
    balancing of that paper); ``norm_topk_prob`` None renormalises the
    chosen weights whenever top_k > 1, True/False says so outright;
    ``routed_scaling_factor`` multiplies them. The variants run on the
    dropless and the inference paths; the capacity and expert-parallel
    paths refuse them by name.

    ``router="mlp"`` (ZAYA1's, arXiv:2511.17127) replaces the one matrix
    by a small network with a state handed from layer to layer:
    ``router_state(x, prev)`` is ``x W_d + b_d (+ g * prev)``
    [.., router_hidden_size], the caller keeps it for the next layer and
    hands it back to ``forward`` / ``forward_inference``, which score it
    through RMSNorm and a three-layer GELU MLP, softmax over the experts
    and, with ``skip_choice``, one more output whose choice sends a row
    through NO expert (its output is 0 and ``load`` does not count it).
    The weights are the softmax's own probabilities. Like the other
    variants: dropless and inference paths only.

    ``experts_held=(first, count)`` builds the layer of ONE chip of an
    expert-parallel deployment: the router stays ``num_experts`` wide and
    the choice and the renormalisation run over all of them, the expert
    leaves are ``[count, ...]`` (experts ``first .. first + count - 1``),
    a row's choices that fall on an expert held elsewhere add nothing here
    (their outputs are the other chips' to add), and ``load`` is over the
    held experts. Nothing stands in for the absent chips or their traffic.
    ``expert_act`` "swiglu" | "relu2" (``expert_ffn``: non-gated experts
    ``relu(x W_up)^2 W_down``, leaves ``w_up`` / ``w_down``). Both on the
    dropless and the inference paths only, like the router's variants.

    ``forward_inference`` (what ``forward`` runs once the layer is in eval
    mode) computes no auxiliary loss and is dropless at any load.
    """

    # rows at or below which the inference path runs EVERY expert held
    # over every row and weights the outputs (0 where an expert was not
    # chosen) instead of sorting rows to their experts, once the rows'
    # choices that fall on the held experts are expected to outnumber them:
    # a decode tick's few rows then hit nearly every held expert anyway (64
    # rows x top-4 over 64 experts, all held: 62.97 expected), the products
    # are bound by the held experts' weights streaming from HBM either way
    # (held x 6 d f bytes against t x held x 6 d f FLOPs: level at t =
    # 197e12 / 819e9 = 240 rows on a v5e), and a plain batched matmul needs
    # no sort, no gather and no per-group tiles: 1.67 ms a layer against
    # ragged_dot's 2.36 at GLM-4.7-Flash's sizes (chip run, PR 27). Up to
    # that level, whole: at 192 rows x top-6 over 64 held experts of 2688 x
    # 1856 a tick read 25.75 ms this way and 122.6 sorted (chip run, PR 33)
    DENSE_ROWS = RIDGE_FLOPS_PER_BYTE

    def __init__(self, hidden_size: int, ffn_size: int, num_experts: int,
                 top_k: int = 2, capacity_factor: Optional[float] = 1.25,
                 dtype=None, gate: str = "gshard",
                 scoring: str = "softmax", select_bias: bool = False,
                 norm_topk_prob: Optional[bool] = None,
                 routed_scaling_factor: float = 1.0,
                 router: str = "linear",
                 router_hidden_size: Optional[int] = None,
                 skip_choice: bool = False,
                 experts_held: Optional[tuple] = None,
                 expert_act: str = "swiglu"):
        super().__init__()
        if router not in ("linear", "mlp"):
            raise ValueError(f"router must be 'linear' or 'mlp', got "
                             f"{router!r}")
        if router == "mlp" and not router_hidden_size:
            raise ValueError("router='mlp' needs router_hidden_size")
        if router == "linear" and skip_choice:
            raise ValueError("skip_choice is the MLP router's: pass "
                             "router='mlp'")
        if router == "mlp" and select_bias:
            raise NotImplementedError(
                "select_bias with router='mlp' (a balancing bias over the "
                "MLP router's outputs) is not built")
        if top_k > num_experts:
            raise ValueError(f"top_k={top_k} > num_experts={num_experts}")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring must be 'softmax' or 'sigmoid', "
                             f"got {scoring!r}")
        self.num_experts = num_experts
        self.top_k = 1 if gate == "switch" else top_k
        self.capacity_factor = capacity_factor
        self.scoring = scoring
        self.renormalize = (self.top_k > 1 if norm_topk_prob is None
                            else bool(norm_topk_prob))
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.router = router
        self.skip_choice = bool(skip_choice)
        if router == "linear":
            self.gate_weight = self.create_parameter(
                [hidden_size, num_experts], dtype="float32",
                initializer=I.Normal(0.0, 0.02))
        else:
            self.add_parameter("gate_weight", None)
            self._make_mlp_router(hidden_size, router_hidden_size,
                                  num_experts + int(self.skip_choice))
        if select_bias:
            self.gate_bias = self.create_parameter(
                [num_experts], dtype="float32", initializer=I.Constant(0.0))
        else:
            self.add_parameter("gate_bias", None)
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and 0 < count and first + count <= num_experts):
            raise ValueError(f"experts_held={experts_held!r} names experts "
                             f"outside 0..{num_experts - 1}")
        self.first_held, self.num_held = int(first), int(count)
        # the router and the experts the capacity and expert-parallel paths
        # were written for: GShard's softmax over every expert, all of them
        # held, SwiGLU
        self._gshard_router = (scoring == "softmax" and not select_bias
                               and self.renormalize == (self.top_k > 1)
                               and self.routed_scaling_factor == 1.0
                               and router == "linear"
                               and self.num_held == num_experts
                               and expert_act == "swiglu")
        if not self._gshard_router and capacity_factor is not None:
            raise ValueError(
                "scoring / select_bias / norm_topk_prob / "
                "routed_scaling_factor / router='mlp' / experts_held / "
                "expert_act run on the dropless path only: pass "
                "capacity_factor=None")
        self.experts = MoEMLP(self.num_held, hidden_size, ffn_size,
                              dtype=dtype, act=expert_act)

    def _make_mlp_router(self, hidden_size: int, r: int, outputs: int):
        """The MLP router's leaves, all float32: the down-projection, the
        gate on the previous layer's state, RMSNorm, three layers."""
        def make(name, shape, init):
            setattr(self, name, self.create_parameter(
                shape, dtype="float32", initializer=init))
        normal, zeros = I.Normal(0.0, 0.02), I.Constant(0.0)
        make("router_down", [hidden_size, r], normal)
        make("router_down_bias", [r], zeros)
        make("router_state_gate", [r], I.Constant(1.0))
        make("router_norm", [r], I.Constant(1.0))
        make("router_w1", [r, r], normal)
        make("router_b1", [r], zeros)
        make("router_w2", [r, r], normal)
        make("router_b2", [r], zeros)
        make("router_w3", [r, outputs], normal)

    def router_state(self, x, prev=None):
        """The MLP router's state of this layer, float32 [.., r]: ``x W_d +
        b_d``, plus ``g * prev`` where a previous layer handed its own
        down (after ITS addition: the state averages over depth)."""
        r = jnp.matmul(x.astype(jnp.float32), self.router_down) \
            + self.router_down_bias
        return r if prev is None else r + self.router_state_gate * prev

    def _route(self, flat, state=None):
        """(scores [t, e(+1)], chosen scores [t, k], ids [t, k]) of the
        rows ``flat`` [t, d]; ``state`` [t, r] is ``router_state``'s. An
        id equal to ``num_experts`` is the skip choice."""
        if self.router == "linear":
            return self._choose(
                jnp.matmul(flat.astype(jnp.float32), self.gate_weight))
        if state is None:
            raise ValueError("router='mlp' routes on router_state(x, prev): "
                             "pass router_state=")
        r = state.reshape(flat.shape[0], -1)
        r = r * jax.lax.rsqrt(jnp.mean(r * r, -1, keepdims=True)
                              + ROUTER_NORM_EPS) * self.router_norm
        h = jax.nn.gelu(jnp.matmul(r, self.router_w1) + self.router_b1,
                        approximate=False)
        h = jax.nn.gelu(jnp.matmul(h, self.router_w2) + self.router_b2,
                        approximate=False)
        return self._choose(jnp.matmul(h, self.router_w3))

    def _choose(self, logits):
        """(scores [t, e], the chosen experts' scores [t, k], ids [t, k]):
        float32 scores by ``scoring``; the choice is the top-k of the
        scores plus ``gate_bias`` where there is one, the weights are the
        scores themselves."""
        scores = (jax.nn.softmax(logits, axis=-1) if self.scoring == "softmax"
                  else jax.nn.sigmoid(logits))
        if self.gate_bias is None:
            gates, ids = jax.lax.top_k(scores, self.top_k)
        else:
            _, ids = jax.lax.top_k(scores + self.gate_bias, self.top_k)
            gates = jnp.take_along_axis(scores, ids, axis=-1)
        return scores, gates, ids

    def _weights(self, gates, axis: int):
        """The chosen experts' weights from their scores (k along
        ``axis``): renormalised and scaled as the layer was told."""
        if self.renormalize:
            gates = gates / jnp.maximum(
                jnp.sum(gates, axis, keepdims=True), 1e-9)
        if self.routed_scaling_factor != 1.0:
            gates = gates * self.routed_scaling_factor
        return gates

    def _held(self, ids):
        """The chosen experts as this layer's own indices
        0 .. num_held - 1, and ``num_held`` for a choice held elsewhere (or
        the skip choice): such a row sorts behind every held expert's run,
        belongs to no group and is dropped by a bounded scatter."""
        if self.num_held == self.num_experts:
            return ids
        local = ids - self.first_held
        return jnp.where((local >= 0) & (local < self.num_held), local,
                         self.num_held)

    def routing_histogram(self, x):
        """Measured per-expert token counts for ``x`` — the histogram
        the planner's entropy-priced all-to-all consumes
        (``price_config(..., moe_histogram=...)``)."""
        flat = x.reshape(-1, x.shape[-1])
        logits = jnp.matmul(flat.astype(jnp.float32), self.gate_weight)
        return routing_stats(logits, self.top_k)[2]

    def forward(self, x, router_state=None):
        b, s, d = x.shape
        t = b * s
        e = self.num_experts
        flat = x.reshape(t, d)

        # expert-parallel path: a real "ep" mesh axis routes through the
        # shard_map'd all-to-all; ep==1 (and shapes ep does not divide)
        # fall through to the GSPMD paths below
        hm = current_mesh()
        ep = hm.axis_size("ep") if hm is not None else 1
        if not self.training and ep == 1 and self.capacity_factor is None:
            out, _ = self.forward_inference(x, router_state)
            return out, jnp.zeros((), jnp.float32)
        if ep > 1 and t % ep == 0 and e % ep == 0 and (t // ep) > 0:
            if not self._gshard_router:
                raise NotImplementedError(
                    "the expert-parallel paths route with the GShard "
                    "router only (softmax, one matrix, no selection bias, "
                    "no scale) over SwiGLU experts all held here (no "
                    "experts_held, no expert_act)")
            if self.capacity_factor is None:
                out, aux = self._forward_dropless_ep(flat, hm.mesh, ep)
            else:
                out, aux = self._forward_capacity_ep(flat, hm.mesh, ep)
            return out.reshape(b, s, d), aux

        if self.capacity_factor is None:
            out, aux = self._forward_dropless(flat,
                                              self._route(flat, router_state))
            return out.reshape(b, s, d), aux

        logits = jnp.matmul(flat.astype(jnp.float32), self.gate_weight)

        capacity = int(math.ceil(t * self.top_k / e * self.capacity_factor))
        slot, gates, aux = top_k_routing(logits, self.top_k, capacity)
        xe = dispatch_tokens(flat, slot, e, capacity)         # [e, c, d]
        xe = _constrain_experts(xe)
        ye = self.experts(xe)
        ye = _constrain_experts(ye)
        out = combine_tokens(ye, slot, gates,
                             renormalize=self.top_k > 1)
        return out.reshape(b, s, d), aux

    def _forward_capacity_ep(self, flat, mesh_, ep: int):
        """shard_map'd expert-parallel capacity routing. Each ep rank
        routes its LOCAL tokens into the full [e, c_local, d] slot
        layout; the tiled all-to-all splits the expert dim over ranks
        while concatenating every rank's slot block, local expert
        shards run one dense SwiGLU over [e/ep, c_local*ep, d], and the
        reverse all-to-all returns each rank's slots for the local
        combine. The aux loss is the pmean over ranks (same estimator
        as dp-averaged gradients)."""
        t, d = flat.shape
        e, k = self.num_experts, self.top_k
        t_l = t // ep
        cap = int(math.ceil(t_l * k / e * self.capacity_factor))
        renorm = k > 1
        gw = self.gate_weight.astype(jnp.float32)
        w_gu = self.experts.w_gate_up.astype(flat.dtype)
        w_dn = self.experts.w_down.astype(flat.dtype)

        def body(xl, gw_, wgu, wdn):
            logits = jnp.matmul(xl.astype(jnp.float32), gw_)
            slot, gates, _ = top_k_routing(logits, k, cap)
            aux = _aux_loss_ep(jax.nn.softmax(logits, axis=-1), e)
            xe = dispatch_tokens(xl, slot, e, cap)            # [e, c, d]
            xe = jax.lax.all_to_all(xe, "ep", split_axis=0,
                                    concat_axis=1, tiled=True)
            ye = _expert_ffn(xe, wgu, wdn)                # [e/ep, c*ep, d]
            ye = jax.lax.all_to_all(ye, "ep", split_axis=1,
                                    concat_axis=0, tiled=True)
            out = combine_tokens(ye, slot, gates, renormalize=renorm)
            return out, aux

        fn = shard_map(body, mesh=mesh_, axis_names=frozenset({"ep"}),
                       in_specs=(P("ep", None), P(None, None),
                                 P("ep", None, None),
                                 P("ep", None, None)),
                       out_specs=(P("ep", None), P()),
                       check_vma=False)
        return fn(flat, gw, w_gu, w_dn)

    def _forward_dropless_ep(self, flat, mesh_, ep: int):
        """shard_map'd DROPLESS expert parallelism. Each rank sorts its
        local assignments by expert and scatters them into a
        statically-bounded [e, t_local, d] slot buffer (an expert can
        receive at most t_local distinct local tokens, so nothing is
        ever dropped); the exact per-expert counts are the a2a split
        sizes in the logical sense — they define slot occupancy inside
        the bound, because this jax ships no ragged_all_to_all. The
        grouped matmul then runs over the received slot blocks, and the
        reverse all-to-all + unsort restores token order."""
        t, d = flat.shape
        e, k = self.num_experts, self.top_k
        t_l = t // ep
        e_l = e // ep
        cap = t_l          # static per-(rank, expert) bound: top_k ids
        renorm = k > 1     # are distinct, so counts[e] <= t_local
        gw = self.gate_weight.astype(jnp.float32)
        w_gu = self.experts.w_gate_up.astype(flat.dtype)
        w_dn = self.experts.w_down.astype(flat.dtype)

        def body(xl, gw_, wgu, wdn):
            logits = jnp.matmul(xl.astype(jnp.float32), gw_)
            probs = jax.nn.softmax(logits, axis=-1)
            gates, ids = jax.lax.top_k(probs, k)              # [t_l, k]
            flat_e = ids.T.reshape(-1)                        # [k*t_l]
            order = jnp.argsort(flat_e, stable=True)
            sorted_e = flat_e[order]
            starts = jnp.searchsorted(sorted_e, jnp.arange(e))
            pos = jnp.arange(k * t_l, dtype=jnp.int32) - starts[sorted_e]
            dest = sorted_e * cap + pos                       # exact, no drop
            xs = xl[order % t_l]
            buf = jnp.zeros((e * cap, d), xl.dtype).at[dest].set(xs)
            buf = jax.lax.all_to_all(buf.reshape(e, cap, d), "ep",
                                     split_axis=0, concat_axis=1,
                                     tiled=True)          # [e_l, ep*cap, d]
            rows = buf.reshape(e_l * ep * cap, d)
            gsz = jnp.full((e_l,), ep * cap, jnp.int32)
            gmm = lambda a, w: grouped_matmul(a, w, gsz)
            ys = expert_ffn(rows, wgu, wdn, "swiglu", gmm, gmm)
            ybuf = jax.lax.all_to_all(ys.reshape(e_l, ep * cap, d), "ep",
                                      split_axis=1, concat_axis=0,
                                      tiled=True)             # [e, cap, d]
            ysr = ybuf.reshape(e * cap, d)[dest]              # sorted order
            y_cm = jnp.zeros_like(ysr).at[order].set(ysr).reshape(
                k, t_l, d)
            g_km = gates.T                                    # [k, t_l]
            if renorm:
                g_km = g_km / jnp.maximum(
                    jnp.sum(g_km, 0, keepdims=True), 1e-9)
            out = jnp.sum(g_km[..., None].astype(ysr.dtype) * y_cm,
                          axis=0)
            return out, _aux_loss_ep(probs, e)

        fn = shard_map(body, mesh=mesh_, axis_names=frozenset({"ep"}),
                       in_specs=(P("ep", None), P(None, None),
                                 P("ep", None, None),
                                 P("ep", None, None)),
                       out_specs=(P("ep", None), P()),
                       check_vma=False)
        return fn(flat, gw, w_gu, w_dn)

    def _forward_dropless(self, flat, routing):
        """Grouped-matmul experts over exact per-expert counts — the
        dropless path (reference analogue: global_scatter's exact
        count_by_gate split sizes). Both products are
        ``grouped_matmul``: XLA's ``lax.ragged_dot`` forward and
        backward since PR 28. The rows go to their experts and come back
        by gathers, forward and backward (``dispatch_rows``,
        ``combine_rows``), and the index work is two sorts
        (``sorted_assignments``). Since PR 43 a row's router weight
        multiplies its hidden activation BEFORE the down product
        (``weighted_hidden``: float32, rounded once), so coming back is a
        gather and a sum over k and nothing of [k, t, d] is kept for the
        backward or built in it. ``routing`` is ``_route``'s. Rows whose
        choice is the skip, or an expert held elsewhere, sort behind every
        expert's run, belong to no group and count as 0 (``live``: by a
        ``where``, their rows are whatever the products left); the MLP
        router trains with no auxiliary term.

        What the backward holds at once decides whether XLA recomputes a
        1 GiB gather at OLMoE's size (PERF.md section 6, PR 43): each
        product's two cotangents leave together
        (``cotangents_together``: d weights is formed where its rows are
        alive, not at the end of the step), and the weighted activation is
        remade from the pre-activation (``jax.checkpoint``) instead of
        kept from the forward."""
        t, d = flat.shape
        e, held = self.num_experts, self.num_held
        act = self.experts.act
        probs, gates, ids = routing                           # [t, k]
        flat_e = self._held(ids).T.reshape(-1)                # [k*t]
        order, inv, group_sizes, live = sorted_assignments(flat_e, held)
        if not (self.skip_choice or held < e):
            live = None                                   # every row has one
        xs = dispatch_rows(flat, order, inv, live)            # [k*t, d]
        # each sorted row's weight, float32: scalars through the same
        # permutation, 0 for a row of no group
        gs = permute_scalars(self._weights(gates.T, 0).reshape(-1), order,
                             inv)
        if live is not None:
            gs = jnp.where(live, gs, 0)

        w_in = self.experts.w_in.astype(flat.dtype)       # [held, d, (2)f]
        w_dn = self.experts.w_down.astype(flat.dtype)     # [held, f, d2]
        pre = grouped_matmul(*cotangents_together(xs, w_in), group_sizes)

        @jax.checkpoint
        def down(pre, gs, w_dn):
            hidden = weighted_hidden(pre, gs, act)
            return grouped_matmul(*cotangents_together(hidden, w_dn),
                                  group_sizes)
        out = combine_rows(down(pre, gs, w_dn), order, inv, live, t)
        return out, (jnp.zeros((), jnp.float32) if self.router == "mlp"
                     else _aux_loss(probs, e))

    def inference_path(self, t: int, dtype=None):
        """How ``forward_inference`` runs the experts over a call of ``t``
        rows of ``dtype`` (the experts' own if not given), from shapes
        alone: ("dense", None): every held expert over every row; or
        ("loop", rows of a step): the rows sorted to their experts and
        ``blocked_expert_rows`` over them (``expert_step_rows``)."""
        e, k, held = self.num_experts, self.top_k, self.num_held
        hits = t * k * held // e    # choices expected on the held experts
        if t <= self.DENSE_ROWS and hits >= held:
            return "dense", None
        return "loop", expert_step_rows(
            t, k, e, dtype or self.experts.w_down.dtype)

    def forward_inference(self, x, router_state=None):
        """The routed block without a loss: x [b, s, d] -> (out [b, s, d],
        load [num_held] int32: the rows each expert held here was sent;
        rows x top-k less its sum chose the skip or an expert held
        elsewhere). Dropless at any load. At most ``DENSE_ROWS`` rows whose
        choices are expected to outnumber the held experts they fall on run
        every held expert over every row as one batched matmul; the rest
        are sorted to their experts, run the loop over an expert's rows
        (``blocked_expert_rows`` at ``expert_step_rows``: faster than XLA's
        ``ragged_dot`` at every width and length a served model's prompts
        have, ``tools/expert_path_probe.py``) and come back by gathers
        (``inference_path`` says which, from shapes alone)."""
        b, s, d = x.shape
        t, e, k = b * s, self.num_experts, self.top_k
        held, act = self.num_held, self.experts.act
        flat = x.reshape(t, d)
        _, gates, ids = self._route(flat, router_state)
        gates = self._weights(gates, -1)                      # [t, k]
        ids = self._held(ids)
        load = jnp.bincount(ids.reshape(-1), length=held).astype(jnp.int32)
        w_in = self.experts.w_in.astype(flat.dtype)       # [held, d, (2)f]
        w_dn = self.experts.w_down.astype(flat.dtype)     # [held, f, d]
        path, step = self.inference_path(t, flat.dtype)
        if path == "dense":
            # weight [t, held]: an expert's share of a row, 0 if not chosen
            weight = jnp.zeros((t, held), jnp.float32).at[
                jnp.arange(t)[:, None], ids].add(gates, mode="drop")
            out = expert_ffn(
                jnp.broadcast_to(flat[None], (held, t, d)), w_in, w_dn, act,
                lambda a, w: jnp.einsum(
                    "etd,edf->etf", a, w,
                    preferred_element_type=jnp.float32),
                lambda h, w: jnp.einsum(
                    "etf,efd->td",
                    (h * weight.T[..., None]).astype(flat.dtype), w,
                    preferred_element_type=jnp.float32))
            return out.astype(x.dtype).reshape(b, s, d), load
        flat_e = ids.T.reshape(-1)                            # [k*t]
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        xs = flat[order % t]                                  # [k*t, d]
        ys = blocked_expert_rows(xs, w_in, w_dn, act, load, step)  # f32
        # each row's k outputs come back by GATHERS (``order`` is a
        # permutation: sorted row inv[j, i] is row i's j-th choice; one that
        # fell on no held expert sorted behind every expert's run, where
        # ``ys`` is 0), weighted and added a choice at a time in float32. A
        # scan, so that the k gathers run one after another: left to itself
        # XLA runs them all first and keeps k x t float32 rows beside ``ys``
        # (the peak of the widest prefill program)
        inv = inverse_permutation(order).reshape(k, t)

        def add(acc, choice):
            rows, gate = choice
            return acc + gate[:, None] * ys[rows], None
        out, _ = jax.lax.scan(add, jnp.zeros((t, d), jnp.float32),
                              (inv, gates.T))
        return out.astype(x.dtype).reshape(b, s, d), load
