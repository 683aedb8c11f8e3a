"""Comm/compute overlap controls.

Reference analogues:
- ``mp_async_allreduce`` (fleet/layers/mpu/mp_layers.py:458-477): overlap
  the TP backward input-grad allreduce with the weight-grad matmul.
- ``allreduce_matmul_grad_overlapping``
  (distributed/passes/allreduce_matmul_grad_overlapping.py): split matmul_grad
  so the dx allreduce overlaps the dW matmul.
- sharding comm overlap (dygraph_sharding_optimizer.py:470): overlap grad
  reduce-scatter with backward compute.

TPU redesign: the reference needs these passes because torch/paddle eager
autograd executes ops in strict sequence on one stream. Under XLA the
dataflow graph ALREADY contains the independence (dx's all-reduce and the
dW dot share no edge — verify with :func:`backward_overlap_independent`),
and the TPU compiler's latency-hiding scheduler turns that independence
into actual overlap when async collectives are enabled. So the knobs here
map to (a) TPU compiler flags, handed to libtpu through ``LIBTPU_INIT_ARGS``
before the backend initializes, and (b) analysis helpers that PROVE the
overlap precondition on compiled HLO — the moral equivalent of the
reference's pass unit tests.

GSPMD also already emits the overlap-friendly grad-sync structure for
gradient accumulation: the dp/fsdp all-reduce sits INSIDE the microbatch
loop body (one per microbatch, overlappable with the next microbatch's
compute) rather than one deferred sync — check with
:func:`collectives_in_loop`.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Tuple

import jax

# The TPU async-collective + latency-hiding-scheduler set: the production
# knobs that let the scheduler hide collective latency behind independent
# compute (the effect the reference's overlap passes hand-implement).
# There is one installation (jax/jaxlib 0.9.0, libtpu 0.0.34) and its
# libtpu accepts all six — through LIBTPU_INIT_ARGS, the route TPU compiler
# flags travel. In XLA_FLAGS every one of them aborts the process at
# backend init ("Unknown flag in XLA_FLAGS", parse_flags_from_env.cc):
# jaxlib's own parser does not know TPU compiler flags. Found by compiling
# for a described v5e with each route; the four-chip phase of
# chip_smoke.py runs with them installed.
OVERLAP_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true "
)
#: the environment variable libtpu reads its flags from
FLAGS_ENV = "LIBTPU_INIT_ARGS"

_WARNED: set = set()


def _warn_once(key: str, msg: str) -> None:
    """stderr warning emitted at most once per process per key — the
    overlap policy is consulted per Trainer construction and per
    compile, and a repeated warning is noise, not information."""
    if key not in _WARNED:
        _WARNED.add(key)
        sys.stderr.write(msg)


def _backend_initialized() -> bool:
    try:
        return bool(jax._src.xla_bridge._backends)  # noqa: SLF001
    except AttributeError:
        return False


def apply_overlap_flags(enable: bool = True, *, target: str = "tpu") -> str:
    """Install the overlap flags into ``LIBTPU_INIT_ARGS`` (idempotent);
    returns the variable's value afterwards.

    Must run BEFORE jax backend initialization — libtpu reads the variable
    once, when it loads; after that this warns and returns the current
    value unchanged. ``PT_NO_OVERLAP=1`` forces them off. A non-TPU
    ``target`` installs nothing."""
    if os.environ.get("PT_NO_OVERLAP"):
        enable = False
    cur = os.environ.get(FLAGS_ENV, "")
    if not enable or target != "tpu":
        return cur
    # match by EXACT flag name so an explicit user "=false" is respected
    # and a longer flag name doesn't shadow a shorter one's install
    cur_names = {tok.split("=")[0] for tok in cur.split()}
    missing = [f for f in OVERLAP_XLA_FLAGS.split()
               if f.split("=")[0] not in cur_names]
    if not missing:
        return cur
    if _backend_initialized():
        _warn_once(
            "backend-initialized",
            "paddle_tpu.overlap: backend already initialized; overlap "
            "flags NOT applied (set strategy before first jax use)\n")
        return cur
    new = (cur + " " + " ".join(missing)).strip()
    os.environ[FLAGS_ENV] = new
    return new


def overlap_fingerprint() -> str:
    """The overlap-relevant environment state as a stable string: which
    OVERLAP_XLA_FLAGS names are present in LIBTPU_INIT_ARGS (with their
    values, so an explicit ``=false`` differs from installed) plus the
    PT_NO_OVERLAP A/B lever. ``Trainer._fp_parts`` folds this into the
    compile-cache fingerprint so a flag flip between runs can never hit
    a stale AOT executable compiled under the other schedule."""
    ours = {f.split("=")[0] for f in OVERLAP_XLA_FLAGS.split()}
    toks = sorted(t for t in os.environ.get(FLAGS_ENV, "").split()
                  if t.split("=")[0] in ours)
    no = "PT_NO_OVERLAP;" if os.environ.get("PT_NO_OVERLAP") else ""
    return no + " ".join(toks)


def enable_overlap(enable: bool = True, *,
                   target: Optional[str] = None) -> Dict[str, object]:
    """THE applied overlap policy (ISSUE 14): install the async-collective
    / latency-hiding flag set before backend init.

    * strict no-op when off — ``enable=False`` or ``PT_NO_OVERLAP=1``
      touches nothing and says so in the returned ``reason``;
    * TPU-only — ``target`` defaults to :func:`_detect_target`.

    Returns ``{"enabled", "applied", "reason", "flags", "fingerprint"}``;
    ``flags`` is LIBTPU_INIT_ARGS and ``fingerprint`` is
    :func:`overlap_fingerprint`, both AFTER the install."""
    if target is None:
        target = _detect_target()
    if os.environ.get("PT_NO_OVERLAP"):
        reason = "PT_NO_OVERLAP"
    elif not enable:
        reason = "disabled"
    elif target != "tpu":
        reason = f"target={target}"
    else:
        reason = ""
    if reason:
        return {"enabled": False, "applied": [], "reason": reason,
                "flags": os.environ.get(FLAGS_ENV, ""),
                "fingerprint": overlap_fingerprint()}
    new = apply_overlap_flags(True, target=target)
    after = {t.split("=")[0] for t in new.split()}
    applied = [f.split("=")[0] for f in OVERLAP_XLA_FLAGS.split()
               if f.split("=")[0] in after]
    # all six or none: apply_overlap_flags only refuses a live backend
    reason = "applied" if applied else "backend-initialized"
    return {"enabled": bool(applied), "applied": applied, "reason": reason,
            "flags": new, "fingerprint": overlap_fingerprint()}


# ---------------------------------------------------------------------------
# HLO analysis: prove the overlap preconditions on the compiled program
# ---------------------------------------------------------------------------

_INSTR_LHS = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
# opcode = first word directly followed by '(' after the (possibly tuple)
# result type — tuple types like "(s32[], f32[4])" never match word-paren
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPND = re.compile(r"%([\w.\-]+)")
# computation header: "%name (params...) -> type {" or "ENTRY %name (...) {"
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{")
_COMP_REF_ONE = re.compile(
    r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_COMP_REF_LIST = re.compile(
    r"(?:calls|branch_computations)=\{([^}]*)\}")
_COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                   "collective-permute", "all-to-all")
_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|reduce-scatter|all-gather|collective-permute|"
    r"all-to-all)(-start|-done)?\(")


def _parse_hlo(txt: str):
    """Returns (graph, comp_of, comp_members): instruction dataflow plus
    computation membership. Instructions that reference a computation
    (while body, fusion calls, conditional branches) get dependency edges
    to EVERY instruction of that computation — a conservative
    over-approximation that keeps independence claims sound."""
    graph: Dict[str, Tuple[str, List[str]]] = {}
    comp_of: Dict[str, str] = {}
    comp_members: Dict[str, List[str]] = {}
    cur = None
    for line in txt.splitlines():
        h = _COMP_HDR.match(line.strip())
        if h and "=" not in line.split("(")[0]:
            cur = h.group(1)
            comp_members.setdefault(cur, [])
        m = _INSTR_LHS.match(line)
        if not m:
            continue
        name = m.group(1)
        rhs = line.split("=", 1)[1]
        mo = _OPCODE.search(" " + rhs)
        if not mo:
            continue
        op = mo.group(1)
        opnds = [o for o in _OPND.findall(rhs) if o != name]
        # computation references become dependencies on the whole callee
        refs = list(_COMP_REF_ONE.findall(rhs))
        for r in _COMP_REF_LIST.findall(rhs):
            refs.extend(p.strip().lstrip("%") for p in r.split(","))
        graph[name] = (op, opnds + [f"comp:{r}" for r in refs if r])
        if cur is not None:
            comp_of[name] = cur
            comp_members[cur].append(name)
    return graph, comp_of, comp_members


def _ancestors(graph, comp_members, name):
    seen = set()
    todo = list(graph.get(name, ("", []))[1])
    while todo:
        n = todo.pop()
        if n.startswith("comp:"):
            for member in comp_members.get(n[5:], ()):
                if member not in seen:
                    seen.add(member)
                    todo.extend(graph.get(member, ("", []))[1])
            continue
        if n in seen or n not in graph:
            continue
        seen.add(n)
        todo.extend(graph[n][1])
    return seen


def backward_overlap_independent(compiled_text: str) -> bool:
    """True if some collective and some dot are mutually independent in the
    HLO — the precondition for the latency-hiding scheduler to overlap the
    TP backward allreduce with the weight-grad matmul
    (reference mp_async_allreduce's effect)."""
    g, _, members = _parse_hlo(compiled_text)
    colls = [n for n, (op, _) in g.items()
             if op.replace("-start", "").replace("-done", "")
             in _COLLECTIVE_OPS]
    dots = [n for n, (op, _) in g.items()
            if op in ("dot", "convolution") or "dot" in op]
    for c in colls:
        anc_c = _ancestors(g, members, c)
        for d in dots:
            if d in anc_c:
                continue
            if c in _ancestors(g, members, d):
                continue
            return True
    return False


def collectives_in_loop(compiled_text: str) -> Tuple[int, int]:
    """(total collectives, collectives inside while bodies), counting the
    async -start forms too. A collective inside the microbatch loop body
    syncs per microbatch — the structure that overlaps grad comm with the
    next microbatch's compute."""
    total = 0
    in_body = 0
    body_names = set(re.findall(r"body=%?([\w.\-]+)", compiled_text))
    cur = None
    for line in compiled_text.splitlines():
        h = _COMP_HDR.match(line.strip())
        if h and "=" not in line.split("(")[0]:
            cur = h.group(1)
        if _COLLECTIVE_RE.search(line) and "=" in line:
            if "-done(" in line:
                continue          # count start/done pairs once
            total += 1
            if cur in body_names:
                in_body += 1
    return total, in_body


def strategy_overlap_summary(strategy) -> Dict[str, bool]:
    """Which reference overlap knobs the strategy requests. Unknown knobs
    land in strategy.extras; the three reference names are honored."""
    tp_cfg = getattr(strategy, "tensor_parallel", None)
    sh_cfg = getattr(strategy, "sharding", None)
    extras = getattr(strategy, "extras", {}) or {}
    return {
        "mp_async_allreduce": bool(
            getattr(tp_cfg, "mp_async_allreduce", False)
            or extras.get("mp_async_allreduce")),
        "allreduce_matmul_grad_overlapping": bool(
            extras.get("allreduce_matmul_grad_overlapping")),
        "sharding_comm_overlap": bool(
            getattr(sh_cfg, "comm_overlap", False)
            or extras.get("comm_overlap")),
    }


def apply_strategy_overlap(strategy, *, target: Optional[str] = None) -> str:
    """Map the reference overlap knobs to the TPU compiler flags. Any one
    of them on → async collectives + latency hiding on (they are one
    mechanism under XLA)."""
    if target is None:
        target = _detect_target()
    if any(strategy_overlap_summary(strategy).values()):
        return apply_overlap_flags(True, target=target)
    return os.environ.get(FLAGS_ENV, "")


def _detect_target() -> str:
    """'tpu' unless jax is pinned to platforms without one: jax's own
    platform choice decides (``jax_platforms`` / ``JAX_PLATFORMS``; unset
    means jax takes the TPU when there is one). The flags live in a
    variable only libtpu reads, so a process that ends up on another
    backend is unharmed by them."""
    jp = jax.config.jax_platforms or ""
    return "tpu" if (not jp or "tpu" in jp) else "cpu"


__all__ = ["OVERLAP_XLA_FLAGS", "FLAGS_ENV", "enable_overlap",
           "overlap_fingerprint", "apply_overlap_flags",
           "backward_overlap_independent", "collectives_in_loop",
           "strategy_overlap_summary", "apply_strategy_overlap"]
