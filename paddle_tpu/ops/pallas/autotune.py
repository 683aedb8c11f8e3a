"""Kernel autotuning cache (block-size selection per shape/device).

Reference analogue: the PHI runtime autotuner —
paddle/phi/kernels/autotune/auto_tune_base.h (TuneBase::Run candidate
timing), cache.h (AutoTuneCache keyed on algorithm+shape), and
switch_autotune.h (step-gated tuning) — plus CINN's persistent tuning DB
(paddle/cinn/auto_schedule/database/). TPU redesign: Pallas kernels have a
tiny discrete config space (block_q, block_k), so instead of an in-process
exhaustive timer on first call (bad under jit: retrace per config), tuning
is OFFLINE (tools/tune_kernels.py sweeps on real hardware) and the result
is a JSON database consulted at dispatch time:

    key = op | device_kind | dtype | bucketed shape signature

Shapes bucket to powers of two so one sweep covers a family; lookups fall
back to the nearest recorded bucket, then to the built-in defaults. The
shipped ``tune_db.json`` IS the database: block choice on the chip depends
only on files git holds. ``tools/tune_kernels.py`` writes a sweep to the
path it is given (``--out``) or into the shipped file (``--write-shipped``).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

_SHIPPED = os.path.join(os.path.dirname(__file__), "tune_db.json")


class TuneDB:
    """One JSON file of kernel configs (default: the shipped DB).

    ``path`` parameterizes the file so sibling databases (the cost
    observatory's :class:`OpCostDB`) and tools writing a sweep elsewhere
    share the exact load/save/corrupt-warning machinery."""

    #: human label used in the corrupt-file warning
    db_label = "kernel tune DB"

    def __init__(self, path: Optional[str] = None):
        self._db: Dict[str, dict] = {}
        self._loaded = False
        self.path = path or _SHIPPED

    def _load(self):
        if self._loaded:
            return
        try:
            with open(self.path) as f:
                self._db.update(json.load(f))
        except OSError:
            pass      # absent DB is normal (no offline sweep run yet)
        except ValueError as e:
            # corrupt JSON: loading nothing SILENTLY would make
            # offline-tuned configs vanish without a trace — say so once
            import warnings
            warnings.warn(
                f"ignoring corrupt {self.db_label} at {self.path} ({e}); "
                f"offline-tuned configs from that file will not be "
                f"applied", RuntimeWarning, stacklevel=2)
        self._loaded = True

    @staticmethod
    def bucket(n: int) -> int:
        """Round up to the next power of two (min 128)."""
        b = 128
        while b < n:
            b <<= 1
        return b

    @staticmethod
    def key(op: str, device_kind: str, dtype: str, **dims) -> str:
        sig = ",".join(f"{k}={TuneDB.bucket(v) if k.startswith('s') else v}"
                       for k, v in sorted(dims.items()))
        return f"{op}|{device_kind.lower().replace(' ', '_')}|{dtype}|{sig}"

    def lookup(self, key: str) -> Optional[dict]:
        self._load()
        return self._db.get(key)

    def record(self, key: str, config: dict):
        self._load()
        self._db[key] = config

    def save(self, path: Optional[str] = None):
        path = path or self.path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        # merge-over-existing so concurrent tuners don't clobber each other
        merged = {}
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            pass
        merged.update(self._db)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, path)


_DB = TuneDB()


def _default_blocks(sq: int, sk: int, d: int) -> Tuple[int, int]:
    """The rule when the DB has no entry, one for every length and head
    size: ONE block for the whole length up to 2,048, so a head is one grid
    step whose K and V sit in VMEM once; a longer length is cut evenly, in
    whole 128s (4,096 runs 2 x 2,048; 2,688 runs 2 x 1,408 and pads 128
    rows: a block need not divide the length, the kernel pads to whole
    blocks). The kernel runs a block in row parts
    (``flash_attention.FWD_PART_ROWS`` / ``BWD_PART_ROWS``), so VMEM holds a
    part's scores and not the block's, and a part on the causal line
    multiplies only the keys up to its last row. On a v5e (PR 42,
    ``tools/tune_kernels.py --flash``) wide blocks beat every grid of
    smaller ones at every length the cells send: a grid step costs ~0.35
    us whatever it holds and re-reads K and V. (One block of 4,096 read 5%
    faster still and compiles three times as long: Mosaic unrolls a
    block's vector code.) ``d`` does not move the width: forward and
    backward compile for a v5e at every head size the gate admits (32 to
    256), because the parts, not the blocks, set what VMEM holds."""
    def cut(s):
        n = -(-s // 2048)
        return min(s, -(-s // (n * 128)) * 128)
    return cut(sq), cut(sk)


def flash_attention_config(sq: int, sk: int, d: int,
                           dtype: str, causal: bool) -> Tuple[int, int]:
    """(block_q, block_k) for a flash-attention call: tuned if the DB has
    this (bucketed) shape on this device, else shape-aware defaults.
    Batch and head count are deliberately NOT part of the key: they scale
    the parallel grid dims, not the per-block working set the block sizes
    tile, so one sweep covers all (b, h)."""
    from ..registry import backend_kind
    if backend_kind() != "tpu":
        return 128, 128
    key = TuneDB.key("flash_attention", _device_kind(default="tpu"), dtype,
                     sq=sq, sk=sk, d=d, causal=int(causal))
    hit = _DB.lookup(key)
    if hit:
        return int(hit["block_q"]), int(hit["block_k"])
    return _default_blocks(sq, sk, d)


def _device_kind(default: str = "cpu") -> str:
    try:
        import jax
        return getattr(jax.devices()[0], "device_kind", default) or default
    except Exception:
        return default


def fused_vocab_ce_config(n: int, h: int, v: int,
                          dtype: str) -> Tuple[Optional[int], int]:
    """(block_n, block_v) for a fused vocab-CE call (ops/pallas/
    fused_vocab_ce.py): tuned if the DB has this (bucketed) shape on this
    device, else VMEM-fitting defaults. ``block_n`` comes back None when no
    candidate divides N — the caller falls through to the XLA path. What
    paces VMEM is the larger of the dhidden kernel's fp32 [block_n, H]
    blocks (its cross-slab sum in and out, double-buffered) and the dW
    kernel's fp32 [H, block_v] accumulator, so the default block_v shrinks
    as H grows; the dW kernel's own row block follows from the same formula
    (``fused_vocab_ce.dw_block_n``)."""
    from ..registry import backend_kind
    key = TuneDB.key("fused_vocab_ce", _device_kind(), dtype,
                     h=h, v=v, sn=n)
    hit = _DB.lookup(key)
    if hit:
        bn, bv = int(hit["block_n"]), int(hit["block_v"])
        # a tuned entry the kernel gate would reject (stale DB after a
        # VMEM_BUDGET change, hand-edited config) must fall through to the
        # defaults, not silently downgrade every TPU call to the XLA path
        if n % bn == 0:
            if backend_kind() != "tpu":
                return bn, bv
            from .fused_vocab_ce import fused_ce_supported
            if fused_ce_supported(n, h, v, dtype, bn, bv):
                return bn, bv
    # defaults come from the kernel module's OWN vmem formula — the same
    # one fused_ce_supported gates on, so a default config is never chosen
    # only to be rejected at dispatch (which would silently route every
    # TPU call to the XLA fallback)
    from .fused_vocab_ce import default_blocks
    return default_blocks(n, h, dtype)


def paged_decode_crossover(default: int = 0) -> int:
    """Context length (tokens) above which the Pallas paged-decode kernel
    beats the dense XLA gather path for one decode step. Measured on v5e
    at the two serving cells' shapes (tools/tune_kernels.py
    --paged-decode: write-then-attend steps chained in one program; PR 32,
    since the kernel fetches its live pages itself): the kernel is ahead
    at EVERY context. B=32, 32 query / 8 KV heads of 128, pages of 128:
    0.13 / 0.14 / 0.23 / 0.41 ms a call at 256 / 512 / 1024 / 2048 tokens
    of a 2048-token table span against 1.04-1.30 ms dense, and 0.25-1.49
    ms against 4.46-5.13 ms over an 8192-token span; B=128, 8 / 2 heads,
    24-page tables: 0.19 / 0.23 / 0.59 ms at 256 / 1024 / 3072 tokens
    against 1.55-1.93 ms — so the default is 0 and every tick of an engine
    takes one executable. (The 4096 it replaced was read off single
    host-timed dispatches, which sit on one ~3 ms floor.) A tuned value
    (op "paged_decode_crossover", config key "ctx") in the TuneDB wins;
    the serving engine consults this per dispatched decode block
    (inference/serving.py)."""
    key = TuneDB.key("paged_decode_crossover", _device_kind(), "any")
    hit = _DB.lookup(key)
    if hit:
        try:
            return int(hit["ctx"])
        except (KeyError, ValueError, TypeError):
            pass
    return default


def get_db() -> TuneDB:
    return _DB


# ---------------------------------------------------------------------------
# OpCostDB: measured op/graph latencies (ISSUE 9 cost observatory)
# ---------------------------------------------------------------------------

_COST_SHIPPED = os.path.join(os.path.dirname(__file__), "op_cost_db.json")


class OpCostDB(TuneDB):
    """Measured-latency database the cost observatory calibrates
    (``tools/op_cost_probe.py``) and the sharding planner will read.

    Same persistence discipline as the kernel TuneDB it sits next to —
    one file (default: ``op_cost_db.json`` beside the tune DB), atomic
    merge-over-existing save, and the corrupt-file warning path (a corrupt
    calibration file must degrade to analytical estimates loudly, never
    silently) — but keyed on MEASURED
    quantities: ``graph:<name>|<device_kind>|any|`` records a canonical
    graph's min-of-rounds execution seconds + its analytical flop/byte
    attribution, ``dot|<device_kind>|<dtype>|k=...,m=...,n=...`` records a
    dominant matmul shape's microbench seconds. Entries carry the numbers
    the planner prices configs with, so calibration survives restarts."""

    db_label = "op cost DB"

    def __init__(self, path: Optional[str] = None):
        super().__init__(path or _COST_SHIPPED)

    @staticmethod
    def graph_key(name: str, device_kind: str) -> str:
        return TuneDB.key(f"graph:{name}", device_kind, "any")

    @staticmethod
    def dot_key(m: int, k: int, n: int, dtype: str,
                device_kind: str) -> str:
        return TuneDB.key("dot", device_kind, dtype, m=m, k=k, n=n)


_COST_DB = OpCostDB()


def get_op_cost_db() -> OpCostDB:
    return _COST_DB


__all__ = ["TuneDB", "get_db", "flash_attention_config",
           "fused_vocab_ce_config", "paged_decode_crossover",
           "OpCostDB", "get_op_cost_db"]
