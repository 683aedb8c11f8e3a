"""Persistent compile / AOT cache for jitted training steps.

Reference analogue: the new executor's program cache + CINN's compiled-kernel
serialization (SURVEY §L5) — a process start must not re-pay tracing and XLA
compilation for a step function it has compiled before. Three layers, each
opt-in and independently useful:

1. **In-process executable cache** — ``acquire()`` maps a *fingerprint*
   (model/optimizer structure + hyperparameters + argument avals + backend)
   to a ``jax.stages.Compiled`` executable. A second cold construction of
   the same step function (fresh ``Trainer`` over an identically-shaped
   model) reuses the executable: no retrace, no recompile. Hit/miss/trace
   counters make this testable.

2. **On-disk AOT artifacts** — ``save_aot``/``load_aot`` serialize the step
   via ``jax.export`` next to the checkpoint directory, so a preempted
   worker's relaunch deserializes StableHLO instead of re-tracing Python.
   Artifacts are keyed by the same fingerprint (stored in a sidecar meta
   JSON) plus the jax version and backend; any mismatch falls through to a
   normal compile — a stale artifact can never produce wrong numerics.

3. **XLA persistent compilation cache** — ``configure_compilation_cache``
   turns it on for a process that owns one: at ``JAX_COMPILATION_CACHE_DIR``
   when the environment sets it (jax reads that itself), else at one fixed
   path inside the checkout, so even the StableHLO→executable step is
   disk-cached across processes.

Fingerprints are deliberately conservative: model class + config scalars +
sublayer structure + optimizer class/hyperparameters + donation/accumulation
flags + full argument aval signature. Anything that changes the traced
program should change the fingerprint; anything that doesn't (buffer
contents, devices' wall clock) must not.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

from ..observability.goodput import ledger as _goodput_ledger
from ..observability.metrics import REGISTRY as _REG
from ..profiler import RecordEvent

__all__ = [
    "acquire", "building", "aval_signature", "fingerprint",
    "configure_compilation_cache", "save_aot", "load_aot", "stats",
    "reset_stats", "clear", "note_trace", "explain_fingerprint_change",
]

_LOCK = threading.Lock()
_EXECUTABLES: "OrderedDict[str, Any]" = OrderedDict()
_MAX_EXECUTABLES = 64

_STATS = {"hits": 0, "misses": 0, "aot_hits": 0, "traces": 0,
          "persistent_hits": 0, "persistent_misses": 0}
_PERSISTENT_DIR: Optional[str] = None
# why the last stale AOT artifact was rejected (ISSUE 8: "a fingerprint
# changed" is useless — operators need to know WHICH key drifted):
# {"name": ..., "diff": [path: old -> new, ...]} or None
_LAST_STALE: Optional[Dict[str, Any]] = None

AOT_META_SUFFIX = ".meta.json"
AOT_BIN_SUFFIX = ".stablehlo.bin"


def note_trace() -> None:
    """Called from inside step-function bodies: increments once per Python
    trace (jit retrace, scan-body trace, export trace). The proof counter
    for "this path did not rebuild"."""
    with _LOCK:
        _STATS["traces"] += 1


def stats() -> Dict[str, Any]:
    with _LOCK:
        out = dict(_STATS)
    out["persistent_dir"] = _PERSISTENT_DIR
    out["executables"] = len(_EXECUTABLES)
    out["last_stale"] = _LAST_STALE
    return out


def reset_stats() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0


def clear() -> None:
    """Drop cached executables + counters (tests use this to simulate a
    process restart without spawning one)."""
    global _LAST_STALE
    with _LOCK:
        _EXECUTABLES.clear()
        for k in _STATS:
            _STATS[k] = 0
        _LAST_STALE = None


# -- fingerprinting ----------------------------------------------------------

def aval_signature(tree) -> Tuple:
    """Stable (treedef, shape, dtype, sharding) signature of a pytree of
    arrays / ShapeDtypeStructs — the dynamic half of a fingerprint.
    Sharding is part of the key: a Compiled executable is specialized to
    its inputs' placement, and two same-shape trainers on different meshes
    must not share one. Python-scalar leaves (jit-legal weak-typed args)
    key on their TYPE, not value — jit does not bake the value either."""
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    sig = tuple(
        (str(l.shape), str(l.dtype), str(getattr(l, "sharding", None)))
        if hasattr(l, "shape") and hasattr(l, "dtype")
        else ("py", type(l).__name__)
        for l in leaves)
    return (str(treedef), sig)


def to_avals(tree):
    """Sharding-preserving aval view of a pytree: arrays become
    ShapeDtypeStructs carrying their placement (a Compiled executable is
    placement-specialized); python scalars pass through unchanged
    (jit-legal weak-typed arguments). The ONE conversion used by both the
    AOT serializer and Trainer.precompile, so the artifact and the
    in-process executable can never diverge."""
    import jax

    def conv(l):
        if hasattr(l, "shape") and hasattr(l, "dtype"):
            return jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=getattr(l, "sharding", None))
        return l
    return jax.tree.map(conv, tree)


def fingerprint(parts) -> str:
    """sha256 over a JSON rendering of ``parts`` (nested tuples/dicts of
    scalars and strings)."""
    blob = json.dumps(parts, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()


def _norm_parts(parts):
    """JSON-normalized view (tuples become lists, keys stay) so parts
    saved to a meta sidecar and parts computed live compare structurally."""
    return json.loads(json.dumps(parts, sort_keys=True, default=str))


def explain_fingerprint_change(old_parts, new_parts, limit: int = 12):
    """Human-readable paths where two fingerprint part trees diverge —
    the "WHY did this recompile / reject the AOT artifact" report. Parts
    are labeled dicts (Trainer._fp_parts), so paths read like
    ``static.env.PT_NAIVE_LOSS_HEAD: False -> True`` instead of a tuple
    index. Returns at most ``limit`` lines."""
    diffs: list = []

    def walk(a, b, path):
        if len(diffs) >= limit:
            return
        if isinstance(a, dict) and isinstance(b, dict):
            for k in sorted(set(a) | set(b), key=str):
                if len(diffs) >= limit:
                    return
                p = f"{path}.{k}" if path else str(k)
                if k not in a:
                    diffs.append(f"{p}: <absent> -> {b[k]!r}"[:240])
                elif k not in b:
                    diffs.append(f"{p}: {a[k]!r} -> <absent>"[:240])
                elif a[k] != b[k]:
                    walk(a[k], b[k], p)
        elif isinstance(a, list) and isinstance(b, list):
            if len(a) != len(b):
                diffs.append(f"{path}: length {len(a)} -> {len(b)}")
                return
            for i, (x, y) in enumerate(zip(a, b)):
                if x != y:
                    walk(x, y, f"{path}[{i}]")
        elif a != b:
            diffs.append(f"{path}: {a!r} -> {b!r}"[:300])

    walk(_norm_parts(old_parts), _norm_parts(new_parts), "")
    return diffs


# -- in-process executable cache ---------------------------------------------

def _store(fp: str, fn) -> None:
    with _LOCK:
        _EXECUTABLES[fp] = fn
        _EXECUTABLES.move_to_end(fp)
        while len(_EXECUTABLES) > _MAX_EXECUTABLES:
            _EXECUTABLES.popitem(last=False)


# what the code being traced says about the build under way (``note``)
_NOTES: contextvars.ContextVar = contextvars.ContextVar("build_notes",
                                                        default=None)


def note(key: str, value) -> None:
    """Called by code a program's build traces (a kernel that chose how to
    run from its shapes): ``value`` joins the list under ``key`` in that
    build's ``build_log`` row, once. Outside a build it is dropped."""
    notes = _NOTES.get()
    if notes is not None and value not in notes.setdefault(key, []):
        notes[key].append(value)


@contextlib.contextmanager
def building(name: str, log: Optional[list] = None, **attrs):
    """One program's build — trace + lower + compile, or the read from
    jax's persistent cache — as a ``compile::<name>`` span (``attrs`` ride
    on it) and, once it succeeds, one row appended to ``log``: ``name``,
    ``t_s`` (``perf_counter`` at the start), ``seconds`` and ``cache``:
    ``hit`` when jax read every executable of the build from its
    persistent cache, ``miss`` when the backend compiled one, ``uncached``
    when no persistent cache is in use. The engine's and the trainer's
    ``build_log`` are such lists: which program compiled, when, for how
    long, and what the traced code said of itself (:func:`note`: the flash
    kernels' ``flash_plan``, a list of the distinct plans of the build)."""
    _listen()
    with _LOCK:
        hits, misses = _STATS["persistent_hits"], _STATS["persistent_misses"]
    t0 = time.perf_counter()
    token = _NOTES.set({})
    try:
        with RecordEvent("compile::" + name, **attrs):
            yield
    finally:
        notes = _NOTES.get()
        _NOTES.reset(token)
    seconds = time.perf_counter() - t0
    if log is not None:
        with _LOCK:
            cache = ("miss" if _STATS["persistent_misses"] > misses else
                     "hit" if _STATS["persistent_hits"] > hits else
                     "uncached")
        log.append(dict(attrs, **notes, name=name, t_s=t0, seconds=seconds,
                        cache=cache))


def acquire(fp: str, jitted, args, *, aot_dir: Optional[str] = None,
            name: str = "step", save_artifact: bool = False,
            donate_argnums: Tuple[int, ...] = (),
            fp_parts=None, build_log: Optional[list] = None):
    """Return ``(callable, outcome)`` for fingerprint ``fp``.

    Lookup order: in-process executable ("hit") → serialized AOT artifact
    under ``aot_dir`` ("aot_hit") → lower+compile ``jitted`` on ``args``
    ("miss", optionally writing the artifact). ``args`` may be concrete
    arrays or ShapeDtypeStructs. ``donate_argnums`` re-establishes buffer
    donation on the deserialized-artifact path (jax.export's call wrapper
    does not inherit the original jit's donation). If AOT lowering is
    unavailable for this function/backend the live jitted callable is
    cached instead — caching never changes semantics, only who pays the
    compile.

    ``fp_parts`` (optional, a labeled dict): the pre-hash fingerprint
    parts. Saved into the AOT meta sidecar, and on a stale-artifact
    rejection diffed against the stored parts so the log says WHICH key
    drifted (model scalar, env escape, aval signature) instead of just
    "fingerprint mismatch".

    Whatever is not an in-process hit is a build: it runs inside
    :func:`building` under the jitted function's own name (the name the
    device trace prints after ``jit_``) and leaves its row in ``build_log``,
    with ``how``: ``compile`` (lower + compile) or ``aot_artifact``.
    """
    import jax

    with _LOCK:
        fn = _EXECUTABLES.get(fp)
        if fn is not None:
            _EXECUTABLES.move_to_end(fp)
            _STATS["hits"] += 1
            hit = fn
        else:
            hit = None
    if hit is not None:
        if aot_dir and save_artifact and not _artifact_matches(
                aot_dir, name, fp):
            # precompile-after-train: the executable was already resident,
            # but the restart artifact must still land on disk
            try:
                save_aot(aot_dir, name, fp, jitted, args, parts=fp_parts)
            except Exception:
                pass
        return hit, "hit"
    program = getattr(jitted, "__name__", name)
    if aot_dir:
        row: list = []              # kept only if an artifact was read
        with building(program, row, how="aot_artifact"), \
                _goodput_ledger().span("compile"):
            fn = load_aot(aot_dir, name, fp, donate_argnums=donate_argnums,
                          expect_parts=fp_parts)
        if fn is not None:
            _store(fp, fn)
            with _LOCK:
                _STATS["aot_hits"] += 1
            if build_log is not None:
                build_log.extend(row)
            return fn, "aot_hit"
    try:
        t0 = time.perf_counter()
        with building(program, build_log, how="compile"), \
                _goodput_ledger().span("compile"):
            fn = jitted.lower(*args).compile()
        if _REG.enabled:
            _REG.histogram("pt_compile_seconds",
                           "trace+lower+XLA-compile wall time per "
                           "executable", "s").observe(
                time.perf_counter() - t0, name=name)
    except jax.errors.JaxRuntimeError:
        raise    # the compiler refused the program: that is the result
    except Exception:
        # exotic arg types AOT lowering cannot take: live dispatch WITHOUT
        # caching — the jitted closure pins its Trainer's model/optimizer,
        # and a process-global cache entry would leak that graph (and
        # alias it into fingerprint-equal later Trainers)
        with _LOCK:
            _STATS["misses"] += 1
        return jitted, "miss"
    with _LOCK:
        _STATS["misses"] += 1
    if aot_dir and save_artifact:
        try:
            save_aot(aot_dir, name, fp, jitted, args, parts=fp_parts)
        except Exception:
            pass             # artifact write is best-effort, never fatal
    _store(fp, fn)
    return fn, "miss"


# -- on-disk AOT artifacts (jax.export) --------------------------------------

def _artifact_base(aot_dir: str, name: str) -> str:
    return os.path.join(aot_dir, f"aot_{name}")


def _artifact_matches(aot_dir: str, name: str, fp: str) -> bool:
    try:
        with open(_artifact_base(aot_dir, name) + AOT_META_SUFFIX) as f:
            return json.load(f).get("fingerprint") == fp
    except Exception:
        return False


def save_aot(aot_dir: str, name: str, fp: str, jitted, args,
             parts=None) -> str:
    """Serialize ``jitted`` specialized to ``args``' avals via ``jax.export``
    and write it (plus a meta sidecar carrying the fingerprint — and, when
    given, the labeled pre-hash ``parts`` a later mismatch is explained
    against) under ``aot_dir``. Returns the artifact path."""
    import jax
    from jax import export

    exp = export.export(jitted)(*to_avals(args))
    data = exp.serialize()
    os.makedirs(aot_dir, exist_ok=True)
    base = _artifact_base(aot_dir, name)
    tmp = base + AOT_BIN_SUFFIX + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, base + AOT_BIN_SUFFIX)
    meta = {"fingerprint": fp, "jax_version": jax.__version__,
            "backend": jax.default_backend(), "name": name}
    if parts is not None:
        meta["parts"] = _norm_parts(parts)
    tmp = base + AOT_META_SUFFIX + ".tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=1, sort_keys=True)
    os.replace(tmp, base + AOT_META_SUFFIX)
    return base + AOT_BIN_SUFFIX


def load_aot(aot_dir: str, name: str, fp: str,
             donate_argnums: Tuple[int, ...] = (),
             expect_parts=None):
    """Deserialize the ``name`` artifact if its meta matches ``fp`` (and the
    current jax version/backend); returns a jitted callable or None. A
    mismatched or unreadable artifact is ignored — the caller compiles —
    but a STALE artifact's rejection is explained: when the sidecar stored
    the labeled fingerprint parts and the caller supplies its current
    ``expect_parts``, the differing keys are warned and recorded in
    ``stats()["last_stale"]`` (e.g. ``static.env.PT_NAIVE_LOSS_HEAD:
    False -> True`` — the operator knows the recompile is the env flip,
    not corruption).
    ``donate_argnums`` must restate the original jit's donation: the
    exported call wrapper does not carry it, and silently dropping it
    would double the params+opt-state HBM footprint on the resume path."""
    import jax
    from jax import export

    global _LAST_STALE
    base = _artifact_base(aot_dir, name)
    try:
        with open(base + AOT_META_SUFFIX) as f:
            meta = json.load(f)
        if (meta.get("fingerprint") != fp
                or meta.get("jax_version") != jax.__version__
                or meta.get("backend") != jax.default_backend()):
            # explanation is OPT-IN (expect_parts supplied): callers on
            # the old contract keep the silent-ignore behavior — a
            # routine jax upgrade must not start raising under -W error
            if expect_parts is not None:
                diff = []
                for key, want in (("jax_version", jax.__version__),
                                  ("backend", jax.default_backend())):
                    if meta.get(key) != want:
                        diff.append(f"{key}: {meta.get(key)!r} -> "
                                    f"{want!r}")
                if meta.get("fingerprint") != fp and "parts" in meta:
                    diff.extend(explain_fingerprint_change(meta["parts"],
                                                           expect_parts))
                if diff:
                    _LAST_STALE = {"name": name, "diff": diff}
                    import warnings
                    warnings.warn(
                        "compile_cache: AOT artifact '%s' is stale, "
                        "recompiling; drift:\n  %s" % (name,
                                                       "\n  ".join(diff)),
                        stacklevel=2)
            return None
        with open(base + AOT_BIN_SUFFIX, "rb") as f:
            data = f.read()
        exported = export.deserialize(data)
        # jit the calling convention once; the original Python body is
        # never re-traced (note_trace() stays untouched on this path)
        return jax.jit(exported.call, donate_argnums=donate_argnums)
    except Exception:
        return None


# -- XLA persistent compilation cache ----------------------------------------

#: where the cache lives when the environment names no directory: ONE fixed
#: path inside the checkout (the path is part of jax's cache key, so a
#: directory that moves — a temp name, a pid, a time — never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_listening = False


def _count_persistent(event: str, **_kw) -> None:
    key = {_HIT_EVENT: "persistent_hits",
           _MISS_EVENT: "persistent_misses"}.get(event)
    if key is not None:
        with _LOCK:
            _STATS[key] += 1


def _listen() -> None:
    """Count jax's persistent-cache hits and misses from here on."""
    global _listening
    if not _listening:
        import jax
        jax.monitoring.register_event_listener(_count_persistent)
        _listening = True


def configure_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    The one placement rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, jax
    reads it itself and this function sets NO directory; otherwise the
    cache goes to :data:`DEFAULT_CACHE_DIR`. Called by every entry point
    that owns a process (chip_smoke.py, the tools that run on the chip)
    before its first compile — never at import, so tests and library
    users keep jax's own default. ``stats()`` then reports the
    directory and the persistent hits/misses jax counts."""
    global _PERSISTENT_DIR
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    _listen()
    _PERSISTENT_DIR = cache_dir
    return cache_dir
