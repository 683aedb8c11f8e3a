"""From samples, counters and the device trace to metrics: the yardstick.

Everything here is arithmetic on plain data, so tests can hold it to hand-
worked numbers (tests/test_reduce.py, on a small recorded trace):

* ``percentile`` interpolates linearly;
* a device trace is a list of ``Event``s read from the profiler's
  ``.xplane.pb`` with nothing but JAX;
* ``REDUCERS`` is the fixed set a ``layer_metrics/<name>.json`` chooses from.
  A reducer that finds nothing to read returns None and the harness leaves
  the metric out of the line.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from . import families

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# a span: ``<prefix>::<name>`` as the harness (``bm::``) and the program
# (``serving::``, ``trainer::``, ``compile::``, whatever a new mechanism
# adds) name theirs; the runtime's own C++ events (``Class::Method``,
# ``tpu::System::Execute``) start with a capital or nest further
SPAN = re.compile(r"^[a-z][a-z0-9_]*::[^:]+$")
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start|-done)?\b")


def percentile(values, q: float):
    """The q-th percentile (0..100), linear interpolation between order
    statistics (numpy's default); None of nothing."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = dataclasses.field(default_factory=dict, compare=False)

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


def read_xplane(path: str) -> list:
    """The events of a trace directory (or one ``.xplane.pb``) a reducer
    can read: everything on the device planes, and from the host plane the
    spans (``SPAN``) with their stats (``block``, ``rid``, ``step_num``,
    ``bucket``...)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            return []
        path = found[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        keep_all = bool(DEVICE_PLANE.match(plane.name))
        if not keep_all and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if keep_all:
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
                elif SPAN.match(e.name):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns),
                                     dict(e.stats)))
    return out


def host_spans(events) -> dict:
    """name -> [(start, end), ...] of the host plane's spans."""
    spans = {}
    for e in events:
        if e.plane == HOST_PLANE:
            spans.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    return spans


def device_ops(events, device: int | None = None) -> list:
    """Operations that ran on a device's cores (the ``XLA Ops`` line)."""
    out = []
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and e.line == OPS_LINE and (device is None
                                         or int(m.group(1)) == device):
            out.append(e)
    return out


def devices_in(events) -> list:
    return sorted({int(DEVICE_PLANE.match(e.plane).group(1))
                   for e in events if DEVICE_PLANE.match(e.plane)})


def union(intervals) -> list:
    """Disjoint, sorted union of [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(ivs) -> float:
    return sum(b - a for a, b in ivs)


def _clip(ivs, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


def subtract(ivs, holes) -> list:
    """Parts of the (disjoint, sorted) ``ivs`` not covered by ``holes``."""
    out, holes = [], union(holes)
    for a, b in ivs:
        cur = a
        for ha, hb in holes:
            if hb <= cur or ha >= b:
                continue
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
        if cur < b:
            out.append((cur, b))
    return out


def window_of(events):
    """The traced window in ns: from the first to the last device operation
    (the profiler starts and stops around it, so its own start-up is not
    counted as idle)."""
    ops = device_ops(events)
    if not ops:
        return None
    return min(e.start_ns for e in ops), max(e.end_ns for e in ops)


def busy_seconds(events, device: int, window=None) -> float:
    window = window or window_of(events)
    ivs = union((e.start_ns, e.end_ns) for e in device_ops(events, device))
    return _length(_clip(ivs, *window)) / 1e9


def short_name(hlo_text: str) -> str:
    """A printable name for a device operation: ``%fusion.3 = bf16[8,128]
    fusion(...)`` -> ``fusion.3:bf16[8,128]``; a Pallas call keeps its
    kernel's name."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*\(?([a-z0-9]+\[[0-9,]*\])?", hlo_text)
    if not m:
        return hlo_text[:60]
    name = m.group(1) + (":" + m.group(2) if m.group(2) else "")
    k = re.search(r'kernel_name\s*=\s*"?([\w.\-]+)', hlo_text)
    return (name + "@" + k.group(1)) if k else name


def op_seconds(events, pattern: str, device: int | None = None) -> float:
    rx = re.compile(pattern)
    return sum(e.dur_ns for e in device_ops(events, device)
               if rx.search(e.name)) / 1e9


def modules(events, device: int = 0) -> dict:
    """Compiled programs that ran on a device: name -> [runs, seconds]
    (the ``XLA Modules`` line; the name is ``jit_<function>(<fingerprint>)``)."""
    out = {}
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and int(m.group(1)) == device and e.line == MODULES_LINE:
            rec = out.setdefault(e.name, [0, 0.0])
            rec[0] += 1
            rec[1] += e.dur_ns / 1e9
    return out


def top_modules(events, device: int = 0, n: int = 10) -> list:
    return sorted(([k, c, s] for k, (c, s) in modules(events, device).items()),
                  key=lambda r: -r[2])[:n]


def most_run_module(events, pattern: str, device: int = 0):
    """(runs, seconds) of the program matching ``pattern`` that ran most
    often: in a serving window that is the decode tick, whose name the
    program shares with its prefills (PERF.md, Open questions)."""
    rx = re.compile(pattern)
    hits = [v for k, v in modules(events, device).items() if rx.search(k)]
    return max(hits, key=lambda v: v[0]) if hits else None


def top_ops(events, device: int = 0, n: int = 10, full: bool = False) -> list:
    total = {}
    for e in device_ops(events, device):
        key = e.name[:240] if full else short_name(e.name)
        total[key] = total.get(key, 0.0) + e.dur_ns / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def exposed_collective_seconds(events, device: int) -> float:
    """Time in collective operations during which nothing else ran on that
    device: the union of the collectives' intervals less the union of every
    other operation's."""
    coll, other = [], []
    for e in device_ops(events, device):
        (coll if COLLECTIVE.search(e.name.split("(")[0]) else other).append(
            (e.start_ns, e.end_ns))
    return _length(subtract(union(coll), other)) / 1e9


def idle_gaps_by_span(events, device: int = 0, n: int = 10) -> list:
    """Idle time of the device inside the window, shared out to the span of
    the harness (``bm::...``) or of the program (``serving::admit``,
    ``compile::<program>``...) that covers each stretch of it; what no span
    covers is ``outside_spans``."""
    window = window_of(events)
    if window is None:
        return []
    busy = union((e.start_ns, e.end_ns) for e in device_ops(events, device))
    idle = subtract([window], busy)
    spans = host_spans(events)
    out, covered = {}, []
    # innermost spans first: a nested span takes its time from its parent
    for name in sorted(spans, key=lambda k: _length(union(spans[k]))):
        mine = subtract(union(spans[name]), covered)
        got = _length([iv for a, b in idle for iv in _clip(mine, a, b)])
        if got:
            out[name] = got / 1e9
        covered.extend(union(spans[name]))
    rest = _length(subtract(idle, covered))
    if rest:
        out["outside_spans"] = rest / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]


def device_summary(events) -> dict | None:
    """``busy_s`` (mean over the chips used) and ``window_s`` of a trace."""
    window = window_of(events)
    devs = devices_in(events)
    if window is None or not devs:
        return None
    busy = [busy_seconds(events, d, window) for d in devs]
    return {"busy_s": sum(busy) / len(busy),
            "window_s": (window[1] - window[0]) / 1e9, "per_device": busy}


# -- the fixed set of reducers ------------------------------------------------
# Each takes (ctx, spec): ctx is what a run gathered (see run.py: samples,
# counters, events, model, shapes, peaks, e2e, window_s), spec the metric's
# file. Each returns a number or None.

def _sample_percentile(ctx, spec):
    vals = ctx["samples"].get(spec["sample"])
    return None if not vals else percentile(vals, spec["q"]) * spec.get("scale", 1.0)


def _sample_mean(ctx, spec):
    vals = ctx["samples"].get(spec["sample"])
    return None if not vals else sum(vals) / len(vals) * spec.get("scale", 1.0)


def _counter_rate(ctx, spec):
    c = ctx["counters"].get(spec["counter"])
    return None if c is None or not ctx.get("window_s") else c / ctx["window_s"]


def _counter(ctx, spec):
    c = ctx["counters"].get(spec["counter"])
    return None if c is None else c * spec.get("scale", 1.0)


def _counter_ratio(ctx, spec):
    """A quotient of two counters of the window (a rate of acceptance, of
    hits): nothing where either is missing or nothing was counted below."""
    num = ctx["counters"].get(spec["numerator"])
    den = ctx["counters"].get(spec["denominator"])
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)


def _idle_share(ctx, spec):
    s = device_summary(ctx.get("events") or [])
    if s is None:
        return None
    worst = min(s["per_device"]) if spec.get("worst") else s["busy_s"]
    return 100.0 * (1.0 - worst / s["window_s"])


def _device_op_share(ctx, spec):
    ev = ctx.get("events") or []
    s = device_summary(ev)
    if s is None:
        return None
    return 100.0 * op_seconds(ev, spec["pattern"], 0) / s["window_s"]


def _span_share(ctx, spec):
    """The host spans ``pattern`` names: their union inside the traced
    window, as a share of it."""
    ev = ctx.get("events") or []
    window = window_of(ev)
    rx = re.compile(spec["pattern"])
    ivs = [iv for name, got in host_spans(ev).items() if rx.search(name)
           for iv in got]
    if window is None or not ivs:
        return None
    return 100.0 * _length(_clip(union(ivs), *window)) / (window[1] - window[0])


def _exposed_collective(ctx, spec):
    ev = ctx.get("events") or []
    s = device_summary(ev)
    if s is None or len(devices_in(ev)) < 2:
        return None
    return 100.0 * max(exposed_collective_seconds(ev, d)
                       for d in devices_in(ev)) / s["window_s"]


def _memory_peak(ctx, spec):
    b = ctx.get("memory_peak_bytes")
    return None if not b else b / 2 ** 30


def _mfu(ctx, spec):
    tok = ctx["e2e"].get(spec["rate"])
    if tok is None:
        return None
    flops = families.of(ctx["model"]).train_flops_per_token(
        ctx["model"], ctx["shapes"]["seq_len"])
    return 100.0 * tok * flops / ctx["peaks"]["bf16_flops"]


def _roofline(ctx, spec):
    """Least time by the chip's peaks over device time from the trace. What
    the work requires is the family's to say (``families/``)."""
    ev = ctx.get("events") or []
    model, peaks = ctx["model"], ctx["peaks"]
    if spec["work"] == "decode_tick":
        # bytes a tick needs over the device time of one decode program
        tick = most_run_module(ev, spec["module"])
        if not tick or not ctx["counters"].get("live_tokens_mean"):
            return None
        need = families.of(model).decode_tick_bytes(
            model, ctx["counters"]["live_tokens_mean"])
        return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (tick[1] / tick[0])
    if spec["work"] in ("kernel", "flash"):
        # what the family's function ``counts`` says one run of ``module``
        # requires of a kernel, pass by pass, over the device time of the
        # operations ``pattern`` names, per run. "flash" is the kernel whose
        # function is ``flash_attention`` (causal attention, every layer,
        # forward and backward)
        step = most_run_module(ev, spec["module"])
        secs = op_seconds(ev, spec["pattern"], 0)
        if not step or not secs:
            return None
        name = "flash_attention" if spec["work"] == "flash" else spec["counts"]
        need = families.kernel_work(model, name, ctx["shapes"])
        least = sum(max(p["flops"] / peaks["bf16_flops"],
                        p["bytes"] / peaks["hbm_bytes_per_s"])
                    for p in need.values())
        return 100.0 * least * step[0] / secs
    raise ValueError(f"unknown work {spec['work']!r}")


REDUCERS = {
    "sample_percentile": _sample_percentile,
    "sample_mean": _sample_mean,
    "counter": _counter,
    "counter_rate": _counter_rate,
    "counter_ratio": _counter_ratio,
    "idle_share": _idle_share,
    "device_op_share": _device_op_share,
    "span_share": _span_share,
    "exposed_collective": _exposed_collective,
    "memory_peak": _memory_peak,
    "mfu": _mfu,
    "roofline": _roofline,
}
