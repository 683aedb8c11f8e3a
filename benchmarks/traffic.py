"""The one traffic generator: a traffic file and a seed in, a schedule out.

A traffic file (``traffic/<name>.json``) holds parameters only; this module
is the only code that reads them, so a later PR adds a mix by adding a file.

What makes a run repeat (PERF.md section 2): every quantity a mix draws
(prompt lengths, output lengths, gaps between arrivals, document lengths)
is STRATIFIED. A distribution is cut into as many slices of equal
probability as there are draws and the mid-point of each slice is taken, so
every seed gets the same multiset; the seed only shuffles the order, inside
consecutive blocks (``stratify_block``), so that any stretch of the run
sees the whole distribution, and draws the token ids.

Distributions: {"dist": "fixed", "value"}, {"dist": "uniform", "lo", "hi"},
{"dist": "loguniform", "lo", "hi"}, {"dist": "lognormal", "median",
"sigma", "lo", "hi"}, {"dist": "pareto", "lo", "alpha", "hi"},
{"dist": "gamma", "shape", "mean"}.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def rng_for(seed: int, stream: str, index: int = 0) -> np.random.Generator:
    """Independent streams from one whole-number seed of any size. The
    stream's name enters as a sum of its bytes, which anagrams share: numbered
    streams (a training row) pass their number as ``index``."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  sum(stream.encode()) * 7919 + len(stream),
                                  int(index)])


def _gamma_quantile(shape: float, u: np.ndarray) -> np.ndarray:
    """Quantiles of gamma(shape, scale 1) by bisection on the regularised
    incomplete gamma function's series (no scipy here)."""
    def cdf(x):
        # series for P(a, x); converges for all x > 0
        term = np.full_like(x, 1.0 / shape)
        total = term.copy()
        for n in range(1, 400):
            term = term * x / (shape + n)
            total += term
        return np.clip(total * np.exp(-x + shape * np.log(np.maximum(x, 1e-300))
                                      - math.lgamma(shape)), 0.0, 1.0)
    lo = np.zeros_like(u)
    hi = np.full_like(u, shape + 40.0 * math.sqrt(shape) + 40.0)
    for _ in range(80):
        mid = (lo + hi) / 2
        below = cdf(mid) < u
        lo, hi = np.where(below, mid, lo), np.where(below, hi, mid)
    return (lo + hi) / 2


def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "fixed":
        return np.full_like(u, float(dist["value"]))
    if kind == "uniform":
        return dist["lo"] + u * (dist["hi"] - dist["lo"])
    if kind == "loguniform":
        return np.exp(math.log(dist["lo"])
                      + u * (math.log(dist["hi"]) - math.log(dist["lo"])))
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        return np.clip(dist["median"] * np.exp(dist["sigma"] * z),
                       dist["lo"], dist["hi"])
    if kind == "pareto":
        return np.minimum(dist["lo"] * (1 - u) ** (-1.0 / dist["alpha"]),
                          dist["hi"])
    if kind == "gamma":
        return _gamma_quantile(float(dist["shape"]), u) * (
            dist["mean"] / dist["shape"])
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, n: int, rng, block: int | None = None,
               integer: bool = True) -> np.ndarray:
    """``n`` draws: consecutive blocks of ``block`` (default: all ``n``),
    each the mid-points of ``block`` equal-probability slices, shuffled."""
    block = min(block or n, n)
    out = []
    for start in range(0, n, block):
        size = min(block, n - start)    # the last block may be a short one
        vals = quantile(dist, (np.arange(size) + 0.5) / size)
        out.append(rng.permutation(vals))
    vals = np.concatenate(out)
    return np.maximum(np.rint(vals), 1).astype(np.int64) if integer else vals


@dataclasses.dataclass
class Request:
    idx: int
    prompt: np.ndarray          # int32 token ids
    out_len: int
    due_s: float | None = None  # open loop: seconds from the window's start
    client: int | None = None   # closed loop
    greedy: bool = True


@dataclasses.dataclass
class Schedule:
    loop: str                   # "open" | "closed"
    requests: list
    clients: int = 0
    warmup_s: float = 0.0       # open loop: traffic sent before the window
    grace_s: float = 0.0        # open loop: wait for first tokens after it


def _prompts(mix: dict, lens: np.ndarray, rng, vocab: int) -> list:
    """Token ids. ``sessions`` {families, shared_share} gives the requests
    of a family a common prefix of that share of each prompt."""
    sessions = mix.get("sessions")
    fam_prefix = None
    if sessions:
        fam_prefix = [rng.integers(0, vocab, int(lens.max()), dtype=np.int32)
                      for _ in range(int(sessions["families"]))]
    out = []
    for i, l in enumerate(lens):
        p = rng.integers(0, vocab, int(l), dtype=np.int32)
        if fam_prefix is not None:
            k = int(l * float(sessions["shared_share"]))
            p[:k] = fam_prefix[i % len(fam_prefix)][:k]
        out.append(p)
    return out


def serving_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                     max_len: int) -> Schedule:
    """Pure function of (mix, seed, seconds, vocab, max_len)."""
    loop = mix["loop"]
    block = mix.get("stratify_block")
    if loop == "closed":
        n = int(mix["requests"])
    else:
        warm = float(mix.get("warmup_s", 0.0))
        n = int(round(float(mix["rate_rps"]) * (warm + seconds)))
    # ``replay`` {times}: a fixed set of distinct prompts, each asked that often
    times = int(mix["replay"]["times"]) if mix.get("replay") else 1
    distinct = -(-n // times)
    p_len = stratified(mix["prompt_len"], distinct,
                       rng_for(seed, "prompt_len"), block)
    prompts = _prompts(mix, p_len, rng_for(seed, "tokens"), vocab)
    if times > 1:
        order = rng_for(seed, "replay").permutation(np.arange(n) % distinct)
        prompts = [prompts[j] for j in order]
        p_len = p_len[order]
    o_len = stratified(mix["output_len"], n, rng_for(seed, "output_len"), block)
    if (p_len + o_len > max_len).any():
        raise ValueError(f"traffic asks for {int((p_len + o_len).max())} "
                         f"positions, the engine holds {max_len}")
    reqs = [Request(i, prompts[i], int(o_len[i])) for i in range(n)]
    if loop == "closed":
        clients = int(mix["clients"])
        # spread the first completions evenly over the first residence time
        frac = rng_for(seed, "first").permutation(
            (np.arange(clients) + 0.5) / clients)
        for c in range(clients):
            reqs[c].out_len = max(1, int(round(reqs[c].out_len * frac[c])))
        for i, r in enumerate(reqs):
            r.client = i % clients
        return Schedule("closed", reqs, clients=clients)
    gaps = stratified({"dist": "gamma",
                       "shape": float(mix.get("interarrival_shape", 1.0)),
                       "mean": 1.0 / float(mix["rate_rps"])}, n,
                      rng_for(seed, "gaps"), block, integer=False)
    burst = int(mix.get("burst_size", 1))
    if burst > 1:   # burst trains: groups of ``burst`` share one due time
        gaps = gaps.reshape(-1)
        for i in range(n):
            if i % burst:
                gaps[i - i % burst] += gaps[i]
                gaps[i] = 0.0
    due = np.cumsum(gaps) - warm      # the same span for every seed
    for r, d in zip(reqs, due):
        r.due_s = float(d)
    return Schedule("open", reqs, warmup_s=warm,
                    grace_s=float(mix.get("grace_s", 10.0)))


def training_rows(mix: dict, seed: int, first_row: int, rows: int,
                  vocab: int) -> dict:
    """Rows ``first_row .. first_row + rows`` of the job's endless stream:
    {"input_ids", "labels"} [rows, seq_len] int32, and for packed documents
    "segment_ids" and "position_ids". Every row differs; a row depends only
    on (seed, its number)."""
    s = int(mix["seq_len"])
    ids = np.empty((rows, s + 1), np.int32)
    seg = np.zeros((rows, s), np.int32)
    pos = np.zeros((rows, s), np.int32)
    packing = mix.get("packing")
    for r in range(rows):
        rng = rng_for(seed, "row", first_row + r)
        ids[r] = rng.integers(0, vocab, s + 1, dtype=np.int32)
        if packing:
            docs = stratified(packing["doc_len"], 64, rng, integer=True)
            cuts = np.cumsum(docs)
            cuts = cuts[cuts < s]
            seg[r] = np.searchsorted(cuts, np.arange(s), side="right")
            starts = np.concatenate([[0], cuts])
            pos[r] = np.arange(s) - starts[seg[r]]
    out = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].copy()}
    if packing:
        out["segment_ids"], out["position_ids"] = seg, pos
        out["labels"][:, :-1][seg[:, 1:] != seg[:, :-1]] = -100
    return out
