"""What decides ``correct``: the timed path's output against the plain
reference of the configuration's family (``families/``), each number beside
its limit (the limits and the readings they
were set from: the configuration's ``check`` group, and PERF.md section 2).

Serving compares, for a seeded sample of the requests the window finished
(the longest among them), the widest and the mean gap by which a served
token's reference logit lies below the reference's best. Training compares
each of the first steps' losses, the first gradient's norm as the optimizer
got it and the norm of the parameters' change, both by the worst leaf.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import adamw, families, traffic, weights


def number(name, value, limit):
    value = float(value)
    return {"name": name, "value": value, "limit": float(limit),
            "ok": bool(np.isfinite(value) and value <= limit)}


def sample_served(served, seed, k):
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][0]) + len(served[i][1]))
    rest = [i for i in range(len(served)) if i != longest]
    rng = traffic.rng_for(seed, "check")
    pick = [longest] + [int(i) for i in rng.permutation(rest)[:k - 1]]
    return [served[i] for i in pick]


def reference_gaps(config, seed, sample, quant=None, rows_per_block=2,
                   pad_to=256):
    """Per served token: (reference's best logit - reference's logit of the
    served token) and the same for the token ``quant`` would put first.
    One reference pass per block of rows; a layer's weights are made once
    and used for every block."""
    n_pos = max(len(p) + len(t) for p, t in sample)
    width = -(-n_pos // pad_to) * pad_to
    blocks = []
    for a in range(0, len(sample), rows_per_block):
        part = sample[a:a + rows_per_block]
        ids = np.zeros((len(part), width), np.int32)
        rows, cols, toks = [], [], []
        for j, (p, t) in enumerate(part):
            ids[j, :len(p)] = p
            ids[j, len(p):len(p) + len(t) - 1] = t[:-1]
            rows += [j] * len(t)
            cols += list(range(len(p) - 1, len(p) - 1 + len(t)))
            toks += [int(x) for x in t]
        blocks.append((jnp.asarray(ids), np.asarray(rows), np.asarray(cols),
                       np.asarray(toks)))
    get = lambda names: weights.make_some(seed, config, names)
    logits_at = families.of(config).logits_at
    with jax.default_matmul_precision("highest"):
        ref = logits_at(config, get, [b[:3] for b in blocks], None)
        low = (logits_at(config, get, [b[:3] for b in blocks], quant)
               if quant else None)
    served_gap, control_gap = [], []
    for i, (_, _, _, toks) in enumerate(blocks):
        r = np.asarray(ref[i])
        best = r.max(-1)
        served_gap += list(best - r[np.arange(len(toks)), toks])
        if low is not None:
            first = np.asarray(low[i]).argmax(-1)
            control_gap += list(best - r[np.arange(len(toks)), first])
    return np.asarray(served_gap), np.asarray(control_gap)


def gap_numbers(config, gaps):
    lim = config["check"]
    return [number("served_logit_gap_max", gaps.max(), lim["served_logit_gap_max"]),
            number("served_logit_gap_mean", gaps.mean(), lim["served_logit_gap_mean"])]


def served_tokens(config, seed, served, log):
    t0 = time.perf_counter()
    sample = sample_served(served, seed, config["check"]["sample_requests"])
    if not sample:
        log(check="no request finished inside the window")
        return [number("finished_requests_missing", 1, 0)]
    gaps, _ = reference_gaps(config, seed, sample)
    out = gap_numbers(config, gaps)
    log(check="served tokens vs float32 reference", requests=len(sample),
        tokens=int(gaps.size), positions=[len(p) + len(t) for p, t in sample],
        seconds=round(time.perf_counter() - t0, 2), numbers=out)
    return out


# -- training -----------------------------------------------------------------

def worst_leaf(program_norms: dict, reference_norms: dict) -> float:
    """The widest gap between the program's norm and the reference's of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    med = float(np.median(list(reference_norms.values())))
    return max(abs(program_norms[k] - reference_norms[k])
               / max(reference_norms[k], med) for k in reference_norms)


def worst_difference(diff_norms: dict, reference_norms: dict) -> float:
    """The largest norm of (program's gradient - reference's) of a leaf,
    against the same denominators as ``worst_leaf``. Rounding errors cancel
    in a norm and add up in a difference: this is the number a lower
    precision moves."""
    med = float(np.median(list(reference_norms.values())))
    return max(diff_norms[k] / max(reference_norms[k], med)
               for k in reference_norms)


def reference_training(config, mix, seed, steps, quant=None, first_grads=None):
    """The reference's first ``steps`` steps on the rows the program was fed:
    (losses, first gradient's norm per leaf, change norm per leaf, norm per
    leaf of (``first_grads`` - the reference's first gradient)).
    ``first_grads`` maps leaf names to host arrays, or is a callable that
    takes the reference's first gradients (the control's come that way)."""
    family, shapes = families.of(config), weights.leaf_shapes(config)
    names = list(shapes)
    batch = config["trainer"]["rows_per_chip"] * config.get("chips", 1)
    with jax.default_matmul_precision("highest"):
        leaves = dict(weights.make_some(seed, config, names))
        state = adamw.adamw_init(leaves)
        losses, grad_norms, diff = [], None, None
        # the configuration STORES its matrices in ``dtype`` and updates
        # float32 master weights: each step multiplies with the stored
        # values (float32 arithmetic on them), the update goes to the master
        dtype = jnp.dtype(config["dtype"])
        stored = jax.jit(lambda t: {
            k: v.astype(dtype).astype(jnp.float32) if shapes[k][1] == "matrix"
            else v for k, v in t.items()})
        norm = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(v * v))
                                  for k, v in t.items()})
        for k in range(steps):
            rows = traffic.training_rows(mix, seed, k * batch, batch,
                                         config["vocab_size"])
            loss, grads = family.loss_and_grads(
                config, stored(leaves), jnp.asarray(rows["input_ids"]),
                jnp.asarray(rows["labels"]), quant,
                config["check"].get("rows_per_block", 1))
            losses.append(float(loss))
            if k == 0:
                grad_norms = {n: float(v) for n, v in norm(grads).items()}
                if first_grads is not None:
                    theirs = (first_grads(grads) if callable(first_grads)
                              else first_grads)
                    diff = {n: float(jnp.sqrt(jnp.sum(jnp.square(
                        jnp.asarray(theirs[n], jnp.float32) - grads[n]))))
                        for n in names}
            leaves, state = adamw.adamw_step(leaves, grads, state,
                                             config["optimizer"])
        del state, grads
        change = {}
        for n in names:         # against the seeded start, a leaf at a time
            start = weights.make_some(seed, config, [n])[n]
            change[n] = float(jnp.sqrt(jnp.sum(jnp.square(leaves[n] - start))))
    return losses, grad_norms, change, diff


def training_numbers(config, program_side, reference_side):
    """Numbers of a training cell beside their limits. The program's side is
    (losses, grad norms, change norms); the reference's has, fourth, the norms
    of the difference between the two first gradients."""
    lim = config["check"]
    pl, pg, pc = program_side[:3]
    rl, rg, rc, diff = reference_side
    out = [number(f"loss_step{i}_abs_diff", abs(a - b), lim["loss_abs_diff"][i])
           for i, (a, b) in enumerate(zip(pl, rl))]
    out.append(number("loss0_vs_seeded_init", abs(
        pl[0] - families.of(config).loss0_expected(config, weights.INIT_STD)),
        lim["loss0_vs_seeded_init"]))
    out.append(number("grad_norm_worst_leaf", worst_leaf(pg, rg),
                       lim["grad_norm_worst_leaf"]))
    out.append(number("grad_difference_worst_leaf", worst_difference(diff, rg),
                      lim["grad_difference_worst_leaf"]))
    out.append(number("param_change_worst_leaf", worst_leaf(pc, rc),
                       lim["param_change_worst_leaf"]))
    return out
