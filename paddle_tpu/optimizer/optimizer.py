"""Optimizers.

Reference: python/paddle/optimizer/ (Optimizer base at optimizer.py:103;
adamw.py, adam.py, momentum.py, lamb.py, sgd.py...). Re-designed functionally
for JAX: every optimizer is defined by two pure functions —

    state = opt.init_state(params)                     # params: flat dict
    params, state = opt.apply_gradients(params, grads, state, lr=None)

which jit/shard cleanly (the trainer donates both pytrees). On top of that
sits the paddle-shaped imperative API: ``opt.step(grads)`` updates the bound
``Layer``'s Parameters in place and advances the LR scheduler.

Master-weight handling mirrors the reference's multi_precision kernels
(e.g. paddle/phi/kernels/gpu/adamw_kernel.cu): when a param is bf16/fp16 an
fp32 master copy lives in the optimizer state, moments are fp32, and the
model weight is a cast of the master after each update.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp

from ..nn.layer import Layer, Parameter
from .clip import ClipGradBase, ClipGradByGlobalNorm
from .lr import LRScheduler


def place_opt_state(state: Dict, params: Dict[str, jax.Array], kind: str):
    """Move an optimizer-state tree into memory space ``kind``
    ("pinned_host" / "device") in ONE batched transfer, laying each
    param-shaped slot/master leaf out like ITS PARAM — an offload
    round-trip must not commit a previously-uncommitted leaf to a single
    device while its mesh-sharded param spans the mesh. The host side of
    GroupSharded ``offload=True`` (reference: group_sharded_storage.py);
    used by Optimizer.step and Trainer.train_step."""
    from jax.sharding import NamedSharding, PartitionSpec

    any_sh = next(iter(params.values())).sharding if params else None
    if any_sh is None:
        return state
    rep = (NamedSharding(any_sh.mesh, PartitionSpec())
           if isinstance(any_sh, NamedSharding) else any_sh)

    def sh_of(path_name, leaf):
        base = (params[path_name].sharding
                if path_name in params else rep)
        return base.with_memory_kind(kind)

    shardings = {}
    for k, v in state.items():
        if k in ("slots", "master") and isinstance(v, dict):
            shardings[k] = {
                name: ({sk: sh_of(name, sv) for sk, sv in entry.items()}
                       if isinstance(entry, dict) else sh_of(name, entry))
                for name, entry in v.items()}
        else:
            shardings[k] = jax.tree.map(
                lambda x: rep.with_memory_kind(kind), v)
    return jax.device_put(state, shardings)


def _is_low_precision(x):
    return x.dtype in (jnp.bfloat16, jnp.float16)


def _slot_like(p, fill=0.0):
    """An fp32 optimizer slot shaped AND PLACED like its parameter: on a mesh
    each device allocates only its shard. ``jnp.zeros(p.shape)`` would put
    every slot whole on the default device — at 1.9 B parameters the two
    AdamW moments are 15 GB on the first chip before anything is sharded."""
    return jnp.full_like(p, fill, dtype=jnp.float32)


class Optimizer:
    def __init__(self, learning_rate: Union[float, LRScheduler] = 0.001,
                 parameters=None, weight_decay: float = 0.0,
                 grad_clip: Optional[ClipGradBase] = None,
                 multi_precision: bool = True,
                 apply_decay_param_fun: Optional[Callable[[str], bool]] = None):
        self._lr = learning_rate
        self._weight_decay = weight_decay if weight_decay is not None else 0.0
        self.grad_clip = grad_clip
        self.multi_precision = multi_precision
        self.apply_decay_param_fun = apply_decay_param_fun
        # imperative binding (list of Parameter or a Layer)
        self._bound_params: Dict[str, Parameter] = {}
        if parameters is not None:
            if isinstance(parameters, Layer):
                self._bound_params = {n: p for n, p in parameters.named_parameters()
                                      if p.trainable}
            else:
                parameters = [p for p in parameters if p.trainable]
                names = [p.name or str(i) for i, p in enumerate(parameters)]
                if len(set(names)) != len(names):
                    dupes = sorted({n for n in names if names.count(n) > 1})
                    raise ValueError(
                        f"list-form parameter binding has colliding names "
                        f"{dupes[:3]} (e.g. lists from several sublayers "
                        f"concatenated, or tied params listed twice) — "
                        f"pass the Layer itself (parameters=model) or one "
                        f"root model.parameters() call, whose names are "
                        f"the unique dotted paths")
                self._bound_params = dict(zip(names, parameters))
        self._state = None

    # -- lr ----------------------------------------------------------------

    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return self._lr.get_last_lr()
        return self._lr

    def set_lr(self, lr: float) -> None:
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = lr

    def set_lr_scheduler(self, scheduler: LRScheduler) -> None:
        """Swap in an LRScheduler (reference: optimizer.py
        set_lr_scheduler:598 — same contract, subsequent get_lr() reads
        the scheduler's current value)."""
        if not isinstance(scheduler, LRScheduler):
            raise TypeError(
                f"scheduler must be an LRScheduler, got "
                f"{type(scheduler).__name__}")
        self._lr = scheduler

    def backward(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None, callbacks=None):
        """Tape-style grads-from-a-loss-value (reference optimizer.py
        backward:1380). This framework keeps no eager tape; differentiate
        the function instead and feed the grads to step()/apply_gradients:

            loss, grads = autograd.layer_grad(model, loss_fn, *inputs)
            opt.step(grads)
        """
        raise NotImplementedError(
            "optimizer.backward(loss) differentiates an eager tape, which "
            "this framework does not keep. Use autograd.layer_grad(model, "
            "loss_fn, *inputs) -> (loss, grads), then opt.step(grads) "
            "(docs/DESIGN_DECISIONS.md eager-tape entry)")

    @property
    def lr_scheduler(self):
        return self._lr if isinstance(self._lr, LRScheduler) else None

    # -- pure functional API ------------------------------------------------

    def init_state(self, params: Dict[str, jax.Array]) -> Dict:
        state = {"step": jnp.zeros([], jnp.int32)}
        if self.multi_precision:
            state["master"] = {k: v.astype(jnp.float32) for k, v in params.items()
                               if _is_low_precision(v)}
        state["slots"] = {k: self._init_slots(v) for k, v in params.items()}
        return state

    def _init_slots(self, p: jax.Array) -> Dict:
        return {}

    def _update(self, name: str, p32: jax.Array, g32: jax.Array, slots: Dict,
                lr, step) -> jax.Array:
        """Return updated fp32 param; mutate slots dict entries by replacing."""
        raise NotImplementedError

    def _decayed(self, name: str) -> bool:
        if self.apply_decay_param_fun is not None:
            return bool(self.apply_decay_param_fun(name))
        return True

    def apply_gradients(self, params: Dict[str, jax.Array],
                        grads: Dict[str, jax.Array], state: Dict,
                        lr=None) -> tuple:
        if lr is None:
            lr = self.get_lr()
        if self.grad_clip is not None:
            grads = self.grad_clip(grads)
        step = state["step"] + 1
        masters = dict(state.get("master", {}))
        new_params = {}
        new_slots = {}
        for k, p in params.items():
            g = grads.get(k)
            if g is None:
                new_params[k] = p
                new_slots[k] = state["slots"][k]
                continue
            p32 = masters.get(k, p).astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            slots = dict(state["slots"][k])
            p32_new = self._update(k, p32, g32, slots, lr, step)
            new_slots[k] = slots
            if k in masters:
                masters[k] = p32_new
                new_params[k] = p32_new.astype(p.dtype)
            else:
                new_params[k] = p32_new.astype(p.dtype)
        new_state = {"step": step, "slots": new_slots}
        if "master" in state:
            new_state["master"] = masters
        return new_params, new_state

    # -- imperative API (paddle-shaped) -------------------------------------

    def step(self, grads: Optional[Dict[str, jax.Array]] = None) -> None:
        """Apply an update to the bound parameters. ``grads`` is the flat dict
        produced by jax.grad over Layer.raw_parameters() keys."""
        if grads is None:
            raise ValueError(
                "paddle_tpu optimizers need explicit grads: opt.step(grads) — "
                "compute them with paddle_tpu.autograd.grad / jax.grad.")
        params = {k: p.value for k, p in self._bound_params.items()}
        if not params:
            raise RuntimeError(
                "optimizer has no trainable parameters bound (empty list or "
                "all trainable=False) — nothing to update")
        if grads and not (set(grads) & set(params)):
            # apply_gradients skips unmatched keys — a fully-disjoint key
            # set would silently update NOTHING (e.g. grads keyed by dotted
            # paths vs an optimizer bound to a different layer's list)
            raise KeyError(
                f"no gradient key matches any bound parameter: grads use "
                f"{sorted(grads)[:3]}..., optimizer bound "
                f"{sorted(params)[:3]}... — bind the optimizer with "
                f"parameters=<same layer>.parameters() (or the Layer)")
        offload = getattr(self, "_offload_opt_state", False)
        if self._state is None:
            # fresh state is already device-resident; the post-step push
            # parks it — no initial host round trip
            self._state = self.init_state(params)
        elif offload:
            self._state = place_opt_state(self._state, params, "device")
        new_params, self._state = self.apply_gradients(params, grads, self._state)
        if offload:
            self._state = place_opt_state(self._state, params, "pinned_host")
        for k, v in new_params.items():
            self._bound_params[k].value = v

    def clear_grad(self) -> None:  # paddle API parity; grads are external here
        pass

    clear_gradients = clear_grad

    def minimize(self, loss=None, startup_program=None, parameters=None,
                 no_grad_set=None, grads=None):
        """Reference: Optimizer.minimize(optimizer.py). Two modes:

        - STATIC: ``loss`` is a program var (static.data/static.nn chain):
          register this optimizer on the loss's program — the Executor
          then runs forward+backward+update per ``exe.run`` (the classic
          static training loop; see static/__init__.py Executor.run).
        - dynamic: explicit ``grads`` (functional autograd), same as
          ``step(grads)``.
        """
        if loss is not None and hasattr(loss, "_build") \
                and hasattr(loss, "_program"):
            hooks = loss._program.__dict__.setdefault("_opt_hooks", [])
            if not any(h[0] is self for h in hooks):
                hooks.append((self, loss))
            return None, None
        if grads is None:
            raise ValueError(
                "minimize needs a static-program loss var, or explicit "
                "grads (functional autograd): opt.minimize(grads=...) — "
                "compute them with jax.grad / paddle_tpu.autograd.")
        self.step(grads)
        return None, None

    def state_dict(self) -> Dict:
        out = {"state": self._state}
        if isinstance(self._lr, LRScheduler):
            out["lr_scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, sd: Dict) -> None:
        self._state = sd.get("state")
        if "lr_scheduler" in sd and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(sd["lr_scheduler"])


class SGD(Optimizer):
    def _update(self, name, p, g, slots, lr, step):
        if self._weight_decay and self._decayed(name):
            g = g + self._weight_decay * p
        return p - lr * g


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum: float = 0.9, parameters=None,
                 use_nesterov: bool = False, weight_decay=0.0, grad_clip=None,
                 multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _init_slots(self, p):
        return {"velocity": _slot_like(p)}

    def _update(self, name, p, g, slots, lr, step):
        if self._weight_decay and self._decayed(name):
            g = g + self._weight_decay * p
        v = self.momentum * slots["velocity"] + g
        slots["velocity"] = v
        if self.use_nesterov:
            return p - lr * (g + self.momentum * v)
        return p - lr * v


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1: float = 0.9, beta2: float = 0.999,
                 epsilon: float = 1e-8, parameters=None, weight_decay=0.0,
                 grad_clip=None, multi_precision=True, lazy_mode: bool = False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _init_slots(self, p):
        return {"m": _slot_like(p),
                "v": _slot_like(p)}

    def _l2(self, name, p, g):
        # plain Adam folds weight decay into the gradient (L2 reg)
        if self._weight_decay and self._decayed(name):
            return g + self._weight_decay * p
        return g

    def _decoupled(self):
        return False

    def _update(self, name, p, g, slots, lr, step):
        g = self._l2(name, p, g)
        m = self.beta1 * slots["m"] + (1 - self.beta1) * g
        v = self.beta2 * slots["v"] + (1 - self.beta2) * jnp.square(g)
        slots["m"], slots["v"] = m, v
        t = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        upd = mhat / (jnp.sqrt(vhat) + self.epsilon)
        if self._decoupled() and self._weight_decay and self._decayed(name):
            upd = upd + self._weight_decay * p
        return p - lr * upd


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py —
    ``param -= lr * (update + wd * param)`` with wd NOT in the moments)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay: float = 0.01, grad_clip=None,
                 multi_precision=True, apply_decay_param_fun=None, lr_ratio=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, multi_precision)
        self.apply_decay_param_fun = apply_decay_param_fun

    def _l2(self, name, p, g):
        return g

    def _decoupled(self):
        return True


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.0, grad_clip=None,
                 multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _init_slots(self, p):
        return {"m": _slot_like(p),
                "u": _slot_like(p)}

    def _update(self, name, p, g, slots, lr, step):
        if self._weight_decay and self._decayed(name):
            g = g + self._weight_decay * p
        m = self.beta1 * slots["m"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * slots["u"], jnp.abs(g))
        slots["m"], slots["u"] = m, u
        t = step.astype(jnp.float32)
        return p - lr / (1 - self.beta1 ** t) * m / (u + self.epsilon)


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon: float = 1e-6, parameters=None,
                 weight_decay=0.0, grad_clip=None, multi_precision=True,
                 initial_accumulator_value: float = 0.0):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.epsilon = epsilon
        self.init_acc = initial_accumulator_value

    def _init_slots(self, p):
        return {"acc": _slot_like(p, self.init_acc)}

    def _update(self, name, p, g, slots, lr, step):
        if self._weight_decay and self._decayed(name):
            g = g + self._weight_decay * p
        acc = slots["acc"] + jnp.square(g)
        slots["acc"] = acc
        return p - lr * g / (jnp.sqrt(acc) + self.epsilon)


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho: float = 0.95, epsilon: float = 1e-6,
                 momentum: float = 0.0, centered: bool = False, parameters=None,
                 weight_decay=0.0, grad_clip=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.rho, self.epsilon, self.momentum, self.centered = rho, epsilon, momentum, centered

    def _init_slots(self, p):
        s = {"ms": _slot_like(p),
             "mom": _slot_like(p)}
        if self.centered:
            s["mg"] = _slot_like(p)
        return s

    def _update(self, name, p, g, slots, lr, step):
        if self._weight_decay and self._decayed(name):
            g = g + self._weight_decay * p
        ms = self.rho * slots["ms"] + (1 - self.rho) * jnp.square(g)
        slots["ms"] = ms
        if self.centered:
            mg = self.rho * slots["mg"] + (1 - self.rho) * g
            slots["mg"] = mg
            denom = jnp.sqrt(ms - jnp.square(mg) + self.epsilon)
        else:
            denom = jnp.sqrt(ms + self.epsilon)
        mom = self.momentum * slots["mom"] + lr * g / denom
        slots["mom"] = mom
        return p - mom


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon: float = 1e-6, rho: float = 0.95,
                 parameters=None, weight_decay=0.0, grad_clip=None,
                 multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.epsilon, self.rho = epsilon, rho

    def _init_slots(self, p):
        return {"avg_sq_grad": _slot_like(p),
                "avg_sq_update": _slot_like(p)}

    def _update(self, name, p, g, slots, lr, step):
        if self._weight_decay and self._decayed(name):
            g = g + self._weight_decay * p
        asg = self.rho * slots["avg_sq_grad"] + (1 - self.rho) * jnp.square(g)
        upd = jnp.sqrt(slots["avg_sq_update"] + self.epsilon) / jnp.sqrt(
            asg + self.epsilon) * g
        asu = self.rho * slots["avg_sq_update"] + (1 - self.rho) * jnp.square(upd)
        slots["avg_sq_grad"], slots["avg_sq_update"] = asg, asu
        return p - lr * upd


class Lamb(Optimizer):
    """Reference: python/paddle/optimizer/lamb.py — Adam update rescaled by
    trust ratio ||p|| / ||update||."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay: float = 0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=True):
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn

    def _init_slots(self, p):
        return {"m": _slot_like(p),
                "v": _slot_like(p)}

    def _update(self, name, p, g, slots, lr, step):
        m = self.beta1 * slots["m"] + (1 - self.beta1) * g
        v = self.beta2 * slots["v"] + (1 - self.beta2) * jnp.square(g)
        slots["m"], slots["v"] = m, v
        t = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self.epsilon)
        wd = self._weight_decay
        if self.exclude_fn is not None and self.exclude_fn(name):
            wd = 0.0
        r = r + wd * p
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
        return p - lr * trust * r


class Rprop(Optimizer):
    """Resilient backprop (reference: python/paddle/optimizer/rprop.py):
    sign-based per-parameter step sizes, grown on agreeing signs and shrunk
    with update rollback on sign flips. Full-batch method like the
    reference documents."""

    def __init__(self, learning_rate: float = 0.001,
                 learning_rate_range=(1e-5, 50.0), parameters=None,
                 etas=(0.5, 1.2), grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate=learning_rate, parameters=parameters,
                         grad_clip=grad_clip,
                         multi_precision=multi_precision)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas
        self._init_lr = learning_rate

    def _init_slots(self, p):
        import jax.numpy as jnp
        return {"step_size": _slot_like(p, self._init_lr),
                "prev_grad": _slot_like(p)}

    def _update(self, name, p, g, slots, lr, step):
        import jax.numpy as jnp
        sign = jnp.sign(g * slots["prev_grad"])
        grow = sign > 0
        flip = sign < 0
        size = jnp.clip(
            jnp.where(grow, slots["step_size"] * self._eta_pos,
                      jnp.where(flip, slots["step_size"] * self._eta_neg,
                                slots["step_size"])),
            self._lr_min, self._lr_max)
        # on sign flip: zero this step's grad (skip update, reference rule)
        g_eff = jnp.where(flip, 0.0, g)
        slots["step_size"] = size
        slots["prev_grad"] = jnp.where(flip, 0.0, g)
        return p - jnp.sign(g_eff) * size
