"""paddle_tpu.static — static-graph-shaped facade over JAX tracing.

Reference: python/paddle/static (Program at base/framework.py:5736, Executor
at base/executor.py:1152). The reference builds an explicit ProgramDesc/PIR
program and runs it through interpreters; on TPU the program IS the jaxpr and
the interpreter IS XLA, so this module keeps only the API *shape*: a
``Program`` records a traced function, an ``Executor`` compiles and runs it.
Useful for porting reference-style code; new code should use jit directly.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..jit import InputSpec

__all__ = ["InputSpec", "Program", "Executor", "default_main_program",
           "program_guard", "data", "CompiledProgram", "name_scope"]


class Program:
    """A deferred computation: feed names -> traced function -> fetch list.

    Built either by ``program_guard`` + ``data()`` + op calls (the ops run
    lazily at Executor.run trace time) or directly from a function.
    """

    def __init__(self):
        self._feed_specs: Dict[str, InputSpec] = {}
        self._builders = []          # list of (fetch_name, fn(feed_dict)->val)
        self._fn: Optional[Callable] = None

    # -- functional construction ------------------------------------------
    @classmethod
    def from_function(cls, fn: Callable, input_spec: Sequence[InputSpec]):
        p = cls()
        p._fn = fn
        for i, s in enumerate(input_spec):
            p._feed_specs[s.name or f"x{i}"] = s
        return p

    def global_block(self):
        return self

    def current_block(self):
        # single-block programs: the reference's block stack collapses to
        # the global block under trace-based capture
        return self

    def block(self, idx: int = 0):
        return self

    def var(self, name: str):
        """Look up a recorded program var by its display name (reference
        Block.var). Feed slots resolve too."""
        vars_ = self.__dict__.get("_vars", {})
        if name in vars_:
            return vars_[name]
        for v in vars_.values():
            if getattr(v, "name", "").split("#")[0] == name:
                return v
        if name in self._feed_specs:
            return _LazyVar(self, lambda env, n=name: env[n], name)
        raise ValueError(f"program has no var named {name!r}")

    def list_vars(self):
        """Iterate the program's vars (reference Program.list_vars):
        materialized parameters (as value-bearing handles) plus the
        recorded lazy vars. Parameters materialize at first trace; this
        forces materialization by abstract-evaluating each recorded var
        so a freshly-built network lists its weights like the
        reference's startup-initialized program does."""
        for v in list(self.__dict__.get("_vars", {}).values()):
            try:
                v._abstract()        # triggers _param materialization
            except Exception:
                pass
        store = self.__dict__.get("_nn_params", {})
        for name in store:
            yield _ParamVar(self, name)
        for v in self.__dict__.get("_vars", {}).values():
            yield v

    def state_dict(self, mode: str = "all", scope=None):
        """Reference Program.state_dict('param'|'opt'|'all'): the
        program's persistables. Optimizer state lives with the Optimizer
        here (functional design), so 'opt' is empty."""
        if mode not in ("param", "opt", "all"):
            raise ValueError("mode must be 'param', 'opt' or 'all'")
        for v in list(self.__dict__.get("_vars", {}).values()):
            try:
                v._abstract()
            except Exception:
                pass
        if mode == "opt":
            return {}
        return {k: jnp.asarray(v)
                for k, v in self.__dict__.get("_nn_params", {}).items()}

    def set_state_dict(self, state_dict, scope=None):
        store = self.__dict__.setdefault("_nn_params", {})
        for k, v in state_dict.items():
            store[k] = np.asarray(v)

    def create_var(self, name=None, dtype="float32", shape=None,
                   persistable=False, type=None, **kw):
        """Declare an output slot (reference Block.create_var) — used as
        the ``out=`` declaration of ``py_func``; carries name/shape/dtype
        only, the value is produced by the op that binds it."""
        return _DeclaredVar(name or f"tmp_{len(self.__dict__.get('_vars', {}))}",
                            dtype, shape)

    def clone(self, for_test: bool = False):
        import copy
        return copy.copy(self)

    @property
    def feed_names(self):
        return list(self._feed_specs)

    def _trace(self, fetch_builders):
        """Compose the recorded graph body into one callable over feeds.
        Side-effect vars (Assert) always build, fetched or not."""
        side = list(self.__dict__.get("_side_effect_vars", []))

        def run_all(feeds: Dict[str, jax.Array]):
            env = dict(feeds)
            for v in side:
                env[v.name] = v._build(env)
            outs = []
            for name, builder in fetch_builders:
                env[name] = builder(env)
                outs.append(env[name])
            return outs
        return run_all


class _DeclaredVar:
    """Shape/dtype-only output declaration (Block.create_var result)."""

    def __init__(self, name, dtype, shape):
        self.name = name
        self.dtype = dtype
        self.shape = tuple(shape) if shape is not None else None


class _ParamVar:
    """Value-bearing handle over a program's materialized parameter
    (what Program.list_vars yields for weights; reference Variable with
    get_value/set_value)."""

    persistable = True

    def __init__(self, program, name):
        self._program = program
        self.name = name

    @property
    def _store(self):
        return self._program.__dict__["_nn_params"]

    @property
    def shape(self):
        return list(self._store[self.name].shape)

    @property
    def dtype(self):
        return self._store[self.name].dtype

    def get_value(self, scope=None):
        return jnp.asarray(self._store[self.name])

    def set_value(self, value, scope=None):
        self._store[self.name] = np.asarray(value)

    def __eq__(self, other):
        return (isinstance(other, _ParamVar)
                and other._program is self._program
                and other.name == self.name)

    def __hash__(self):
        return hash((id(self._program), self.name))


class _LazyVar:
    """Symbolic handle returned by ``static.data`` inside a program_guard.
    Ops on it are recorded, then replayed at run() trace time."""

    __array_priority__ = 200
    _serial = 0

    def __init__(self, program: Program, build: Callable, name: str):
        self._program = program
        self._build = build
        # unique name: the Executor caches compiled fetch sets by name, so
        # two distinct expressions must never share one
        _LazyVar._serial += 1
        self.name = f"{name}#{_LazyVar._serial}"
        # name registry: Executor.run accepts fetches BY NAME (reference
        # fetch_list takes Variable or str)
        program.__dict__.setdefault("_vars", {})[self.name] = self

    @staticmethod
    def _lift(v):
        if isinstance(v, _LazyVar):
            return v._build
        return lambda env: v

    def _map(self, op, name):
        """New lazy var applying ``op`` to this var's built value (used by
        lazy-aware tensor functions like paddle.mean on program vars)."""
        sb = self._build
        return _LazyVar(self._program, lambda env: op(sb(env)),
                        f"{name}({self.name})")

    def _binop(self, other, op, name):
        ob = self._lift(other)
        sb = self._build
        oname = other.name if isinstance(other, _LazyVar) else repr(other)
        return _LazyVar(self._program, lambda env: op(sb(env), ob(env)),
                        f"({self.name}.{name}.{oname})")

    def __add__(self, o): return self._binop(o, lambda a, b: a + b, "add")
    def __radd__(self, o): return self.__add__(o)
    def __sub__(self, o): return self._binop(o, lambda a, b: a - b, "sub")
    def __mul__(self, o): return self._binop(o, lambda a, b: a * b, "mul")
    def __rmul__(self, o): return self.__mul__(o)
    def __truediv__(self, o): return self._binop(o, lambda a, b: a / b, "div")
    def __matmul__(self, o): return self._binop(o, jnp.matmul, "matmul")

    def apply(self, fn: Callable, name: str = "apply"):
        sb = self._build
        return _LazyVar(self._program, lambda env: fn(sb(env)),
                        f"{self.name}.{name}")

    # common Tensor-method spellings recorded lazily (doctests call them
    # on program vars)
    def astype(self, dtype):
        from ..core.dtype import convert_dtype
        return self._map(lambda v: v.astype(convert_dtype(dtype)), "astype")

    cast = astype

    def mean(self, axis=None, keepdim=False):
        return self._map(lambda v: jnp.mean(v, axis=axis,
                                            keepdims=keepdim), "mean")

    def sum(self, axis=None, keepdim=False):
        return self._map(lambda v: jnp.sum(v, axis=axis,
                                           keepdims=keepdim), "sum")

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return self._map(lambda v: jnp.reshape(v, shape), "reshape")

    def unsqueeze(self, axis):
        return self._map(lambda v: jnp.expand_dims(v, axis), "unsqueeze")

    # -- shape/dtype inspection (reference Variable.shape/.dtype): infer
    # by abstract evaluation over the program's declared feed specs —
    # the static-graph InferShape pass, done with jax.eval_shape
    def _abstract(self):
        from ..core.dtype import convert_dtype

        def _specs(sub):
            out, dynamic = {}, False
            for name, spec in self._program._feed_specs.items():
                dims = []
                for d in spec.shape:
                    if d is None or (isinstance(d, int) and d < 0):
                        dims.append(sub)
                        dynamic = True
                    else:
                        dims.append(d)
                out[name] = jax.ShapeDtypeStruct(tuple(dims),
                                                 convert_dtype(spec.dtype))
            return out, dynamic
        try:
            s2, dynamic = _specs(2)
            r2 = jax.eval_shape(self._build, s2)
            if not dynamic:
                return r2, r2.shape
            # dims that track a dynamic feed dim change with the
            # substitute — report those as -1 (the reference's marker)
            r3 = jax.eval_shape(self._build, _specs(3)[0])
            shape = tuple(-1 if a != b else a
                          for a, b in zip(r2.shape, r3.shape))
            return r2, shape
        except Exception as e:
            # AttributeError keeps hasattr(var, "shape") duck-typing safe
            raise AttributeError(
                f"cannot infer shape/dtype of program var {self.name!r}: "
                f"{type(e).__name__}: {e}") from e

    @property
    def shape(self):
        # declared shape (static.data sets it) wins; derived vars infer
        if getattr(self, "_shape", None) is not None:
            return self._shape
        return list(self._abstract()[1])

    @shape.setter
    def shape(self, v):
        self._shape = tuple(v) if v is not None else None

    @property
    def dtype(self):
        if getattr(self, "_dtype", None) is not None:
            return self._dtype
        return self._abstract()[0].dtype

    @dtype.setter
    def dtype(self, v):
        self._dtype = v

    @property
    def ndim(self):
        return len(self.shape)

    def _set_error_clip(self, clip):
        raise NotImplementedError(
            "per-var error clip rewrote the legacy block IR's backward; "
            "under trace-based capture use gradient clipping on the "
            "OPTIMIZER instead: optimizer(..., grad_clip="
            "nn.ClipGradByValue(...)) (docs/DESIGN_DECISIONS.md)")


def lazy_apply(fn, *args, name="apply", **kwargs):
    """Lift ``fn`` over any mix of program vars and concrete values: the
    result is a new lazy var whose build evaluates every lazy input then
    applies ``fn``. This is the generic static-op recorder behind the
    lazy-aware spellings of dynamic functions (e.g. F.cross_entropy on
    static.data vars)."""
    lazies = [a for a in args if isinstance(a, _LazyVar)]
    lazies += [v for v in kwargs.values() if isinstance(v, _LazyVar)]
    if not lazies:
        return fn(*args, **kwargs)
    prog = lazies[0]._program

    def build(env):
        a = [x._build(env) if isinstance(x, _LazyVar) else x for x in args]
        kw = {k: (v._build(env) if isinstance(v, _LazyVar) else v)
              for k, v in kwargs.items()}
        return fn(*a, **kw)
    label = ",".join(v.name for v in lazies)
    return _LazyVar(prog, build, f"{name}({label})")


_default_program = Program()
_program_stack = []


def default_main_program() -> Program:
    return _program_stack[-1] if _program_stack else _default_program


class program_guard:
    def __init__(self, main_program: Program, startup_program: Optional[Program] = None):
        self.main = main_program

    def __enter__(self):
        _program_stack.append(self.main)
        return self.main

    def __exit__(self, *exc):
        _program_stack.pop()
        return False


def data(name: str, shape: Sequence[Optional[int]], dtype="float32",
         lod_level: int = 0) -> _LazyVar:
    """Declare a feed slot in the current program (reference: static.data)."""
    prog = default_main_program()
    prog._feed_specs[name] = InputSpec(shape, dtype, name)
    var = _LazyVar(prog, lambda env: env[name], name)
    var._feed_name = name  # autodiff needs the raw feed key, not the
    # reference Variables expose declared shape/dtype; None dims stay None
    var.shape = tuple(shape)
    var.dtype = dtype
    return var             # uniquified display name


def name_scope(prefix: str):
    import contextlib
    return contextlib.nullcontext()


class CompiledProgram:
    """Kept for API parity; compilation happens inside Executor.run."""

    def __init__(self, program: Program, build_strategy=None):
        self.program = program


class Executor:
    """Compile-and-run front end (reference: base/executor.py:1152).

    ``run(program, feed={...}, fetch_list=[vars])`` jits the recorded graph
    once per (program, fetch set) and replays it on subsequent calls — the
    analogue of the reference's _ExecutorCache + StandaloneExecutor.
    """

    def __init__(self, place: Optional[str] = None):
        self.place = place
        self._cache: Dict[int, Callable] = {}

    def run(self, program: Optional[Program] = None, feed: Optional[Dict] = None,
            fetch_list: Optional[Sequence] = None, return_numpy: bool = True):
        import numpy as np
        program = program.program if isinstance(program, CompiledProgram) else program
        program = program or default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        if not isinstance(fetch_list, (list, tuple)):
            fetch_list = [fetch_list]
        from ..optimizer.lr import LRScheduler as _LRS
        from ..optimizer.lr import _SCHED_REGISTRY

        def _resolve(v):
            if program._fn is not None:
                # function-backed programs (from_function / loaded
                # inference artifacts) fetch POSITIONALLY — names like
                # "fetch_0" are labels, not recorded vars
                return v
            if isinstance(v, str):
                hit = program.__dict__.get("_vars", {}).get(v)
                if hit is not None:
                    return hit
                if v in _SCHED_REGISTRY:
                    return _SCHED_REGISTRY[v]
                if v in program._feed_specs:      # fetch a feed by name
                    var = _LazyVar(program, (lambda env, n=v: env[n]), v)
                    # register under the RAW name too: the next run must
                    # hit the cache key, not mint a fresh serial
                    program.__dict__["_vars"][v] = var
                    return var
                known = (list(program.__dict__.get("_vars", {}))[:5]
                         + list(program._feed_specs))
                raise ValueError(
                    f"unknown fetch name {v!r}; known vars include "
                    f"{known} and scheduler names")
            return v
        fetch_list = [_resolve(v) for v in fetch_list]
        # schedulers fetch host-side (their lr must track step state, not
        # freeze into a compiled constant); program vars go through the
        # traced path, results merged back in order
        sched_pos = {i: v for i, v in enumerate(fetch_list)
                     if isinstance(v, _LRS)}
        if sched_pos:
            import numpy as np
            var_items = [v for v in fetch_list
                         if not isinstance(v, _LRS)]
            var_outs = self.run(program, feed=feed, fetch_list=var_items,
                                return_numpy=return_numpy) \
                if var_items else []
            outs, vi = [], 0
            for i in range(len(fetch_list)):
                if i in sched_pos:
                    outs.append(np.asarray(
                        [sched_pos[i].get_last_lr()], np.float32))
                else:
                    outs.append(var_outs[vi])
                    vi += 1
            return outs

        if program._fn is not None:
            args = [jnp.asarray(feed[n]) for n in program.feed_names]
            key = id(program)
            if key not in self._cache:
                self._cache[key] = jax.jit(program._fn)
            outs = self._cache[key](*args)
            outs = outs if isinstance(outs, (tuple, list)) else [outs]
        else:
            builders = [(getattr(v, "name", f"fetch{i}"),
                         v._build if hasattr(v, "_build")
                         else (lambda env, c=v: jnp.asarray(c)))
                        for i, v in enumerate(fetch_list)]
            env = {k: jnp.asarray(v) for k, v in feed.items()}
            hooks = program.__dict__.get("_opt_hooks")
            if hooks:
                outs = self._run_train_step(program, builders, env, hooks)
            else:
                # side-effect count in the key: an Assert recorded AFTER a
                # fetch set compiled must invalidate that cache entry
                key = (id(program), tuple(n for n, _ in builders),
                       len(program.__dict__.get("_side_effect_vars", [])))
                if key not in self._cache:
                    run_all = program._trace(builders)
                    self._cache[key] = jax.jit(
                        lambda env: run_all(env))
                outs = self._cache[key](env)

        if return_numpy:
            outs = [np.asarray(o) for o in outs]
        return outs

    def _run_train_step(self, program, builders, env, hooks):
        """minimize() support: one compiled forward+backward+update per
        ``run`` (reference: the program's appended grad+optimizer ops
        executed by StandaloneExecutor; here one jitted step closing over
        the program builders, params exposed as traced inputs via
        prog._param_env — see static/nn.py _param)."""
        import numpy as np
        opt, loss = hooks[0]
        if len(hooks) > 1:
            raise NotImplementedError(
                "one optimizer per static program (reference allows one "
                "minimize per program too)")
        if "_nn_params" not in program.__dict__:
            program.__dict__["_nn_params"] = {}
        store = program.__dict__["_nn_params"]
        key = (id(program), "train", tuple(n for n, _ in builders))
        if key not in self._cache and not program.__dict__.get(
                "_warm_built"):
            # warm up ONCE per program: a partially populated store (an
            # earlier inference fetch touched only some layers) would
            # bake the missing params in as untrained constants. The
            # invariant is program state, so later executors/fetch sets
            # skip the eager forward
            loss._build(dict(env))
            program.__dict__["_warm_built"] = True
        params = {k: jnp.asarray(v) for k, v in store.items()}
        state = program.__dict__.get("_opt_state")
        if state is None:
            state = opt.init_state(params)
        if key not in self._cache:
            def step(params, state, env, lr):
                program.__dict__["_param_env"] = params
                try:
                    def loss_of(p):
                        program.__dict__["_param_env"] = p
                        return jnp.sum(loss._build(dict(env)))
                    loss_v, grads = jax.value_and_grad(loss_of)(params)
                    new_p, new_s = opt.apply_gradients(params, grads,
                                                       state, lr=lr)
                    # fetches evaluate under the PRE-update params, like
                    # the reference (fetch ops run in the same pass)
                    program.__dict__["_param_env"] = params
                    fetches = [b(dict(env)) for _, b in builders]
                    return new_p, new_s, fetches
                finally:
                    program.__dict__.pop("_param_env", None)
            self._cache[key] = jax.jit(step)
        new_p, new_s, outs = self._cache[key](params, state, env,
                                              jnp.float32(opt.get_lr()))
        for k, v in new_p.items():
            store[k] = v   # jit OUTPUTS are concrete device arrays — no
                           # per-step host round trip (the numpy-only rule
                           # in static/nn.py covers values created INSIDE
                           # a trace, which these are not)
        program.__dict__["_opt_state"] = new_s
        # fluid-era decay schedules advance per executor step (the
        # reference appends the decay ops to the program); modern
        # schedulers advance via the user's scheduler.step()
        sched = getattr(opt, "_learning_rate", None)
        if sched is None:
            sched = getattr(opt, "lr_scheduler", None)
        if callable(getattr(sched, "step", None)) and \
                getattr(sched, "_auto_step", False):
            sched.step()
        return outs

    def close(self):
        self._cache.clear()


# ---------------------------------------------------------------------------
# static-graph autodiff (reference: python/paddle/base/backward.py —
# append_backward:1974 builds grad ops into the program; gradients:2713)
# ---------------------------------------------------------------------------

def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """Symbolic gradients of ``targets`` w.r.t. ``inputs`` as new lazy vars
    in the same program. TPU-native: instead of per-op GradOpMaker rewrites,
    the whole traced builder goes through jax.grad when the fetch executes."""
    tgt_list = targets if isinstance(targets, (list, tuple)) else [targets]
    in_list = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    prog = tgt_list[0]._program

    def make(inp):
        if not isinstance(inp, _LazyVar):
            raise TypeError("inputs must be program vars (e.g. static.data)")

        def build(env):
            name = getattr(inp, "_feed_name", inp.name)

            def scalar_loss(x):
                env2 = dict(env)
                env2[name] = x
                total = None
                for t in tgt_list:
                    v = jnp.sum(t._build(env2))
                    total = v if total is None else total + v
                return total

            return jax.grad(scalar_loss)(jnp.asarray(env[name]))

        return _LazyVar(prog, build, f"{inp.name}@GRAD")

    outs = [make(i) for i in in_list]
    return outs if isinstance(inputs, (list, tuple)) else outs[0]


def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None):
    """reference: base/backward.py append_backward — returns
    [(param_var, grad_var)] pairs; here parameters are the program's feed
    vars (static params feed through the same slots)."""
    prog = loss._program
    if parameter_list is None:
        parameter_list = []
        for n in prog.feed_names:
            v = _LazyVar(prog, (lambda env, n=n: env[n]), n)
            v._feed_name = n
            parameter_list.append(v)
    grads = gradients([loss], list(parameter_list))
    return list(zip(parameter_list, grads))


# ---------------------------------------------------------------------------
# round-3 parity batch: scopes/places, inference model IO, EMA, misc
# (reference: python/paddle/static/{__init__.py,io.py,nn/common.py},
# base/executor.py global_scope)
# ---------------------------------------------------------------------------

Variable = _LazyVar  # paddle.static.Variable — the lazy program var


class _Scope:
    """Name->value store (reference: paddle.static.global_scope Scope)."""

    def __init__(self):
        self._vars: Dict[str, Any] = {}

    def var(self, name: str):
        self._vars.setdefault(name, None)
        return _ScopeVar(self, name)

    def find_var(self, name: str):
        return _ScopeVar(self, name) if name in self._vars else None

    def set(self, name: str, value):
        self._vars[name] = value


class _ScopeVar:
    def __init__(self, scope: _Scope, name: str):
        self._scope = scope
        self.name = name

    def get_tensor(self):
        return self._scope._vars.get(self.name)

    def set(self, value, place=None):
        self._scope._vars[self.name] = jnp.asarray(value)


_GLOBAL_SCOPE = _Scope()


def global_scope() -> _Scope:
    return _GLOBAL_SCOPE


def scope_guard(scope: _Scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _GLOBAL_SCOPE
        prev, _GLOBAL_SCOPE = _GLOBAL_SCOPE, scope
        try:
            yield scope
        finally:
            _GLOBAL_SCOPE = prev

    return guard()


def cpu_places(device_count: Optional[int] = None):
    from ..base import CPUPlace
    if device_count is None:
        try:
            device_count = len(jax.devices("cpu"))
        except RuntimeError:  # no cpu platform registered
            device_count = 1
    return [CPUPlace() for _ in range(max(1, device_count))]


def cuda_places(device_ids=None):
    """Accelerator places (CUDA name kept for parity; resolves to TPU)."""
    from ..base import CUDAPlace
    if device_ids is None:
        device_ids = range(jax.device_count())
    return [CUDAPlace(i) for i in device_ids]


def device_guard(device: str = "cpu"):
    """Pin ops in the region to a device (reference: static/device_guard).
    Maps to jax.default_device."""
    import contextlib

    @contextlib.contextmanager
    def guard():
        name = device.split(":")[0]
        plat = {"cpu": "cpu", "gpu": "tpu", "tpu": "tpu"}.get(name, "cpu")
        try:
            devs = jax.devices(plat)
        except RuntimeError:
            devs = jax.devices()
        with jax.default_device(devs[0]):
            yield

    return guard()


def ipu_shard_guard(index=-1, stage=-1):
    import contextlib

    @contextlib.contextmanager
    def guard():
        yield

    return guard()


class IpuStrategy:
    """IPU backends are not a TPU target; constructible shim
    (reference: static/__init__.py IpuStrategy)."""

    def __init__(self):
        self.num_ipus = 0

    def set_graph_config(self, **kw):
        return None


class IpuCompiledProgram:
    def __init__(self, program=None, scope=None, ipu_strategy=None):
        self.program = program

    def compile(self, feed_list=None, fetch_list=None):
        return self.program


class BuildStrategy:
    """Graph-build knobs (reference: BuildStrategy pybind). XLA performs
    these fusions already; the knobs are recorded for introspection."""

    def __init__(self):
        self.fuse_elewise_add_act_ops = False
        self.fuse_bn_act_ops = False
        self.enable_addto = False
        self.memory_optimize = True
        self.reduce_strategy = 0
        self.debug_graphviz_path = ""


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 10


class WeightNormParamAttr:
    """Weight-normalized parameter attribute (reference:
    static/nn/common.py WeightNormParamAttr): g * v / ||v||."""

    def __init__(self, dim=None, name=None, initializer=None,
                 learning_rate: float = 1.0, regularizer=None,
                 trainable: bool = True, do_model_average: bool = False,
                 need_clip: bool = True):
        self.dim = dim
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.trainable = trainable


class ExponentialMovingAverage:
    """EMA of parameters (reference: static/__init__.py
    ExponentialMovingAverage): update() folds current params in;
    apply()/restore() swap shadow params into a layer."""

    def __init__(self, decay: float = 0.999, thres_steps=None, name=None):
        self._decay = decay
        self._shadow: Dict[str, jax.Array] = {}
        self._backup: Dict[str, jax.Array] = {}
        self._step = 0

    def update(self, layer=None, parameters=None):
        named = (layer.state_dict().items() if layer is not None
                 else parameters or [])
        self._step += 1
        d = min(self._decay, (1 + self._step) / (10 + self._step))
        for name, v in named:
            arr = jnp.asarray(v)
            if name in self._shadow:
                self._shadow[name] = d * self._shadow[name] + (1 - d) * arr
            else:
                self._shadow[name] = arr

    def apply(self, executor=None, need_restore: bool = True, layer=None):
        import contextlib

        @contextlib.contextmanager
        def guard():
            if layer is not None:
                self._backup = {k: jnp.asarray(v)
                                for k, v in layer.state_dict().items()}
                layer.set_state_dict({k: self._shadow.get(k, v)
                                      for k, v in self._backup.items()})
            try:
                yield
            finally:
                if need_restore and layer is not None:
                    layer.set_state_dict(self._backup)

        return guard()

    def restore(self, executor=None, layer=None):
        if layer is not None and self._backup:
            layer.set_state_dict(self._backup)


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from ..base import create_parameter as _cp
    return _cp(shape, dtype, name=name, attr=attr, is_bias=is_bias,
               default_initializer=default_initializer)


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    from ..base import create_global_var as _cgv
    return _cgv(shape, value, dtype, persistable=persistable, name=name)


def _pyfunc_spec(o):
    from ..core.dtype import convert_dtype
    if getattr(o, "shape", None) is None or any(
            d is None or int(d) < 0 for d in o.shape):
        raise ValueError(
            f"py_func out var {getattr(o, 'name', o)!r} needs an explicit "
            f"concrete shape (pure_callback requires the result shape "
            f"up front): create_var(name=..., dtype=..., shape=[...])")
    shape = tuple(int(d) for d in o.shape)
    return jax.ShapeDtypeStruct(shape, convert_dtype(str(o.dtype)))


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-callback op (reference: static/nn/common.py py_func:3100).

    Maps to jax.pure_callback with the declared ``out`` shape; when
    ``backward_func`` is given the op carries a custom_vjp whose backward
    is a second host callback receiving, per the reference contract, the
    non-skipped inputs, the outputs, and the output gradients (in that
    order) and returning one gradient per input. ``out=None`` (debug
    hook) runs the callback for effect via jax.debug.callback.

    Works in BOTH modes: on arrays directly, and on program vars (the op
    is recorded and replayed at Executor.run trace time). Platform note:
    host callbacks need PJRT send/recv support (CPU and the standard TPU
    runtime have it); py_func graphs are a host-interop feature, not a
    TPU hot path."""
    xs = list(x) if isinstance(x, (list, tuple)) else [x]
    outs = (list(out) if isinstance(out, (list, tuple))
            else ([] if out is None else [out]))
    skips = (list(skip_vars_in_backward_input)
             if isinstance(skip_vars_in_backward_input, (list, tuple))
             else ([] if skip_vars_in_backward_input is None
                   else [skip_vars_in_backward_input]))
    skip_idx = {i for i, v in enumerate(xs)
                if any(v is s for s in skips)}

    if not outs:
        def effect_op(*vals):
            jax.debug.callback(lambda *a: func(*a), *vals)
            return None
        if any(isinstance(v, _LazyVar) for v in xs):
            # debug hooks on program vars: not wired to any fetch, so a
            # lazy recording would be dead code — run on the abstract
            # values' concrete replay only if fetched; recorded as no-op
            return None
        return effect_op(*[jnp.asarray(v) for v in xs])

    specs = [_pyfunc_spec(o) for o in outs]
    single = len(specs) == 1

    def fwd_raw(*vals):
        return jax.pure_callback(func, specs[0] if single else specs, *vals)

    if backward_func is None:
        op = fwd_raw
    else:
        @jax.custom_vjp
        def op(*vals):
            return fwd_raw(*vals)

        def _fwd(*vals):
            y = fwd_raw(*vals)
            keep = tuple(v for i, v in enumerate(vals)
                         if i not in skip_idx)
            return y, (keep, y, tuple(
                jax.ShapeDtypeStruct(v.shape, v.dtype) for v in vals))

        def _bwd(res, dy):
            keep, y, xspecs = res
            # pure_callback yields a LIST for multi-output ops
            ys = tuple(y) if isinstance(y, (list, tuple)) else (y,)
            dys = tuple(dy) if isinstance(dy, (list, tuple)) else (dy,)
            grads = jax.pure_callback(
                backward_func, list(xspecs) if len(xspecs) > 1
                else xspecs[0], *keep, *ys, *dys)
            return (tuple(grads) if isinstance(grads, (list, tuple))
                    else (grads,))

        op.defvjp(_fwd, _bwd)

    if any(isinstance(v, _LazyVar) for v in xs):
        lv = lazy_apply(op, *xs, name="py_func")
        prog = lv._program
        # bind the result to the DECLARED out var names so
        # fetch_list=[output.name] resolves (reference: py_func writes
        # into the pre-created block vars)
        reg = prog.__dict__.setdefault("_vars", {})
        if single:
            reg[outs[0].name] = lv
            return lv

        def _once(env, _k="__pyfunc_%x_%s" % (id(lv), lv.name)):
            # memoized per trace env: each component indexes ONE host
            # call, not one call per fetched output. id(lv) in the key:
            # lv.name derives from input VAR names, so two multi-output
            # py_func ops over the same inputs would otherwise collide
            # and the second would silently read the first's results
            # (round-4 advice, medium)
            if _k not in env:
                env[_k] = lv._build(env)
            return env[_k]
        comps = []
        for i, o in enumerate(outs):
            c = _LazyVar(prog, (lambda env, i=i: _once(env)[i]), o.name)
            reg[o.name] = c
            comps.append(c)
        return comps
    return op(*[jnp.asarray(v) for v in xs])


def Print(input, first_n: int = -1, message: Optional[str] = None,
          summarize: int = 20, print_tensor_name: bool = True,
          print_tensor_type: bool = True, print_tensor_shape: bool = True,
          print_tensor_layout: bool = True, print_tensor_lod: bool = True,
          print_phase: str = "both"):
    """Debug-print op (reference: static/nn/control_flow.py Print). Maps to
    jax.debug.print so it fires under jit too."""
    arr = jnp.asarray(input)
    jax.debug.print((message or "") + " {x}", x=arr)
    return arr


def accuracy(input, label, k: int = 1, correct=None, total=None):
    from ..metric import accuracy as _acc
    return _acc(input, label, k=k)


def auc(input, label, curve: str = "ROC", num_thresholds: int = 4095,
        topk: int = 1, slide_steps: int = 1):
    """Batch AUC (reference: static/nn/metric.py auc). Returns
    (auc_value, batch_auc, [state]) shaped like the reference's first two."""
    from ..metric import Auc
    m = Auc(curve=curve, num_thresholds=num_thresholds)
    import numpy as _np
    pred = _np.asarray(input)
    lab = _np.asarray(label).reshape(-1, 1)
    m.update(pred, lab)
    v = jnp.asarray(m.accumulate(), jnp.float32)
    return v, v, []


# -- inference model save/load (reference: static/io.py) --------------------

def normalize_program(program: Program, feeds, fetches, **kwargs) -> Program:
    """reference: static/io.py normalize_program — prune to feed/fetch.
    Tracing already yields exactly the feed->fetch closure."""
    return program


def serialize_program(feeds, fetches, **kwargs) -> bytes:
    import pickle
    return pickle.dumps({"feeds": [getattr(f, "name", str(f))
                                   for f in _as_list(feeds)],
                         "fetches": len(_as_list(fetches))})


def serialize_persistables(feed_vars, fetch_vars, executor=None) -> bytes:
    import pickle
    return pickle.dumps(dict(global_scope()._vars))


def _as_list(v):
    return v if isinstance(v, (list, tuple)) else [v]


def save_to_file(path: str, content: bytes) -> None:
    import os
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def deserialize_program(data: bytes):
    import pickle
    return pickle.loads(data)


def deserialize_persistables(program, data: bytes, executor=None):
    import pickle
    state = pickle.loads(data)
    global_scope()._vars.update(state)
    return state


def save_inference_model(path_prefix: str, feed_vars, fetch_vars,
                         executor=None, program=None, **kwargs) -> None:
    """Save a deployable model (reference: static/io.py
    save_inference_model). The executable artifact is the jit-exported
    StableHLO from paddle_tpu.jit.save; this writes the program metadata +
    persistables next to it in the reference's two-file layout."""
    save_to_file(path_prefix + ".pdmodel",
                 serialize_program(feed_vars, fetch_vars))
    save_to_file(path_prefix + ".pdiparams",
                 serialize_persistables(feed_vars, fetch_vars))


def load_inference_model(path_prefix: str, executor=None, **kwargs):
    """Load the pair written by save_inference_model; returns
    [program_meta, feed_names, fetch_count] like the reference triplet.
    Also accepts a jit.save/TracedLayer.save_inference_model artifact
    (.pdexport StableHLO) — the reference's TracedLayer example saves with
    one API and loads with this one, so both formats resolve here."""
    import os as _os
    if (not _os.path.exists(path_prefix + ".pdmodel")
            and _os.path.exists(path_prefix + ".pdexport")):
        from ..jit import load as _jit_load
        tl = _jit_load(path_prefix)
        n = int(getattr(tl, "n_inputs", 1) or 1)
        names = [f"feed_{i}" for i in range(n)]
        prog = Program()
        prog._fn = lambda *a: tl(*a)
        for nm in names:
            prog._feed_specs[nm] = InputSpec((None,), "float32", nm)
        prog.__dict__["_translated_layer"] = tl
        return [prog, names, ["fetch_0"]]
    meta = deserialize_program(load_from_file(path_prefix + ".pdmodel"))
    deserialize_persistables(None, load_from_file(path_prefix
                                                  + ".pdiparams"))
    return [meta, meta.get("feeds", []), meta.get("fetches", 0)]


def save(program: Program, model_path: str, protocol: int = 4) -> None:
    from .. import framework as _fw
    _fw.save(dict(global_scope()._vars), model_path + ".pdparams")


def load(program: Program, model_path: str, executor=None,
         var_list=None) -> None:
    from .. import framework as _fw
    global_scope()._vars.update(_fw.load(model_path + ".pdparams"))


def load_program_state(model_path: str, var_list=None):
    from .. import framework as _fw
    return _fw.load(model_path + ".pdparams", return_numpy=True)


def set_program_state(program: Program, state_dict) -> None:
    global_scope()._vars.update(
        {k: jnp.asarray(v) for k, v in state_dict.items()})


def ctr_metric_bundle(input, label, ins_tag_weight=None):
    """CTR sub-metrics (reference: static/nn/metric.py ctr_metric_bundle):
    returns (sqrerr, abserr, prob, q, pos, total) accumulators."""
    import numpy as _np
    pred = jnp.asarray(input).reshape(-1)
    lab = jnp.asarray(label).reshape(-1).astype(pred.dtype)
    sqrerr = jnp.sum((pred - lab) ** 2)
    abserr = jnp.sum(jnp.abs(pred - lab))
    prob = jnp.sum(pred)
    q = jnp.sum(pred * pred)
    pos = jnp.sum(lab)
    total = jnp.asarray(pred.shape[0], pred.dtype)
    return sqrerr, abserr, prob, q, pos, total


_STARTUP_PROGRAM = Program()


def default_startup_program() -> Program:
    """reference: base/framework.py default_startup_program — parameter
    initialization program; initialization is eager here, so this is a
    stable empty Program handle."""
    return _STARTUP_PROGRAM


def xpu_places(device_ids=None):
    return cuda_places(device_ids)


def set_ipu_shard(call_func, index=-1, stage=-1):
    return call_func


from . import nn  # noqa: E402  (paddle.static.nn builders)
from . import amp  # noqa: E402  (paddle.static.amp facade)


def save_vars(executor=None, dirname=None, main_program=None, vars=None,
              predicate=None, filename=None):
    """Save a var list's values (reference: static/io.py save_vars).
    ``vars`` holds value-bearing handles (list_vars output /
    create_parameter arrays); ``predicate`` filters main_program's vars."""
    import os as _os
    prog = main_program or default_main_program()
    if vars is None:
        vars = [v for v in prog.list_vars()
                if (predicate is None or predicate(v))
                and hasattr(v, "get_value")]
    payload = {}
    for i, v in enumerate(vars):
        name = getattr(v, "name", f"var_{i}")
        if hasattr(v, "get_value"):
            payload[name] = np.asarray(v.get_value())
        else:
            payload[name] = np.asarray(v)
    from .. import framework as _fw
    path = (_os.path.join(dirname, filename) if filename
            else _os.path.join(dirname, "__all_vars__"))
    _fw.save(payload, path)
    return path


def load_vars(executor=None, dirname=None, main_program=None, vars=None,
              predicate=None, filename=None):
    """Counterpart of save_vars: restores values into the program's
    parameter store (reference: static/io.py load_vars)."""
    import os as _os
    from .. import framework as _fw
    prog = main_program or default_main_program()
    path = (_os.path.join(dirname, filename) if filename
            else _os.path.join(dirname, "__all_vars__"))
    payload = _fw.load(path, return_numpy=True)
    if vars is not None:
        names = {getattr(v, "name", None) for v in vars}
        payload = {k: v for k, v in payload.items() if k in names}
    elif predicate is not None:
        keep = {v.name for v in prog.list_vars()
                if predicate(v) and hasattr(v, "get_value")}
        payload = {k: v for k, v in payload.items() if k in keep}
    prog.set_state_dict(payload)


# reference path paddle.static.io.* (save_vars/load_vars/serialize live in
# static/io.py there; consolidated here)
from ..utils import register_submodule_aliases as _rsa  # noqa: E402
import sys as _sys  # noqa: E402
_rsa(__name__, {"io": _sys.modules[__name__]})
io = _sys.modules[__name__]


def get_program_persistable_vars(program: Program):
    """Persistable (parameter) vars of a program (reference:
    static/io.py get_program_persistable_vars)."""
    return [v for v in program.list_vars() if getattr(v, "persistable",
                                                      False)]


# place classes addressable as paddle.static.CPUPlace etc. (reference
# re-exports them through the static namespace)
from ..device import CPUPlace, CUDAPlace, XPUPlace, TPUPlace  # noqa: E402
