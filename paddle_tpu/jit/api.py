"""paddle_tpu.jit.to_static — whole-program XLA compilation.

Reference parity: python/paddle/jit/dy2static (Dy2Static ProgramTranslator):
the reference AST-transforms dygraph code into a ProgramDesc graph executed by
the fluid executor. TPU-native redesign: we TRACE the user's imperative
function (model forward, `loss.backward()`, `opt.step()` — all of it) with JAX
tracers. Every framework-mutable tensor (Parameters, buffers, optimizer
accumulators, the RNG key, the LR scalar) is lifted from the global state
registry into pytree inputs, and their post-trace values are returned as
outputs — a pure function compiled ONCE by XLA per input signature. State
arrays are donated so XLA updates parameters in place (no HBM copies).

This is the TPU-native analogue of the whole-graph executor: one fused XLA
program per step instead of per-op kernel dispatch.
"""
from __future__ import annotations

import inspect
import threading
import time

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework import state as fstate
from paddle_tpu.observability import recompile as _obs_recompile
from paddle_tpu.observability import span as _span

_tree = jax.tree_util

_trace_state = threading.local()


class _CompiledEntry(__import__("typing").NamedTuple):
    """One compiled signature of a StaticFunction. Field access, not
    positional unpacking, is the supported way to consume this (the
    3->4-tuple growth broke five positional unpackers at once)."""

    jitted: object
    out_info: object
    state_list: list
    grad_idx: tuple
    # uids whose grads the traced fn CLEARED (clear_grad) during the
    # step: their materialized grads overwrite param.grad; all others
    # accumulate onto whatever .grad held before the call — matching
    # what the same fn does in eager mode (the traced program always
    # starts grads at None, so its grad outputs are per-step deltas)
    grad_cleared: frozenset = frozenset()


def _in_to_static_trace():
    return getattr(_trace_state, "active", False)


def _audit_input_infos(state_list, tensor_vals):
    """InputInfos for one traced signature's jaxpr invars: the lifted
    state tensors then the user tensor args.  ONE builder for both the
    audit=True hook and traced_program, so the same defect fingerprints
    identically no matter which path found it."""
    from paddle_tpu import analysis
    infos = analysis.input_infos_from_state(state_list)
    for i, v in enumerate(tensor_vals):
        infos.append(analysis.InputInfo(
            name=f"arg{i}", kind="input", shape=tuple(v.shape),
            dtype=str(v.dtype), nbytes=int(getattr(v, "nbytes", 0) or 0)))
    return infos


def note_grad_cleared(uid):
    """Called by Tensor.clear_grad: records, during a to_static trace,
    that the step clears this tensor's grad (see _CompiledEntry)."""
    if getattr(_trace_state, "active", False):
        getattr(_trace_state, "cleared_uids", set()).add(uid)


def _is_tensor(x):
    return isinstance(x, Tensor)


class _StateSnapshot:
    """Save/restore all mutable fields of state tensors around a trace."""

    def __init__(self, tensors):
        self.tensors = tensors
        self.ids = {id(t) for t in tensors}
        self.saved = [(t._value, t._version, t._node, t.grad, t.stop_gradient)
                      for t in tensors]

    def restore(self):
        for t, (v, ver, node, grad, sg) in zip(self.tensors, self.saved):
            t._value = v
            t._version = ver
            t._node = node
            t.grad = grad
            t.stop_gradient = sg
        # State tensors CREATED during the trace (lazy optimizer accumulators,
        # the RNG key) may hold leaked tracers; re-init them from their spec.
        for t in fstate.state_tensors():
            if id(t) not in self.ids and isinstance(t._value, jax.core.Tracer):
                reinit = t.__dict__.get("_reinit")
                if reinit is None:
                    raise RuntimeError(
                        f"state tensor {t.name} created inside a to_static "
                        "trace without a _reinit spec")
                # escape the ambient trace so the rebuilt value is concrete
                with jax.ensure_compile_time_eval():
                    t._value = reinit()
                t._node = None
                t.grad = None


def _ordered_state():
    ts = fstate.state_tensors()
    ts.sort(key=lambda t: t.__dict__.get("_state_serial", 0))
    return ts


class StaticFunction:
    """Callable wrapper produced by @to_static."""

    def __init__(self, function, input_spec=None, build_strategy=None,
                 backend=None, donate_state=True, check=False, audit=False,
                 amp_policy=None, remat=None, guard=False):
        self._raw_function = function
        # guard=True arms the training-sentinel loss probe
        # (resilience/sentinel.py): every scalar float output leaf
        # (the loss) gets its value + finite flag computed INSIDE the
        # compiled program and returned as one tiny extra output — so
        # detection adds zero lifetime compiles (same trace, same
        # cache key) and the host reads a (n, 2) f32 array it was
        # going to sync anyway.  The parsed probe lands on
        # ``fn.last_guard`` and feeds the ambient TrainingSentinel.
        self._guard = bool(guard)
        self.last_guard = None
        # trace-scoped mixed-precision storage policy (amp/policy.py):
        # amp_policy="bf16" casts f32 activations to bf16 at Layer
        # boundaries (params stay f32 master weights) and enables the
        # O1 white-list downcasts; remat=True/"bf16" turns on the
        # model's recompute units ("bf16" also narrows saved boundary
        # activations).  Pushed around EVERY trace of this function —
        # eager code and other StaticFunctions never see it.
        self._amp_policy = amp_policy
        self._remat = remat
        # opt-in tracelint (analysis/): AST pass now, jaxpr pass at the
        # first compile of each signature — findings surface as
        # TracelintWarning instead of opaque trace-time errors
        self._check = bool(check)
        # opt-in shardlint (analysis/shard_rules + cost_audit): the full
        # SL-rule sharding/collective/memory audit of each signature's
        # traced jaxpr at first compile — findings surface as
        # ShardlintWarning; the latest CostReport lands on .last_audit
        self._audit = bool(audit)
        self.last_audit = None
        if self._check:
            from paddle_tpu import analysis
            analysis.warn_findings(analysis.lint_callable(function))
        # Dy2Static AST pass (jit/dy2static.py): tensor-dependent
        # if/while/for in the traced function (and, via convert_call, in
        # everything it calls) become select/lax.while_loop programs;
        # Python-valued control flow keeps eager semantics. Best-effort:
        # falls back to the untransformed function on any failure.
        from paddle_tpu.jit.dy2static import convert_to_static
        self._function = convert_to_static(function)
        self._input_spec = input_spec
        self._donate = donate_state
        self._compiled = {}
        self._last_state = None
        self.__name__ = getattr(function, "__name__", "static_fn")
        self._span_name = f"jit.{self.__name__}"
        self._param_names = None    # resolved lazily on first cache miss

    @property
    def dygraph_function(self):
        return self._raw_function

    def _make_pure(self, in_treedef, n_state, static_leaves):
        fn = self._function

        def pure(state_vals, tensor_vals):
            state_list = self._trace_state_list
            snap = _StateSnapshot(state_list)
            _trace_state.active = True
            _trace_state.cleared_uids = set()
            try:
                for t, v in zip(state_list, state_vals):
                    t._value = v
                    t._node = None
                    t.grad = None
                leaves = []
                ti = iter(tensor_vals)
                for s in static_leaves:
                    leaves.append(Tensor(next(ti)) if s is _ARRAY else s)
                args, kwargs = _tree.tree_unflatten(in_treedef, leaves)
                if self._amp_policy or self._remat:
                    from paddle_tpu.amp.policy import activation_residency
                    with activation_residency(
                            self._amp_policy if self._amp_policy
                            else None, remat=self._remat or False):
                        out = fn(*args, **kwargs)
                else:
                    out = fn(*args, **kwargs)
                from paddle_tpu.jit.dy2static import UNDEF as _UNDEF
                out_leaves, out_treedef = _tree.tree_flatten(out, is_leaf=_is_tensor)
                if any(o is _UNDEF for o in out_leaves):
                    raise ValueError(
                        "to_static: the function returned a variable "
                        "bound in only one branch of a tensor-valued "
                        "`if` (unrepresentable under a trace) — bind it "
                        "on every path")
                out_vals = [o._value if isinstance(o, Tensor) else o
                            for o in out_leaves]
                out_static = [_ARRAY if isinstance(o, (Tensor, jax.Array))
                              or hasattr(o, "aval") else o for o in out_leaves]
                new_state = [t._value for t in state_list]
                self._out_info = (out_treedef, out_static)
                # grads that survive to the end of the step (backward ran
                # and nothing cleared them) materialize back onto
                # param.grad — paddle semantics; a user reading .grad
                # after a jitted step must not silently see None
                grad_idx, grad_vals = [], []
                for i, t in enumerate(state_list):
                    g = t.grad
                    if g is not None and isinstance(
                            g._value, (jax.core.Tracer, jax.Array)):
                        grad_idx.append(i)
                        grad_vals.append(g._value)
                self._grad_idx = tuple(grad_idx)
                self._grad_cleared = frozenset(_trace_state.cleared_uids)
                arrays = [v for v, s in zip(out_vals, out_static) if s is _ARRAY]
                if not self._guard:
                    return arrays, new_state, grad_vals
                # sentinel probe: (value, isfinite) per scalar float
                # output leaf, f32, computed in-trace — NL-clean (one
                # scalar convert, no narrow reductions)
                probes = []
                for v, s in zip(out_vals, out_static):
                    if s is not _ARRAY:
                        continue
                    shp = jnp.shape(v)
                    if any(int(d) != 1 for d in shp):
                        continue
                    dt = getattr(v, "dtype", None)
                    if dt is None or not jnp.issubdtype(dt, jnp.floating):
                        continue
                    val = jnp.reshape(v, ()).astype(jnp.float32)
                    probes.append(jnp.stack(
                        [val, jnp.isfinite(val).astype(jnp.float32)]))
                guard_arr = (jnp.stack(probes) if probes
                             else jnp.zeros((0, 2), jnp.float32))
                return arrays, new_state, grad_vals, [guard_arr]
            finally:
                _trace_state.active = False
                snap.restore()
        return pure

    @staticmethod
    def _flatten_inputs(args, kwargs):
        """One flatten rule for every path that traces this function
        (__call__ and traced_program): tensor-like leaves become traced
        array inputs, everything else is a static (cache-keying) leaf."""
        leaves, in_treedef = _tree.tree_flatten((args, kwargs),
                                                is_leaf=_is_tensor)
        tensor_vals, static_leaves = [], []
        for l in leaves:
            if isinstance(l, Tensor):
                tensor_vals.append(l._value)
                static_leaves.append(_ARRAY)
            elif isinstance(l, jax.Array):
                tensor_vals.append(l)
                static_leaves.append(_ARRAY)
            else:
                static_leaves.append(l)
        return in_treedef, tensor_vals, static_leaves

    def _leaf_names(self, args, kwargs):
        """One human-readable name per flattened leaf of (args, kwargs),
        aligned with :meth:`_flatten_inputs` leaf order — so a recompile
        event can say WHICH argument's shape/dtype/static value changed
        (``ids``, ``arg1['mask']``, ...) instead of a leaf index."""
        if self._param_names is None:
            try:
                self._param_names = [
                    p.name for p in inspect.signature(
                        self._raw_function).parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY,
                                  p.POSITIONAL_OR_KEYWORD)]
            except (TypeError, ValueError):
                self._param_names = []
        try:
            flat, _ = _tree.tree_flatten_with_path((args, kwargs),
                                                   is_leaf=_is_tensor)
        except Exception:  # noqa: BLE001 — naming is best-effort
            return None
        names = []
        for path, _leaf in flat:
            if len(path) >= 2 and getattr(path[0], "idx", None) == 0:
                i = getattr(path[1], "idx", None)
                base = (self._param_names[i]
                        if i is not None and i < len(self._param_names)
                        else f"arg{i}")
            elif len(path) >= 2:
                base = str(getattr(path[1], "key", path[1]))
            else:
                base = "args"
            names.append(base + "".join(str(p) for p in path[2:]))
        return names

    def __call__(self, *args, **kwargs):
        with _span(self._span_name) as call_span:
            return self._call(args, kwargs, call_span)

    def _call(self, args, kwargs, call_span):
        in_treedef, tensor_vals, static_leaves = self._flatten_inputs(
            args, kwargs)

        for attempt in range(3):
            state_list = _ordered_state()
            state_vals = [t._value for t in state_list]
            if self._donate:
                # two state tensors can end up holding the SAME jax.Array
                # (e.g. set_state_dict from another live Layer's
                # state_dict) — donating one buffer twice is an XLA
                # execute error, so break accidental aliasing here
                seen = set()
                for i, v in enumerate(state_vals):
                    if id(v) in seen:
                        state_vals[i] = jnp.array(v, copy=True)
                        state_list[i]._value = state_vals[i]
                    else:
                        seen.add(id(v))
            reg_ver = fstate.registry_version()
            key = (
                in_treedef,
                tuple((tuple(v.shape), str(v.dtype)) for v in tensor_vals),
                tuple(s if s is _ARRAY else _hashable(s) for s in static_leaves),
                reg_ver,
            )
            entry = self._compiled.get(key)
            call_span.set(hit=entry is not None)
            event = None
            if entry is None:
                prior_keys = list(self._compiled)
                t_trace0 = time.perf_counter()
                self._trace_state_list = state_list
                pure = self._make_pure(in_treedef, len(state_vals), static_leaves)
                jitted = jax.jit(pure, donate_argnums=(0,) if self._donate else ())
                # Discovery trace (no execution, nothing donated): lazily
                # created state (optimizer accumulators, RNG key) registers
                # during the trace; if that happened, retrace with it lifted.
                if self._check or self._audit:
                    # trace() exposes the jaxpr for the post-trace lint
                    # (TL4xx) / shardlint audit at no extra cost vs the
                    # discovery lower()
                    traced = jitted.trace(state_vals, tensor_vals)
                    from paddle_tpu import analysis
                    where = f"<to_static {self.__name__}>"
                    infos = _audit_input_infos(state_list, tensor_vals)
                    if self._check:
                        analysis.warn_findings(
                            analysis.check_jaxpr(traced.jaxpr, where=where))
                        # numlint rides the same opt-in: the numerics &
                        # precision-flow pass over the same traced
                        # program (NLxxx), warned alongside the TL4xx
                        # jaxpr findings
                        analysis.warn_findings(
                            analysis.check_numerics(traced.jaxpr,
                                                    where=where,
                                                    inputs=infos),
                            category=analysis.NumlintWarning,
                            prefix="numlint")
                        # kernlint: the KL pass over every pallas_call
                        # interior the program reaches (numlint keeps
                        # the body opaque; KL103 owns it)
                        analysis.warn_findings(
                            analysis.check_kernels(traced.jaxpr,
                                                   where=where),
                            category=analysis.KernlintWarning,
                            prefix="kernlint")
                    if self._audit:
                        findings, self.last_audit = analysis.audit_jaxpr(
                            traced.jaxpr, where=where, inputs=infos)
                        analysis.warn_findings(
                            findings, category=analysis.ShardlintWarning,
                            prefix="shardlint")
                else:
                    jitted.lower(state_vals, tensor_vals)
                if fstate.registry_version() != reg_ver:
                    continue
                self._compiled[key] = _CompiledEntry(
                    jitted, self._out_info, state_list, self._grad_idx,
                    self._grad_cleared)
                entry = self._compiled[key]
                # recompile attribution: diff this cache key against the
                # nearest cached signature so the event can say WHY the
                # miss happened (which arg's shape/dtype/static leaf, or
                # the state registry, changed)
                event = _obs_recompile.note_jit_compile(
                    self.__name__, key, prior_keys,
                    self._leaf_names(args, kwargs), _ARRAY,
                    trace_ms=round(
                        (time.perf_counter() - t_trace0) * 1e3, 3))
            jitted = entry.jitted
            t_run0 = time.perf_counter()
            if self._guard:
                (out_arrays, new_state, grad_vals,
                 guard_out) = jitted(state_vals, tensor_vals)
            else:
                out_arrays, new_state, grad_vals = jitted(state_vals,
                                                          tensor_vals)
                guard_out = None
            if event is not None:
                # first execution of a fresh entry: XLA compiles here
                # (the lower() above only traced), so this wall time is
                # compile-dominated
                event.compile_ms = round(
                    (time.perf_counter() - t_run0) * 1e3, 3)
            self._apply(entry, out_arrays, new_state, grad_vals)
            if guard_out is not None:
                self._note_guard(guard_out)
            return self._rewrap(entry, out_arrays)
        raise RuntimeError("to_static: state registry kept changing during trace")

    def _note_guard(self, guard_out):
        """Parse the in-trace probe outputs onto ``last_guard`` and
        hand them to the ambient TrainingSentinel (informational —
        the policy runs through explicit ``observe()`` calls)."""
        import numpy as np
        ga = np.asarray(guard_out[0], np.float64)
        values = [float(x) for x in ga[:, 0]] if ga.size else []
        finite = [bool(x >= 0.5) for x in ga[:, 1]] if ga.size else []
        self.last_guard = {
            "values": values,
            "finite": finite,
            "loss": values[0] if values else None,
            "loss_finite": finite[0] if finite else True,
        }
        try:
            from paddle_tpu.resilience import sentinel as _sentinel
            s = _sentinel.current()
            if s is not None:
                s.note_probe(self.__name__, self.last_guard)
        except Exception:
            pass

    def _apply(self, entry, out_arrays, new_state, grad_vals):
        state_list, grad_idx = entry.state_list, entry.grad_idx
        for t, v in zip(state_list, new_state):
            t._value = v
            t._version += 1
            t._node = None
        for i, gv in zip(grad_idx, grad_vals):
            t = state_list[i]
            if t.grad is None:
                t.grad = Tensor(gv, stop_gradient=True,
                                name=t.name + "@GRAD")
            elif t._uid in entry.grad_cleared:
                # the step clears before backward — fresh grads replace
                t.grad._value = gv
            else:
                # the step did NOT clear: eager semantics accumulate the
                # per-step grad onto the pre-call .grad (the compiled
                # program always starts its grads at None, so gv is this
                # step's delta, never a running total)
                t.grad._value = t.grad._value + gv

    def _rewrap(self, entry, out_arrays):
        out_treedef, out_static = entry.out_info
        it = iter(out_arrays)
        leaves = [Tensor(next(it)) if s is _ARRAY else s for s in out_static]
        return _tree.tree_unflatten(out_treedef, leaves)

    def traced_program(self, *args, **kwargs):
        """Trace (never compile or run) this signature; returns
        ``(closed_jaxpr, input_infos)`` where `input_infos` is one
        :class:`analysis.InputInfo` per jaxpr invar — the lifted state
        tensors (with their names, kinds and dist_spec shardings) then
        the user tensor args.  This is the entry point shardlint's CLI
        and bench lane use to audit a program without paying a compile.
        """
        in_treedef, tensor_vals, static_leaves = self._flatten_inputs(
            args, kwargs)
        # same discovery-retrace loop as __call__ (lazily created state
        # registers during the first trace), minus donation/compilation
        for attempt in range(3):
            state_list = _ordered_state()
            state_vals = [t._value for t in state_list]
            reg_ver = fstate.registry_version()
            self._trace_state_list = state_list
            pure = self._make_pure(in_treedef, len(state_vals),
                                   static_leaves)
            traced = jax.jit(pure).trace(state_vals, tensor_vals)
            if fstate.registry_version() != reg_ver:
                # lazily created state (optimizer accumulators, the RNG
                # key) registered during the trace: retrace with it
                # lifted so the audit sees it as a named input
                continue
            return traced.jaxpr, _audit_input_infos(state_list, tensor_vals)
        raise RuntimeError(
            "to_static: state registry kept changing during trace")

    def concrete_program(self, *args, **kwargs):
        raise NotImplementedError


class _Array:
    __slots__ = ()

    def __repr__(self):
        return "<array-leaf>"


_ARRAY = _Array()


def _hashable(x):
    try:
        hash(x)
        return x
    except TypeError:
        return repr(x)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, check=False, audit=False, amp_policy=None,
              remat=None, guard=False, **kwargs):
    """Decorator/wrapper: compile a dygraph function or Layer to one XLA program.

    Usage matches paddle.jit.to_static: bare decorator, decorator with
    input_spec, or `net = to_static(net)` on a Layer.

    ``check=True`` opts into tracelint (paddle_tpu.analysis): an AST
    pass over the function and its module-local reach at wrap time, and
    a jaxpr pass after each first-compile — hazards are reported as
    ``TracelintWarning`` with TLxxx codes and file:line.  The numlint
    numerics & precision-flow pass (NLxxx — narrow accumulation,
    double-rounding, unstabilized narrow transcendentals, quantization
    readiness) runs on the same trace and warns as
    ``NumlintWarning``.

    ``audit=True`` opts into shardlint: the SL-rule sharding /
    collective-safety / memory-layout audit of each signature's traced
    jaxpr at first compile.  Findings surface as ``ShardlintWarning``
    and the latest :class:`analysis.CostReport` (estimated peak HBM,
    MXU padding waste) is kept on ``fn.last_audit``.

    ``amp_policy="bf16"`` enables bf16 activation residency for the
    traced step (params stay f32 master weights); ``remat=True`` /
    ``remat="bf16"`` turns on the model's recompute units, the latter
    saving boundary activations in bf16.  Both are trace-scoped — see
    paddle_tpu/amp/policy.py and docs/performance_guide.md.

    ``guard=True`` arms the training-sentinel loss probe: each scalar
    float output's value + finite flag is computed inside the compiled
    program (zero extra compiles — the probe is part of the one traced
    program) and parsed onto ``fn.last_guard``.  Pair with
    ``Optimizer(guard=True)`` for the gradient-side probe and the
    in-trace zero-update skip — docs/resilience.md "Numerics
    sentinel".
    """
    from paddle_tpu.nn.layer.layers import Layer

    def wrap(fn):
        if isinstance(fn, Layer):
            static = StaticFunction(fn.forward, input_spec, check=check,
                                    audit=audit, amp_policy=amp_policy,
                                    remat=remat, guard=guard)
            fn.forward = static
            fn._static_forward = static
            return fn
        return StaticFunction(fn, input_spec, check=check, audit=audit,
                              amp_policy=amp_policy, remat=remat,
                              guard=guard)

    if function is not None:
        return wrap(function)
    return wrap


def not_to_static(function):
    function._not_to_static = True
    return function


class ProgramTranslator:
    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance.enable_to_static = True
        return cls._instance

    @classmethod
    def get_instance(cls):
        return cls()

    def enable(self, enable_to_static):
        self.enable_to_static = enable_to_static


def enable_to_static(flag=True):
    ProgramTranslator().enable(flag)
