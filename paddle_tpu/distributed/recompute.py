"""Activation recomputation. Reference: python/paddle/distributed/fleet/recompute/.

TPU-native: `jax.checkpoint` (rematerialization) — XLA recomputes the segment
in the backward pass, trading FLOPs for HBM. The wrapped Layer's parameters
are lifted to explicit arguments of the checkpointed function (temporarily
re-bound during the inner run) so parameter gradients flow through the
rematerialized region in both eager-tape and to_static modes.
"""
from __future__ import annotations

import threading

import jax

from paddle_tpu.amp.auto_cast import amp_state, auto_cast
from paddle_tpu.core.dispatch import apply
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import metrics as _obs_metrics

_rc_tls = threading.local()


def recompute_active():
    """True while a recompute region's forward (or backward re-run) is
    executing on this thread — the guard ``Layer.__call__`` uses so a
    per-Layer ``enable_recompute`` can wrap through ``recompute(self,
    ...)`` without recursing, and nested remat layers are not
    re-wrapped (the outermost region wins)."""
    return getattr(_rc_tls, "depth", 0) > 0


def _owner_layer(function):
    from paddle_tpu.nn.layer.layers import Layer
    if isinstance(function, Layer):
        return function
    self_obj = getattr(function, "__self__", None)
    if isinstance(self_obj, Layer):
        return self_obj
    return None


def recompute(function, *args, **kwargs):
    kwargs.pop("preserve_rng_state", True)
    kwargs.pop("use_reentrant", True)

    layer = _owner_layer(function)
    params = list(layer.parameters()) if layer is not None else []
    buffers = list(layer.buffers()) if layer is not None else []
    # the global RNG key threads through like a buffer: stochastic ops
    # (dropout) inside the checkpointed region draw sub-trace keys, and
    # (a) the advanced key must ESCAPE as a checkpoint output (a bare
    # mutation would leak a sub-trace tracer into the ambient state),
    # (b) the backward rematerialization re-enters with the SAME key, so
    # the recomputed dropout mask matches the forward's exactly
    from paddle_tpu.framework.state import _key_tensor
    buffers = buffers + [_key_tensor()]
    state = params + buffers
    n_args = len(args)
    arg_is_tensor = [isinstance(a, Tensor) for a in args]
    tensor_args = [a for a in args if isinstance(a, Tensor)]

    meta = {"n_user": 1, "is_seq": False}

    # a region re-runs the way it ran: the autocast state is thread-local
    # and ``backward()`` is usually called after the ``auto_cast`` block
    # has exited, so the state current at this call is snapshotted here
    # and re-entered round every run of the region — the forward (where
    # it is already current) and ckpt_bwd's re-run alike.  Reference:
    # RecomputeFunction saves is_fw_autocast / amp_level / amp_dtype and
    # both lists at forward and re-enters auto_cast in backward.
    st = amp_state()
    amp = dict(enable=st.enabled, level=st.level, dtype=st.dtype,
               custom_white_list=set(st.custom_white),
               custom_black_list=set(st.custom_black))
    state_name = (f"{st.level}/{jax.numpy.dtype(st.dtype).name}"
                  if st.enabled else "off")
    _obs_metrics.registry().counter(
        "recompute_regions_total",
        help="recompute() calls (trace time), by the autocast state "
             "the region runs and re-runs under",
        labels={"autocast": state_name}).inc()

    # VJP-only rematerialization (NOT jax.checkpoint): the eager tape
    # pre-lowers every op's custom_vjp into raw fwd/bwd calls, so by the
    # time jax.checkpoint would linearize this region via JVP the flash
    # attention pallas_call appears raw — and pallas has no usable JVP
    # rule (AssertionError in _pallas_call_jvp_rule; found the first
    # time recompute wrapped flash ON TPU). A custom_vjp whose backward
    # re-executes the forward needs no JVP anywhere: fwd saves ONLY the
    # inputs, bwd re-runs the region (that re-trace IS the remat) and
    # pulls the cotangent through it.
    def inner(arg_vals, state_vals):
        saved = [(t._value, t._version, t._node, t.stop_gradient) for t in state]
        _rc_tls.depth = getattr(_rc_tls, "depth", 0) + 1
        try:
            for t, v in zip(state, state_vals):
                t._value = v
                t._node = None
            it = iter(arg_vals)
            call_args = []
            for i in range(n_args):
                if arg_is_tensor[i]:
                    nt = Tensor(next(it))
                    nt.stop_gradient = False
                    call_args.append(nt)
                else:
                    call_args.append(args[i])
            with auto_cast(**amp):
                out = function(*call_args, **kwargs)
            if isinstance(out, (tuple, list)):
                meta["is_seq"] = True
                outs = tuple(o._value if isinstance(o, Tensor) else o
                             for o in out)
            else:
                meta["is_seq"] = False
                outs = (out._value if isinstance(out, Tensor) else out,)
            meta["n_user"] = len(outs)
            # buffer updates (BN running stats …) must ESCAPE the
            # checkpointed region: the finally below restores every
            # state tensor, so thread the post-run buffer values out as
            # extra outputs and reapply them outside
            new_buf = tuple(t._value for t in buffers)
            return outs + new_buf
        finally:
            _rc_tls.depth -= 1
            for t, (v, ver, node, sg) in zip(state, saved):
                t._value = v
                t._version = ver
                t._node = node
                t.stop_gradient = sg

    @jax.custom_vjp
    def ckpt(arg_vals, state_vals):
        return inner(arg_vals, state_vals)

    def ckpt_fwd(arg_vals, state_vals):
        # residuals = the region's INPUTS only — the jax.checkpoint
        # memory contract.  Under an amp remat="bf16" policy the saved
        # ACTIVATION boundaries narrow to bf16 (the only live copies of
        # the residual stream between forward and backward are then
        # half-size); lifted params/buffers are never narrowed — they
        # are the master weights.
        from paddle_tpu.amp.policy import current_policy
        pol = current_policy()
        saved_args = arg_vals
        if pol is not None and pol.remat == "bf16":
            saved_args = [pol.cast_saved(v) for v in arg_vals]
        # scalar zero protos carry the primal dtypes to the bwd rule
        # (residual leaves must be jax values, not dtype objects)
        protos = [jax.numpy.zeros((), v.dtype) for v in arg_vals]
        return inner(arg_vals, state_vals), \
            (saved_args, state_vals, protos)

    def ckpt_bwd(res, ct):
        saved_args, state_vals, protos = res
        # bf16-saved boundaries are cast back up before the re-run so
        # the rematerialized region (and its cotangent structure)
        # matches the forward's dtypes exactly — the precision loss is
        # confined to the saved boundary value's bf16 round-trip
        arg_vals = [v.astype(p.dtype) if v.dtype != p.dtype else v
                    for v, p in zip(saved_args, protos)]
        # barrier: without it XLA CSEs the re-run against the forward's
        # values and silently un-remats the region
        arg_vals, state_vals = jax.lax.optimization_barrier(
            (arg_vals, state_vals))
        _, pull = jax.vjp(inner, arg_vals, state_vals)
        return pull(ct)

    ckpt.defvjp(ckpt_fwd, ckpt_bwd)

    def fn(*vals):
        avals = list(vals[:len(tensor_args)])
        svals = list(vals[len(tensor_args):])
        return ckpt(avals, svals)

    result = apply(fn, *tensor_args, *state)
    result = result if isinstance(result, tuple) else (result,)
    user = result[:meta["n_user"]]
    for t, new in zip(buffers, result[meta["n_user"]:]):
        t._set_value(new._value)
    if not meta["is_seq"]:
        return user[0]
    return tuple(user)


class _SegmentChain:
    """Callable chunk of a Sequential whose parameters recompute() can
    lift: registers every member Layer so _owner_layer finds them all."""

    def __init__(self, fns):
        from paddle_tpu.nn.layer.layers import Layer
        self._holder = Layer()
        self._fns = list(fns)
        for i, f in enumerate(self._fns):
            # a member may be a Layer OR a bound method of one — lift
            # the owner either way, else its params silently lose grads
            owner = _owner_layer(f)
            if owner is not None:
                self._holder.add_sublayer(str(i), owner)
        # recompute() lifts params via function.__self__
        self.__self__ = self._holder

    def __call__(self, *args, **kwargs):
        # first member takes the user's full signature; the rest chain
        # on its (single) output like the reference's do_run
        x = self._fns[0](*args, **kwargs)
        for f in self._fns[1:]:
            x = f(x)
        return x


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Chunk a Sequential into ctx['segments'] recompute regions
    (reference fleet/recompute/recompute.py:512). Each chunk is wrapped
    so ALL member layers' parameters lift into the checkpointed region
    — a bare closure would silently drop their gradients."""
    ctx = dict(ctx or {})
    segments = max(int(ctx.get("segments", 1)), 1)
    from paddle_tpu.nn.layer.container import Sequential
    if isinstance(functions, Sequential):
        functions = [m for _, m in functions.named_children()]
    functions = list(functions)
    seg = max(len(functions) // segments, 1)
    out = args
    pos = 0
    while pos < len(functions):
        end = min(pos + seg, len(functions))
        if len(functions) - end < seg:
            end = len(functions)
        chain = _SegmentChain(functions[pos:end])
        out = recompute(chain, *(out if isinstance(out, tuple)
                                 else (out,)), **kwargs)
        pos = end
    return out


def recompute_hybrid(ctx, function, *args, **kwargs):
    """Hybrid-parallel recompute (reference recompute_hybrid.py:234):
    the ctx's mp_group/offload/partition keys configure hand-partitioned
    activation storage there; under XLA rematerialized values keep their
    producers' shardings, so this reduces to recompute."""
    kwargs.pop("preserve_rng_state", None)
    return recompute(function, *args, **kwargs)
