"""Whole-program roofline profiler with per-layer HLO cost attribution.

BENCH_r05 showed the train step pinned at ~98.5% HBM bandwidth with MFU
0.27 — bytes/step is the lever, but the XLA ``cost_analysis()`` totals
say nothing about WHICH layer the bytes go to.  This module closes that
gap for any whole-traced program (``StaticFunction.traced_program()``,
``LLMEngine.audit_programs()``):

- **scope threading** — ``nn.Layer.__call__`` wraps ``forward`` in a
  ``jax.named_scope`` derived from the layer tree (attribute path under
  the parent, so two Linears never collide), and ``optimizer.step``
  scopes its update math.  JAX carries the name stack through ``jvp``
  and ``transpose``, so the BACKWARD eqns of a layer land in the same
  scope as its forward — no autograd changes needed;
- **deterministic per-op cost model** — every jaxpr eqn gets analytic
  flops (2·M·N·K for ``dot_general``, kernel-volume MACs for conv,
  element counts for pointwise/reduce) and bytes (operands + results, the
  HLO bytes-accessed convention), multiplied through ``scan`` trip
  counts.  Deterministic by construction: the same program always
  yields the same numbers, which is what ``tools/perfgate.py`` gates on;
- **attribution** — eqn costs aggregate per normalized scope path;
  anything outside a scope lands in an explicit ``<unattributed>``
  bucket (the acceptance bar: >= 90% of bytes and flops attributed on
  the gpt hybrid train target);
- **roofline classification** — per-layer arithmetic intensity against
  a target :class:`ChipSpec` (compute- vs memory-bound), whole-program
  predicted step time ``max(flops/peak, bytes/bw)``, reconciled with
  measured span wall-times (:func:`reconcile`) and optional true XLA
  ``cost_analysis()`` totals (:func:`xla_cost_totals`).

Module-level imports stay light (stdlib + jax); rendering lives in
``tools/obs_report.py --roofline`` and the regression gate in
``tools/perfgate.py``.  See docs/observability.md "Roofline profiler".
"""
from __future__ import annotations

import contextlib
import re
import threading
from dataclasses import dataclass, field

import jax

__all__ = [
    "ChipSpec", "CHIP_SPECS", "LayerCost", "RooflineReport",
    "V5E", "attached_chip", "backward_scope", "chip_spec",
    "current_scope", "eqn_cost",
    "kernel_interiors", "layer_scope", "normalize_scope",
    "profile_engine", "profile_static_function", "profile_traced",
    "reconcile", "scope", "scope_tagging", "set_scope_tagging",
    "xla_cost_totals",
]


# ------------------------------------------------------- scope threading
_TAGGING = [True]               # list, not bool: mutation without `global`
_NULL = contextlib.nullcontext()
_tls = threading.local()

# backward-replay marker (see backward_scope): "~bwd~" never appears in
# layer names, "|" stands in for "/" so the recorded path stays ONE
# name-stack component
BWD_MARKER = "~bwd~"


def set_scope_tagging(flag=True):
    """Globally enable/disable layer-scope tagging; returns previous
    value.  Off, ``layer_scope`` is a shared no-op context."""
    prev = _TAGGING[0]
    _TAGGING[0] = bool(flag)
    return prev


def scope_tagging():
    return _TAGGING[0]


def current_scope():
    """The full scope path active on this thread (``'model/fc1'``) —
    what the autograd tape records per Node so backward replay can
    re-enter it (mirror of the jax name stack, kept here because jax
    exposes no public read of its own)."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else ""


class layer_scope:
    """The one scope primitive instrumented code uses: enters a
    ``jax.named_scope`` (so traced eqns carry the name on their name
    stack) AND mirrors the full path on a host-side stack for the tape
    (:func:`current_scope`).  ``nn.Layer.__call__`` wraps ``forward``
    in one per layer; user code can open extra scopes the same way::

        with profile.scope("loss"):
            loss = F.cross_entropy(logits, labels)

    Tagging off (or an empty name) makes both halves no-ops."""

    __slots__ = ("name", "_ns", "_pushed")

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        if not _TAGGING[0] or not self.name:
            self._ns = None
            self._pushed = False
            return self
        st = getattr(_tls, "stack", None)
        if st is None:
            st = _tls.stack = []
        parent = st[-1] if st else ""
        st.append(f"{parent}/{self.name}" if parent else self.name)
        self._pushed = True
        self._ns = jax.named_scope(self.name)
        self._ns.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ns is not None:
            self._ns.__exit__(exc_type, exc, tb)
        if self._pushed:
            _tls.stack.pop()
        return False


scope = layer_scope


def backward_scope(recorded):
    """Context for replaying a tape node's pullback.

    Plain ``jax.vjp`` transposes keep the forward eqns' name stacks
    (``transpose(jvp(model))/fc1``), but custom-vjp-style backwards are
    traced FRESH at pull time with an empty stack — those eqns would
    land in ``<unattributed>``.  Re-entering the node's recorded
    forward scope under a marker component fixes exactly that case:
    :func:`normalize_scope` prefers any real components AFTER the
    marker (a survived stack wins, no double-counted path) and decodes
    the marker's embedded path only when nothing survived."""
    if not _TAGGING[0] or not recorded:
        return _NULL
    return jax.named_scope(BWD_MARKER + recorded.replace("/", "|"))


# jvp(model) / transpose(jvp(model)) / vmap(f) ... — transform wrappers
# jax stacks around scope components; stripped so forward and backward
# eqns of the same layer share one attribution key
_WRAP_RE = re.compile(r"[A-Za-z_][\w.]*\(")


def normalize_scope(stack_str):
    """``'transpose(jvp(model))/fc1'`` -> ``'model/fc1'``: drop the
    transform wrappers, keep the user scope path.  A backward-replay
    marker (see :func:`backward_scope`) yields to any real components
    after it, else decodes to its recorded forward path."""
    if not stack_str:
        return ""
    s = _WRAP_RE.sub("", stack_str).replace(")", "")
    parts = [p for p in s.split("/") if p]
    for i in range(len(parts) - 1, -1, -1):
        if parts[i].startswith(BWD_MARKER):
            rest = parts[i + 1:]
            if rest:
                parts = rest
            else:
                parts = parts[i][len(BWD_MARKER):].split("|")
            break
    return "/".join(parts)


# ------------------------------------------------------------ chip specs
@dataclass(frozen=True)
class ChipSpec:
    """Roofline parameters of one accelerator generation (the same
    numbers bench.py uses for MFU / HBM-utilization)."""

    name: str
    peak_tflops: float          # bf16 peak, TFLOP/s per chip
    hbm_gbs: float              # HBM bandwidth, GB/s per chip
    # conservative per-core VMEM budget (the figure kernlint's KL102
    # prices Pallas block buffers against); ~16 MiB across generations
    vmem_mb: float = 16.0

    @property
    def peak_flops(self):
        return self.peak_tflops * 1e12

    @property
    def bw_bytes(self):
        return self.hbm_gbs * 1e9

    @property
    def ridge(self):
        """Arithmetic intensity (flop/byte) where compute == memory."""
        return self.peak_flops / self.bw_bytes

    @property
    def vmem_bytes(self):
        return int(self.vmem_mb * (1 << 20))

    def to_dict(self):
        return {"name": self.name, "peak_tflops": self.peak_tflops,
                "hbm_gbs": self.hbm_gbs,
                "ridge_flop_per_byte": round(self.ridge, 1),
                "vmem_mb": self.vmem_mb}


# The chip the deterministic cost model prices programs for.  It is a
# property of the model, not of the host that traces it: a CPU host
# profiles *for* the v5e, and perfgate's baselines would move if this
# followed the attached device.
V5E = ChipSpec("TPU v5e", 197.0, 819.0)
_V6E = ChipSpec("TPU v6e", 918.0, 1640.0)

# Published per-chip peaks (Google Cloud TPU documentation, one page per
# generation), keyed by the ``device_kind`` jax reports — a v5e says
# "TPU v5 lite", a v6e "TPU v6 lite".
CHIP_SPECS = {
    "TPU v4": ChipSpec("TPU v4", 275.0, 1228.0),
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
    "TPU v5p": ChipSpec("TPU v5p", 459.0, 2765.0),
    "TPU v6 lite": _V6E,
    "TPU v6e": _V6E,
}


def chip_spec(device_kind):
    """Peaks of the chip a measurement ran on.  A device that is not in
    the table is an error, never a default."""
    try:
        return CHIP_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(CHIP_SPECS)}") from None


def attached_chip():
    """(device, ChipSpec) of the first attached device — what a device
    measurement divides by.  Raises on a CPU host like on any device
    whose kind has no published peaks."""
    dev = jax.devices()[0]
    return dev, chip_spec(dev.device_kind)


# ----------------------------------------------------- per-eqn cost model
def _prod(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _var_elems(v):
    aval = getattr(v, "aval", None)
    return _prod(tuple(getattr(aval, "shape", ()) or ()))


def _var_bytes(v):
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return _var_elems(v) * int(getattr(dt, "itemsize", 4) or 4)


# pointwise prims: one flop per output element
_ELEMENTWISE = frozenset((
    "abs", "add", "add_any", "and", "atan2", "ceil", "clamp", "cos",
    "cosh", "div", "eq", "erf", "erf_inv", "erfc", "exp", "expm1",
    "floor", "ge", "gt", "integer_pow", "is_finite", "le", "log",
    "log1p", "logistic", "lt", "max", "min", "mul", "ne", "neg",
    "nextafter", "not", "or", "pow", "rem", "round", "rsqrt", "select_n",
    "sign", "sin", "sinh", "sqrt", "square", "sub", "tan", "tanh",
    "xor",
))
# reductions / scans: one flop per INPUT element
_REDUCTION = frozenset((
    "argmax", "argmin", "cumlogsumexp", "cummax", "cummin", "cumprod",
    "cumsum", "reduce_and", "reduce_max", "reduce_min", "reduce_or",
    "reduce_prod", "reduce_sum", "reduce_window_max", "reduce_window_min",
    "reduce_window_sum",
))


def eqn_cost(eqn):
    """Deterministic (flops, bytes) for one jaxpr eqn.

    Bytes follow the HLO bytes-accessed convention: every non-literal
    operand is read, every result written.  Flops are analytic: MXU ops
    from their contraction volume, pointwise/reduce ops from element
    counts, everything else (layout/copy/gather ops) zero flops but
    full bytes — exactly the traffic a memory-bound step pays."""
    in_bytes = sum(_var_bytes(v) for v in eqn.invars
                   if not hasattr(v, "val"))
    out_bytes = sum(_var_bytes(v) for v in eqn.outvars)
    nbytes = in_bytes + out_bytes
    prim = eqn.primitive.name
    out_elems = sum(_var_elems(v) for v in eqn.outvars)

    if prim == "dot_general":
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        lhs_shape = tuple(getattr(getattr(eqn.invars[0], "aval", None),
                                  "shape", ()) or ())
        k = _prod(lhs_shape[d] for d in lhs_c) if lhs_shape else 1
        return 2 * out_elems * k, nbytes
    if prim == "conv_general_dilated":
        rhs_shape = tuple(getattr(getattr(eqn.invars[1], "aval", None),
                                  "shape", ()) or ())
        kernel_elems = _prod(rhs_shape) if rhs_shape else 1
        dn = eqn.params.get("dimension_numbers")
        out_c_dim = getattr(dn, "rhs_spec", (0,))[0]
        out_c = rhs_shape[out_c_dim] if rhs_shape else 1
        return 2 * out_elems * max(1, kernel_elems // max(1, out_c)), nbytes
    if prim in _ELEMENTWISE:
        return out_elems, nbytes
    if prim in _REDUCTION:
        return sum(_var_elems(v) for v in eqn.invars
                   if not hasattr(v, "val")), nbytes
    return 0, nbytes


def _iter_sub_jaxprs(params):
    for v in params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for x in items:
            if hasattr(x, "eqns"):
                yield x                      # open Jaxpr
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr                # ClosedJaxpr


def _join(prefix, own):
    if prefix and own:
        return f"{prefix}/{own}"
    return prefix or own


def _pallas_grid_size(eqn):
    """Total grid-step count of a pallas_call (1 when unreadable)."""
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", ()) or ()
    n = 1
    for d in grid:
        try:
            n *= max(1, int(d))
        except (TypeError, ValueError):
            pass
    return n


def _walk(jaxpr, prefix, mult, sink):
    """Accumulate ``sink[scope] = [flops, bytes, n_eqns]`` over `jaxpr`.

    Container eqns (scan/while/cond/pjit/custom_*) contribute their
    BODY's cost — the container's own operands alias the body inputs,
    so counting both would double the traffic.  ``scan`` bodies
    multiply by the trip count; ``while`` bodies count once (trip count
    is data-dependent — documented under-estimate); ``cond`` takes its
    most expensive branch (only one runs).

    ``pallas_call`` is the one container costed at its CALL BOUNDARY:
    a fused kernel's HBM traffic is its operands + results — the body
    describes per-block VMEM/register ops that never round-trip HBM,
    and walking it for bytes would both double-count (block reads) and
    erase exactly the fusion the kernel exists for.  The body is still
    walked for FLOPS (x grid steps), and the whole cost lands in the
    CALLER's scope path (the eqn's own name stack), so a fused LN never
    falls into ``<unattributed>``."""
    for eqn in jaxpr.eqns:
        own = normalize_scope(str(eqn.source_info.name_stack))
        path = _join(prefix, own)
        prim = eqn.primitive.name
        subs = list(_iter_sub_jaxprs(eqn.params))
        if prim == "pallas_call":
            flops = 0
            grid = _pallas_grid_size(eqn)
            for sub in subs:
                trial = {}
                _walk(sub, path, mult * grid, trial)
                flops += sum(v[0] for v in trial.values())
            _zero, nbytes = eqn_cost(eqn)
            agg = sink.setdefault(path, [0, 0, 0])
            agg[0] += flops
            agg[1] += nbytes * mult
            agg[2] += 1
            continue
        if subs:
            m = mult
            if prim == "scan":
                m = mult * max(1, int(eqn.params.get("length", 1) or 1))
            if prim == "cond":
                best, best_bytes = None, -1
                for sub in subs:
                    trial = {}
                    _walk(sub, path, m, trial)
                    b = sum(v[1] for v in trial.values())
                    if b > best_bytes:
                        best, best_bytes = trial, b
                for k, (f, b, n) in (best or {}).items():
                    agg = sink.setdefault(k, [0, 0, 0])
                    agg[0] += f
                    agg[1] += b
                    agg[2] += n
            else:
                for sub in subs:
                    _walk(sub, path, m, sink)
            continue
        flops, nbytes = eqn_cost(eqn)
        agg = sink.setdefault(path, [0, 0, 0])
        agg[0] += flops * mult
        agg[1] += nbytes * mult
        agg[2] += 1


def _iter_eqns_rec(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _iter_sub_jaxprs(eqn.params):
            yield from _iter_eqns_rec(sub)


def kernel_interiors(closed_jaxpr, chip=V5E):
    """Opt-in per-kernel INTERIOR roofline rows — the dual of the
    call-boundary cost ``_walk`` books for ``pallas_call``.

    The boundary row says what a fused kernel costs the *program*
    (operands + results over HBM); the interior row says what each grid
    step moves through *VMEM* (one copy of every in/out block) and the
    arithmetic intensity the kernel body achieves against that traffic.
    ``reuse_factor`` = interior bytes / boundary bytes — how many times
    the kernel re-touches each HBM byte inside VMEM, i.e. exactly the
    reuse that justifies fusing (a factor near 1.0 means the kernel
    gains nothing over the unfused composition)."""
    from paddle_tpu.analysis.vmem_model import estimate_vmem
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    rows = []
    for eqn in _iter_eqns_rec(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        grid = _pallas_grid_size(eqn)
        flops = 0
        for sub in _iter_sub_jaxprs(eqn.params):
            trial = {}
            _walk(sub, "", grid, trial)
            flops += sum(v[0] for v in trial.values())
        est = estimate_vmem(eqn)
        per_step = sum(one for _o, one, _b in est.blocks)
        interior_bytes = per_step * max(1, grid)
        _zero, boundary_bytes = eqn_cost(eqn)
        name = (str(eqn.params.get("name_and_src_info", "") or "")
                .split(" at ")[0]) or "<kernel>"
        intensity = flops / interior_bytes if interior_bytes else 0.0
        rows.append({
            "kernel": name,
            "grid_steps": int(max(1, grid)),
            "vmem_step_bytes": int(per_step),
            "interior_bytes": int(interior_bytes),
            "boundary_bytes": int(boundary_bytes),
            "flops": int(flops),
            "interior_intensity": round(intensity, 3),
            "bound": "compute" if intensity >= chip.ridge else "memory",
            "reuse_factor": round(interior_bytes / boundary_bytes, 2)
            if boundary_bytes else 0.0,
            "vmem_total_bytes": int(est.total_bytes),
            "double_buffered": bool(est.double_buffered),
        })
    return rows


# --------------------------------------------------------------- reports
UNATTRIBUTED = "<unattributed>"


@dataclass
class LayerCost:
    """Aggregated cost of one scope path (one layer, usually)."""

    name: str
    flops: int = 0
    bytes: int = 0
    n_eqns: int = 0

    @property
    def intensity(self):
        """Arithmetic intensity, flop/byte."""
        return self.flops / self.bytes if self.bytes else 0.0

    def bound(self, chip):
        return "compute" if self.intensity >= chip.ridge else "memory"

    def to_dict(self, chip=None):
        d = {"name": self.name, "flops": self.flops, "bytes": self.bytes,
             "n_eqns": self.n_eqns, "intensity": round(self.intensity, 3)}
        if chip is not None:
            d["bound"] = self.bound(chip)
        return d


@dataclass
class RooflineReport:
    """Per-layer bytes/flops attribution + roofline classification of
    one whole traced program."""

    where: str
    chip: ChipSpec
    layers: list = field(default_factory=list)   # LayerCost, bytes desc
    unattributed: LayerCost = None
    xla: dict = None            # {"flops", "bytes_accessed"} | None
    measured_ms: float = None
    measured_source: str = None
    # opt-in per-kernel interior rows (kernel_interiors() dicts)
    interiors: list = None

    def __post_init__(self):
        if self.unattributed is None:
            self.unattributed = LayerCost(UNATTRIBUTED)

    # ---- totals / fractions
    @property
    def attributed_flops(self):
        return sum(l.flops for l in self.layers)

    @property
    def attributed_bytes(self):
        return sum(l.bytes for l in self.layers)

    @property
    def total_flops(self):
        return self.attributed_flops + self.unattributed.flops

    @property
    def total_bytes(self):
        return self.attributed_bytes + self.unattributed.bytes

    @property
    def frac_attributed_flops(self):
        return self.attributed_flops / self.total_flops \
            if self.total_flops else 1.0

    @property
    def frac_attributed_bytes(self):
        return self.attributed_bytes / self.total_bytes \
            if self.total_bytes else 1.0

    @property
    def bound_fraction(self):
        """Fraction of attributed bytes living in memory-bound layers —
        1.0 means every byte of the program is on the HBM roofline."""
        if not self.attributed_bytes:
            return 0.0
        mem = sum(l.bytes for l in self.layers
                  if l.bound(self.chip) == "memory")
        return mem / self.attributed_bytes

    @property
    def top_layer(self):
        return self.layers[0].name if self.layers else ""

    @property
    def predicted_ms(self):
        """Roofline step-time floor on `chip`:
        ``max(flops/peak, bytes/bw)``."""
        return max(self.total_flops / self.chip.peak_flops,
                   self.total_bytes / self.chip.bw_bytes) * 1e3

    def rows(self):
        """Every bucket including ``<unattributed>``, bytes-descending
        (the rendering order obs_report uses)."""
        out = list(self.layers)
        if self.unattributed.n_eqns:
            out.append(self.unattributed)
        return sorted(out, key=lambda l: (-l.bytes, l.name))

    def to_dict(self):
        d = {
            "where": self.where,
            "chip": self.chip.to_dict(),
            "total_flops": self.total_flops,
            "total_bytes": self.total_bytes,
            "attributed_flops_pct": round(
                100.0 * self.frac_attributed_flops, 2),
            "attributed_bytes_pct": round(
                100.0 * self.frac_attributed_bytes, 2),
            "bound_fraction": round(self.bound_fraction, 4),
            "predicted_ms": round(self.predicted_ms, 6),
            "top_layer": self.top_layer,
            "layers": [l.to_dict(self.chip) for l in self.rows()],
        }
        if self.xla is not None:
            d["xla"] = self.xla
        if self.measured_ms is not None:
            d["measured_ms"] = round(self.measured_ms, 3)
            d["measured_source"] = self.measured_source
        if self.interiors:
            d["interiors"] = self.interiors
        return d

    @classmethod
    def from_dict(cls, d):
        """Rebuild from :meth:`to_dict` output (the JSONL dump path
        ``tools/obs_report.py --roofline`` renders)."""
        chip = ChipSpec(d["chip"]["name"], d["chip"]["peak_tflops"],
                        d["chip"]["hbm_gbs"])
        layers, unattributed = [], None
        for row in d.get("layers", ()):
            lc = LayerCost(row["name"], int(row["flops"]),
                           int(row["bytes"]), int(row.get("n_eqns", 0)))
            if lc.name == UNATTRIBUTED:
                unattributed = lc
            else:
                layers.append(lc)
        rep = cls(where=d.get("where", "<dump>"), chip=chip,
                  layers=sorted(layers, key=lambda l: (-l.bytes, l.name)),
                  unattributed=unattributed,
                  xla=d.get("xla"),
                  measured_ms=d.get("measured_ms"),
                  measured_source=d.get("measured_source"),
                  interiors=d.get("interiors"))
        return rep


# ---------------------------------------------------------- entry points
def profile_traced(closed_jaxpr, where="<traced program>", chip=V5E,
                   include_xla=False, include_interiors=False):
    """Roofline-profile one traced program: per-eqn cost model,
    attributed to the normalized ``jax.named_scope`` paths the layer
    tree threaded through tracing.  ``include_interiors=True`` adds the
    per-kernel INTERIOR rows (:func:`kernel_interiors`) next to the
    call-boundary attribution."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    sink = {}
    _walk(jaxpr, "", 1, sink)
    layers, unattributed = [], LayerCost(UNATTRIBUTED)
    for path, (flops, nbytes, n) in sink.items():
        if path:
            layers.append(LayerCost(path, flops, nbytes, n))
        else:
            unattributed = LayerCost(UNATTRIBUTED, flops, nbytes, n)
    layers.sort(key=lambda l: (-l.bytes, l.name))
    rep = RooflineReport(where=where, chip=chip, layers=layers,
                         unattributed=unattributed)
    if include_xla:
        rep.xla = xla_cost_totals(closed_jaxpr)
    if include_interiors:
        rep.interiors = kernel_interiors(closed_jaxpr, chip=chip)
    return rep


def profile_static_function(fn, *args, where=None, chip=V5E,
                            include_xla=False, **kwargs):
    """Profile one ``@to_static`` function's signature: traces (never
    compiles or runs) via :meth:`StaticFunction.traced_program` and
    attributes the program's cost back to the model's layers."""
    jaxpr, _infos = fn.traced_program(*args, **kwargs)
    return profile_traced(
        jaxpr, where=where or f"<{getattr(fn, '__name__', 'static_fn')}>",
        chip=chip, include_xla=include_xla)


def profile_engine(engine, chip=V5E, include_xla=False):
    """{program_name: RooflineReport} over every program the serving
    engine will ever compile (``LLMEngine.audit_programs()``)."""
    return {
        name: profile_traced(jaxpr, where=f"<serving {name}>", chip=chip,
                             include_xla=include_xla)
        for name, jaxpr in engine.audit_programs().items()
    }


def xla_cost_totals(closed_jaxpr):
    """True XLA ``cost_analysis()`` totals for a traced program — the
    numbers the deterministic cost model is reconciled against.  Pays a
    real backend compile; returns None when the backend can't provide
    the analysis (the deterministic model stands alone then)."""
    try:
        fn = jax.core.jaxpr_as_fun(closed_jaxpr)
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                 for v in jaxpr.invars]
        ca = jax.jit(fn).lower(*avals).compile().cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return {"flops": float(ca.get("flops", 0.0) or 0.0),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)
                                        or 0.0)}
    except Exception:  # noqa: BLE001 — totals are best-effort garnish
        return None


def reconcile(report, span_name, recorder=None):
    """Fill ``measured_ms`` from the span layer's per-name aggregates
    (e.g. ``jit.train_step``), so predicted-vs-measured sits in one
    report.  On a CPU host the ratio is diagnostic only — the
    prediction is for `report.chip`, the measurement for the host."""
    from paddle_tpu.observability import spans as _spans
    rec = recorder or _spans.recorder()
    agg = rec.aggregates().get(span_name)
    if agg and agg.get("count"):
        report.measured_ms = agg["total_ms"] / agg["count"]
        report.measured_source = f"span {span_name} (n={agg['count']})"
    return report
