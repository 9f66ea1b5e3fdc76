"""kernlint KL1xx: static audit of Pallas kernel INTERIORS.

Every other analyzer in the lint_all stack stops at the ``pallas_call``
boundary — dtype_flow documents the body as deliberately opaque, the
roofline profiler costs call-boundary bytes only.  This module walks
*through* the boundary: a traced jaxpr's ``pallas_call`` eqn carries the
kernel jaxpr, the grid, and every in/out BlockMapping in ``eqn.params``,
which is enough to statically decide tile alignment (KL101), the VMEM
bill (KL102, via :mod:`vmem_model`), in-kernel accumulation dtypes
(KL103), ``input_output_aliases`` hazards (KL104), grid x block coverage
(KL105) and unguarded ragged tails (KL106) — all before XLA or Mosaic
ever see the kernel.

Two passes, same codes:

- :func:`check_kernels` — the jaxpr pass.  Findings resolve to real
  file:line through the eqn's jax source_info (so per-line
  ``# kernlint: disable=KLxxx`` comments apply), and fall back to a
  stable signature string when no user frame survives.
- :func:`check_kernel_files` — a pure-AST pass over ``ops/pallas/*.py``
  that needs no trace: conservative static twins of KL103 (dot-like
  call in a kernel body without ``preferred_element_type``) and KL101
  (literal block-shape tuples that no dtype's tile can satisfy).

Both passes honour the family-scoped suppression comments parsed by
:mod:`visitor` — a ``# kernlint: disable=ALL`` waives KL findings only,
and no foreign spelling can waive a KL code.
"""
from __future__ import annotations

import ast
import itertools
import os
from dataclasses import dataclass

from paddle_tpu.analysis import vmem_model
from paddle_tpu.analysis.dtype_flow import NARROW_FLOATS
from paddle_tpu.analysis.jaxpr_rules import _iter_eqns
from paddle_tpu.analysis.rules import KERNLINT_CODES, message_for
from paddle_tpu.analysis.shard_rules import (_REPO_ROOT, _mk_finding,
                                             apply_suppressions)
from paddle_tpu.analysis.visitor import (Finding, _dotted,
                                         parse_suppressions, rel_path)

__all__ = ["KernelConfig", "check_kernels", "check_kernel_files",
           "iter_pallas_eqns", "KERNLINT_CODES"]

_MIB = 1 << 20


@dataclass(frozen=True)
class KernelConfig:
    """Knobs for the KL rule family (one set shared by the CLI, the
    to_static(check=True) hook, and the tests)."""

    # KL102: per-call VMEM budget in MiB (None -> the default chip's
    # vmem_mb from observability.profile.ChipSpec)
    vmem_budget_mb: float = None
    # KL102: fraction of the budget the STATIC estimate may fill before
    # flagging — Mosaic's own spill overhead comes on top, so 1.0 means
    # "flag only what is already guaranteed over"
    vmem_fill_limit: float = 1.0
    # KL105: coverage enumeration stops beyond this many grid points
    grid_enum_cap: int = 4096


# --------------------------------------------------------------- plumbing
def iter_pallas_eqns(closed_jaxpr):
    """All ``pallas_call`` eqns of a (Closed)Jaxpr, however nested."""
    for eqn in _iter_eqns(closed_jaxpr):
        if eqn.primitive.name == "pallas_call":
            yield eqn


class _Call:
    """One decoded ``pallas_call`` eqn.  Every field is best-effort —
    missing params leave it empty and the rules that need it skip."""

    def __init__(self, eqn):
        self.eqn = eqn
        p = eqn.params
        self.name = (str(p.get("name_and_src_info", "") or "")
                     .split(" at ")[0]) or "<kernel>"
        gm = p.get("grid_mapping")
        self.grid = tuple(getattr(gm, "grid", ()) or ())
        bms = list(getattr(gm, "block_mappings", ()) or ())
        self.n_in = int(getattr(gm, "num_inputs", 0) or 0)
        self.n_out = int(getattr(gm, "num_outputs", 0) or 0)
        self.n_idx = int(getattr(gm, "num_index_operands", 0) or 0)
        self.in_bms = bms[:self.n_in]
        self.out_bms = bms[self.n_in:self.n_in + self.n_out]
        kj = p.get("jaxpr")
        self.kjaxpr = getattr(kj, "jaxpr", kj)
        self.aliases = tuple(p.get("input_output_aliases", ()) or ())
        self._body = None

    def all_bms(self):
        for bm in self.in_bms:
            yield bm, False
        for bm in self.out_bms:
            yield bm, True

    def body_eqns(self):
        if self._body is None:
            self._body = ([] if self.kjaxpr is None
                          else list(_iter_eqns(self.kjaxpr)))
        return self._body


def _origin(bm):
    return str(getattr(bm, "origin", "") or "<operand>")


def _bm_facts(bm):
    """(array_shape, block_dims, dtype) for one BlockMapping, or None
    when ranks disagree / params are unreadable."""
    sd = getattr(bm, "array_shape_dtype", None)
    dtype = getattr(sd, "dtype", None)
    ashape = tuple(int(s) for s in (getattr(sd, "shape", ()) or ()))
    dims = vmem_model._int_dims(getattr(bm, "block_shape", ()))
    if dtype is None or not dims or len(dims) != len(ashape):
        return None
    return ashape, dims, dtype


def _is_narrow(dtype):
    return getattr(dtype, "name", str(dtype)) in NARROW_FLOATS


def _out_dtype(eqn):
    try:
        return eqn.outvars[0].aval.dtype
    except Exception:
        return None


# ----------------------------------------------------------------- KL101
def _kl101(call, where):
    out = []
    for bm, _is_out in call.all_bms():
        facts = _bm_facts(bm)
        if facts is None:
            continue
        ashape, dims, dtype = facts
        sub, lane = vmem_model.native_tile(dtype)
        reqs = [(len(dims) - 1, lane)]
        if len(dims) >= 2:
            reqs.append((len(dims) - 2, sub))
        bad = []
        for pos, req in reqs:
            d, a = dims[pos], ashape[pos]
            # dim 1 (one row/lane at a time) and the full array extent
            # are both idiomatic and as tight as the array permits
            if d in (1, a) or d % req == 0:
                continue
            bad.append(f"dim {pos} = {d} needs a multiple of {req}")
        if bad:
            dname = getattr(dtype, "name", str(dtype))
            out.append(_mk_finding(
                "KL101",
                f"{tuple(dims)} for {dname} operand `{_origin(bm)}` of "
                f"kernel `{call.name}` ({'; '.join(bad)}; native tile "
                f"{vmem_model.native_tile(dtype)})",
                where, eqn=call.eqn,
                sig=f"{call.name} KL101 {_origin(bm)} {tuple(dims)}"))
    return out


# ----------------------------------------------------------------- KL102
def _kl102(call, config, where):
    est = vmem_model.estimate_vmem(call.eqn)
    if est.total_bytes <= 0:
        return []
    budget_mb, chip = config.vmem_budget_mb, ""
    if budget_mb is None:
        # the static audit prices kernels for the v5e, by name
        from paddle_tpu.observability import profile
        budget_mb = profile.V5E.vmem_mb
        chip = f" ({profile.V5E.name})"
    limit = float(budget_mb) * float(config.vmem_fill_limit) * _MIB
    if est.total_bytes <= limit:
        return []
    return [_mk_finding(
        "KL102",
        f"{est.describe()} for kernel `{call.name}` exceeds the "
        f"{float(budget_mb):.0f} MiB/core VMEM budget{chip}",
        where, eqn=call.eqn, sig=f"{call.name} KL102")]


# ----------------------------------------------------------------- KL103
_REDUCE_PRIMS = ("reduce_sum", "cumsum", "cumlogsumexp")
_ADD_PRIMS = ("add", "add_any", "sub")


def _kl103(call, where):
    out = []
    eqns = call.body_eqns()
    producer = {}   # id(outvar) -> eqn
    get_src = {}    # id(outvar of a `get`) -> the ref var it read
    for beqn in eqns:
        for ov in beqn.outvars:
            producer[id(ov)] = beqn
        if beqn.primitive.name == "get" and beqn.invars:
            for ov in beqn.outvars:
                get_src[id(ov)] = beqn.invars[0]
    for beqn in eqns:
        prim = beqn.primitive.name
        odt = _out_dtype(beqn)
        if prim == "dot_general" and _is_narrow(odt):
            out.append(_mk_finding(
                "KL103",
                f"dot_general producing {odt.name} in `{call.name}` "
                f"(pass preferred_element_type=jnp.float32)",
                where, eqn=beqn,
                sig=f"{call.name} KL103 dot {odt.name}"))
        elif prim in _REDUCE_PRIMS and _is_narrow(odt):
            out.append(_mk_finding(
                "KL103",
                f"{prim} reduction carried in {odt.name} in "
                f"`{call.name}` (accumulate in float32 and cast on "
                f"the final store)",
                where, eqn=beqn,
                sig=f"{call.name} KL103 {prim} {odt.name}"))
        elif prim in ("swap", "addupdate") and len(beqn.invars) >= 2:
            val = beqn.invars[1]
            vdt = getattr(getattr(val, "aval", None), "dtype", None)
            if not _is_narrow(vdt):
                continue
            ref = beqn.invars[0]
            if prim == "addupdate":
                carried = True      # ref += narrow, by definition
            else:
                # read-modify-write of the SAME ref: the stored value
                # comes from an add/sub whose operand was `get(ref)`
                p = producer.get(id(val))
                carried = (p is not None
                           and p.primitive.name in _ADD_PRIMS
                           and any(get_src.get(id(iv)) is ref
                                   for iv in p.invars))
            if carried:
                out.append(_mk_finding(
                    "KL103",
                    f"accumulator ref `+=` in {vdt.name} in "
                    f"`{call.name}` (carry the running value in a "
                    f"float32 scratch ref)",
                    where, eqn=beqn,
                    sig=f"{call.name} KL103 carry {vdt.name}"))
    return out


# ----------------------------------------------------------------- KL104
def _kl104(call, where):
    out = []
    if not call.aliases:
        return out
    invars = list(getattr(call.kjaxpr, "invars", ()) or ())
    out_avals = tuple(call.eqn.params.get("out_avals", ()) or ())
    for pair in call.aliases:
        try:
            i_in, j_out = int(pair[0]), int(pair[1])
        except Exception:
            continue
        in_aval = None
        if i_in < len(call.eqn.invars):
            in_aval = getattr(call.eqn.invars[i_in], "aval", None)
        o_aval = out_avals[j_out] if j_out < len(out_avals) else None
        if in_aval is not None and o_aval is not None and (
                tuple(in_aval.shape) != tuple(o_aval.shape)
                or in_aval.dtype != o_aval.dtype):
            out.append(_mk_finding(
                "KL104",
                f"({i_in} -> {j_out}) of `{call.name}` alias "
                f"{in_aval.dtype.name}{list(in_aval.shape)} onto "
                f"{o_aval.dtype.name}{list(o_aval.shape)} — the "
                f"donated buffer cannot be reused in place",
                where, eqn=call.eqn,
                sig=f"{call.name} KL104 shape {i_in}->{j_out}"))
            continue
        # read-after-store: kernel invars are [scalar-prefetch refs,
        # in refs, out refs, scratch]; eqn invar i_in maps to kernel
        # invar i_in (prefetch operands lead both lists in order)
        in_ref = invars[i_in] if i_in < len(invars) else None
        oref_idx = call.n_idx + call.n_in + j_out
        out_ref = invars[oref_idx] if oref_idx < len(invars) else None
        if in_ref is None or out_ref is None:
            continue
        stored = False
        for beqn in call.body_eqns():
            prim = beqn.primitive.name
            if prim in ("swap", "addupdate") and beqn.invars \
                    and beqn.invars[0] is out_ref:
                stored = True
            elif stored and prim == "get" and beqn.invars \
                    and beqn.invars[0] is in_ref:
                out.append(_mk_finding(
                    "KL104",
                    f"({i_in} -> {j_out}) of `{call.name}` — aliased "
                    f"input read AFTER the aliased output was stored; "
                    f"the store already clobbered the shared buffer",
                    where, eqn=beqn,
                    sig=f"{call.name} KL104 raw {i_in}->{j_out}"))
                break
    return out


# ----------------------------------------------------------------- KL105
# index-map jaxprs are tiny affine programs; evaluating them in pure
# python (no jax dispatch) keeps full-grid enumeration cheap.  Any
# primitive outside this table -> the map is skipped, never guessed.
_PY_PRIMS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "max": lambda a, b: max(a, b),
    "min": lambda a, b: min(a, b),
    "rem": lambda a, b: a % b if b else 0,
    "div": lambda a, b: int(a / b) if b else 0,   # lax.div truncates
    "neg": lambda a: -a,
    "clamp": lambda lo, x, hi: min(max(x, lo), hi),
    "convert_element_type": lambda a: a,
    "squeeze": lambda a: a,
    "broadcast_in_dim": lambda a: a,
    # the comparison/select set jnp's floor_divide expansion uses
    "sign": lambda a: (a > 0) - (a < 0),
    "eq": lambda a, b: int(a == b),
    "ne": lambda a, b: int(a != b),
    "lt": lambda a, b: int(a < b),
    "le": lambda a, b: int(a <= b),
    "gt": lambda a, b: int(a > b),
    "ge": lambda a, b: int(a >= b),
    "and": lambda a, b: int(bool(a) and bool(b)),
    "or": lambda a, b: int(bool(a) or bool(b)),
    "not": lambda a: int(not a),
    "select_n": lambda which, *cases: cases[int(which)],
}


class _Unsupported(Exception):
    pass


def _eval_int_jaxpr(jaxpr, consts, args):
    """Pure-python evaluation of a small integer jaxpr (no jax
    dispatch); ``pjit``/call wrappers are inlined recursively.  Raises
    _Unsupported on any primitive outside the table."""
    env = {}

    def read(v):
        if hasattr(v, "val"):          # Literal
            return int(v.val)
        return env[id(v)]

    for v, c in zip(jaxpr.constvars, consts):
        env[id(v)] = int(c)
    for v, a in zip(jaxpr.invars, args):
        env[id(v)] = int(a)
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        try:
            if prim in ("pjit", "closed_call", "core_call", "custom_jvp_call",
                        "custom_vjp_call"):
                sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                subj = getattr(sub, "jaxpr", sub)
                if subj is None:
                    raise _Unsupported
                vals = _eval_int_jaxpr(subj,
                                       getattr(sub, "consts", ()) or (),
                                       [read(v) for v in eqn.invars])
                for ov, val in zip(eqn.outvars, vals):
                    env[id(ov)] = val
                continue
            fn = _PY_PRIMS.get(prim)
            if fn is None or len(eqn.outvars) != 1:
                raise _Unsupported
            env[id(eqn.outvars[0])] = int(fn(*[read(v)
                                               for v in eqn.invars]))
        except _Unsupported:
            raise
        except Exception:
            raise _Unsupported
    return tuple(int(read(v)) for v in jaxpr.outvars)


def _eval_index_map(imj, point):
    """Evaluate one index-map ClosedJaxpr at a grid point, pure python.
    Raises _Unsupported for data-dependent / non-affine maps."""
    jaxpr = getattr(imj, "jaxpr", None)
    if jaxpr is None or len(jaxpr.invars) != len(point):
        raise _Unsupported
    return _eval_int_jaxpr(jaxpr, list(getattr(imj, "consts", ()) or ()),
                           point)


def _kl105(call, config, where):
    out = []
    try:
        grid = [int(g) for g in call.grid]
    except Exception:
        return out                     # dynamic grid -> undecidable
    total = 1
    for g in grid:
        total *= max(1, g)
    if not grid or total <= 1 or total > config.grid_enum_cap:
        return out
    points = list(itertools.product(*[range(max(1, g)) for g in grid]))
    for bm, is_out in call.all_bms():
        facts = _bm_facts(bm)
        if facts is None:
            continue
        ashape, dims, _dtype = facts
        nblocks = [max(1, -(-a // d)) for a, d in zip(ashape, dims)]
        if len(nblocks) != len(getattr(bm, "block_shape", ()) or ()):
            continue
        imj = getattr(bm, "index_map_jaxpr", None)
        visits = {}                    # block tuple -> [step ordinals]
        try:
            for step, pt in enumerate(points):
                idx = _eval_index_map(imj, pt)
                if len(idx) != len(nblocks):
                    raise _Unsupported
                # Mosaic clamps block indices to the array bounds
                t = tuple(min(max(i, 0), n - 1)
                          for i, n in zip(idx, nblocks))
                visits.setdefault(t, []).append(step)
        except _Unsupported:
            continue                   # data-dependent map -> skip
        want = 1
        for n in nblocks:
            want *= n
        missing = want - len(visits)
        if missing:
            role = "output" if is_out else "operand"
            verb = "written" if is_out else "read"
            out.append(_mk_finding(
                "KL105",
                f"under-covers {role} `{_origin(bm)}` of "
                f"`{call.name}`: {missing} of {want} blocks never "
                f"{verb} (grid {tuple(grid)}, blocks "
                f"{tuple(nblocks)})",
                where, eqn=call.eqn,
                sig=f"{call.name} KL105 cover {_origin(bm)}"))
        if is_out:
            # revisiting an output block on CONSECUTIVE steps is the
            # accumulation idiom (the block stays resident in VMEM);
            # a NON-consecutive revisit re-fetches and double-writes
            for t, steps in visits.items():
                if steps != list(range(steps[0],
                                       steps[0] + len(steps))):
                    out.append(_mk_finding(
                        "KL105",
                        f"double-writes output block {t} of "
                        f"`{_origin(bm)}` in `{call.name}` on "
                        f"non-consecutive grid steps "
                        f"{steps[:4]}{'...' if len(steps) > 4 else ''}",
                        where, eqn=call.eqn,
                        sig=f"{call.name} KL105 dwrite {_origin(bm)}"))
                    break
    return out


# ----------------------------------------------------------------- KL106
_GUARD_PRIMS = ("cond", "iota", "select_n")


def _kl106(call, where):
    partials = []
    for bm, _is_out in call.all_bms():
        facts = _bm_facts(bm)
        if facts is None:
            continue
        ashape, dims, _dtype = facts
        for k, (a, d) in enumerate(zip(ashape, dims)):
            if d in (1, a) or d <= 0:
                continue
            if a % d:
                partials.append(
                    f"`{_origin(bm)}` dim {k}: {a} rows / {d}-row "
                    f"blocks leaves a {a % d}-row tail")
    if not partials:
        return []
    prims = {beqn.primitive.name for beqn in call.body_eqns()}
    if prims & set(_GUARD_PRIMS):
        return []                      # @pl.when / iota / where mask
    return [_mk_finding(
        "KL106",
        f"in `{call.name}` ({'; '.join(partials[:3])}; guard the tail "
        f"with @pl.when or an iota >= length mask)",
        where, eqn=call.eqn, sig=f"{call.name} KL106")]


# ------------------------------------------------------------ jaxpr pass
def check_kernels(closed_jaxpr, where="<traced program>", config=None,
                  suppress=True):
    """KL101..KL106 over every ``pallas_call`` eqn reachable from
    `closed_jaxpr`.  Duplicate findings (the same kernel traced once
    per layer) collapse to one."""
    config = config or KernelConfig()
    findings, seen = [], set()
    for eqn in iter_pallas_eqns(closed_jaxpr):
        call = _Call(eqn)
        for f in (_kl101(call, where) + _kl102(call, config, where)
                  + _kl103(call, where) + _kl104(call, where)
                  + _kl105(call, config, where) + _kl106(call, where)):
            key = (f.code, f.path, f.line, f.source_line)
            if key in seen:
                continue
            seen.add(key)
            findings.append(f)
    if suppress:
        findings = apply_suppressions(findings)
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings


# -------------------------------------------------------------- AST pass
_DOT_CALLS = ("dot", "matmul", "dot_general", "einsum", "tensordot")


def default_kernel_paths(root=None):
    d = os.path.join(root or _REPO_ROOT, "paddle_tpu", "ops", "pallas")
    if not os.path.isdir(d):
        return []
    return [os.path.join(d, f) for f in sorted(os.listdir(d))
            if f.endswith(".py")]


def _kernel_fns(tree):
    """FunctionDefs that look like Pallas kernel bodies: two or more
    ``*_ref`` parameters, or passed (possibly via functools.partial) as
    the first argument of a ``pallas_call``."""
    named, kernels = {}, {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            named.setdefault(node.name, node)
            a = node.args
            params = [x.arg for x in (a.posonlyargs + a.args)]
            if sum(1 for p in params if p.endswith("_ref")) >= 2:
                kernels[id(node)] = node
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _dotted(node.func).split(".")[-1] == "pallas_call"
                and node.args):
            continue
        a0 = node.args[0]
        if isinstance(a0, ast.Call) \
                and _dotted(a0.func).split(".")[-1] == "partial" \
                and a0.args:
            a0 = a0.args[0]
        if isinstance(a0, ast.Name) and a0.id in named:
            kernels[id(named[a0.id])] = named[a0.id]
    return list(kernels.values())


def _widened(call_node):
    """True when any argument is an explicit .astype(...float32...) —
    the idiom that widens a dot's operands by hand."""
    for a in call_node.args:
        if isinstance(a, ast.Call) and isinstance(a.func, ast.Attribute) \
                and a.func.attr == "astype" and a.args \
                and "float32" in ast.dump(a.args[0]):
            return True
    return False


def _static_kl103(rel, fn):
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted.split(".")[-1] not in _DOT_CALLS:
            continue
        if any(kw.arg == "preferred_element_type"
               for kw in node.keywords):
            continue
        if _widened(node):
            continue
        out.append(Finding(
            path=rel, line=node.lineno, col=node.col_offset,
            code="KL103",
            message=message_for(
                "KL103",
                detail=f"`{dotted}(...)` in kernel `{fn.name}` without "
                       f"preferred_element_type=jnp.float32 (the static "
                       f"pass cannot prove a wide accumulator)")))
    return out


def _static_kl101(rel, tree):
    """Literal block-shape tuples no dtype's tile can satisfy: a dim
    LARGER than the loosest (f32) tile requirement yet not a multiple
    of it is wrong for every dtype.  Smaller literals may equal the
    full array extent, which only the jaxpr pass can decide."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        last = _dotted(node.func).split(".")[-1]
        if last not in ("BlockSpec", "_vmem_spec"):
            continue
        tup = node.args[0] if node.args else None
        for kw in node.keywords:
            if kw.arg == "block_shape":
                tup = kw.value
        if not isinstance(tup, ast.Tuple) or len(tup.elts) < 1:
            continue
        dims = [e.value if isinstance(e, ast.Constant)
                and isinstance(e.value, int) else None
                for e in tup.elts]
        reqs = [(len(dims) - 1, vmem_model.LANE)]
        if len(dims) >= 2:
            reqs.append((len(dims) - 2, 8))
        bad = []
        for pos, req in reqs:
            d = dims[pos]
            if d is not None and d > req and d % req:
                bad.append(f"dim {pos} = {d} (needs a multiple of "
                           f"{req} for every dtype)")
        if bad:
            out.append(Finding(
                path=rel, line=node.lineno, col=node.col_offset,
                code="KL101",
                message=message_for(
                    "KL101",
                    detail=f"literal {tuple(dims)} — "
                           + "; ".join(bad))))
    return out


def check_kernel_files(paths=None):
    """The trace-free AST pass over Pallas kernel sources."""
    findings = []
    for path in (default_kernel_paths() if paths is None else paths):
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src)
        except (OSError, SyntaxError):
            continue
        sup, skip = parse_suppressions(src)
        if skip:
            continue
        rel = rel_path(path, base=_REPO_ROOT)
        lines = src.splitlines()
        raw = _static_kl101(rel, tree)
        for fn in _kernel_fns(tree):
            raw.extend(_static_kl103(rel, fn))
        for f in raw:
            codes = sup.get(f.line, ())
            if "ALL" in codes or "ALL:KL" in codes or f.code in codes:
                continue
            if 1 <= f.line <= len(lines):
                f.source_line = lines[f.line - 1].strip()
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return findings
