"""protolint rules: the PLxxx family over :mod:`kv_model`.

Whole-package pass over the coordination-KV world model — every key
the package constructs, normalized to its construction-site pattern,
with its set/get/delete flow and the process role of each site.  The
seven hand-rolled protocols this audits (fleet wire/disagg/server,
the ``_coord_*`` collectives, elastic heartbeats, sentinel votes,
resilience.fleet) enforce exactly-once and key-lifecycle invariants
by convention only; these rules turn the conventions into a gate.

Findings resolve to real file:line sites and honor the same
``# protolint: disable=PLxxx`` suppression comments the sibling
analyzers use (``# tracelint:`` is the universal spelling; foreign
family spellings like ``# racelint:`` cannot waive PL rules).  The
pass over-approximates on purpose: a finding is a *hazard*, and the
checked-in baseline (tools/protolint_baseline.json) absorbs the
reviewed backlog so ``--check`` fails only on regressions.

Rule summary (catalogue text lives in :mod:`rules`):

- **PL101** key set but never reclaimed — no consumer and no covering
  delete, or the key lives outside the run namespace (so the
  end-of-run root reap can't reach it) with no delete of its own.
- **PL102** exactly-once key (a ``<seq>``-bearing lane) consumed
  without a covering delete — double-delivery hazard.
- **PL103** un-timed/unbounded raw ``blocking_key_value_get`` —
  deadline-bounded and watchdog/abort-covered sites are exempt.
- **PL104** cross-role wait cycle: role A blocks unbounded on a key
  only role B sets while B blocks on one only A sets (the
  multi-process analogue of RL102).
- **PL105** liveness deadline does not clear the heartbeat interval's
  miss budget (deadline must be ≥ interval × 2).
- **PL201** response lane of a request/response pair whose payload
  carries no typed-error envelope — a failing peer can only time the
  caller out instead of delivering the error.
- **PL202** the seq counter feeding an exactly-once key can be reset
  non-monotonically, so key identities may be reused.
"""
from __future__ import annotations

import ast
import os

from paddle_tpu.analysis import kv_model
from paddle_tpu.analysis.kv_model import PackageModel
from paddle_tpu.analysis.rules import message_for
from paddle_tpu.analysis.visitor import (Finding, iter_py_files,
                                         parse_suppressions, rel_path)

# PL105's miss budget: a peer must be allowed to miss this many
# heartbeats before the deadline declares it dead (docs/protolint.md)
_MISS_BUDGET = 2.0


def modname_for(path, base=None):
    rel = rel_path(path, base)
    mod = rel[:-3] if rel.endswith(".py") else rel
    mod = mod.replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def build_package_model(paths, base=None):
    """Parse every .py under `paths` into one PackageModel.  Returns
    (model, {path: (suppressions, skip_file, lines)}, [parse-error
    Finding])."""
    pm = PackageModel()
    sups = {}
    errors = []
    for path in iter_py_files(paths):
        # the analyzers themselves are not protocol surfaces: the KV
        # tracer's pass-through proxy methods and residual-key sweep
        # would otherwise register as wildcard consumers/deleters and
        # mask real leaks everywhere else in the package
        norm = path.replace(os.sep, "/")
        if "/analysis/" in norm or norm.startswith("analysis/"):
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as fh:
                source = fh.read()
        except OSError:
            continue
        rel = rel_path(path, base)
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            errors.append(Finding(
                path=rel, line=e.lineno or 1, col=e.offset or 0,
                code="PL000", message=f"syntax error: {e.msg}"))
            continue
        sup, skip = parse_suppressions(source)
        sups[rel] = (sup, skip, source.splitlines())
        mm = kv_model.ModuleBuilder(
            path=rel, modname=modname_for(path, base),
            tree=tree).build()
        pm.add(mm)
    pm.finalize()
    return pm, sups, errors


def _finding(op, code, detail):
    return Finding(path=op.path, line=op.line, col=op.col, code=code,
                   message=message_for(code, detail))


# ------------------------------------------------------------ PL101
def _check_key_leak(pm):
    out = []
    for c, info in sorted(pm.pattern_table.items()):
        if not info.sets or c == "<*>":
            continue
        consumed = bool(info.gets) or bool(pm.dir_get_covers(c))
        reclaimed = bool(pm.delete_covers(c))
        site = min(info.sets, key=lambda o: (o.path, o.line))
        if not consumed and not reclaimed:
            out.append(_finding(
                site, "PL101",
                f"'{info.display}' (no consumer and no covering "
                f"delete)"))
        elif not info.ns_rooted and not reclaimed:
            # outside the run namespace the end-of-run root reap
            # (key_value_delete of the namespace) can't reach it
            out.append(_finding(
                site, "PL101",
                f"'{info.display}' (outlives the run namespace; "
                f"nothing ever deletes it)"))
    return out


# ------------------------------------------------------------ PL102
def _check_consume_without_delete(pm):
    out = []
    for c, info in sorted(pm.pattern_table.items()):
        if not info.seq_lane or not info.gets:
            continue
        if pm.delete_covers(c):
            continue
        site = min(info.gets, key=lambda o: (o.path, o.line))
        out.append(_finding(
            site, "PL102",
            f"'{info.display}' (a crashed-and-restarted consumer "
            f"re-reads the stale payload)"))
    return out


# ------------------------------------------------------------ PL103
def _check_unbounded_get(pm):
    out = []
    for f in pm.funcs:
        for item in f.items:
            if item[0] != "op":
                continue
            op = item[1]
            if op.kind != "get_raw" or op.timed or op.watchdog:
                continue
            what = op.pattern if not op.opaque else f.qualname
            out.append(_finding(
                op, "PL103",
                f"'{what}' (no deadline: a dead peer hangs this "
                f"process forever)"))
    return out


# ------------------------------------------------------------ PL104
def _check_cross_role_cycle(pm):
    edges = {}      # (role_a, role_b) -> (op, canon)
    for f in pm.top_funcs():
        role = f.role
        for op in pm.expanded_ops(f):
            if op.kind != "get_raw" or op.timed or op.watchdog \
                    or op.opaque:
                continue
            info = pm.pattern_table.get(op.canon)
            if info is None:
                continue
            for setter in sorted(info.set_roles):
                if setter != role:
                    edges.setdefault((role, setter), (op, op.canon))
    out = []
    for cycle in _cycles({a: set() for a, _ in edges} | {
            b: set() for _, b in edges}, edges):
        ops = [edges[e] for e in cycle]
        site = ops[0][0]
        desc = " -> ".join(f"{a} waits on {b} ('{edges[(a, b)][1]}')"
                           for a, b in cycle)
        out.append(_finding(site, "PL104", desc))
    return out


def _cycles(nodes, edges):
    """Elementary cycles in the (tiny, ≤4-node) role graph, each as
    an edge list; deduped by node set."""
    adj = {n: [] for n in nodes}
    for a, b in edges:
        adj.setdefault(a, []).append(b)
    seen = set()
    found = []

    def dfs(start, node, path):
        for nxt in adj.get(node, ()):
            if nxt == start:
                key = frozenset(p[0] for p in path) | {node}
                if key not in seen:
                    seen.add(key)
                    found.append(path + [(node, nxt)])
            elif all(nxt != p[0] for p in path) and nxt != node:
                dfs(start, nxt, path + [(node, nxt)])

    for n in sorted(adj):
        dfs(n, n, [])
    return found


# ------------------------------------------------------------ PL105
def _check_liveness_budget(pm):
    out = []
    for lp in pm.liveness_pairs:
        if lp.deadline >= _MISS_BUDGET * lp.interval:
            continue
        f = Finding(
            path=lp.path, line=lp.line, col=0, code="PL105",
            message=message_for(
                "PL105",
                f"{lp.scope}.{lp.deadline_name}={lp.deadline:g}s "
                f"allows fewer than {_MISS_BUDGET:g} missed beats at "
                f"{lp.interval_name}={lp.interval:g}s"))
        out.append(f)
    return out


# ------------------------------------------------------------ PL201
def _lane_pairs(pm):
    """Request/response canon pairs: same shape, exactly one
    differing segment, both differing segments literal."""
    canons = [c for c, info in pm.pattern_table.items()
              if info.sets or info.gets]
    pairs = []
    for i, a in enumerate(canons):
        sa = a.split("/")
        for b in canons[i + 1:]:
            sb = b.split("/")
            if len(sa) != len(sb):
                continue
            diff = [k for k in range(len(sa)) if sa[k] != sb[k]]
            if len(diff) == 1 and "<" not in sa[diff[0]] \
                    and "<" not in sb[diff[0]]:
                pairs.append((a, b))
    return pairs


def _check_error_envelope(pm):
    # response side of a pair = the lane one function GETS after
    # SETTING the other (the initiator's post-then-await order)
    responses = set()
    pairs = _lane_pairs(pm)
    if pairs:
        for f in pm.top_funcs():
            ops = pm.expanded_ops(f)
            for a, b in pairs:
                for req, rsp in ((a, b), (b, a)):
                    set_at = [i for i, op in enumerate(ops)
                              if op.kind == "set" and op.canon == req]
                    get_at = [i for i, op in enumerate(ops)
                              if op.kind in ("get", "get_raw")
                              and op.canon == rsp]
                    if set_at and get_at and min(set_at) < max(get_at):
                        responses.add(rsp)
    out = []
    for rsp in sorted(responses):
        info = pm.pattern_table[rsp]
        if not info.sets:
            continue        # produced outside the package
        if any(op.envelope for op in info.sets):
            continue
        site = min(info.sets, key=lambda o: (o.path, o.line))
        out.append(_finding(
            site, "PL201",
            f"'{info.display}' (a peer failure can only surface "
            f"as the initiator's timeout)"))
    return out


# ------------------------------------------------------------ PL202
def _check_seq_reuse(pm):
    by_qual = {}
    for f in pm.funcs:
        by_qual[f.qualname] = f
    out = []
    for c, info in sorted(pm.pattern_table.items()):
        seen_site = set()
        for op in info.sets:
            if not op.seq_src or (op.path, op.line) in seen_site:
                continue
            kind = op.seq_src[0]
            detail = None
            if kind == "attr":
                _, cls, attr = op.seq_src
                assigns = pm.attr_assigns.get((cls, attr), ())
                resets = [a for a in assigns
                          if a[2] and a[0] != "__init__"]
                if resets:
                    detail = (f"'{info.display}' ({cls}.{attr} is "
                              f"reset to a constant in "
                              f"{resets[0][0]}())")
            elif kind == "global":
                _, mod, name = op.seq_src
                resets = pm.global_const_assigns.get((mod, name), ())
                if resets:
                    detail = (f"'{info.display}' ({name} is rewound "
                              f"by {resets[0][0]}())")
            elif kind == "local":
                _, qual, name = op.seq_src
                f = by_qual.get(qual)
                assigns = (f.local_assigns.get(name, ())
                           if f is not None else ())
                augs = [a[0] for a in assigns if a[2]]
                consts = [a[0] for a in assigns if a[1]]
                if augs and any(cl > min(augs) for cl in consts):
                    detail = (f"'{info.display}' (local counter "
                              f"{name} is re-seeded after it has "
                              f"advanced)")
            if detail:
                seen_site.add((op.path, op.line))
                out.append(_finding(op, "PL202", detail))
    return out


ALL_CHECKS = (
    _check_key_leak,
    _check_consume_without_delete,
    _check_unbounded_get,
    _check_cross_role_cycle,
    _check_liveness_budget,
    _check_error_envelope,
    _check_seq_reuse,
)


def lint_package(paths, base=None):
    """The protolint entry: AST-model every file under `paths`, run
    the PL rules package-wide, apply suppressions.  Returns
    [Finding]."""
    pm, sups, findings = build_package_model(paths, base=base)
    for check in ALL_CHECKS:
        findings.extend(check(pm))
    out = []
    for f in findings:
        entry = sups.get(f.path)
        if entry is not None:
            sup, skip, lines = entry
            if skip:
                continue
            codes = sup.get(f.line, ())
            if "ALL" in codes or "ALL:PL" in codes or f.code in codes:
                continue
            if 1 <= f.line <= len(lines):
                f.source_line = lines[f.line - 1].strip()
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return out


def static_kv_model(paths, base=None):
    """The PackageModel alone — what :mod:`kv_tracer`'s
    ``check_static`` cross-checks runtime event streams against."""
    pm, _sups, _errors = build_package_model(paths, base=base)
    return pm


def bench_report(paths=None, base=None):
    """The bench.py lane: finding count + per-rule breakdown, so
    every BENCH report records the protocol-audit picture alongside
    the racelint concurrency numbers."""
    import time
    t0 = time.time()
    if paths is None:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        paths = [os.path.join(repo, "paddle_tpu")]
        base = repo
    findings = lint_package(paths, base=base)
    breakdown = {}
    for f in findings:
        breakdown[f.code] = breakdown.get(f.code, 0) + 1
    return {
        "protolint_finding_count": len(findings),
        "protolint_rule_breakdown": dict(sorted(breakdown.items())),
        "protolint_elapsed_s": round(time.time() - t0, 2),
    }
