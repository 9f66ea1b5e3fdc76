"""tracelint rule registry — the single source of truth for diagnostics.

Every hazard the `to_static` pipeline can hit has a code here (TL0xx
conversion-subset, TL1xx host-sync/purity, TL3xx recompile hazards,
TL4xx post-trace jaxpr findings).  The CLI (`tools/tracelint.py`), the
opt-in `to_static(check=True)` hook, and the *runtime* diagnostics in
`jit/dy2static.py` all pull their message text from this table, so a
user sees the same wording whether the problem is caught ahead of trace
or at trace time.

This module is pure stdlib (no jax import) so the AST pass stays cheap
and importable anywhere — including from `jit/dy2static.py` without an
import cycle.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Rule:
    code: str
    name: str
    message: str       # one-line diagnostic (str.format over kwargs)
    rationale: str     # why this is a hazard under the whole-program trace
    fixit: str         # what the user should do instead


class TraceHazardError(RuntimeError):
    """Named runtime diagnostic for a construct outside the `to_static`
    conversion subset hit with a tensor-valued condition.

    Raised by `jit/dy2static.py` guards instead of letting the generic
    jax concretization error surface; carries the rule code so the CLI
    and the runtime agree on wording.
    """

    def __init__(self, code, filename, lineno, detail=""):
        self.code = code
        self.filename = filename
        self.lineno = lineno
        rule = RULES[code]
        msg = (f"{code} at {filename}:{lineno}: "
               f"{rule.message.format(detail=detail)}\n"
               f"  why: {rule.rationale}\n"
               f"  fix: {rule.fixit}\n"
               f"  (run `python tools/tracelint.py <your file>` to find "
               f"these before tracing)")
        super().__init__(msg)


_R = Rule

RULES = {r.code: r for r in [
    # ---- TL0xx: constructs outside the dy2static conversion subset ----
    _R("TL001", "return-in-converted-loop",
       "`return` inside a loop{detail} — the loop stays plain Python and "
       "a tensor-valued condition there fails at trace time",
       "a lax.while_loop carry cannot hold a value first bound mid-loop, "
       "so dy2static leaves loops containing `return` unconverted; under "
       "a trace the loop condition then hits bool() on a tracer",
       "hoist the result into a variable, `break` out (range-for/while), "
       "and `return` after the loop — or keep the condition "
       "Python-valued"),
    _R("TL002", "break-in-nonrange-for",
       "`break`/`continue` in a non-range `for` loop{detail} — outside "
       "the conversion subset, the loop stays plain Python",
       "only `for <name> in range(...)` lowers to the counter-while form "
       "that can carry the break/continue guard flags",
       "iterate `for i in range(len(xs))` and index, or restructure "
       "without break/continue"),
    _R("TL003", "loop-else-clause",
       "loop `else:` clause{detail} — outside the conversion subset, the "
       "loop stays plain Python",
       "the converted while/for forms have no place for the else block "
       "(it would need a 'did not break' flag across the carry)",
       "move the else body after the loop, guarded on the exit flag you "
       "manage yourself"),
    _R("TL004", "generator-under-trace",
       "`yield` in a function reachable from a `@to_static` entry",
       "generators cannot be traced into one XLA program; convert_call "
       "skips them, so tensor control flow inside stays eager",
       "materialize the sequence into a list before the traced region"),
    _R("TL005", "identity-test-of-branch-bound-name",
       "identity test (`is` / `is not`) of {detail}, which is bound in "
       "only one branch of a convertible `if`",
       "a variable bound in only one branch of a tensor-converted `if` "
       "merges to dy2static's poison sentinel; every ordinary read "
       "raises NameError, but Python's `is` operator cannot be "
       "intercepted — `maybe_bound is None` would silently evaluate "
       "False and take the wrong path",
       "bind the variable on every path (e.g. initialize it to None "
       "before the `if`) when its identity is tested afterwards; if "
       "the test is provably unreachable when unbound (a short-circuit "
       "guard), waive with `# tracelint: disable=TL005` on its line"),

    # ---- TL1xx: host syncs & trace-time side effects ----
    _R("TL101", "host-sync-numpy",
       "`.{detail}()` on a tensor inside traced code — host sync / "
       "concretization error under the trace",
       "the whole-program trace has no concrete values; .numpy()/.item()/"
       ".tolist() force a device->host transfer that cannot happen inside "
       "one XLA program",
       "keep the value as a tensor; move host-side reads (logging, "
       "thresholds) outside the @to_static function"),
    _R("TL102", "tensor-concretize",
       "`{detail}()` of a tensor value — concretizes under the trace",
       "float()/int()/bool() need a concrete scalar; under the trace they "
       "raise a ConcretizationTypeError (or silently bake a trace-time "
       "constant via __index__)",
       "use tensor arithmetic (the converter handles tensor `if`/`while` "
       "conditions), or compute the scalar before entering traced code"),
    _R("TL103", "tensor-to-numpy-array",
       "np.{detail}() over a tensor value — host transfer under the trace",
       "numpy constructors force concretization; inside the trace this "
       "either errors or silently freezes the value at trace time",
       "use paddle_tpu / jnp ops end to end inside the traced function"),
    _R("TL104", "print-of-tensor",
       "`print` of a tensor value inside traced code — prints a tracer "
       "once at trace time, not per step",
       "side effects run only while tracing; the compiled program never "
       "prints, and what does print is `Traced<...>`, not the value",
       "return the value and print it outside, or drop the print"),
    _R("TL105", "untraced-randomness",
       "`{detail}` inside traced code — evaluated once at trace time and "
       "baked into the program as a constant",
       "host randomness / clocks are not traced: every compiled step "
       "replays the same trace-time value, which is almost never intended",
       "use paddle_tpu's traced RNG ops (paddle.rand/randn, nn dropout) "
       "or pass the value in as an argument"),
    _R("TL106", "trace-time-mutation",
       "mutation of {detail} inside traced code — happens once at trace "
       "time, not per step",
       "appending tensors to module-level / closure lists (or writing "
       "globals) under the trace stores tracers and runs only during "
       "tracing; the compiled step never re-executes it",
       "return values out of the traced function and accumulate outside"),

    # ---- TL3xx: recompile-storm hazards ----
    _R("TL301", "unhashable-static-arg",
       "mutable default argument {detail} on a `@to_static` entry — "
       "unhashable static leaf, falls back to repr() caching",
       "non-tensor args key the compile cache; a list/dict/set default is "
       "repr()-keyed, so equal-but-not-identical values silently miss the "
       "cache and recompile",
       "use a tuple / frozen value, or make the argument a tensor"),
    _R("TL302", "to-static-in-loop",
       "`to_static(...)` constructed inside a loop — every iteration "
       "builds a fresh compile cache",
       "each StaticFunction owns its cache; wrapping per iteration means "
       "nothing is ever reused and every step pays a full XLA compile",
       "hoist the to_static wrapping out of the loop and reuse it"),

    # ---- TL4xx: post-trace jaxpr findings ----
    _R("TL401", "f64-promotion",
       "program contains {detail} values — unintended widening past the "
       "default float32",
       "f64/c128 on TPU runs on the slow path (or is silently demoted); "
       "a stray Python float or np.float64 scalar upcasting an op is the "
       "usual cause",
       "cast inputs explicitly or keep scalars as Python floats under "
       "jax's default x64-disabled config"),
    _R("TL402", "large-baked-constant",
       "constant of {detail} baked into the compiled program",
       "closure-captured arrays are embedded in the executable — they "
       "bloat compile time and HBM, and a changed value silently "
       "recompiles",
       "pass the array as an argument (it becomes a donated/traced "
       "input) instead of closing over it"),
    _R("TL403", "collective-outside-mesh",
       "collective `{detail}` issued with no device mesh initialized",
       "psum/all_gather and friends need a mesh axis to reduce over; "
       "outside `init_mesh`/shard_map they are at best identities and at "
       "worst trace errors on real multi-chip runs",
       "call paddle.distributed.init_mesh(...) (or run under shard_map) "
       "before tracing collectives"),
    _R("TL404", "axis-name-mismatch",
       "collective `{detail}` — axis name not bound by the current mesh",
       "an axis name that doesn't match the mesh's axis_names raises at "
       "dispatch on multi-chip and silently no-ops in single-process "
       "fallbacks",
       "use one of the mesh's declared axis names (see init_mesh "
       "axis_names=...)"),

    # ---- SL1xx: sharding (shardlint, analysis/shard_rules.py) ----
    _R("SL101", "large-replicated-array",
       "large program input {detail} is fully replicated on every device "
       "of the mesh",
       "a replicated array costs its full size in HBM on EVERY chip; past "
       "a few MiB that is usually an unannotated weight the mesh was "
       "supposed to shard",
       "annotate it with shard_tensor(t, ...) / a PartitionSpec over a "
       "mesh axis, or accept it into the shardlint baseline if the "
       "replication is intentional"),
    _R("SL102", "unsharded-optimizer-state",
       "optimizer state {detail} is replicated under a data-parallel mesh",
       "optimizer accumulators are pure per-parameter state — replicating "
       "them across dp ranks wastes HBM that ZeRO stage 1/2 reclaims for "
       "free (grads already reduce-scatter)",
       "wrap with distributed.sharding.group_sharded_parallel (stage "
       "'os'/'os_g'), or shard the accumulator like its parameter"),
    _R("SL103", "resharding-thrash",
       "value resharded {detail} — an A->B->A constraint chain",
       "each conflicting sharding constraint materializes a resharding "
       "collective; bouncing a value between two layouts pays the "
       "transfer twice for no net layout change",
       "pick one layout for the value's lifetime, or move the consumer "
       "needing the other layout next to the first constraint"),

    # ---- SL2xx: collective safety ----
    _R("SL201", "collective-order-mismatch",
       "cond branches issue different collective sequences ({detail})",
       "under SPMD a collective is a rendezvous: if shards can disagree "
       "on the branch (or the branches order their collectives "
       "differently) some chips wait forever — a silent multi-chip "
       "deadlock",
       "hoist the collectives out of the cond, or make every branch "
       "issue the SAME collectives in the SAME order"),
    _R("SL202", "all-gather-over-budget",
       "all_gather materializes {detail} — past the per-chip HBM budget",
       "all_gather multiplies the operand by the axis size on EVERY "
       "chip; a gather that exceeds the HBM budget OOMs at runtime even "
       "though each shard individually fits",
       "keep the value sharded (reduce_scatter + local compute), or "
       "gather in chunks"),
    _R("SL203", "loop-invariant-collective",
       "collective `{detail}` inside a scan body has loop-invariant "
       "operands",
       "XLA does not hoist collectives out of loops: a psum/all_gather "
       "of values that never change inside the scan pays the full "
       "network latency every iteration",
       "compute the collective once before the scan and pass the result "
       "in as a carry/const"),

    # ---- SL3xx: memory & layout cost ----
    _R("SL301", "peak-hbm-over-budget",
       "estimated peak HBM {detail}",
       "the liveness estimate over the traced program exceeds the "
       "declared per-chip budget — the step will OOM (or silently spill) "
       "on real silicon",
       "shard or rematerialize the top contributors (see the cost "
       "report), shrink the batch, or raise the documented budget"),
    _R("SL302", "mxu-padding-waste",
       "operand {detail} — padded to the MXU tile, wasting compute/HBM",
       "TPU tiles are (sublane, 128-lane) blocks — 8x128 f32, 16x128 "
       "bf16; a dim just past a tile boundary pays for the whole next "
       "tile in both memory and MXU cycles",
       "round the dim to a multiple of 128 (lane) / the dtype sublane "
       "count, e.g. pad vocab or hidden sizes at model-config time"),
    _R("SL303", "f32-param-bf16-compute",
       "f32 input {detail} is only consumed through a bf16 cast",
       "storing a parameter in f32 when every use first converts it to "
       "bf16 doubles its HBM residency and the cast bandwidth every "
       "step",
       "store the parameter in bf16 (keep an f32 master copy only where "
       "the optimizer needs it)"),
    # ---- NL1xx: precision loss (numlint, num_rules.py/dtype_flow.py) ----
    _R("NL101", "narrow-accumulation",
       "reduction {detail} accumulates in a narrow dtype",
       "summing N values in bf16 keeps an 8-bit mantissa on the RUNNING "
       "total: past a few hundred addends the small contributions are "
       "absorbed entirely (classic bias-grad / loss-mean corruption); "
       "the MXU accumulates dot products wide in hardware, but a "
       "reduce_sum lowers to exactly the narrow serial sum it says",
       "accumulate wide: preferred_element_type=float32 on the "
       "dot_general, or cast the operand up before the reduce and back "
       "down after (one rounding of the result, not one per addend)"),
    _R("NL102", "double-rounding-roundtrip",
       "f32 value narrowed then re-widened ({detail}) while the wide "
       "value was still live",
       "float32(bfloat16(x)) != x — the round trip costs 16 mantissa "
       "bits; when the original wide value still has live consumers the "
       "narrow copy existed only in passing, so downstream math pays "
       "double rounding for zero residency savings",
       "consume the original wide value directly; narrow only at a "
       "residency boundary where the wide copy genuinely dies "
       "(a cast chain rooted at a PROGRAM INPUT is shardlint SL303's "
       "finding, not this one — see docs/shardlint.md)"),
    _R("NL103", "narrow-master-state",
       "optimizer-plane state {detail} is stored narrow without a "
       "moment_dtype opt-in",
       "param update math below ~1e-3 relative step size rounds to ZERO "
       "in bf16 — narrow master weights stop learning late in training, "
       "and narrow moments bias the adaptive scale; PR 10 pinned this "
       "invariant dynamically (SL303=0 on the flagship), numlint proves "
       "it statically on every audited program",
       "store params and moments f32 (master weights); narrow moments "
       "only through the explicit Adam/AdamW moment_dtype opt-in, which "
       "declares the tolerance contract"),

    # ---- NL2xx: stability ----
    _R("NL201", "unstabilized-narrow-transcendental",
       "`{detail}` on a narrow dtype with no stabilization upstream",
       "exp overflows bf16 at x>88 ln2-scaled and float16 at x>11; "
       "log/div amplify near zero — without a max-subtraction (softmax) "
       "or eps-guard (denominators) the narrow evaluation saturates to "
       "inf/nan exactly on the outlier activations that matter",
       "subtract the row max before exp (jax.nn.softmax does), add an "
       "eps before log/div, or upcast the operand to f32 for the "
       "transcendental and narrow the result"),
    _R("NL202", "narrow-scan-carry",
       "scan carry {detail} is narrower than its body math",
       "a carry that the body widens, updates, and re-narrows rounds "
       "the running value EVERY iteration — error compounds linearly "
       "with loop length, unlike a single end-of-loop rounding",
       "keep the carry at the body's compute dtype and narrow once "
       "after the scan (the carry is live-range-bounded; residency "
       "savings are per-iteration only)"),

    # ---- NL3xx: quantization readiness ----
    _R("NL301", "scale-free-quantized-consumption",
       "quantized value {detail} consumed with no adjacent scale "
       "operand",
       "int8/fp8 codes are meaningless without their quantization "
       "scale: math on raw codes silently treats quantization bins as "
       "real units — the KV-quantization plane (ROADMAP item 2) must "
       "carry a per-page scale next to every pool read",
       "dequantize first (convert + multiply by the scale), or pass "
       "the scale into the consuming kernel alongside the codes"),
    _R("NL302", "dequant-requant-roundtrip",
       "dequantized value {detail} immediately requantized",
       "a dequant->requant chain whose intermediate float has no other "
       "consumer materializes a full-width tensor only to round it "
       "away again — and the two roundings need not compose to the "
       "identity even at equal scales",
       "fuse the rescale into one integer/fp8-domain op (or one "
       "convert with the combined scale) instead of bouncing through "
       "floats"),

    # ---- KL1xx: Pallas kernel interiors (kernlint, kernel_rules.py) ----
    _R("KL101", "block-tile-misalignment",
       "block shape {detail} is not a multiple of the dtype's native "
       "TPU tile",
       "VMEM tiles are (sublane, 128-lane) blocks — (8,128) f32, "
       "(16,128) bf16, (32,128) int8/fp8; a BlockSpec dim that is "
       "neither 1, the full array dim, nor a tile multiple forces "
       "Mosaic to pad every block copy, wasting VMEM and MXU cycles "
       "on every grid step (the in-kernel twin of SL302)",
       "round the block dim to the dtype's sublane multiple / 128 "
       "lanes (ops/pallas/norm.py `_sublane` + `_auto_block_rows` are "
       "the house helpers), or pad the array so the full dim is the "
       "block"),
    _R("KL102", "vmem-over-budget",
       "estimated VMEM footprint {detail}",
       "Pallas double-buffers every grid-iterated block, and scratch "
       "lives alongside — the static estimate (tile-padded block "
       "buffers x2 + scratch) exceeding the per-core VMEM budget means "
       "Mosaic either spills or refuses to compile, discovered only "
       "after a full XLA lowering on real silicon",
       "shrink the block shape (fewer rows per grid step), move large "
       "accumulators to f32 scratch only where needed, or iterate an "
       "extra grid dimension instead of widening blocks"),
    _R("KL103", "narrow-in-kernel-accumulation",
       "kernel body {detail} accumulates in a narrow dtype",
       "numlint's NL101 deliberately stops at the pallas_call boundary "
       "(the body is VMEM-resident, not HBM traffic) — but inside the "
       "kernel the same math rules hold: a dot without "
       "preferred_element_type=f32 or a bf16 += reduction carry rounds "
       "the running total every block, and the wrong answer never "
       "surfaces as an error",
       "pass preferred_element_type=jnp.float32 to in-kernel dots, "
       "keep accumulator refs/scratch f32, and cast once when storing "
       "the block result"),
    _R("KL104", "input-output-alias-hazard",
       "input_output_aliases {detail}",
       "an aliased pair shares one buffer: a shape/dtype mismatch "
       "corrupts the donated storage layout, and a read of the aliased "
       "input AFTER the aliased output's block was stored observes the "
       "new value on TPU while interpret mode still shows the old one "
       "— a silent TPU-only wrong answer",
       "alias only identically-shaped/dtyped pairs, and finish every "
       "read of the aliased input ref before the first store to its "
       "aliased output ref"),
    _R("KL105", "grid-coverage-mismatch",
       "grid x block {detail}",
       "Pallas writes exactly the blocks the index maps name: an "
       "output region no grid step covers keeps uninitialized garbage, "
       "an input tail never mapped is silently unprocessed, and two "
       "NON-consecutive grid steps naming the same output block "
       "overwrite each other's result (consecutive revisits are the "
       "legal accumulation pattern)",
       "make ceil(array_dim / block_dim) grid steps per dim with an "
       "identity-ish index map, or mask the overlap; data-dependent "
       "(scalar-prefetch) maps are skipped — keep them total by "
       "construction"),
    _R("KL106", "unguarded-ragged-tail",
       "partial final block {detail} read without a guard",
       "when block x grid overshoots the array, the final block's "
       "out-of-range rows are padding with undefined contents; a "
       "reduction or dot that consumes them unmasked folds garbage "
       "into real outputs — the exact hazard class a ragged "
       "paged-attention kernel lives in",
       "guard tail loads with @pl.when(pid < full_blocks), mask with "
       "broadcasted_iota against the true length, or pad the operand "
       "to a block multiple before the call (the norm.py _pad_rows "
       "pattern)"),

    # ---- RL1xx: host-runtime concurrency (racelint, race_rules.py) ----
    _R("RL101", "unguarded-shared-attribute",
       "{detail} is accessed from multiple thread roots with no "
       "consistent lock",
       "attributes reached from two thread roots with empty (or "
       "disjoint) lock sets are classic data races: lost updates, torn "
       "reads, and ordering bugs that only fire under load — exactly "
       "the class of bug the GIL hides until a preemption lands between "
       "a read and its write-back",
       "guard every access with ONE lock (document it next to the "
       "attribute), make the attribute a thread-safe type "
       "(Queue/Event), or confine it to a single thread"),
    _R("RL102", "lock-order-inversion",
       "lock-order cycle: {detail}",
       "two threads taking the same locks in opposite orders deadlock "
       "the moment their windows overlap; the acquired-while-holding "
       "graph must stay acyclic for the whole package, not per module",
       "pick one global order (docs/internals.md 'Threading model & "
       "lock hierarchy') and re-nest the offending acquisition — or "
       "drop to a single lock"),
    _R("RL103", "blocking-call-under-lock",
       "blocking {detail} while holding a lock",
       "a lock held across join/IO/un-timed queue waits turns every "
       "other acquirer into a convoy behind the slow operation — and "
       "into a deadlock if the blocking operation itself needs the "
       "lock (a callback, a signal handler, a joined thread)",
       "move the blocking call outside the critical section: snapshot "
       "state under the lock, release, then block"),
    _R("RL104", "unsafe-signal-handler",
       "signal handler does more than set a flag: {detail}",
       "Python signal handlers run between bytecodes of WHATEVER the "
       "main thread was doing: acquiring a lock the interrupted code "
       "holds (buffered IO locks included — print!) deadlocks, and "
       "allocation/IO there is reentrancy-unsafe by construction",
       "set a flag (threading.Event) in the handler and do the real "
       "work at a polled step boundary — the drain pattern "
       "resilience.preemption documents"),
    _R("RL105", "thread-lifecycle-leak",
       "{detail}",
       "a non-daemon thread nobody joins blocks interpreter exit; an "
       "executor nobody shuts down leaks its workers; a loop with no "
       "stop path cannot be drained on preemption — all three turn "
       "clean shutdowns into hangs",
       "join (or make daemon) every thread, `shutdown()` every "
       "executor, and give every loop a stop Event the owner sets"),

    # ---- RL2xx: atomicity ----
    _R("RL201", "check-then-act-toctou",
       "check-then-act on {detail} outside its guarding lock",
       "`if key in shared: shared[key]...` is two operations; another "
       "thread can invalidate the check before the act (the serving "
       "metrics `_release_labels` bug this repo already shipped once) — "
       "the attribute has a lock, but this site doesn't hold it",
       "take the attribute's lock around the WHOLE check+act sequence, "
       "or use an atomic primitive (dict.setdefault, dict.pop(k, "
       "None))"),

    # ================= PLxxx: protolint (coordination-KV protocols) ====
    # Cross-process protocol audit over the coordination-KV surfaces
    # (kv_model.py / proto_rules.py; tools/protolint.py; docs/
    # protolint.md).  PL1xx: key lifecycle & liveness; PL2xx: wire
    # payload & ordering discipline.
    _R("PL101", "kv-key-leak",
       "KV key {detail} is set but never reclaimed",
       "a key nobody consumes or reaps accrues in the coordination "
       "store for the life of the service: per-round keys grow O(steps),"
       " and keys outside the launch namespace survive the end-of-run "
       "namespace reap entirely — next launch reads this run's debris "
       "(stale heartbeats flag healthy hosts dead, stale round keys "
       "corrupt fresh rendezvous)",
       "give every set key a consumer AND a reap: delete-on-consume for "
       "exactly-once lanes, a two-rounds-behind prefix sweep for round "
       "keys (collective._coord_reap is the model), and root every key "
       "under coord_namespace() so finalize()'s namespace reap is the "
       "backstop"),
    _R("PL102", "consume-without-delete",
       "exactly-once key {detail} is consumed but never deleted",
       "a seq-numbered lane key left in the store after its one "
       "legitimate read is a double-delivery hazard: a wedged peer that "
       "resumes (SIGSTOP→SIGCONT) or a retried reader re-consumes the "
       "same payload — the exactly-once contract of the wire lane "
       "silently becomes at-least-once",
       "delete the key the moment it is consumed (wire.await_response/"
       "read_request pattern), or cover the whole round with a "
       "non-root prefix reap that runs before the seq can recycle"),
    _R("PL103", "unbounded-kv-wait",
       "unbounded blocking KV get: {detail}",
       "a blocking_key_value_get with no finite deadline hangs the "
       "process forever when the peer died before setting the key — "
       "the exact failure the fleet watchdog exists to convert into a "
       "typed CollectiveTimeout with a DEAD verdict",
       "route every wait through resilience.fleet.kv_get_bytes (sliced "
       "deadline + RetryPolicy backoff + abort_if watchdog hook) or "
       "pass an explicit finite timeout_in_ms"),
    _R("PL104", "cross-role-wait-cycle",
       "cross-role KV wait cycle: {detail}",
       "role A blocking on a key only role B sets while B blocks on "
       "one only A sets is the multi-process analogue of a lock-order "
       "inversion (RL102): with unbounded waits the fleet deadlocks "
       "the first time both sides enter their waits, and no "
       "single-process tracer can see it",
       "break the cycle by ordering the protocol (set your side's key "
       "BEFORE blocking on the peer's — the wire req/rsp lane's "
       "set-then-get shape) or bound one side with a deadline + retry"),
    _R("PL105", "heartbeat-deadline-mismatch",
       "liveness deadline vs heartbeat interval mismatch: {detail}",
       "a staleness deadline that is not comfortably larger than the "
       "publish interval (deadline >= interval x miss-budget) flags "
       "healthy hosts dead on a single delayed beat — one GC pause or "
       "slow KV round trip away from a spurious fleet reconfigure",
       "derive the deadline from the interval with an explicit miss "
       "budget (FleetConfig's suspect_after_s = 3x / dead_after_s = 6x "
       "heartbeat_interval_s is the house pattern) and validate the "
       "ratio at config time"),
    _R("PL201", "untyped-error-envelope",
       "wire response without a typed-error envelope: {detail}",
       "an RPC lane whose responses carry only the success payload has "
       "no way to ship a replica-side exception: the caller times out "
       "on application errors and every failure collapses into "
       "'peer dead', losing the typed backpressure (AdmissionRejected) "
       "the routing layer dispatches on",
       "marshal every response through an ok/err discriminated "
       "envelope (wire.post_response + _marshal_error/_unmarshal_error "
       "is the house pattern) and post the error branch from the "
       "serve loop's except handler"),
    _R("PL202", "seq-reuse",
       "seq counter feeding {detail} can be reused non-monotonically",
       "a sequence slot rewound outside construction lets a fresh "
       "request collide with an undeleted key from the previous life "
       "of the counter — the lane silently pairs a new request with a "
       "stale response (or vice versa), breaking exactly-once pairing",
       "make the counter monotonic for the lifetime of the key "
       "namespace: reset it only together with a namespace/generation "
       "bump (collective.reset_coord_rounds documents that coupling)"),
]}


def message_for(code, detail=""):
    """Formatted one-line message for `code` (shared CLI/runtime text)."""
    return RULES[code].message.format(detail=detail)


# Codes whose AST rules only make sense on functions REACHED from a
# @to_static entry (everything AST-side, today — kept explicit for the
# CLI docs).  SLxxx codes are all post-trace (jaxpr-level): the
# shardlint passes in shard_rules.py / cost_audit.py.  RLxxx codes are
# the host-runtime concurrency family (racelint, race_rules.py).
AST_CODES = tuple(c for c in RULES if c.startswith("TL") and c < "TL400")
JAXPR_CODES = tuple(c for c in RULES
                    if c.startswith("SL") or (c.startswith("TL")
                                              and c >= "TL400"))
SHARDLINT_CODES = tuple(c for c in RULES if c.startswith("SL"))
RACELINT_CODES = tuple(c for c in RULES if c.startswith("RL"))
NUMLINT_CODES = tuple(c for c in RULES if c.startswith("NL"))
KERNLINT_CODES = tuple(c for c in RULES if c.startswith("KL"))
PROTOLINT_CODES = tuple(c for c in RULES if c.startswith("PL"))
