"""Runner ``train``: one ``@jit.to_static`` train step (autocast, AdamW)
fed by ``io.DataLoader`` over seeded random token rows.

Set-up builds ONE compiled step with its state, drives it through its first
steps on the loader's own batches (the readings that decide ``correct``),
warms it, and hands that same object and that same loader to the window.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import compare
from benchmark.reference import common as refc

FOLLOWED_STEPS = 3


def _dataset(traffic, vocab, seed):
    from paddle_tpu.io import Dataset
    seq, rows = traffic["seq_len"], traffic["max_steps"] * traffic["batch"]
    shifted = traffic["labels"] == "next_token"

    class SeededTokens(Dataset):
        """Row i is drawn from (seed, i): every row of every step differs,
        and the same seed gives the same rows."""

        def __len__(self):
            return rows

        def __getitem__(self, i):
            toks = np.random.default_rng((seed, i)).integers(
                0, vocab, 2 * seq + 1, dtype=np.int32)
            if shifted:
                return toks[:seq], toks[1:seq + 1]
            return toks[:seq], toks[seq + 1:]

    return SeededTokens()


def _view_norms(ctx, tree):
    """Norm of every leaf as the comparison sees it, in one jitted call."""
    import jax
    views, cfg = ctx.family.reference.views, ctx.cfg
    out = jax.jit(lambda t: refc.norms(views(cfg, t)))(tree)
    return {k: float(x) for k, x in out.items()}


def build(ctx):
    """The model with the benchmark's weights, the optimizer, the compiled
    step, the loader: the one object set-up drives and the window times."""
    import paddle_tpu as P
    from paddle_tpu.io import DataLoader
    cfg, traffic, hyper = ctx.cfg, ctx.traffic, ctx.traffic["optimizer"]
    family = ctx.family
    P.seed(ctx.seed % (1 << 31))
    model = family.build(cfg, training=True)
    spec = family.reference.weight_spec(cfg)
    params = dict(model.named_parameters())
    leaves = {mine: params[theirs]
              for mine, theirs in family.leaf_names(cfg).items()}
    weights = refc.make_weights(spec, ctx.seed)
    for name, p in leaves.items():
        p._set_value(weights[name])
    del weights
    opt = P.optimizer.AdamW(
        learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
        beta2=hyper["beta2"], epsilon=hyper["epsilon"],
        weight_decay=hyper["weight_decay"], parameters=model.parameters())
    loss_of = family.make_loss(model)
    amp = traffic["autocast"]

    @P.jit.to_static
    def train_step(ids, labels):
        opt.clear_grad()
        with P.amp.auto_cast(level=amp["level"], dtype=amp["dtype"]):
            loss = loss_of(ids, labels)
        loss.backward()
        opt.step()
        return loss

    loader = DataLoader(_dataset(traffic, cfg["vocab_size"], ctx.seed),
                        batch_size=traffic["batch"], shuffle=False,
                        num_workers=traffic["loader_workers"])
    return model, opt, train_step, loader, leaves


def follow_first_steps(ctx, opt, train_step, feed, leaves):
    """Drive the step through its first steps on the feed's own batches and
    take the program's readings: each loss, every leaf's first gradient
    norm as the optimizer got it (moment1 after one step over 1 - beta1),
    every leaf's change after the last step."""
    import jax.numpy as jnp
    hyper = ctx.traffic["optimizer"]
    batches, losses, grad_norm = [], [], None
    for step in range(FOLLOWED_STEPS):
        ids, labels = next(feed)
        batches.append((np.asarray(ids._value), np.asarray(labels._value)))
        losses.append(float(train_step(ids, labels).numpy()))
        if step == 0:
            moments = {k: opt._accumulators.get(("moment1", id(p)))
                       for k, p in leaves.items()}
            grad_norm = {
                k: x / (1.0 - hyper["beta1"]) for k, x in _view_norms(ctx, {
                    k: (m._value if m is not None
                        else jnp.zeros_like(leaves[k]._value))
                    for k, m in moments.items()}).items()}
    start = refc.make_weights(ctx.family.reference.weight_spec(ctx.cfg),
                              ctx.seed)
    change = _view_norms(ctx, {
        k: p._value.astype(jnp.float32) - start[k] for k, p in leaves.items()})
    del start
    return batches, {"loss": losses, "grad_norm": grad_norm,
                     "change_norm": change}


def reference_follower(ctx, mode="f32"):
    """The plain reference's AdamW, compiled once for this cell."""
    ref, cfg = ctx.family.reference, ctx.cfg

    def loss_fn(params, ids, labels):
        return ref.loss(cfg, params, ids, labels, mode)

    return refc.AdamWReference(
        loss_fn, ctx.traffic["optimizer"], lambda tree: ref.views(cfg, tree),
        row_block=ctx.traffic.get("reference_row_block"))


def reference_readings(ctx, batches, mode="f32", rows=None, follower=None):
    """The plain reference over the same batches from the same seed."""
    follower = follower or reference_follower(ctx, mode)
    weights = refc.make_weights(
        ctx.family.reference.weight_spec(ctx.cfg), ctx.seed)
    return follower.follow(weights, batches, rows=rows)


def run(ctx):
    traffic = ctx.traffic
    tokens_per_step = traffic["batch"] * traffic["seq_len"]
    model, opt, train_step, loader, leaves = build(ctx)
    feed = iter(loader)
    attempted = failed = 0
    prog = None
    try:
        batches, prog = follow_first_steps(ctx, opt, train_step, feed, leaves)
        for _ in range(traffic["warm_steps"]):
            loss = train_step(*next(feed))
        loss._value.block_until_ready()
    except Exception as e:  # noqa: BLE001 — a fault of the step is counted
        ctx.note(f"set-up step failed: {type(e).__name__}: {e}")
        failed += 1
    setup_s = time.perf_counter() - ctx.t_start
    compiles_before = ctx.compiles.new_compiles

    # ------------------------------------------------------------ window
    in_flight = traffic["steps_in_flight"]
    pending, done_steps = [], 0
    capture = ctx.capture
    capture.start()
    deadline = capture.t0 + ctx.window_seconds
    walls, t_prev = [], capture.t0       # one loop iteration each
    while failed == 0:
        try:
            with ctx.spans.span("data.next"):
                ids, labels = next(feed)
            attempted += 1
            with ctx.spans.span("train.step"):
                pending.append(train_step(ids, labels))
            if len(pending) > in_flight:
                pending.pop(0)._value.block_until_ready()
                done_steps += 1
        except StopIteration:
            ctx.note("the loader ran out of rows before the window closed")
            break
        except Exception as e:  # noqa: BLE001
            ctx.note(f"step {attempted} failed: {type(e).__name__}: {e}")
            failed += 1
            break
        now = time.perf_counter()
        walls.append(now - t_prev)
        t_prev = now
        if now >= deadline:
            break
    last_loss = None
    try:
        if pending:
            last_loss = float(pending[-1].numpy())   # the window closes here
            done_steps += len(pending)
    except Exception as e:  # noqa: BLE001
        ctx.note(f"last step failed: {type(e).__name__}: {e}")
        failed += len(pending)
    capture.stop()
    window_s = capture.t1 - capture.t0
    if last_loss is not None and not np.isfinite(last_loss):
        ctx.note(f"the last loss of the window is {last_loss}")
        failed += 1
    if walls:      # a far-off rate is one stalled iteration or all of them
        waits = ctx.spans.durations.get("data.next", [0.0])
        ctx.note(f"{done_steps} steps in {window_s:.3f} s; loop iteration "
                 f"median {1e3 * np.median(walls):.1f} ms, longest "
                 f"{1e3 * max(walls):.1f} ms (number "
                 f"{int(np.argmax(walls)) + 1}); longest wait for a batch "
                 f"{1e3 * max(waits):.1f} ms")
    new_compiles = ctx.compiles.new_compiles - compiles_before
    memory_peak = ctx.memory_peak()

    feed.close()
    del model, opt, train_step, loader, leaves, feed, pending
    gc.collect()

    # -------------------------------------------------- after the window
    numbers = {}
    if prog is not None:
        t0 = time.perf_counter()
        ref = reference_readings(ctx, batches)
        numbers = compare.training_numbers(prog, ref)
        ctx.note(f"reference followed {FOLLOWED_STEPS} steps in "
                 f"{time.perf_counter() - t0:.1f} s; losses program "
                 f"{prog['loss']} reference {ref['loss']}")
    return {
        "attempted": attempted, "failed": failed,
        "setup_s": setup_s, "window_s": window_s,
        "new_compiles_in_window": new_compiles,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {
            "train_tokens_per_s": done_steps * tokens_per_step / window_s},
        "numbers": numbers,
        "record": {"steps": done_steps, "tokens": done_steps * tokens_per_step,
                   "tokens_per_step": tokens_per_step, "last_loss": last_loss},
    }

