"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, in ONE process, through the entry points a
user calls, at the full width of GPT-355M (random weights from a seed):

1. trainer — ``io.DataLoader`` (two forked workers) over a seeded synthetic
   token dataset -> one ``@jit.to_static`` train step (bf16 ``auto_cast`` O1,
   ``GPTPretrainingCriterion``, ``optimizer.AdamW``), batch 4 x seq 2048, five
   steps over a repeating batch.  Checks: loss finite and falling, exactly one
   compiled entry, and the lowered step holds the Mosaic kernels of flash
   forward/backward and LayerNorm forward/backward (no kernel gave way).
2. server — the same model cast to bf16 behind ``serving.LLMEngine`` with an
   ``AOTProgramCache``; eight seeded requests (prompts 32-1024 tokens, 64 new
   tokens, greedy and temperature/top-p mixed).  Checks: every request
   finishes with its token count, no decode fault was absorbed, compiles stay
   inside the bound, one greedy request agrees with the argmax of a plain
   full-sequence forward, and a second engine boots from the cache directory
   with zero compile events and zero cache errors.

It refuses to run without a TPU, catches nothing (the first failed check is an
uncaught exception: traceback, non-zero exit, no result line), and prints as
its last line ``{"ok": true, "device": {...}}``.

    python chip_smoke.py                  # one chip, full width (the default)
    python chip_smoke.py --chips 4        # four-chip host: dp2 x tp2 training
                                          # against one chip (batch 8 x seq
                                          # 1024), 4 Router replicas on 4
                                          # devices; depth cut to 6
    python chip_smoke.py --tiny-cpu-test  # TEST ONLY: tiny width on the CPU,
                                          # to catch typos before chip time
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time

SEED = 0
NEW_TOKENS = 64
TRAIN_STEPS = 5
FOUR_CHIP_DEPTH = 6          # --chips 4 establishes placement, not depth
# bf16 keeps 8 significant bits: two logits closer than 4 ulp of the larger
# may swap order between the paged and the dense path
TIE_RTOL = 2.0 ** -6
# dp2 x tp2 against one chip: same seed, same global batch, bf16 autocast,
# XLA attention/LayerNorm there against the Pallas kernels here
MESH_LOSS_ATOL = 0.05


class SmokeFailure(AssertionError):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def sizes(tiny):
    if tiny:
        return dict(vocab=512, hidden=64, layers=2, heads=4, seq=64,
                    batch=2, model_len=128, slots=4, page=16,
                    prompt_lo=8, prompt_hi=48, new_tokens=8)
    return dict(vocab=50304, hidden=1024, layers=24, heads=16, seq=2048,
                batch=4, model_len=2048, slots=16, page=16,
                prompt_lo=32, prompt_hi=1024, new_tokens=NEW_TOKENS)


def gpt_config(sz):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=sz["vocab"], hidden_size=sz["hidden"],
                     num_layers=sz["layers"],
                     num_heads=sz["heads"], max_seq_len=sz["model_len"],
                     dropout=0.0, attention_dropout=0.0, use_recompute=True)


class XlaCompileCounter:
    """Counts XLA compile requests and persistent-cache hits (jax's own
    monitoring events); the difference is what was compiled anew."""

    def __init__(self):
        import jax
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def new_compiles(self):
        return self.requests - self.hits


# ------------------------------------------------------------------ trainer
def make_dataset(sz, rows, batch):
    import numpy as np

    from paddle_tpu.io import Dataset

    class SyntheticTokens(Dataset):
        """Seeded token rows; row i repeats with period `batch`, so every
        batch the loader yields is the same batch."""

        def __len__(self):
            return rows

        def __getitem__(self, i):
            rng = np.random.default_rng((SEED, i % batch))
            toks = rng.integers(0, sz["vocab"], sz["seq"] + 1,
                                dtype=np.int32)
            return toks[:-1], toks[1:]

    return SyntheticTokens()


def one_batch(sz):
    from paddle_tpu.io import DataLoader
    return next(iter(DataLoader(make_dataset(sz, sz["batch"], sz["batch"]),
                                batch_size=sz["batch"])))


def mosaic_kernels(lowered_text):
    """{kernel name: count} of the Mosaic custom calls in a lowering."""
    names = re.findall(r'kernel_name = "([^"]+)"', lowered_text)
    return {n: names.count(n) for n in sorted(set(names))}


def build_trainer(cfg):
    import paddle_tpu as P
    from paddle_tpu.models.gpt import (GPTForCausalLM,
                                       GPTPretrainingCriterion)
    P.seed(SEED)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters())

    @P.jit.to_static
    def train_step(ids, labels):
        opt.clear_grad()
        with P.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss = crit(model(ids), labels)
        loss.backward()
        opt.step()
        return loss

    return model, opt, train_step


def run_steps(train_step, loader, place=None):
    """Five steps; returns (losses, wall seconds of each step)."""
    losses, walls = [], []
    for ids, labels in loader:
        if place is not None:
            ids, labels = place(ids), place(labels)
        t0 = time.perf_counter()
        loss = train_step(ids, labels)
        losses.append(float(loss.numpy()))       # waits for the step
        walls.append(round(time.perf_counter() - t0, 3))
    return losses, walls


def trainer_phase(sz, on_tpu):
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu import native
    from paddle_tpu.io import DataLoader
    from paddle_tpu.observability import recompile_log

    print("[trainer] GPT %d x %d layers, batch %d x seq %d" % (
        sz["hidden"], sz["layers"], sz["batch"], sz["seq"]), flush=True)
    model, opt, train_step = build_trainer(gpt_config(sz))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    print(f"  params: {n_params / 1e6:.1f} M", flush=True)

    loader = DataLoader(make_dataset(sz, TRAIN_STEPS * sz["batch"],
                                     sz["batch"]),
                        batch_size=sz["batch"], shuffle=False, num_workers=2)
    print("  data path: %s (libptdata available: %s)" % (
        "2 forked worker processes" if loader._process_mode()
        else "in-process", native.available()), flush=True)

    losses, walls = run_steps(train_step, loader)
    event = [e for e in recompile_log().events()
             if e.kind == "jit" and e.fn == "train_step"][-1]
    print(f"  losses: {[round(v, 4) for v in losses]}", flush=True)
    print(f"  step walls (s): {walls} — the first holds the trace "
          f"({event.trace_ms / 1e3:.1f} s) and compile + first run "
          f"({event.compile_ms / 1e3:.1f} s)", flush=True)
    check(len(losses) == TRAIN_STEPS, f"{TRAIN_STEPS} steps ran")
    check(all(np.isfinite(losses)), "loss finite at every step")
    check(losses[-1] < losses[0], "loss falls over the repeating batch "
          f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    check(len(train_step._compiled) == 1,
          "exactly one compiled entry in train_step._compiled")

    entry = next(iter(train_step._compiled.values()))
    ids, labels = one_batch(sz)
    text = entry.jitted.lower([t._value for t in entry.state_list],
                              [ids._value, labels._value]).as_text()
    kernels = mosaic_kernels(text)
    print(f"  Mosaic kernels in the lowered step: {kernels}", flush=True)
    if on_tpu:
        for name in ("flash_fwd", "flash_dq", "flash_dkv",
                     "layer_norm_fwd", "layer_norm_bwd"):
            check(kernels.get(name, 0) >= 1,
                  f"lowered step calls the Mosaic kernel {name}")
    else:
        check(not kernels, "CPU test run lowers no Mosaic kernel")
    del train_step, opt, entry
    gc.collect()
    return model, losses


# ------------------------------------------------------------------- server
def make_requests(sz):
    import numpy as np

    from paddle_tpu import serving
    rng = np.random.default_rng(SEED)
    lens = [sz["prompt_lo"], sz["prompt_hi"]] + [
        int(n) for n in rng.integers(sz["prompt_lo"], sz["prompt_hi"] + 1, 6)]
    prompts = [[int(t) for t in rng.integers(1, sz["vocab"], n)]
               for n in lens]
    sps = [serving.SamplingParams(max_new_tokens=sz["new_tokens"], seed=i)
           if i % 2 == 0 else
           serving.SamplingParams(max_new_tokens=sz["new_tokens"],
                                  temperature=0.8, top_p=0.95, seed=i)
           for i in range(len(prompts))]
    return prompts, sps


def engine_config(sz):
    import jax.numpy as jnp

    from paddle_tpu import serving
    return serving.EngineConfig(max_num_seqs=sz["slots"],
                                page_size=sz["page"],
                                max_model_len=sz["model_len"],
                                dtype=jnp.bfloat16)


def check_results(results, sz):
    check(all(len(r.output_token_ids) == sz["new_tokens"]
              and r.finish_reason == "length" for r in results),
          f"all {len(results)} requests finished with "
          f"{sz['new_tokens']} tokens")


def check_against_dense(model, result, sz):
    """Greedy tokens of the paged engine against the argmax of one plain
    full-sequence forward of the model over prompt + output."""
    import numpy as np

    import paddle_tpu as P

    @P.jit.to_static
    def dense_forward(ids):
        return model(ids)

    seq = result.prompt_token_ids + result.output_token_ids
    with P.no_grad():
        logits = np.asarray(dense_forward(P.to_tensor(
            np.asarray([seq], np.int32)))._value.astype("float32"))[0]
    first = len(result.prompt_token_ids) - 1
    ties = 0
    for i, tok in enumerate(result.output_token_ids):
        row = logits[first + i]
        if int(row.argmax()) == tok:
            continue
        top = float(row.max())
        gap = top - float(row[tok])
        check(gap <= TIE_RTOL * max(1.0, abs(top)),
              f"position {i}: engine token {tok} is within the bf16 tie "
              f"tolerance of the dense argmax (gap {gap:.4g})")
        ties += 1
    check(ties <= len(result.output_token_ids) // 4,
          f"greedy request agrees with the dense forward "
          f"({len(result.output_token_ids) - ties} of "
          f"{len(result.output_token_ids)} tokens equal, {ties} bf16 ties)")


def server_phase(model, sz, on_tpu):
    from paddle_tpu import serving
    from paddle_tpu.observability import recompile_log
    from paddle_tpu.serving.aot_cache import AOTProgramCache
    from paddle_tpu.utils.compile_cache import serving_aot_dir

    print("[server] LLMEngine bf16, %d slots, max_model_len %d" % (
        sz["slots"], sz["model_len"]), flush=True)
    model.to(dtype="bfloat16")
    model.eval()
    cache = AOTProgramCache(serving_aot_dir())
    engine = serving.LLMEngine(model, engine_config(sz), program_cache=cache)
    boot = engine.warmup()
    print(f"  boot: {boot}; attention: {engine.attention_path}", flush=True)
    if on_tpu:
        check(engine.attention_path.startswith("paged_decode/"),
              "a bf16 engine on the TPU decodes through the Pallas kernel")

    prompts, sps = make_requests(sz)
    t0 = time.perf_counter()
    results = engine.generate(prompts, sps)
    print(f"  served {len(results)} requests (prompts "
          f"{[len(p) for p in prompts]}) in "
          f"{time.perf_counter() - t0:.1f} s after boot", flush=True)
    check_results(results, sz)
    m = engine.metrics
    check(m.decode_fault_recoveries == 0, "no decode fault was absorbed")
    check(m.compile_count <= engine.config.compile_bound,
          f"compiles {m.compile_count} <= bound "
          f"{engine.config.compile_bound}")
    check(m.compile_count + m.aot_cache_loads == boot["programs"],
          "serving ran only the programs the boot prepared")
    check_against_dense(model, results[0], sz)
    engine.shutdown()

    events = recompile_log().count
    second = serving.LLMEngine(model, engine_config(sz), program_cache=cache)
    boot2 = second.warmup()
    print(f"  second engine boot: {boot2}", flush=True)
    check(boot2["compiled"] == 0 and recompile_log().count == events,
          "second engine booted from the cache with zero compile events")
    check(boot2["cache_loads"] == boot["programs"],
          f"all {boot['programs']} programs loaded from the cache")
    (again,) = second.generate(prompts[:1], sps[:1])
    check(again.output_token_ids == results[0].output_token_ids,
          "loaded programs reproduce the first engine's tokens")
    second.shutdown()
    stats = cache.stats()
    print(f"  AOT cache: {stats}", flush=True)
    check(stats["errors"] == 0 and stats["serialize_supported"],
          "AOT cache stored and loaded every program without an error")


# ---------------------------------------------------------------- four chips
def mesh_trainer_phase(sz, one_chip_losses):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu as P
    from paddle_tpu import distributed
    from paddle_tpu.distributed.mesh import get_dist_spec, set_mesh
    from paddle_tpu.io import DataLoader

    print("[trainer dp2 x tp2] same seed, same global batch as the "
          "one-chip run above", flush=True)
    mesh = distributed.init_mesh({"dp": 2, "tp": 2},
                                 devices=jax.devices()[:4])
    model, opt, train_step = build_trainer(gpt_config(sz))
    data = NamedSharding(mesh, PartitionSpec("dp", None))
    loader = DataLoader(make_dataset(sz, TRAIN_STEPS * sz["batch"],
                                     sz["batch"]),
                        batch_size=sz["batch"], shuffle=False, num_workers=2)
    losses, walls = run_steps(
        train_step, loader,
        place=lambda t: P.Tensor(jax.device_put(t._value, data)))
    print(f"  losses: {[round(v, 4) for v in losses]}", flush=True)
    print(f"  step walls (s): {walls}", flush=True)
    check(len(train_step._compiled) == 1,
          "exactly one compiled entry in train_step._compiled")

    sharded = [p for p in model.parameters()
               if "tp" in tuple(get_dist_spec(p) or ())]
    check(len(sharded) > 0, f"{len(sharded)} parameters carry a tp spec")
    moments = [t for (name, _), t in opt._accumulators.items()
               if name in ("moment1", "moment2")
               and "tp" in tuple(get_dist_spec(t) or ())]
    check(len(moments) == 2 * len(sharded),
          "every tp parameter's two AdamW moments carry its spec")
    whole = [
        f"{t.name} {[s.data.shape for s in t._value.addressable_shards]}"
        for t in sharded + moments
        if len(t._value.addressable_shards) != 4 or any(
            s.data.shape == t._value.shape
            for s in t._value.addressable_shards)]
    check(not whole, "tp parameters and their moments live as four shards, "
          "each smaller than the global shape"
          + (f" — not so: {whole}" if whole else ""))

    entry = next(iter(train_step._compiled.values()))
    ids, labels = one_batch(sz)
    hlo = entry.jitted.lower(
        [t._value for t in entry.state_list],
        [jax.device_put(ids._value, data),
         jax.device_put(labels._value, data)]).compile().as_text()
    n_ar = len(re.findall(r"\ball-reduce(-start)?\(", hlo))
    check(n_ar > 0, f"compiled step holds {n_ar} all-reduce ops")
    worst = max(abs(a - b) for a, b in zip(losses, one_chip_losses))
    check(worst <= MESH_LOSS_ATOL and np.isfinite(worst),
          f"loss curve within {MESH_LOSS_ATOL} of the one-chip run "
          f"(worst |diff| {worst:.4f})")
    del train_step, opt, entry
    set_mesh(None)
    gc.collect()
    return model


def router_phase(model, sz):
    import jax

    from paddle_tpu.serving.aot_cache import AOTProgramCache
    from paddle_tpu.serving.router import Router
    from paddle_tpu.utils.compile_cache import serving_aot_dir

    print("[router] 4 replicas, default factory", flush=True)
    model.to(dtype="bfloat16")
    model.eval()
    cache = AOTProgramCache(serving_aot_dir())
    t0 = time.perf_counter()
    router = Router(model, engine_config(sz), num_replicas=4,
                    program_cache=cache)
    print(f"  boot {time.perf_counter() - t0:.1f} s: "
          f"{[h.boot_info for h in router.replicas]}", flush=True)
    for i, h in enumerate(router.replicas):
        where = {d for pool in h.engine._k_pools + h.engine._v_pools
                 for d in pool.devices()}
        where |= {d for v in h.engine._params.values()
                  for d in v.devices()}
        check(where == {jax.devices()[i]},
              f"replica {i}: weights and KV pools live on "
              f"{jax.devices()[i]} only")
    prompts, sps = make_requests(sz)
    t0 = time.perf_counter()
    results = router.generate(prompts, sps)
    print(f"  served {len(results)} requests in "
          f"{time.perf_counter() - t0:.1f} s on replicas "
          f"{[r.replica for r in results]}", flush=True)
    check_results(results, sz)
    check(len({r.replica for r in results}) == 4,
          "all four replicas served requests")
    snap = router.snapshot()
    check(snap["failovers"] == 0 and all(
        h.engine.metrics.decode_fault_recoveries == 0
        for h in router.replicas), "no failover, no absorbed decode fault")
    check(sum(1 for h in router.replicas if h.boot_info["warm"]) >= 3,
          "replicas on other chips booted from the programs replica 0 "
          "compiled")
    stats = cache.stats()
    print(f"  AOT cache: {stats}", flush=True)
    check(stats["errors"] == 0, "AOT cache: zero errors")
    router.shutdown()


# --------------------------------------------------------------------- main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny-cpu-test", action="store_true",
                    help="TEST ONLY: tiny width, runs on the CPU backend")
    args = ap.parse_args()
    if args.tiny_cpu_test:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny_cpu_test:
        raise SystemExit(
            f"chip_smoke.py needs a TPU; jax found {dev.platform!r}")
    if len(jax.devices()) < args.chips:
        raise SystemExit(f"--chips {args.chips} on a host with "
                         f"{len(jax.devices())} device(s)")

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    xla = XlaCompileCounter()
    sz = sizes(args.tiny_cpu_test)
    t_start = time.perf_counter()

    if args.chips == 1:
        model, _ = trainer_phase(sz, on_tpu)
        server_phase(model, sz, on_tpu)
    else:
        sz = dict(sz, layers=min(sz["layers"], FOUR_CHIP_DEPTH),
                  batch=2 * sz["batch"], seq=sz["seq"] // 2)
        model, losses = trainer_phase(sz, on_tpu)
        del model
        gc.collect()
        model = mesh_trainer_phase(sz, losses)
        router_phase(model, sz)

    print(f"XLA compile requests: {xla.requests}, served from the "
          f"persistent cache: {xla.hits}, new compiles: "
          f"{xla.new_compiles}", flush=True)
    print(f"total: {time.perf_counter() - t_start:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
