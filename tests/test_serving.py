"""paddle_tpu.serving — continuous-batching engine, scheduler policy,
traced sampler, metrics, and the bounded-recompile contract.

The e2e tests drive the REAL engine (tiny GPT, compiled prefill/decode)
on the CPU mesh; scheduler/sampler/metrics units run without compiling
anything.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.serving.request import Request, RequestState
from paddle_tpu.serving.sampler import sample_tokens, sampler_path
from paddle_tpu.serving.scheduler import (Scheduler, bucket_for,
                                          default_buckets)

pytestmark = pytest.mark.serving


# ------------------------------------------------------------- bucketing
class TestBucketing:
    def test_bucket_for_picks_smallest_cover(self):
        buckets = (16, 32, 64)
        assert bucket_for(1, buckets) == 16
        assert bucket_for(16, buckets) == 16
        assert bucket_for(17, buckets) == 32
        assert bucket_for(64, buckets) == 64

    def test_bucket_overflow_raises(self):
        with pytest.raises(ValueError, match="exceeds the largest"):
            bucket_for(65, (16, 32, 64))

    def test_default_buckets_cover_max_len(self):
        assert default_buckets(256) == (16, 32, 64, 128, 256)
        assert default_buckets(100) == (16, 32, 64, 100)
        assert default_buckets(8) == (8,)

    def test_compile_bound_declared(self):
        cfg = serving.EngineConfig(max_model_len=64,
                                   prefill_buckets=(16, 32, 64))
        assert cfg.compile_bound == 3 + 3
        assert cfg.compile_bound <= 2 * len(cfg.prefill_buckets)


# ----------------------------------------------------- scheduler policy
def _req(i, prompt_len=4, **sp):
    r = Request(f"r{i}", list(range(1, prompt_len + 1)),
                serving.SamplingParams(**sp) if sp
                else serving.SamplingParams(), arrival_index=i)
    return r


class TestScheduler:
    @pytest.mark.smoke
    def test_fcfs_order_and_head_of_line_blocking(self):
        s = Scheduler(buckets=(16,), page_size=4, growth_reserve_pages=0)
        big = _req(0, prompt_len=16)     # needs 4 pages
        small = _req(1, prompt_len=2)    # needs 1 page
        s.enqueue(big)
        s.enqueue(small)
        # only 2 pages free: the head doesn't fit, and FCFS refuses to
        # let the small one jump the queue
        assert s.pop_admissible(free_slots=4, free_pages=2) is None
        assert s.queue_depth == 2
        # pool grows: head goes first
        assert s.pop_admissible(4, 10) is big
        assert s.pop_admissible(4, 10) is small

    def test_no_free_slot_blocks(self):
        s = Scheduler((16,), 4)
        s.enqueue(_req(0))
        assert s.pop_admissible(free_slots=0, free_pages=100) is None

    def test_page_budget_includes_growth_reserve(self):
        s = Scheduler((16,), page_size=4, growth_reserve_pages=1)
        r = _req(0, prompt_len=8)        # 2 pages + 1 reserve
        assert s.pages_for_prompt(8) == 3
        s.enqueue(r)
        assert s.pop_admissible(1, 2) is None
        assert s.pop_admissible(1, 3) is r

    def test_victim_selection_is_latest_arrival(self):
        s = Scheduler((16,), 4)
        rs = [_req(i) for i in range(3)]
        for r in rs:
            r.state = RequestState.DECODE
        assert s.select_victim(rs) is rs[2]
        # PREFILL-state rows are not preemptible
        rs[2].state = RequestState.PREFILL
        assert s.select_victim(rs) is rs[1]

    def test_requeue_front_keeps_priority(self):
        s = Scheduler((16,), 4)
        a, b = _req(0), _req(1)
        s.enqueue(a)
        s.enqueue(b)
        assert s.pop_admissible(4, 100) is a
        s.requeue_front(a)
        assert s.pop_admissible(4, 100) is a


# ------------------------------------------------------- request states
class TestRequestStateMachine:
    def test_lifecycle_transitions(self):
        r = _req(0)
        r.transition(RequestState.PREFILL)
        r.transition(RequestState.DECODE)
        r.transition(RequestState.EVICTED)
        r.transition(RequestState.PREFILL)
        r.transition(RequestState.DECODE)
        r.transition(RequestState.FINISHED)

    def test_illegal_transition_raises(self):
        r = _req(0)
        with pytest.raises(RuntimeError, match="illegal request"):
            r.transition(RequestState.DECODE)   # waiting -> decode

    def test_replay_tokens_include_generated(self):
        r = _req(0, prompt_len=3)
        r.state = RequestState.DECODE
        r.append_token(7)
        r.append_token(9)
        assert r.replay_token_ids == [1, 2, 3, 7, 9]
        assert r.total_len == 5

    def test_sampling_params_validation(self):
        with pytest.raises(ValueError):
            serving.SamplingParams(max_new_tokens=0)
        with pytest.raises(ValueError):
            serving.SamplingParams(temperature=-1.0)
        with pytest.raises(ValueError):
            serving.SamplingParams(top_p=0.0)


# -------------------------------------------------------------- sampler
class TestSampler:
    def _logits(self, v=16):
        rng = np.random.default_rng(0)
        return jnp.asarray(rng.standard_normal((3, v)).astype(np.float32))

    def _args(self, lg, **kw):
        b = lg.shape[0]
        d = dict(seeds=np.zeros(b, np.int32),
                 positions=np.zeros(b, np.int32),
                 temperatures=np.zeros(b, np.float32),
                 top_ks=np.zeros(b, np.int32),
                 top_ps=np.ones(b, np.float32))
        d.update({k: np.asarray(v) for k, v in kw.items()})
        return (lg, jnp.asarray(d["seeds"]), jnp.asarray(d["positions"]),
                jnp.asarray(d["temperatures"]), jnp.asarray(d["top_ks"]),
                jnp.asarray(d["top_ps"]))

    def test_greedy_is_argmax(self):
        lg = self._logits()
        out = sample_tokens(*self._args(lg))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.argmax(np.asarray(lg), -1))

    def test_seed_and_position_determinism(self):
        lg = self._logits()
        a1 = self._args(lg, temperatures=np.full(3, 0.8, np.float32),
                        seeds=np.array([1, 2, 3], np.int32),
                        positions=np.array([5, 6, 7], np.int32))
        t1 = np.asarray(sample_tokens(*a1))
        t2 = np.asarray(sample_tokens(*a1))
        np.testing.assert_array_equal(t1, t2)
        # different position -> (almost surely) independent draw path;
        # at minimum it must not crash and stays in-vocab
        a2 = self._args(lg, temperatures=np.full(3, 0.8, np.float32),
                        seeds=np.array([1, 2, 3], np.int32),
                        positions=np.array([8, 9, 10], np.int32))
        t3 = np.asarray(sample_tokens(*a2))
        assert ((0 <= t3) & (t3 < 16)).all()

    def test_top_k_restricts_support(self):
        lg = self._logits()
        top2 = np.argsort(np.asarray(lg), -1)[:, -2:]
        for seed in range(8):
            out = np.asarray(sample_tokens(*self._args(
                lg, temperatures=np.full(3, 1.5, np.float32),
                seeds=np.full(3, seed, np.int32),
                top_ks=np.full(3, 2, np.int32))))
            for b in range(3):
                assert out[b] in top2[b]

    def test_top_p_tiny_is_greedy(self):
        lg = self._logits()
        out = np.asarray(sample_tokens(*self._args(
            lg, temperatures=np.full(3, 1.0, np.float32),
            top_ps=np.full(3, 1e-6, np.float32))))
        np.testing.assert_array_equal(out, np.argmax(np.asarray(lg), -1))

    # ---- the threshold search draws what the sorting sampler drew
    def _rows(self, v, width, seed, temps, ks, ps):
        """One seeded batch: the knobs cycle over the rows, each list
        from another offset, so a batch mixes every combination."""
        rng = np.random.default_rng(seed)
        lg = (rng.standard_normal((width, v)) * 3).astype(np.float32)

        def pick(xs, off):
            return np.asarray([xs[(i + off) % len(xs)]
                               for i in range(width)])
        return self._args(
            jnp.asarray(lg),
            seeds=rng.integers(0, 2 ** 31 - 1, width).astype(np.int32),
            positions=rng.integers(0, 4096, width).astype(np.int32),
            temperatures=pick(temps, seed).astype(np.float32),
            top_ks=pick(ks, seed // 2).astype(np.int32),
            top_ps=pick(ps, seed // 3).astype(np.float32))

    def _same_as_sorted(self, args):
        got = np.asarray(jax.jit(sample_tokens)(*args))
        want = np.asarray(jax.jit(jax.vmap(_sample_row_sorted))(*args))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("seed", range(6))
    @pytest.mark.parametrize("v,width", [(1000, 1), (1000, 32),
                                         (50304, 32)])
    def test_mixed_rows_draw_the_sorted_token(self, v, width, seed):
        self._same_as_sorted(self._rows(
            v, width, seed, temps=(0.0, 0.8, 1.5), ks=(0, 1, 20, v),
            ps=(1.0, 0.95, 0.5, 1e-6, 0.95)))

    def test_widest_vocabulary_draws_the_sorted_token(self):
        self._same_as_sorted(self._rows(
            128256, 32, 11, temps=(0.0, 0.8, 1.5), ks=(0, 20, 128256),
            ps=(1.0, 0.95, 0.5, 1e-6, 0.95)))

    @pytest.mark.parametrize("case,temps,ks,ps", [
        ("all_greedy", (0.0,), (0, 20), (1.0, 0.5)),
        ("no_top_p", (0.0, 0.8, 1.5), (0, 20), (1.0,)),
        ("no_top_k", (0.0, 0.8, 1.5), (0,), (0.95, 0.5)),
        ("draw_only", (0.8, 1.5), (0,), (1.0,)),
        ("both_in_every_row", (0.8,), (20,), (0.5,)),
    ])
    @pytest.mark.parametrize("width", [1, 32])
    def test_each_branch_draws_the_sorted_token(self, case, temps, ks, ps,
                                                width):
        args = self._rows(1000, width, 5, temps, ks, ps)
        self._same_as_sorted(args)
        path = tuple(map(bool, sampler_path(*map(np.asarray, args[3:]))))
        want = {"all_greedy": (False, False, False),
                "no_top_p": (None, None, False),
                "no_top_k": (None, False, None),
                "draw_only": (True, False, False),
                "both_in_every_row": (True, True, True)}[case]
        assert all(w is None or w == got for w, got in zip(want, path))

    @pytest.mark.parametrize("seed", range(3))
    def test_ties_at_the_kth_value_are_kept(self, seed):
        """Logits on a coarse grid: the k-th value is shared by many
        tokens, all of which stay (`scaled < kth` drops none of them)."""
        args = list(self._rows(1000, 32, seed, temps=(0.8, 1.5),
                               ks=(1, 5, 20, 500), ps=(1.0, 0.9)))
        args[0] = jnp.round(args[0])
        self._same_as_sorted(tuple(args))

    @pytest.mark.parametrize("seed", range(3))
    def test_rows_with_minus_inf_logits(self, seed):
        args = list(self._rows(1000, 32, seed, temps=(0.0, 0.8, 1.5),
                               ks=(0, 20, 1000), ps=(1.0, 0.95, 0.5)))
        banned = np.random.default_rng(seed).random((32, 1000)) < 0.7
        args[0] = jnp.where(jnp.asarray(banned), -jnp.inf, args[0])
        self._same_as_sorted(tuple(args))

    @pytest.mark.parametrize("width", [1, 4])
    def test_no_sort_in_the_compiled_sampler(self, width):
        def primitives(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from primitives(sub)
        args = self._rows(1000, width, 0, (0.8,), (20,), (0.5,))
        names = set(primitives(jax.make_jaxpr(sample_tokens)(*args).jaxpr))
        assert "cond" in names and names & {"while", "scan"}
        assert not {n for n in names if "sort" in n or "top_k" in n}


def _sample_row_sorted(logits, seed, position, temperature, top_k, top_p):
    """The sampler as it was while it sorted the vocabulary, verbatim:
    the reference `sample_tokens` must keep drawing from."""
    _NEG_INF = jnp.finfo(jnp.float32).min
    V = logits.shape[0]
    logits = logits.astype(jnp.float32)

    # temperature; <=0 means greedy (selected at the end)
    scaled = logits / jnp.maximum(temperature, 1e-6)

    # top-k: mask everything below the k-th largest logit (k<=0: off)
    k = jnp.where(top_k <= 0, V, jnp.clip(top_k, 1, V))
    desc = jnp.sort(scaled)[::-1]
    kth = desc[jnp.maximum(k - 1, 0)]
    scaled = jnp.where(scaled < kth, _NEG_INF, scaled)

    # top-p (nucleus) over the top-k-filtered distribution: keep the
    # smallest prefix of descending-prob tokens whose mass reaches p
    probs = jax.nn.softmax(scaled)
    sp = jnp.sort(probs)[::-1]
    cum = jnp.cumsum(sp)
    keep_sorted = (cum - sp) < top_p        # mass BEFORE this token < p
    keep_sorted = keep_sorted.at[0].set(True)  # never drop the argmax
    pmin = jnp.min(jnp.where(keep_sorted, sp, jnp.inf))
    log_probs = jnp.where(probs >= pmin, jnp.log(probs), _NEG_INF)

    # Gumbel-max draw from the filtered distribution
    key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
    gumbel = jax.random.gumbel(key, (V,), jnp.float32)
    sampled = jnp.argmax(log_probs + gumbel)

    greedy = jnp.argmax(logits)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


# -------------------------------------------------------------- metrics
class TestMetrics:
    def test_snapshot_schema(self):
        m = serving.EngineMetrics()
        m.pages_total = 10
        m.pages_in_use = 5
        m.generated_tokens = 100
        m.ttft.observe(0.25)
        snap = m.snapshot()
        for key in ("requests", "queue_depth", "running", "steps",
                    "tokens", "pages", "compiles", "ttft_ms",
                    "inter_token_ms", "e2e_latency_ms"):
            assert key in snap, key
        assert snap["pages"]["utilization"] == 0.5
        assert snap["ttft_ms"]["p50"] == 250.0
        assert snap["tokens"]["per_s"] > 0

    def test_compile_bound_enforced(self):
        m = serving.EngineMetrics()
        m.compile_bound = 2
        m.note_compile()
        m.note_compile()
        with pytest.raises(RuntimeError, match="recompile storm"):
            m.note_compile()

    def test_histogram_percentiles(self):
        h = serving.Histogram()
        for i in range(1, 101):
            h.observe(i / 1000.0)
        s = h.summary()
        assert s["count"] == 100
        assert s["p50"] == pytest.approx(50.0, abs=2.0)
        assert s["p99"] == pytest.approx(99.0, abs=2.0)


# ------------------------------------------------------- engine (e2e)
@pytest.fixture(scope="module")
def tiny_model():
    P.seed(0)
    return GPTForCausalLM(gpt3_tiny())


def _cfg(**kw):
    d = dict(max_num_seqs=8, page_size=4, max_model_len=48,
             prefill_buckets=(8, 16, 32))
    d.update(kw)
    return serving.EngineConfig(**d)


class TestEngineE2E:
    def test_continuous_batching_token_identical_to_sequential(
            self, tiny_model):
        """Acceptance: >= 8 concurrent mixed-length requests through
        continuous batching produce tokens identical to one-at-a-time
        decode, and the compile counter stays within the declared
        bucket bound."""
        rng = np.random.default_rng(42)
        prompts = [list(rng.integers(1, 256, n))
                   for n in (3, 7, 12, 5, 17, 2, 9, 27)]
        sps = [serving.SamplingParams(
            max_new_tokens=6, temperature=0.7 if i % 2 else 0.0,
            top_k=20 if i % 3 else 0, top_p=0.9 if i % 2 else 1.0,
            seed=i) for i in range(len(prompts))]

        cont = serving.LLMEngine(tiny_model, _cfg())
        batched = cont.generate(prompts, sps)
        assert cont.metrics.compile_count <= \
            2 * len(cont.config.prefill_buckets)
        assert cont.metrics.compile_count <= cont.metrics.compile_bound
        cont.shutdown()

        seq = serving.LLMEngine(tiny_model, _cfg())
        for i, (p, sp) in enumerate(zip(prompts, sps)):
            (one,) = seq.generate([p], [sp])
            assert one.output_token_ids == batched[i].output_token_ids, \
                f"request {i} diverged"
        seq.shutdown()

        assert all(len(r.output_token_ids) == 6 for r in batched)
        snap = cont.metrics.snapshot()
        assert snap["requests"]["finished"] == 8
        assert snap["pages"]["in_use"] == 0          # all freed

    def test_preemption_is_deterministic_and_token_identical(
            self, tiny_model):
        """Pages run out mid-decode: the latest-arrived request is
        evicted, replayed, and still produces the sequential tokens."""
        cfg = _cfg(max_num_seqs=4, max_model_len=16, num_pages=11,
                   prefill_buckets=(8, 16))
        rng = np.random.default_rng(3)
        prompts = [list(rng.integers(1, 256, 3 + i)) for i in range(4)]
        sps = [serving.SamplingParams(max_new_tokens=8, temperature=0.9,
                                      seed=i) for i in range(4)]
        eng = serving.LLMEngine(tiny_model, cfg)
        res = eng.generate(prompts, sps)
        assert eng.metrics.requests_evicted >= 1    # pressure was real
        assert eng.metrics.compile_count <= eng.metrics.compile_bound
        eng.shutdown()

        seq = serving.LLMEngine(tiny_model, cfg)
        for i, (p, sp) in enumerate(zip(prompts, sps)):
            (one,) = seq.generate([p], [sp])
            assert one.output_token_ids == res[i].output_token_ids
        seq.shutdown()

        # determinism of the whole schedule: run the batch again
        eng2 = serving.LLMEngine(tiny_model, cfg)
        res2 = eng2.generate(prompts, sps)
        assert [r.output_token_ids for r in res2] == \
            [r.output_token_ids for r in res]
        assert eng2.metrics.requests_evicted == eng.metrics.requests_evicted
        eng2.shutdown()

    def test_streaming_callbacks_and_step_api(self, tiny_model):
        eng = serving.LLMEngine(tiny_model, _cfg(max_num_seqs=2))
        got = []
        eng.add_request([5, 6, 7],
                        serving.SamplingParams(max_new_tokens=4),
                        stream=lambda r, t, fin: got.append((t, fin)))
        steps = 0
        while eng.has_unfinished():
            events = eng.step()
            steps += 1
            for rid, tok, fin in events:
                assert rid == "req-0"
        assert len(got) == 4
        assert got[-1][1] is True           # finished flag on last token
        assert [f for _, f in got[:-1]] == [False] * 3
        assert steps <= 5
        eng.shutdown()

    def test_eos_stops_early(self, tiny_model):
        eng = serving.LLMEngine(tiny_model, _cfg(max_num_seqs=1))
        # greedy decode from this prompt repeats a token; use the first
        # generated token as eos for a second run -> stops at 1 token
        (probe,) = eng.generate([[9, 8, 7]],
                                serving.SamplingParams(max_new_tokens=3))
        eos = probe.output_token_ids[1]
        (r,) = eng.generate([[9, 8, 7]], serving.SamplingParams(
            max_new_tokens=8, eos_token_id=eos))
        assert r.finish_reason == "stop"
        assert r.output_token_ids[-1] == eos
        assert len(r.output_token_ids) <= 3
        eng.shutdown()

    def test_request_validation(self, tiny_model):
        eng = serving.LLMEngine(tiny_model, _cfg())
        with pytest.raises(ValueError, match="max_model_len"):
            eng.add_request(list(range(1, 40)),
                            serving.SamplingParams(max_new_tokens=20))
        with pytest.raises(ValueError, match="at least one token"):
            eng.add_request([], serving.SamplingParams())
        # worst-case REPLAY length (prompt + max_new - 1) must be
        # bucketable, or an eviction could crash the engine mid-flight:
        # prompt 28 buckets fine at 32, but 28 + 10 - 1 = 37 does not
        with pytest.raises(ValueError, match="largest bucket"):
            eng.add_request(list(range(1, 29)),
                            serving.SamplingParams(max_new_tokens=10))
        eng.shutdown()

    def test_compile_counter_stable_across_reuse(self, tiny_model):
        """Serving many mixed batches must never compile past the
        declared bound (the recompile-storm tripwire)."""
        eng = serving.LLMEngine(tiny_model, _cfg())
        rng = np.random.default_rng(0)
        for round_ in range(3):
            prompts = [list(rng.integers(1, 256, int(n)))
                       for n in rng.integers(2, 30, 5)]
            eng.generate(prompts,
                         serving.SamplingParams(max_new_tokens=3))
        assert eng.metrics.compile_count <= eng.metrics.compile_bound
        snap = eng.metrics.snapshot()
        assert snap["compiles"]["count"] == eng.metrics.compile_count
        eng.shutdown()

    def test_profiler_metrics_report_wiring(self, tiny_model):
        from paddle_tpu import profiler
        eng = serving.LLMEngine(tiny_model, _cfg(max_num_seqs=1),
                                metrics_name="serving.pytest")
        eng.generate([[1, 2, 3]], serving.SamplingParams(max_new_tokens=2))
        rep = profiler.metrics_report()
        assert "serving.pytest" in rep
        assert rep["serving.pytest"]["tokens"]["generated"] == 2
        eng.shutdown()
        assert "serving.pytest" not in profiler.metrics_report()

    def test_predictor_serve_adapter(self, tiny_model):
        from paddle_tpu import inference
        cfg = inference.Config()
        cfg.set_layer(tiny_model)
        eng = inference.create_predictor(cfg).serve(
            max_num_seqs=2, page_size=4, max_model_len=32,
            prefill_buckets=(8, 16))
        (r,) = eng.generate([[3, 1, 4]],
                            serving.SamplingParams(max_new_tokens=2))
        assert len(r.output_token_ids) == 2
        eng.shutdown()


# ------------------------------------------------- CI baseline gates
def test_api_coverage_native_namespace_baseline():
    """The checked-in api_coverage baseline records the paddle_tpu-native
    namespaces (serving, analysis); the current surface must not regress
    against it."""
    import json
    import os
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import api_coverage
    finally:
        sys.path.remove(tools)
    doc = api_coverage.to_json_doc(api_coverage.collect())
    assert "<native>.serving" in doc["namespaces"]
    with open(os.path.join(tools, "api_coverage_baseline.json"),
              encoding="utf-8") as fh:
        baseline = json.load(fh)
    assert "<native>.serving" in baseline["namespaces"]
    assert api_coverage.diff_regressions(doc, baseline) == []


class TestEngineLifecycleHygiene:
    def test_unadmittable_request_rejected_up_front(self, tiny_model):
        """A request whose admission (pages + growth reserve) can never
        be satisfied even on an empty pool must fail at add_request, not
        deadlock generate() later."""
        cfg = serving.EngineConfig(max_num_seqs=1, page_size=4,
                                   max_model_len=16,
                                   prefill_buckets=(16,))
        eng = serving.LLMEngine(tiny_model, cfg)
        # 4 allocatable pages; prompt 13 needs ceil(13/4)+1 reserve = 5
        with pytest.raises(ValueError, match="growth reserve"):
            eng.add_request(list(range(1, 14)),
                            serving.SamplingParams(max_new_tokens=3))
        # a genuinely servable request still goes through
        (r,) = eng.generate([[1, 2, 3]],
                            serving.SamplingParams(max_new_tokens=2))
        assert len(r.output_token_ids) == 2
        eng.shutdown()

    def test_finished_requests_move_out_of_live_table(self, tiny_model):
        """The live request table must drain as requests finish (a
        perpetual step() loop must not leak one Request per request
        served); finished ones stay inspectable up to the retention
        cap."""
        cfg = _cfg(max_num_seqs=2, finished_retention=3)
        eng = serving.LLMEngine(tiny_model, cfg)
        for i in range(5):
            eng.add_request([1 + i, 2, 3],
                            serving.SamplingParams(max_new_tokens=2))
        while eng.has_unfinished():
            eng.step()
        assert eng._requests == {}
        assert len(eng.finished_requests) == 3      # capped, oldest gone
        assert list(eng.finished_requests) == ["req-2", "req-3", "req-4"]
        # generate() drains its own entries
        eng.generate([[9, 9]], serving.SamplingParams(max_new_tokens=1))
        assert "req-5" not in eng.finished_requests
        eng.shutdown()

    def test_kv_ctx_with_recompute_training_raises(self):
        """Serving a recompute-enabled model left in training mode must
        fail loudly, not silently skip the cache writes."""
        P.seed(0)
        model = GPTForCausalLM(gpt3_tiny(use_recompute=True))
        eng = serving.LLMEngine(model, _cfg(max_num_seqs=1))
        model.train()      # user error after engine init
        with pytest.raises(RuntimeError, match="eval mode"):
            eng.generate([[1, 2, 3]],
                         serving.SamplingParams(max_new_tokens=1))
        model.eval()
        eng.shutdown()

    def test_generate_batch_validation_is_all_or_nothing(self, tiny_model):
        """A bad prompt anywhere in the batch must reject the WHOLE
        generate() call before anything is enqueued — no stranded
        requests silently served and discarded later."""
        eng = serving.LLMEngine(tiny_model, _cfg(max_num_seqs=2))
        with pytest.raises(ValueError, match="max_model_len"):
            eng.generate([[1, 2, 3], list(range(1, 45))],
                         serving.SamplingParams(max_new_tokens=8))
        assert eng.scheduler.queue_depth == 0      # nothing enqueued
        assert eng._requests == {}
        # the engine is unharmed: a clean batch still serves
        (r,) = eng.generate([[1, 2, 3]],
                            serving.SamplingParams(max_new_tokens=2))
        assert len(r.output_token_ids) == 2
        eng.shutdown()
