"""A recompute region re-runs under the autocast state of its forward.

``recompute()`` snapshots ``amp.auto_cast``'s thread-local state when it
is called and re-enters it round every run of the region, so the re-run
that ``backward()`` triggers (usually after the ``auto_cast`` block has
exited) and the backward taken through it keep the forward's dtypes.
Reference: RecomputeFunction saves is_fw_autocast / amp_level /
amp_dtype and both lists at forward and re-enters auto_cast in backward.

Gradients are NOT compared bit for bit against the run without
recompute: XLA fuses the region as a whole, and bf16-level differences
appear either way.
"""
import collections
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.amp.auto_cast import amp_state
from paddle_tpu.analysis.jaxpr_rules import _iter_eqns
from paddle_tpu.distributed.recompute import (recompute, recompute_active,
                                              recompute_sequential)
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
from paddle_tpu.observability import metrics as obs_metrics

LAYERS = 2


def _products_by_dtype(closed):
    """dot_general count of a step's jaxpr by operand dtypes."""
    return collections.Counter(
        tuple(str(v.aval.dtype) for v in eqn.invars)
        for eqn in _iter_eqns(closed) if eqn.primitive.name == "dot_general")


def _program_digest(closed):
    """sha256 (16 hex) over every equation of the program in order,
    sub-programs included: primitive, operand and result types, plain
    parameters.  Variable names and the program's own inputs are left
    out — ``to_static`` lifts whatever state is alive in the process,
    so the text of a jaxpr depends on the tests that ran before."""
    lines = []
    for eqn in _iter_eqns(closed):
        plain = {k: v for k, v in eqn.params.items()
                 if isinstance(v, (int, float, bool, str, tuple, type(None),
                                   np.dtype))
                 and "0x" not in str(v) and "{ lambda" not in str(v)}
        lines.append("%s %s -> %s %s" % (
            eqn.primitive.name, [str(v.aval) for v in eqn.invars],
            [str(v.aval) for v in eqn.outvars], sorted(plain.items())))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]


def _tiny_gpt(use_recompute):
    P.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=LAYERS, num_heads=2,
        max_seq_len=32, dropout=0.0, attention_dropout=0.0,
        use_recompute=use_recompute))
    model.train()
    return model


def _gpt_step_jaxpr(how, autocast=True):
    """The traced train step of a 2-layer GPT whose blocks are
    recomputed through `how` (None: not at all); ``backward()`` is
    called after the ``auto_cast`` block has exited, as trainers do."""
    model = _tiny_gpt(use_recompute=how == "recompute")
    if how == "enable_recompute":
        for layer in model.gpt.layers:
            layer.enable_recompute(True)
    crit = GPTPretrainingCriterion()
    opt = P.optimizer.AdamW(learning_rate=1e-3,
                            parameters=model.parameters())

    def logits(ids):
        if how != "recompute_sequential":
            return model(ids)
        gpt = model.gpt
        h = recompute_sequential({"segments": LAYERS}, list(gpt.layers),
                                 gpt.embeddings(ids))
        return P.matmul(gpt.final_ln(h),
                        gpt.embeddings.word_embeddings.weight,
                        transpose_y=True)

    @P.jit.to_static
    def train_step(ids, labels):
        opt.clear_grad()
        with P.amp.auto_cast(enable=autocast, level="O1", dtype="bfloat16"):
            loss = crit(logits(ids), labels)
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(rng.integers(0, 128, (2, 16)), dtype="int64")
    labels = P.to_tensor(rng.integers(0, 128, (2, 16)), dtype="int64")
    return train_step.traced_program(ids, labels)[0]


@pytest.fixture
def attention_dtypes(monkeypatch):
    """dtype of q, k, v at every attention call, in call order."""
    seen = []
    real = F.scaled_dot_product_attention

    def tap(q, k, v, *args, **kwargs):
        seen.append((str(q.dtype), str(k.dtype), str(v.dtype)))
        return real(q, k, v, *args, **kwargs)

    monkeypatch.setattr(F, "scaled_dot_product_attention", tap)
    return seen


@pytest.mark.parametrize(
    "how", ["recompute", "recompute_sequential", "enable_recompute"])
def test_rerun_and_backward_keep_the_forwards_dtypes(how, attention_dtypes):
    plain = _products_by_dtype(_gpt_step_jaxpr(None))
    del attention_dtypes[:]
    remat = _products_by_dtype(_gpt_step_jaxpr(how))
    # the re-run adds products (the region runs twice) and every one of
    # them on the operands the forward had: no f32 product that the
    # step without recompute does not hold as well
    assert sum(remat.values()) > sum(plain.values())
    for dtypes, n in remat.items():
        if "float32" in dtypes:
            assert n <= plain[dtypes], (dtypes, remat, plain)
    # q, k, v reach attention in bf16 in the forward AND in the re-run
    # (one trace = LAYERS forward calls + LAYERS re-run calls)
    assert len(attention_dtypes) >= 2 * LAYERS
    assert set(attention_dtypes) == {("bfloat16",) * 3}, attention_dtypes


def _probe_region(fail_on_run=None):
    """A Linear region that records, at every run, the autocast state
    it sees and the dtype its product came out in."""
    lin = nn.Linear(8, 8)
    runs = []

    def region(x):
        st = amp_state()
        y = lin(x)
        runs.append(dict(enabled=st.enabled, dtype=jnp.dtype(st.dtype).name,
                         level=st.level, white=set(st.custom_white),
                         black=set(st.custom_black), out=str(y.dtype),
                         matmul=str(P.matmul(y, y, transpose_y=True).dtype)))
        if fail_on_run == len(runs):
            raise RuntimeError("region failed")
        return y

    region.__self__ = lin           # recompute() lifts lin's parameters
    x = P.to_tensor(np.ones((2, 8), np.float32), stop_gradient=False)
    return region, lin, x, runs


def test_region_outside_autocast_reruns_in_f32_inside_someones_block():
    region, lin, x, runs = _probe_region()
    y = recompute(region, x)
    with P.amp.auto_cast(level="O1", dtype="bfloat16"):
        y.sum().backward()
    assert [r["enabled"] for r in runs] == [False, False]
    assert [r["out"] for r in runs] == ["float32", "float32"]
    assert lin.weight.grad is not None
    assert str(lin.weight.grad.dtype) == "float32"


def test_lists_level_and_float16_survive_into_the_rerun():
    region, lin, x, runs = _probe_region()
    with P.amp.auto_cast(level="O2", dtype="float16",
                         custom_white_list={"my_op"},
                         custom_black_list={"matmul"}):
        y = recompute(region, x)
    assert not amp_state().enabled
    y.astype("float32").sum().backward()
    assert len(runs) == 2 and runs[0] == runs[1], runs
    assert runs[1] == dict(enabled=True, dtype="float16", level="O2",
                           white={"my_op"}, black={"matmul"},
                           out="float16",      # linear: white-listed
                           matmul="float32")   # matmul: custom black list
    assert lin.weight.grad is not None


def _counts():
    snap = obs_metrics.registry().snapshot()
    prefix = "recompute_regions_total{autocast="
    return collections.Counter(
        {k[len(prefix):-1]: v for k, v in snap.items()
         if k.startswith(prefix)})


def test_nested_outermost_region_wins_and_snapshots_once():
    P.seed(0)
    block = nn.Sequential(nn.Linear(8, 8), nn.GELU(), nn.Linear(8, 8))
    block[0].enable_recompute(True)
    outs = []
    block[0].register_forward_post_hook(
        lambda layer, inp, out: outs.append(
            (recompute_active(), str(out.dtype))))
    x = P.to_tensor(np.ones((2, 8), np.float32), stop_gradient=False)
    before = _counts()
    with P.amp.auto_cast(level="O1", dtype="bfloat16"):
        y = recompute(block, x)
    y.astype("float32").sum().backward()
    # one region: the inner layer ran inside it, forward and re-run,
    # was not wrapped again, and gave bf16 both times
    assert _counts() - before == {"O1/bfloat16": 1}
    assert outs == [(True, "bfloat16"), (True, "bfloat16")]
    assert not recompute_active()
    assert all(p.grad is not None for p in block.parameters())


@pytest.mark.parametrize("fail_on_run", [1, 2], ids=["forward", "rerun"])
def test_state_restored_when_the_region_raises(fail_on_run):
    region, _, x, runs = _probe_region(fail_on_run=fail_on_run)
    ambient = dict(level="O2", dtype="float16", custom_white_list={"a"},
                   custom_black_list={"b"})

    def state():
        st = amp_state()
        return (st.enabled, jnp.dtype(st.dtype).name, st.level,
                set(st.custom_white), set(st.custom_black))

    with pytest.raises(RuntimeError, match="region failed"):
        if fail_on_run == 1:
            with P.amp.auto_cast(**ambient):
                recompute(region, x)
        else:
            y = recompute(region, x)            # snapshot: off
            with P.amp.auto_cast(**ambient):
                try:
                    y.sum().backward()
                finally:
                    # the re-run ran under its own snapshot and gave
                    # the caller's block its state back
                    assert runs[1]["enabled"] is False
                    assert state() == (True, "float16", "O2", {"a"}, {"b"})
    assert len(runs) == fail_on_run
    assert not recompute_active()
    assert state()[0] is False and state()[3:] == (set(), set())


def test_counter_reads_the_regions_of_one_forward():
    model = _tiny_gpt(use_recompute=True)
    ids = P.to_tensor(np.zeros((1, 8)), dtype="int64")
    before = _counts()
    with P.amp.auto_cast(level="O1", dtype="bfloat16"):
        loss = model(ids).astype("float32").mean()
    loss.backward()             # the re-runs are not counted again
    assert _counts() - before == {"O1/bfloat16": LAYERS}
    before = _counts()
    model(ids).mean().backward()
    assert _counts() - before == {"off": LAYERS}


# `_program_digest` of the steps above as the PARENT of PR 34 traces them
# under this container's jax (a jax upgrade re-pins): without autocast a
# recomputed step is the parent's equation for equation, and so is an
# autocast step that recomputes nothing.
# The one program PR 34 changed is pinned as it stands since (the
# parent's read a7488be4c1d5c306).
PINNED = {
    ("recompute", False): "18014ec7926e4ae8",
    (None, False): "f33c946564b1df83",
    (None, True): "321bbc1593310de1",
    ("recompute", True): "90099a1aaf6d3e2e",
}


@pytest.mark.parametrize("how,autocast", list(PINNED),
                         ids=["recompute-f32", "plain-f32", "plain-O1",
                              "recompute-O1"])
def test_programs_the_snapshot_must_not_change(how, autocast):
    jaxpr = _gpt_step_jaxpr(how, autocast=autocast)
    assert _program_digest(jaxpr) == PINNED[(how, autocast)]
