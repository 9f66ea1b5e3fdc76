"""GPT's serving programs are what they were before the engine learned to
size its pools from a model's declaration (the PR that brought the latent
pool): same prefill and decode jaxprs, so its timings do not move.  The
two sampler digests and the fingerprint are those of the sampler that
searches its cut-offs (`SAMPLER_REVISION` 2); the model's four are older.

The digests are of the jaxprs' text under this container's jax; a jax
upgrade changes the text, not the programs: regenerate them then from a
tree known to be good (the loop below prints what it found)."""
import hashlib

import pytest

import paddle_tpu as P
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

PINNED = {
    "decode": "025e37112adaaf33",
    "prefill_16": "dcedaa8bd41290b1",
    "prefill_32": "c5c9a83e2d916c04",
    "prefill_64": "800debbb78504944",
    "sample_1": "754b408424c64bc3",
    "sample_4": "4dd049c980ec47d2",
}
FINGERPRINT = "b5ae386a0e38a4dc77417c47"


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    P.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    model.eval()
    eng = serving.LLMEngine(
        model, serving.EngineConfig(max_num_seqs=4, page_size=8,
                                    max_model_len=64),
        program_cache=str(tmp_path_factory.mktemp("aot")))
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def digests(engine):
    found = {name: hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
             for name, jaxpr in engine.audit_programs().items()}
    print(found)
    return found


@pytest.mark.parametrize("program", sorted(PINNED))
def test_gpt_program_unchanged(digests, program):
    assert digests[program] == PINNED[program]


def test_gpt_fingerprint_and_pools_unchanged(engine):
    assert engine.program_fingerprint == FINGERPRINT
    assert engine.attention_path == "xla"
    assert engine._pool.kind == "kv"
    assert len(engine._k_pools) == len(engine._v_pools) == 2
    assert "moe" not in engine.metrics.snapshot()
