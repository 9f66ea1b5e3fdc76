"""GPT's serving programs are what they were before the engine learned to
size its pools from a model's declaration (the PR that brought the latent
pool): same prefill jaxprs, so their timings do not move.  The two sampler
digests are those of the sampler that searches its cut-offs
(`SAMPLER_REVISION` 2); the prefills' are older.  The decode digest and
the fingerprint are those of the decode pass that takes the previous
pass's sampler output (`NEXT_TOKEN_REVISION` 1: the run-ahead).

The digests are of the jaxprs' text under this container's jax; a jax
upgrade changes the text, not the programs: regenerate them then from a
tree known to be good (the loop below prints what it found)."""
import hashlib

import pytest

import paddle_tpu as P
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

PINNED = {
    "decode": "87ffba66d22a6118",
    "prefill_16": "dcedaa8bd41290b1",
    "prefill_32": "c5c9a83e2d916c04",
    "prefill_64": "800debbb78504944",
    "sample_1": "754b408424c64bc3",
    "sample_4": "4dd049c980ec47d2",
}
FINGERPRINT = "ad2f461473dcf33584ccff1c"


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
    assert engine.attention_path == "xla+next_token/1"
    assert engine._pool.kind == "kv"
    assert len(engine._k_pools) == len(engine._v_pools) == 2
    assert "moe" not in engine.metrics.snapshot()


def test_next_token_revision_is_part_of_the_fingerprint(
        engine, monkeypatch, tmp_path):
    """A tree whose next-token decode program differs (the revision in
    `NextToken.path`) never loads this one's executables, nor the one
    before the run-ahead (no path at all) this one's."""
    from paddle_tpu.serving import generation
    seen = {engine.program_fingerprint}
    for path in ("+next_token/2", ""):
        monkeypatch.setattr(generation.NextToken, "path", path)
        other = serving.LLMEngine(
            engine._model, engine.config, program_cache=str(tmp_path))
        assert other.attention_path == "xla" + path
        seen.add(other.program_fingerprint)
        other.shutdown()
    assert len(seen) == 3
