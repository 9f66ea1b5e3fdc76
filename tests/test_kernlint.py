"""kernlint (paddle_tpu/analysis kernel_rules + vmem_model): rule unit
tests per KL family (one flagged + one clean Pallas kernel each),
hand-computed VMEM-model pins, the seeded acceptance fixture (one
deliberately broken kernel — unaligned block + bf16 accumulator +
unguarded tail — vs its corrected twin), suppression scoping in BOTH
directions (a `# kernlint:` spelling waives nothing outside KL; no
foreign family spelling waives a KL code), the NL/KL ownership split
(numlint keeps pallas_call bodies opaque — KL103 owns them), the
trace-free AST pass, the to_static(check=True) KernlintWarning hook,
the kernel-interior roofline rows, the bench report lane, and the CLI
baseline gate run exactly as CI runs it.

Everything traces tiny pallas_call jaxprs on CPU — nothing compiles,
nothing runs a kernel.
"""
import importlib.util
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import KernelConfig, kernel_rules, vmem_model

pytestmark = pytest.mark.kernlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

F32 = jnp.float32
BF16 = jnp.bfloat16


def codes_of(jaxpr, config=None):
    return [f.code for f in analysis.check_kernels(
        jaxpr, where="<test>", config=config)]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ----------------------------------------------------- fixture kernels
def _copy(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _add2(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] + y_ref[...]


def _dot_narrow(x_ref, y_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], y_ref[...])


def _dot_wide(x_ref, y_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _carry_narrow(x_ref, o_ref):
    o_ref[...] = o_ref[...] + x_ref[...]


def _carry_widened(x_ref, o_ref):
    o_ref[...] = (o_ref[...].astype(jnp.float32)
                  + x_ref[...].astype(jnp.float32)).astype(jnp.bfloat16)


def _grid_trace(kernel, x_sds, out_sds, grid, in_map, out_map,
                in_block, out_block):
    return jax.make_jaxpr(lambda v: pl.pallas_call(
        kernel, out_shape=out_sds, grid=grid,
        in_specs=[pl.BlockSpec(in_block, in_map)],
        out_specs=pl.BlockSpec(out_block, out_map))(v))(x_sds)


# --------------------------------------------------------------- KL101
@pytest.mark.smoke
def test_kl101_misaligned_block_flagged_aligned_clean():
    # (100, 200) f32: 100 % 8 and 200 % 128 both misaligned; grid (4,2)
    # fully covers (400, 400), so KL101 is the ONLY finding
    flagged = _grid_trace(_copy, _sds((400, 400), F32),
                          _sds((400, 400), F32), (4, 2),
                          lambda i, j: (i, j), lambda i, j: (i, j),
                          (100, 200), (100, 200))
    assert set(codes_of(flagged)) == {"KL101"}
    clean = _grid_trace(_copy, _sds((512, 512), F32),
                        _sds((512, 512), F32), (4, 4),
                        lambda i, j: (i, j), lambda i, j: (i, j),
                        (128, 128), (128, 128))
    assert codes_of(clean) == []


def test_kl101_exempts_dim1_and_full_extent():
    # (1, full-row) is the vector idiom norm's weight/bias rows use
    jaxpr = _grid_trace(_copy, _sds((16, 40), F32), _sds((16, 40), F32),
                        (16,), lambda i: (i, 0), lambda i: (i, 0),
                        (1, 40), (1, 40))
    assert codes_of(jaxpr) == []


def test_kl101_bf16_needs_16_row_tiles():
    # 24 rows: fine for f32 (24 % 8 == 0), wrong for bf16 (24 % 16)
    bad = _grid_trace(_copy, _sds((96, 128), BF16), _sds((96, 128), BF16),
                      (4,), lambda i: (i, 0), lambda i: (i, 0),
                      (24, 128), (24, 128))
    assert set(codes_of(bad)) == {"KL101"}
    ok = _grid_trace(_copy, _sds((96, 128), F32), _sds((96, 128), F32),
                     (4,), lambda i: (i, 0), lambda i: (i, 0),
                     (24, 128), (24, 128))
    assert codes_of(ok) == []


# --------------------------------------------------------------- KL102
def _vmem_hog_jaxpr():
    big = _sds((4096, 4096), F32)
    return jax.make_jaxpr(lambda a, b: pl.pallas_call(
        _add2, out_shape=big, grid=(2,),
        in_specs=[pl.BlockSpec((4096, 4096), lambda i: (0, 0)),
                  pl.BlockSpec((4096, 4096), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((4096, 4096), lambda i: (0, 0)))(a, b))(
        big, big)


@pytest.mark.smoke
def test_kl102_vmem_hog_flagged_budget_override_clean():
    jaxpr = _vmem_hog_jaxpr()
    findings = analysis.check_kernels(jaxpr, where="<test>")
    assert {f.code for f in findings} == {"KL102"}
    assert "VMEM budget" in findings[0].message
    # 3 blocks x 128 MiB double-buffered = 384 MiB: a large enough
    # budget clears it without touching the kernel
    assert codes_of(jaxpr, config=KernelConfig(vmem_budget_mb=1024.0)) \
        == []


def test_kl102_estimate_pinned_by_hand():
    eqn = next(kernel_rules.iter_pallas_eqns(_vmem_hog_jaxpr()))
    est = vmem_model.estimate_vmem(eqn)
    # 3 BlockMappings x (4096*4096*4 B one copy) x2 double-buffered
    assert len(est.blocks) == 3
    assert all(one == 4096 * 4096 * 4 for _o, one, _b in est.blocks)
    assert est.double_buffered
    assert est.scratch_bytes == 0
    assert est.total_bytes == 3 * 2 * 4096 * 4096 * 4
    assert "x2 double-buffered" in est.describe()
    assert est.to_dict()["total_bytes"] == est.total_bytes


def test_kl102_scratch_counts_once_no_double_buffer():
    def k(x_ref, o_ref, s_ref):
        s_ref[...] = x_ref[...] * 2.0
        o_ref[...] = s_ref[...]

    jaxpr = jax.make_jaxpr(lambda v: pl.pallas_call(
        k, out_shape=_sds((8, 128), F32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)])(v))(
        _sds((8, 128), F32))
    est = vmem_model.estimate_vmem(
        next(kernel_rules.iter_pallas_eqns(jaxpr)))
    one = 8 * 128 * 4
    assert not est.double_buffered          # single grid step
    assert est.scratch_bytes == one
    assert est.total_bytes == 3 * one       # in + out + scratch, all x1


def test_vmem_model_padding_pins():
    f32 = np.dtype("float32")
    bf16 = np.dtype(jnp.bfloat16)
    i8 = np.dtype("int8")
    assert vmem_model.native_tile(f32) == (8, 128)
    assert vmem_model.native_tile(bf16) == (16, 128)
    assert vmem_model.native_tile(i8) == (32, 128)
    assert vmem_model.sublane(np.dtype("float64")) == 8  # floored at 8
    assert vmem_model.padded_block_bytes((100, 200), f32) \
        == 104 * 256 * 4
    assert vmem_model.padded_block_bytes((100, 200), bf16) \
        == 112 * 256 * 2
    assert vmem_model.padded_block_bytes((100, 200), i8) == 128 * 256
    assert vmem_model.padded_block_bytes((1, 4), f32) == 8 * 128 * 4
    assert vmem_model.padded_block_bytes((5,), f32) == 128 * 4
    # major dims count as-is; only the two minor dims pad
    assert vmem_model.padded_block_bytes((3, 100, 200), f32) \
        == 3 * 104 * 256 * 4
    assert vmem_model.padded_block_bytes((), f32) == 4


# --------------------------------------------------------------- KL103
@pytest.mark.smoke
def test_kl103_narrow_dot_flagged_preferred_type_clean():
    x, y = _sds((128, 512), BF16), _sds((512, 128), BF16)
    flagged = jax.make_jaxpr(lambda a, b: pl.pallas_call(
        _dot_narrow, out_shape=_sds((128, 128), BF16))(a, b))(x, y)
    kl = analysis.check_kernels(flagged, where="<test>")
    assert {f.code for f in kl} == {"KL103"}
    assert "preferred_element_type" in kl[0].message
    clean = jax.make_jaxpr(lambda a, b: pl.pallas_call(
        _dot_wide, out_shape=_sds((128, 128), F32))(a, b))(x, y)
    assert codes_of(clean) == []


def test_kl103_narrow_ref_carry_flagged_widened_clean():
    x = _sds((128, 128), BF16)
    flagged = jax.make_jaxpr(lambda v: pl.pallas_call(
        _carry_narrow, out_shape=_sds((128, 128), BF16))(v))(x)
    assert set(codes_of(flagged)) == {"KL103"}
    clean = jax.make_jaxpr(lambda v: pl.pallas_call(
        _carry_widened, out_shape=_sds((128, 128), BF16))(v))(x)
    assert codes_of(clean) == []


def test_kl103_narrow_reduction_flagged_upcast_clean():
    # jnp.sum upcasts by construction; jnp.cumsum keeps the operand
    # dtype — the raw narrow-reduction KL103 exists to catch
    def red_narrow(x_ref, o_ref):
        o_ref[...] = jnp.cumsum(x_ref[...], axis=-1)

    def red_wide(x_ref, o_ref):
        o_ref[...] = jnp.cumsum(x_ref[...], axis=-1,
                                dtype=jnp.float32).astype(jnp.bfloat16)

    x = _sds((128, 512), BF16)
    flagged = jax.make_jaxpr(lambda v: pl.pallas_call(
        red_narrow, out_shape=_sds((128, 512), BF16))(v))(x)
    assert set(codes_of(flagged)) == {"KL103"}
    clean = jax.make_jaxpr(lambda v: pl.pallas_call(
        red_wide, out_shape=_sds((128, 512), BF16))(v))(x)
    assert codes_of(clean) == []


# --------------------------------------------------------------- KL104
def test_kl104_read_after_store_flagged_read_first_clean():
    def bad(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0
        o_ref[...] = o_ref[...] + x_ref[...]   # reads x AFTER the store

    def good(x_ref, o_ref):
        v = x_ref[...]
        o_ref[...] = v * 2.0 + v

    x = _sds((128, 128), F32)
    flagged = jax.make_jaxpr(lambda v: pl.pallas_call(
        bad, out_shape=_sds((128, 128), F32),
        input_output_aliases={0: 0})(v))(x)
    kl = analysis.check_kernels(flagged, where="<test>")
    assert {f.code for f in kl} == {"KL104"}
    assert "AFTER" in kl[0].message
    clean = jax.make_jaxpr(lambda v: pl.pallas_call(
        good, out_shape=_sds((128, 128), F32),
        input_output_aliases={0: 0})(v))(x)
    assert codes_of(clean) == []


def test_kl104_quiet_without_aliases():
    def twice(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0
        o_ref[...] = o_ref[...] + x_ref[...]

    jaxpr = jax.make_jaxpr(lambda v: pl.pallas_call(
        twice, out_shape=_sds((128, 128), F32))(v))(_sds((128, 128), F32))
    assert codes_of(jaxpr) == []


# --------------------------------------------------------------- KL105
@pytest.mark.smoke
def test_kl105_under_coverage_flagged_full_grid_clean():
    # 4 row blocks, grid of 2: half the array is never touched
    flagged = _grid_trace(_copy, _sds((512, 128), F32),
                          _sds((512, 128), F32), (2,),
                          lambda i: (i, 0), lambda i: (i, 0),
                          (128, 128), (128, 128))
    kl = analysis.check_kernels(flagged, where="<test>")
    assert {f.code for f in kl} == {"KL105"}
    assert any("never read" in f.message for f in kl)
    assert any("never written" in f.message for f in kl)
    clean = _grid_trace(_copy, _sds((512, 128), F32),
                        _sds((512, 128), F32), (4,),
                        lambda i: (i, 0), lambda i: (i, 0),
                        (128, 128), (128, 128))
    assert codes_of(clean) == []


def test_kl105_nonconsecutive_double_write_flagged():
    # out block (0,0) written on steps 0 and 2 — a re-fetch + re-write,
    # not the resident-accumulator idiom
    jaxpr = _grid_trace(_copy, _sds((256, 128), F32),
                        _sds((256, 128), F32), (4,),
                        lambda i: (i % 2, 0), lambda i: (i % 2, 0),
                        (128, 128), (128, 128))
    kl = analysis.check_kernels(jaxpr, where="<test>")
    assert {f.code for f in kl} == {"KL105"}
    assert any("non-consecutive" in f.message for f in kl)


def test_kl105_consecutive_accumulator_revisits_clean():
    # every grid step maps to the SAME output block (the flash-style
    # resident accumulator): consecutive revisits are the idiom
    def accum(x_ref, o_ref):
        o_ref[...] = o_ref[...] + x_ref[...]

    jaxpr = jax.make_jaxpr(lambda v: pl.pallas_call(
        accum, out_shape=_sds((128, 128), F32), grid=(4,),
        in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)))(v))(
        _sds((512, 128), F32))
    assert codes_of(jaxpr) == []


# --------------------------------------------------------------- KL106
@pytest.mark.smoke
def test_kl106_unguarded_tail_flagged_guarded_clean():
    flagged = _grid_trace(_copy, _sds((300, 128), F32),
                          _sds((300, 128), F32), (3,),
                          lambda i: (i, 0), lambda i: (i, 0),
                          (128, 128), (128, 128))
    kl = analysis.check_kernels(flagged, where="<test>")
    assert {f.code for f in kl} == {"KL106"}
    assert "tail" in kl[0].message

    def guarded(x_ref, o_ref):
        rows = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
        o_ref[...] = jnp.where(rows < 44, x_ref[...] * 2.0, 0.0)

    clean = _grid_trace(guarded, _sds((300, 128), F32),
                        _sds((300, 128), F32), (3,),
                        lambda i: (i, 0), lambda i: (i, 0),
                        (128, 128), (128, 128))
    assert codes_of(clean) == []


def test_kl106_exact_multiple_clean():
    jaxpr = _grid_trace(_copy, _sds((384, 128), F32),
                        _sds((384, 128), F32), (3,),
                        lambda i: (i, 0), lambda i: (i, 0),
                        (128, 128), (128, 128))
    assert codes_of(jaxpr) == []


# --------------------------------------- seeded acceptance fixture pair
def _acceptance_jaxpr(fixed):
    """ISSUE 17's acceptance fixture: one deliberately broken kernel
    (unaligned bf16 block + bf16 `+=` accumulator + unguarded 20-row
    tail) vs its corrected twin (16-row-aligned blocks that divide the
    array exactly, f32 accumulation)."""
    if fixed:
        kernel, block, grid, odt = _carry_f32, (64, 256), (5,), F32
    else:
        kernel, block, grid, odt = _carry_narrow, (100, 256), (4,), BF16
    return jax.make_jaxpr(lambda v: pl.pallas_call(
        kernel, out_shape=_sds((320, 256), odt), grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0)))(v))(
        _sds((320, 256), BF16))


def _carry_f32(x_ref, o_ref):
    o_ref[...] = o_ref[...] + x_ref[...].astype(jnp.float32)


@pytest.mark.smoke
def test_acceptance_broken_kernel_vs_corrected_twin():
    from paddle_tpu.analysis import report

    broken = analysis.check_kernels(_acceptance_jaxpr(fixed=False),
                                    where="<acceptance>")
    codes = [f.code for f in broken]
    assert len(broken) >= 3
    assert {"KL101", "KL103", "KL106"} <= set(codes)
    # fingerprints are stable across re-traces: the baseline contract
    fp1 = sorted(report.fingerprint(f) for f in broken)
    again = analysis.check_kernels(_acceptance_jaxpr(fixed=False),
                                   where="<acceptance>")
    fp2 = sorted(report.fingerprint(f) for f in again)
    assert fp1 == fp2
    assert analysis.check_kernels(_acceptance_jaxpr(fixed=True),
                                  where="<acceptance>") == []


def test_duplicate_calls_collapse_to_one_finding_set():
    bad = pl.pallas_call(
        _copy, out_shape=_sds((400, 400), F32), grid=(4, 2),
        in_specs=[pl.BlockSpec((100, 200), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((100, 200), lambda i, j: (i, j)))
    jaxpr = jax.make_jaxpr(lambda v: bad(bad(v)))(_sds((400, 400), F32))
    assert sum(1 for _ in kernel_rules.iter_pallas_eqns(jaxpr)) == 2
    # same kernel, same site, same signatures -> ONE set of findings
    assert codes_of(jaxpr) == ["KL101", "KL101"]   # in + out operand


# ------------------------------------------------- suppression scoping
_KL_SUPP_SRC = """
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _k(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def build():
    x = jax.ShapeDtypeStruct((400, 400), jnp.float32)
    return jax.make_jaxpr(lambda v: pl.pallas_call(_k, out_shape=jax.ShapeDtypeStruct((400, 400), jnp.float32), grid=(4, 2), in_specs=[pl.BlockSpec((100, 200), lambda i, j: (i, j))], out_specs=pl.BlockSpec((100, 200), lambda i, j: (i, j)))(v))(x){comment}
"""


def _kl_supp_codes(tmp_path, name, comment):
    path = tmp_path / f"{name}.py"
    path.write_text(_KL_SUPP_SRC.format(comment=comment))
    spec = importlib.util.spec_from_file_location(name, str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return codes_of(mod.build())


def test_kernlint_and_tracelint_spellings_waive(tmp_path):
    for i, comment in enumerate(("  # kernlint: disable=KL101",
                                 "  # tracelint: disable=KL101",
                                 "  # kernlint: disable=ALL")):
        assert "KL101" not in _kl_supp_codes(tmp_path, f"waive{i}",
                                             comment), comment


def test_foreign_spellings_cannot_waive_kl(tmp_path):
    for i, comment in enumerate(("  # numlint: disable=KL101",
                                 "  # shardlint: disable=KL101",
                                 "  # numlint: disable=ALL",
                                 "  # racelint: disable=ALL")):
        assert "KL101" in _kl_supp_codes(tmp_path, f"keep{i}",
                                         comment), comment


def test_kernlint_spelling_cannot_waive_nl(tmp_path):
    """The other direction: a kernlint-spelled comment is scoped to KL
    and must NOT silence a numlint finding on the same line."""
    path = tmp_path / "nl_keep.py"
    path.write_text("import jax.numpy as jnp\n\n\n"
                    "def risky(x):\n"
                    "    return jnp.exp(x)  # kernlint: disable=ALL\n")
    spec = importlib.util.spec_from_file_location("nl_keep", str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    jaxpr = jax.make_jaxpr(mod.risky)(jnp.ones((4,), jnp.bfloat16))
    nl = [f.code for f in analysis.check_numerics(jaxpr, where="<x>")]
    assert "NL201" in nl


def test_finding_points_into_fixture_file(tmp_path):
    path = tmp_path / "kern_site.py"
    path.write_text(_KL_SUPP_SRC.format(comment=""))
    spec = importlib.util.spec_from_file_location("kern_site", str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    findings = analysis.check_kernels(mod.build(), where="<site>")
    f = next(f for f in findings if f.code == "KL101")
    assert "kern_site.py" in f.path and f.line > 0


# ---------------------------------------------- NL/KL ownership split
@pytest.mark.smoke
def test_numlint_keeps_kernel_bodies_opaque():
    """docs/numlint.md ownership contract: the SAME narrow contraction
    is NL101's outside a kernel and KL103's inside one — never both."""
    from paddle_tpu.analysis import NumConfig

    cfg = NumConfig(reduce_min_elems=64)
    x, y = _sds((128, 512), BF16), _sds((512, 128), BF16)
    inside = jax.make_jaxpr(lambda a, b: pl.pallas_call(
        _dot_narrow, out_shape=_sds((128, 128), BF16))(a, b))(x, y)
    assert "KL103" in codes_of(inside)
    nl = [f.code for f in analysis.check_numerics(
        inside, where="<own>", config=cfg)]
    assert "NL101" not in nl                 # body is numlint-opaque
    outside = jax.make_jaxpr(jnp.matmul)(
        jnp.ones((128, 512), BF16), jnp.ones((512, 128), BF16))
    assert "NL101" in [f.code for f in analysis.check_numerics(
        outside, where="<own>", config=cfg)]
    assert codes_of(outside) == []           # no pallas_call, no KL


# -------------------------------------------------------- AST pass
_AST_SRC = """
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mm_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], y_ref[...]){k103}


def matmul(x, y):
    return pl.pallas_call(
        _mm_kernel,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], y.shape[1]), x.dtype),
        in_specs=[pl.BlockSpec((100, 200), lambda i, j: (i, j)),{k101}
                  pl.BlockSpec((128, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((128, 128), lambda i, j: (i, j)))(x, y)
"""


def _ast_codes(tmp_path, name, k101="", k103=""):
    path = tmp_path / f"{name}.py"
    path.write_text(_AST_SRC.format(k101=k101, k103=k103))
    return [f.code for f in analysis.check_kernel_files([str(path)])]


def test_ast_pass_flags_and_suppresses(tmp_path):
    assert sorted(_ast_codes(tmp_path, "raw")) == ["KL101", "KL103"]
    assert _ast_codes(tmp_path, "supp",
                      k101="  # kernlint: disable=KL101",
                      k103="  # kernlint: disable=KL103") == []
    assert sorted(_ast_codes(tmp_path, "foreign",
                             k101="  # numlint: disable=KL101",
                             k103="  # shardlint: disable=ALL")) \
        == ["KL101", "KL103"]


def test_ast_pass_widened_and_preferred_clean(tmp_path):
    src = (
        "import jax.numpy as jnp\n\n\n"
        "def _k(x_ref, y_ref, o_ref):\n"
        "    a = jnp.dot(x_ref[...].astype(jnp.float32), y_ref[...])\n"
        "    b = jnp.dot(x_ref[...], y_ref[...],\n"
        "                preferred_element_type=jnp.float32)\n"
        "    o_ref[...] = a + b\n")
    path = tmp_path / "widened.py"
    path.write_text(src)
    assert analysis.check_kernel_files([str(path)]) == []


def test_ast_pass_shipped_kernels_clean():
    """The self-audit's static half: every ops/pallas source passes."""
    paths = kernel_rules.default_kernel_paths()
    assert len(paths) >= 5
    assert analysis.check_kernel_files() == []


# ------------------------------------------------ to_static(check=True)
def test_to_static_check_emits_kernlint_warning(monkeypatch):
    """The jit/api.py hook wiring: findings from check_kernels on the
    traced program surface as KernlintWarning (the shipped kernels are
    clean, so the finding is injected)."""
    from paddle_tpu.analysis.visitor import Finding

    fake = Finding(path="k.py", line=1, col=0, code="KL101",
                   message="block shape (100, 200) is misaligned",
                   source_line="s")
    monkeypatch.setattr(analysis, "check_kernels",
                        lambda jaxpr, where="", **kw: [fake])
    paddle.seed(0)
    x = paddle.to_tensor(np.ones((8, 8), np.float32))

    @paddle.jit.to_static(check=True)
    def f(v):
        return v * 2.0

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        f(x)
    msgs = [str(w.message) for w in rec
            if isinstance(w.message, analysis.KernlintWarning)]
    assert any("KL101" in m for m in msgs), \
        [str(w.message) for w in rec]


def test_kernlint_warning_category():
    assert issubclass(analysis.KernlintWarning, analysis.TracelintWarning)
    assert analysis.KernlintWarning is not analysis.NumlintWarning


# ------------------------------------------- kernel-interior rooflines
def _interior_jaxpr():
    return _grid_trace(_copy, _sds((512, 128), F32),
                       _sds((512, 128), F32), (4,),
                       lambda i: (i, 0), lambda i: (i, 0),
                       (128, 128), (128, 128))


def test_kernel_interiors_rows_pinned():
    from paddle_tpu.observability import profile

    rows = profile.kernel_interiors(_interior_jaxpr())
    assert len(rows) == 1
    r = rows[0]
    step = 2 * 128 * 128 * 4            # one in + one out block copy
    assert r["grid_steps"] == 4
    assert r["vmem_step_bytes"] == step
    assert r["interior_bytes"] == 4 * step
    assert r["vmem_total_bytes"] == 2 * step    # x2 double-buffered
    assert r["double_buffered"] is True
    assert r["boundary_bytes"] > 0
    assert r["reuse_factor"] > 0
    assert r["bound"] in ("compute", "memory")
    assert r["kernel"]


def test_profile_traced_interiors_opt_in_and_roundtrip():
    from paddle_tpu.observability import profile

    jaxpr = _interior_jaxpr()
    rep = profile.profile_traced(jaxpr, where="<k>",
                                 include_interiors=True)
    assert rep.interiors and rep.interiors[0]["grid_steps"] == 4
    d = rep.to_dict()
    assert d["interiors"] == rep.interiors
    back = profile.RooflineReport.from_dict(d)
    assert back.interiors == rep.interiors
    # default stays byte-identical to the pre-interiors report shape
    plain = profile.profile_traced(jaxpr, where="<k>")
    assert not plain.interiors
    assert "interiors" not in plain.to_dict()


def test_chip_spec_carries_vmem_budget():
    from paddle_tpu.observability import profile

    spec = profile.chip_spec("TPU v5 lite")
    assert spec is profile.V5E
    with pytest.raises(ValueError, match="no published peaks"):
        profile.chip_spec("cpu")
    assert spec.vmem_mb == 16.0
    assert spec.vmem_bytes == 16 << 20
    assert spec.to_dict()["vmem_mb"] == 16.0
    # the pre-PR-17 3-arg construction (what RooflineReport.from_dict
    # uses on old serialized reports) still works and gets the default
    assert profile.ChipSpec("x", 100.0, 800.0).vmem_mb == 16.0


def test_obs_report_renders_interior_table(capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    from paddle_tpu.observability import profile

    rep = profile.profile_traced(_interior_jaxpr(), where="<k>",
                                 include_interiors=True)
    obs_report.render_rooflines([rep.to_dict()])
    out = capsys.readouterr().out
    assert "kernel interiors" in out
    assert "_copy" in out


# ----------------------------------------------------- CLI & bench lane
KERNLINT = os.path.join(REPO, "tools", "kernlint.py")


def test_rules_catalogue():
    proc = subprocess.run([sys.executable, KERNLINT, "--rules"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for code in ("KL101", "KL102", "KL103", "KL104", "KL105", "KL106"):
        assert code in proc.stdout
    # only KL rules are catalogued (prose may NAME foreign codes when
    # documenting the ownership split, but no foreign rule entry prints)
    heads = [ln.split()[0] for ln in proc.stdout.splitlines()
             if ln and not ln.startswith(" ")]
    assert all(h.startswith("KL") for h in heads), heads


def test_cli_check_gate_clean():
    """The self-audit gate exactly as lint_all runs it: every shipped
    kernel (flagship, serving, each ops/pallas standalone, the AST
    pass) must be clean against the reviewed baseline."""
    proc = subprocess.run([sys.executable, KERNLINT, "--check"],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=280)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "kernlint: 0 finding(s)" in proc.stdout


def test_cli_diff_informational():
    proc = subprocess.run(
        [sys.executable, KERNLINT, "--diff", "--targets", "norm",
         "pallas_source"],
        cwd=REPO, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "baseline" in proc.stdout and "current" in proc.stdout


def test_cli_per_target_lines():
    proc = subprocess.run(
        [sys.executable, KERNLINT, "--targets", "norm", "optim"],
        cwd=REPO, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for line in ("== norm/layer_norm: 0 finding(s)",
                 "== norm/rms_norm: 0 finding(s)",
                 "== optim/adamw: 0 finding(s)",
                 "== optim/adamw_guard: 0 finding(s)"):
        assert line in proc.stdout, proc.stdout


def test_cli_baseline_flow(tmp_path):
    """--write-baseline then --check against it: the broken acceptance
    fixture's findings baseline away, and the gate stays armed for NEW
    findings on top."""
    from argparse import Namespace

    from paddle_tpu.analysis import common, report

    findings = analysis.check_kernels(_acceptance_jaxpr(fixed=False),
                                      where="<acceptance>")
    assert len(findings) >= 3
    base = tmp_path / "base.json"
    report.write_baseline(findings, str(base))
    args = Namespace(check=True, baseline=str(base),
                     write_baseline=False, json=None, diff=False)
    rc = common.run_baseline_flow(list(findings), args, tool="kernlint",
                                  repo=REPO, elapsed=0.1)
    assert rc == 0                       # fully baselined
    extra = analysis.check_kernels(_vmem_hog_jaxpr(), where="<new>")
    rc = common.run_baseline_flow(list(findings) + list(extra), args,
                                  tool="kernlint", repo=REPO,
                                  elapsed=0.1)
    assert rc == 1                       # the NEW KL102 still gates


def test_bench_report_lane_keys():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import kernlint
    finally:
        sys.path.pop(0)
    rep = kernlint.bench_report(targets=("norm", "pallas_source"))
    assert rep["kernlint_finding_count"] == 0
    assert rep["kernlint_rule_breakdown"] == {}
    assert rep["kernlint_elapsed_s"] >= 0
