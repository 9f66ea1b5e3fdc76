"""The flash attention kernels keep their names through the chip's own
compiler: compiled here for one DESCRIBED v5e chip (no chip attached —
on-chip-measurement guide, section 2) at the two benchmark cells' sizes,
the program holds ``%flash_fwd`` / ``%flash_dq`` / ``%flash_dkv``.  The
device trace names an event by its HLO instruction, so these names are
what ``breakdown.device_ops`` and a per-kernel roofline find.

The topology is described inside a module fixture (never at import: one
process at a time may load the TPU's library), and every compile runs in
this one file so that one xdist worker owns it.
"""
import pytest

import jax
import jax.numpy as jnp

# (batch, heads, seq, head_dim), dtype, causal — as the cells call them
CELLS = {
    "gpt355m_train": ((4, 16, 2048, 64), jnp.bfloat16, True),
    "bert_base_train": ((48, 12, 512, 64), jnp.bfloat16, False),
    # GPT's step without autocast (the cell's own until PR 34)
    "gpt355m_f32": ((4, 16, 2048, 64), jnp.float32, True),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_text(one_chip):
    """{(cell, pass): HLO text}, each program compiled once, with the
    persistent compile cache off (an entry written for a described chip
    cannot be read back without one, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas import flash_attention as fa
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    texts = {}
    try:
        for cell, (shape, dtype, causal) in CELLS.items():
            arg = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            row = jax.ShapeDtypeStruct(shape[:3], jnp.float32,
                                       sharding=one_chip)
            scale = shape[-1] ** -0.5
            blocks = (None, None, False)       # the shape's own schedule

            def forward(q, k, v, causal=causal, scale=scale, blocks=blocks):
                return fa._flash_bhsd(q, k, v, causal, scale, *blocks)

            def backward(q, k, v, do, lse, delta, causal=causal,
                         scale=scale, blocks=blocks):
                return fa._flash_bwd_impl(q, k, v, do, lse, delta, causal,
                                          scale, *blocks)

            def loss(q, k, v, forward=forward):
                return jnp.sum(forward(q, k, v).astype(jnp.float32))

            texts[cell, "forward"] = jax.jit(forward).lower(
                arg, arg, arg).compile().as_text()
            texts[cell, "backward"] = jax.jit(backward).lower(
                arg, arg, arg, arg, row, row).compile().as_text()
            texts[cell, "grad"] = jax.jit(
                jax.grad(loss, argnums=(0, 1, 2))).lower(
                    arg, arg, arg).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return texts


def _kernel_calls(text):
    """{instruction name: line} of the program's Mosaic calls."""
    return {ln.split(" = ")[0].strip().lstrip("%"): ln
            for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln}


def _attention_shape(cell):
    b, h, s, d = CELLS[cell][0]
    return "[%d,%d,%d]" % (b * h, s, d)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("which,kernel", [
    ("forward", "flash_fwd"), ("backward", "flash_dq"),
    ("backward", "flash_dkv")])
def test_kernel_name_is_the_compiled_instruction(compiled_text, cell, which,
                                                 kernel):
    calls = {name: ln for name, ln in
             _kernel_calls(compiled_text[cell, which]).items()
             if name.split(".")[0] == kernel}
    assert calls, f"no %{kernel} custom-call in the {which} program"
    for ln in calls.values():
        # the reader of flash_attn_roofline finds attention by this layout
        assert _attention_shape(cell) in ln.split(" custom-call(")[0]
        assert f"/{kernel}/pallas_call" in ln            # op_name metadata


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_differentiated_step_keeps_the_kernel_in_the_name(compiled_text,
                                                          cell, kernel):
    # under jax.grad the instruction keeps the kernel's name (%flash_fwd.1
    # since the kernels' functions are jitted; %jvp_flash_fwd_,
    # %transpose_jvp_flash_dq__ before that): no call is anonymous
    # (%jvp__ once)
    calls = _kernel_calls(compiled_text[cell, "grad"])
    assert len(calls) == 3
    assert sum(kernel in name for name in calls) == 1, sorted(calls)
    assert not any(name.split(".")[0].strip("_") in ("jvp", "transpose_jvp")
                   for name in calls)


def test_mla_paged_decode_compiles_at_the_cell_size(one_chip):
    """The latent decode kernel through Mosaic at
    ``kanana2_serve_reasoning``'s sizes (32 slots x 256 pages of 16 rows
    of 640 = 576 padded to lane tiles): it keeps its name and the pool is
    read where it lies (no copy of a pool-shaped operand)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas.mla_paged_attention import mla_paged_decode

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda q, p, t, n: mla_paged_decode(
            q, p, t, n, rank=512, scale=192 ** -0.5)).lower(
                S((32, 32, 640), jnp.bfloat16),
                S((8193, 16, 640), jnp.bfloat16),
                S((32, 256), jnp.int32), S((32,), jnp.int32)
            ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    calls = _kernel_calls(text)
    assert [n.split("%")[-1].split(".")[0] for n in calls] == [
        "mla_paged_decode"]
    assert not [ln for ln in text.splitlines()
                if "[8193,16,640]" in ln.split(" = ")[-1].split("(")[0]
                and (" copy(" in ln or " convert(" in ln)]


def test_grouped_matmul_compiles_at_the_block_pass_size(one_chip):
    """The grouped expert product through Mosaic at ``sdar30b_serve_chat``'s
    block pass (1,024 rows over 128 experts, both products) under the
    tiles the path rule gives it: each keeps its name, so
    ``breakdown.device_ops`` and ``tools/idle_gaps.py`` find it, and
    VMEM holds its tiles."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                      pick_tiles)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for k, n in ((2048, 1536), (768, 2048)):
            tiles = pick_tiles(1024, 128, k, n, jnp.bfloat16, kernel=True)
            text = jax.jit(lambda x, w, c: grouped_matmul(
                x, w, c, tiles, interpret=False)).lower(
                    S((1024, k), jnp.bfloat16), S((128, k, n), jnp.bfloat16),
                    S((128,), jnp.int32)).compile().as_text()
            calls = _kernel_calls(text)
            assert [c.split("%")[-1].split(".")[0] for c in calls] == [
                "grouped_matmul"]
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_windowed_grouped_flash_compiles_at_the_cell_size(one_chip):
    """The flash forward with a window over grouped K/V, through Mosaic at
    ``smallthinker21b_serve_longdoc``'s longest prefill (28 query heads
    over 4 K/V heads of 128, 16,384 positions, a window of 4,096): it keeps
    its name and the layout the prefill roofline finds it by, and K/V are
    read in place (no 28-head copy of them)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas import flash_attention as fa

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda q, k, v: fa._flash_bhsd(
            q, k, v, True, 128 ** -0.5, None, None, False, 4096)).lower(
                S((1, 28, 16384, 128)), S((1, 4, 16384, 128)),
                S((1, 4, 16384, 128))).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    calls = _kernel_calls(text)
    assert [name.split(".")[0] for name in calls] == ["flash_fwd"]
    (line,) = calls.values()
    assert "[28,16384,128]" in line.split(" custom-call(")[0]
    assert "bf16[4,16384,128]" in line.split(" custom-call(")[1]


@pytest.mark.parametrize("cell,slots,q_heads,kv_heads,pages,width,scale", [
    ("smallthinker21b_serve_longdoc", 32, 28, 4, 32769, 1024, 128 ** -0.5),
    ("granite4h_serve_chat", 64, 32, 8, 8192, 128, 1 / 128)])
def test_grouped_paged_decode_compiles_at_the_cell_sizes(
        one_chip, cell, slots, q_heads, kv_heads, pages, width, scale):
    """The grouped-query decode kernel through Mosaic at the two cells'
    full layers (bf16 row pages of 16 rows, head_dim 128): it keeps its
    name, so ``breakdown.device_ops`` and ``grouped_decode_roofline.serve``
    find it, and the pool is read where it lies (no copy of a pool-shaped
    operand)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas.paged_attention import grouped_paged_decode

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (pages, 16, kv_heads * 128)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda q, k, v, t, n: grouped_paged_decode(
            q, k, v, t, n, scale=scale)).lower(
                S((slots, q_heads, 128)), S(pool), S(pool),
                S((slots, width), jnp.int32),
                S((slots,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    calls = _kernel_calls(text)
    assert [n.split("%")[-1].split(".")[0] for n in calls] == [
        "grouped_paged_decode"], cell
    shape = "[%d,16,%d]" % (pages, kv_heads * 128)
    assert not [ln for ln in text.splitlines()
                if shape in ln.split(" = ")[-1].split("(")[0]
                and (" copy(" in ln or " convert(" in ln)], cell
