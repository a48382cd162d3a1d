"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e,
at the published widths of qwen2.5-3b and smollm-135m.

Nothing runs: the TPU compiler installed with JAX compiles for a described
chip, and refuses what the chip would refuse (unaligned blocks, layouts or
ops Mosaic cannot lower) -- faults the CPU's interpret mode never sees.
Each test asserts that the compiled program holds the kernel
(``tpu_custom_call``).  The topology is described inside a fixture, never
at import: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config

ARCHS = ("qwen2.5-3b", "smollm-135m")
BATCH, SPAN, PAGE, PAGES_PER_ROW = 8, 32, 32, 32     # max_len 1024


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent
    compilation cache off: a described chip's executables can be written to
    it but never read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # any failure: no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        mp.undo()


def _widths(arch):
    cfg = get_config(arch)
    return (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, cfg.vocab,
            cfg.d_model)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_shapes(arch):
    Hq, Hkv, D, _, _ = _widths(arch)
    pool = (BATCH * PAGES_PER_ROW + 1, PAGE, Hkv, D)
    return Hq, D, [(pool, jnp.bfloat16), (pool, jnp.bfloat16),
                   ((BATCH, PAGES_PER_ROW), jnp.int32), ((BATCH,), jnp.int32),
                   ((1,), jnp.int32)]


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_mixed_attention_compiles(arch, one_chip):
    from repro.kernels.decode_attention.kernel import paged_mixed_attention_fwd
    Hq, D, rest = _paged_shapes(arch)
    txt = _compiled_text(paged_mixed_attention_fwd, one_chip,
                         ((BATCH, SPAN, Hq, D), jnp.bfloat16), *rest)
    assert "tpu_custom_call" in txt


# The benchmark cells' served shapes: (arch giving the widths, rows, table
# pages); both at span 32 and page 32.  Mistral-NeMo's attention widths
# (Hq 32, Hkv 8, D 128) are pixtral-12b's.
CELL_SHAPES = {"qwen2.5-3b.chat": ("qwen2.5-3b", 32, 64),
               "mistral-nemo-12b.l10.rag": ("pixtral-12b", 8, 128)}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_paged_mixed_attention_compiles_at_cell_shapes(cell, one_chip):
    """The mixed kernel compiles at each benchmark cell's served shapes, and
    the compiled call still names ``_paged_mixed_kernel``: the benchmark
    finds the kernel's operation by that name."""
    from bench.trace import kernel_ops
    from repro.kernels.decode_attention.kernel import paged_mixed_attention_fwd
    arch, rows, pages = CELL_SHAPES[cell]
    Hq, Hkv, D, _, _ = _widths(arch)
    pool = (rows * pages + 1, PAGE, Hkv, D)
    txt = _compiled_text(paged_mixed_attention_fwd, one_chip,
                         ((rows, SPAN, Hq, D), jnp.bfloat16),
                         (pool, jnp.bfloat16), (pool, jnp.bfloat16),
                         ((rows, pages), jnp.int32), ((rows,), jnp.int32),
                         ((1,), jnp.int32))
    found = kernel_ops(txt, {"paged_mixed_attention": "_paged_mixed_kernel"})
    assert list(found.values()) == ["paged_mixed_attention"]


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_attention_compiles(arch, one_chip):
    from repro.kernels.decode_attention.kernel import paged_decode_attention_fwd
    Hq, D, rest = _paged_shapes(arch)
    txt = _compiled_text(paged_decode_attention_fwd, one_chip,
                         ((BATCH, Hq, D), jnp.bfloat16), *rest)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("arch", ARCHS)
def test_lmhead_epilogue_compiles(arch, one_chip):
    from repro.kernels.sampling.kernel import lmhead_epilogue_fwd
    _, _, _, V, d = _widths(arch)
    txt = _compiled_text(lmhead_epilogue_fwd, one_chip,
                         ((BATCH * SPAN, d), jnp.bfloat16),
                         ((d, V), jnp.bfloat16))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("arch", ARCHS)
def test_greedy_epilogue_compiles(arch, one_chip):
    from repro.kernels.sampling.kernel import greedy_epilogue_fwd
    _, _, _, V, _ = _widths(arch)
    txt = _compiled_text(greedy_epilogue_fwd, one_chip,
                         ((BATCH, V), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_prefill_attention_compiles(arch, one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    Hq, Hkv, D, _, _ = _widths(arch)
    S = 512
    txt = _compiled_text(flash_attention_fwd, one_chip,
                         ((BATCH, Hq, S, D), jnp.bfloat16),
                         ((BATCH, Hkv, S, D), jnp.bfloat16),
                         ((BATCH, Hkv, S, D), jnp.bfloat16),
                         ((1,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_ssd_intra_chunk_compiles(one_chip):
    from repro.kernels.ssd.kernel import ssd_intra_fwd
    cfg = get_config("mamba2-1.3b")
    s = cfg.ssm
    heads = s.expand * cfg.d_model // s.head_dim
    q = s.chunk
    txt = _compiled_text(ssd_intra_fwd, one_chip,
                         ((4, q, heads, s.head_dim), jnp.float32),
                         ((4, q, heads), jnp.float32),
                         ((4, q, heads, s.d_state), jnp.float32),
                         ((4, q, heads, s.d_state), jnp.float32))
    assert "tpu_custom_call" in txt
