"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e,
at the published widths of qwen2.5-3b and smollm-135m.

Nothing runs: the TPU compiler installed with JAX compiles for a described
chip, and refuses what the chip would refuse (unaligned blocks, layouts or
ops Mosaic cannot lower) -- faults the CPU's interpret mode never sees.
Each test asserts that the compiled program holds the kernel
(``tpu_custom_call``).  The topology is described inside a fixture, never
at import: only one process may load the TPU library at a time.
"""
import dataclasses
import json
import math
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config

ARCHS = ("qwen2.5-3b", "smollm-135m")
BENCH_CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
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


# -- the whole mixed step ---------------------------------------------------

# Each benchmark cell's configuration file, and the layers compiled of it
# (its widths and ``serve`` sizes are the file's).
STEP_CELLS = {"qwen2.5-3b.chat": ("qwen2.5-3b", 4),
              "mistral-nemo-12b.l10.rag": ("mistral-nemo-12b.l10", 3)}
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_OP = re.compile(r" = .*? ([a-z][\w-]*)\(")


def _mixed_step(mc, serve, one_chip, monkeypatch):
    """Compile ``ServingEngine._mixed_step_fn`` of model ``mc`` at ``serve``
    sizes and the TPU's tuned defaults, from shapes alone, with the
    kernels compiled for the chip (the CPU would interpret them).  Returns
    the compiled text and the pool's leaves (L, P, page, Hkv, w)."""
    from repro.kernels.decode_attention import ops as attention_ops
    from repro.kernels.decode_attention.autotune import DEFAULTS
    from repro.kernels.sampling import ops as sampling_ops
    from repro.models import build_model
    from repro.serving import ServeConfig, ServingEngine
    for mod in (attention_ops, sampling_ops):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    tpu = DEFAULTS["tpu"]
    model = build_model(mc, use_kernel=True)
    cfg = ServeConfig(max_batch=serve["max_batch"], max_len=serve["max_len"],
                      page_size=tpu["page_size"], chunk_size=tpu["chunk_size"],
                      draft_len=tpu["draft_len"],
                      lmhead_block_v=tpu["lmhead_block_v"], num_pages=2)
    eng = ServingEngine(model, None, cfg)     # its own pool: 2 pages
    na, H = cfg.max_batch, cfg.max_len + 1
    n_table = cfg.max_len // cfg.page_size
    P = na * n_table + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = jax.tree.map(
        lambda s: sds((s.shape[0], P) + s.shape[2:], s.dtype),
        jax.eval_shape(lambda: model.init_cache(1, cfg.page_size)))
    params = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                          model.abstract_params())
    i32 = jnp.int32
    args = (params, pool, sds((na, H), i32), sds((na,), i32),
            sds((na,), i32), sds((na,), i32), sds((na,), jnp.bool_),
            sds((na, n_table), i32), sds((), i32))
    txt = eng._mixed_jit.lower(*args).compile().as_text()
    return txt, [leaf.shape for leaf in jax.tree.leaves(pool)]


def _ops(txt):
    """(opcode, [element count of each result shape], line) of every
    instruction, fused computations' included."""
    for line in txt.splitlines():
        m = _OP.search(line)
        if m is None or "%" not in line.split(" = ", 1)[0]:
            continue
        head = line[:m.end()]
        counts = [math.prod(int(d) for d in dims.split(",") if d)
                  for dims in _SHAPE.findall(head.split(" = ", 1)[1])]
        yield m.group(1), counts, line


def _aliased_params(txt):
    """Shapes of the entry parameters the compiled program writes in place
    (its ``input_output_alias``)."""
    header = txt.splitlines()[0]
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", header)
    params = re.search(r"entry_computation_layout=\{\((.*?)\)->", header)
    shapes = [tuple(int(d) for d in dims.split(",") if d)
              for dims in _SHAPE.findall(params.group(1))]
    return [shapes[int(i)] for i in
            re.findall(r"\((\d+), \{\}", alias.group(1) if alias else "")]


@pytest.mark.parametrize("cell", sorted(STEP_CELLS))
def test_mixed_step_keeps_the_kv_pool_in_place(cell, one_chip, monkeypatch):
    """The whole mixed step at a cell's widths and sizes: the loop writes
    the KV pool in place.  No operation slices, stacks, copies or allocates
    a buffer the size of a pool leaf or of one layer of it; each pool
    parameter is donated (aliased to an output); and the compiled step
    still names ``_paged_mixed_kernel``, which the benchmark reads."""
    from bench.models import dense
    from bench.trace import kernel_ops
    name, n_layers = STEP_CELLS[cell]
    spec = json.loads((BENCH_CONFIGS / f"{name}.json").read_text())
    mc = dataclasses.replace(dense.model_config(spec), n_layers=n_layers)
    txt, leaves = _mixed_step(mc, spec["serve"], one_chip, monkeypatch)
    leaf = math.prod(leaves[0])
    pool_sized = {leaf, leaf // n_layers}
    moves = {"dynamic-slice", "dynamic-update-slice", "copy", "copy-start",
             "copy-done"}
    found = [line.strip()[:160] for op, counts, line in _ops(txt)
             if pool_sized & set(counts)
             and (op in moves or 'custom_call_target="AllocateBuffer"' in line)]
    assert not found, "\n".join(found)
    assert sorted(_aliased_params(txt)) == sorted(leaves)
    assert "paged_mixed_attention" in kernel_ops(
        txt, {"paged_mixed_attention": "_paged_mixed_kernel"}).values()


def test_mixed_step_pads_one_layer_at_narrow_heads(one_chip, monkeypatch):
    """At a head dim of 64 the kernel's row view pads each pool to 128
    lanes: the step pads one layer per call, never the stacked pools."""
    n_layers = 3
    mc = dataclasses.replace(get_config("smollm-135m"), n_layers=n_layers)
    txt, leaves = _mixed_step(mc, {"max_batch": 8, "max_len": 1024}, one_chip,
                              monkeypatch)
    layer = math.prod(leaves[0]) // n_layers
    pads = [counts[0] for op, counts, _ in _ops(txt) if op == "pad"]
    assert pads and max(pads) == 2 * layer      # 64 -> 128 lanes, one layer
