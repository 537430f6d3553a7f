"""Compile-only checks of the main path for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached; compiling
against a described ``v5e:2x2`` topology raises what the chip's compiler
would raise (tiling rules interpret mode never checks, a Mosaic kernel
GSPMD cannot partition); ``memory_analysis`` shows whether a program fits
the chip. Nothing runs, so these say nothing about results or times.
Shapes are deepseek-7b's published widths as the chip smoke serves them:
4 slots, ``max_len`` 512, 128-row pages (17 pool pages with the dummy);
olmoe-1b-7b's as its benchmark cell serves them: 2 slots of 4096;
DeepSeek-V2-Lite's, 16 of 64 routed experts held, as its cell serves them:
4 slots of 16384 over the latent pool.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers import every file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bwd, flash_attention_fwd
from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode_fwd, paged_flash_decode_fwd
from repro.models import build_model
from repro.models import transformer as T
from repro.serve import ServeEngine

GiB = 2**30
HBM_BYTES = 15.748 * GiB  # the allocator limit a v5e chip reports (16 GiB HBM)
SLOTS, MAX_LEN, PAGE = 4, 512, 128
N_PAGES = SLOTS * MAX_LEN // PAGE + 1
MOE_SLOTS, MOE_MAX_LEN = 2, 4096
MLA_SLOTS, MLA_MAX_LEN = 4, 16384


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A TPU compile written to the persistent cache cannot be read back
    # without a chip; keep the cache off while this module compiles.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    # attn_impl would resolve to "xla" on this CPU host; the chip runs pallas.
    return get_config("deepseek-7b").with_(attn_impl="pallas")


@pytest.fixture(scope="module")
def olmoe_cfg():
    return get_config("olmoe-1b-7b").with_(attn_impl="pallas")


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernels(compiled) -> set:
    """Base names of the Pallas kernels in a compiled module: the names the
    profiler trace shows them under (``paged_flash_decode_fwd.7``)."""
    return {
        re.sub(r"\.\d+$", "", m)
        for m in re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                            compiled.as_text())
    }


def _in_hbm(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


@pytest.mark.parametrize("c", [1, MAX_LEN], ids=["decode", "chunk"])
def test_paged_decode_kernel_compiles(one_chip, cfg, c):
    """The ragged paged kernel at C = 1 and C = the prefill chunk."""
    s = lambda shape, dt=jnp.int32: _spec(one_chip, shape, dt)
    pool = s((N_PAGES, cfg.n_kv_heads, PAGE, cfg.hd), jnp.bfloat16)
    fn = jax.jit(
        lambda q, k, v, lens, bt, qlens: paged_flash_decode_fwd(
            q, k, v, lens, bt, q_lens=qlens, order="sawtooth"
        )
    )
    compiled = fn.lower(
        s((SLOTS, c, cfg.n_heads, cfg.hd), jnp.bfloat16),
        pool,
        pool,
        s((SLOTS,)),
        s((SLOTS, MAX_LEN // PAGE)),
        s((SLOTS,)),
    ).compile()
    assert _kernels(compiled) == {"paged_flash_decode_fwd"}


def test_contiguous_decode_kernel_compiles(one_chip, cfg):
    """The static path's decode kernel, whose (B, 1, S) mask keeps its
    block legal for a batch of more than one row."""
    cache = _spec(one_chip, (SLOTS, 2048, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, lens: flash_decode_fwd(q, k, v, lens))
    compiled = fn.lower(
        _spec(one_chip, (SLOTS, 1, cfg.n_heads, cfg.hd), jnp.bfloat16),
        cache,
        cache,
        _spec(one_chip, (SLOTS,), jnp.int32),
    ).compile()
    assert _kernels(compiled) == {"flash_decode_fwd"}


def _attn_specs(one_chip, cfg, seq=2048):
    return _spec(one_chip, (1, seq, cfg.n_heads, cfg.hd), jnp.bfloat16)


def test_flash_forward_compiles_at_512_tiles(one_chip, cfg):
    x = _attn_specs(one_chip, cfg)
    fn = jax.jit(
        lambda q, k, v: flash_attention_fwd(
            q, k, v, causal=True, q_block=512, kv_block=512, return_lse=True
        )
    )
    assert _kernels(fn.lower(x, x, x).compile()) == {"flash_attention_fwd"}


def test_flash_fused_backward_compiles_at_512_tiles(one_chip, cfg):
    x = _attn_specs(one_chip, cfg)
    lse = _spec(one_chip, (1, 2048, cfg.n_heads), jnp.float32)
    fn = jax.jit(
        lambda q, k, v, o, lse, do: flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, q_block=512, kv_block=512
        )
    )
    compiled = fn.lower(x, x, x, x, lse, x).compile()
    assert _kernels(compiled) == {
        "flash_attention_bwd_delta", "flash_attention_bwd_dq", "flash_attention_bwd_dkv"}


def test_flash_attention_grad_compiles_on_four_chips(topo, cfg):
    """GSPMD cannot partition a Mosaic kernel; under a mesh the attention
    op must run the kernels per batch shard (FSDP training, 4x1 mesh)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    x = jax.ShapeDtypeStruct(
        (4, 2048, cfg.n_heads, cfg.hd), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data")),
    )

    def loss(q, k, v):
        o = ops.attention(q, k, v, causal=True, q_block=512, kv_block=512,
                          impl="pallas")
        return o.astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        compiled = grad.lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jitted_init_fits_one_chip(one_chip, cfg):
    """Full-width random init under jit: the bf16 weights and nothing like
    an f32 copy of a stacked leaf on top."""
    lm = build_model(cfg)
    key = _spec(one_chip, (2,), jnp.uint32)
    compiled = jax.jit(lm.init).lower(key).compile()
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes > 12.5 * GiB  # 6.9 B bf16 parameters
    assert m.temp_size_in_bytes < 0.1 * GiB
    assert _in_hbm(compiled) < HBM_BYTES


def _compile_mixed_step(one_chip, cfg, slots, max_len, width, chunk=None):
    """The engine's ragged mixed step for ``slots`` x ``max_len`` rows of
    ``PAGE``-row pages, at ``width`` tokens a row, on one described chip."""
    lm = build_model(cfg)
    params = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lm.init, jax.random.PRNGKey(0)),
    )
    eng = ServeEngine(
        lm, None, batch_size=slots, max_len=max_len, scheduler="continuous",
        page_size=PAGE, prefill_chunk=chunk,
    )
    s = lambda shape, dt=jnp.int32: _spec(one_chip, shape, dt)
    blocks = max_len // PAGE
    pages = jax.tree.map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda: T.init_pages(cfg, cfg.n_layers, slots * blocks + 1, PAGE)),
    )
    return eng._mixed_step_fn().lower(
        params,
        s((slots, width)),
        pages,
        s((slots, blocks)),
        s((slots,)),
        s((slots,)),
        s(()),
        s((slots,), jnp.float32),
        s((slots,)),
        s((slots,)),
    ).compile()


def test_serve_mixed_step_fits_one_chip(one_chip, cfg):
    """The engine's ragged mixed step at chunk width: weights, the donated
    page pool and the step's temporaries together within one chip."""
    compiled = _compile_mixed_step(one_chip, cfg, SLOTS, MAX_LEN, MAX_LEN)
    assert "paged_flash_decode_fwd" in _kernels(compiled)  # the name the benchmark reads
    assert compiled.memory_analysis().alias_size_in_bytes > 0.9 * GiB
    assert _in_hbm(compiled) < HBM_BYTES


@pytest.mark.parametrize("width", [1, 512], ids=["narrow", "wide"])
def test_moe_mixed_step_reads_experts_in_place(one_chip, olmoe_cfg, width):
    """olmoe-1b-7b's dropless mixed step: the grouped GEMMs read each
    layer's experts from the stacked weights, so no value shaped like one
    layer's expert stack (a copy of all 64 experts) is made."""
    compiled = _compile_mixed_step(
        one_chip, olmoe_cfg, MOE_SLOTS, MOE_MAX_LEN, width, chunk=512
    )
    text = compiled.as_text()
    m = olmoe_cfg.moe
    d, ff = olmoe_cfg.d_model, m.d_ff_expert
    for shape in ((m.num_experts, d, ff), (m.num_experts, ff, d)):
        assert f"bf16[{','.join(map(str, shape))}]" not in text
    assert "ragged-dot" in text
    assert "paged_flash_decode_fwd" in _kernels(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * GiB


def _latent_cfg():
    cfg = get_config("deepseek-v2-lite").with_(attn_impl="pallas")
    return cfg.with_(moe=dataclasses.replace(cfg.moe, experts_held=16))


@pytest.mark.parametrize("width", [1, 512], ids=["narrow", "wide"])
def test_latent_mixed_step_fits_one_chip(one_chip, width):
    """DeepSeek-V2-Lite's mixed step over the latent pool: the paged kernel
    (the name the benchmark reads) serves the latent attention, the pool
    keeps the TPU's row-major layout (no pool-shaped value with the page
    rows minor, which would be a transposed copy for the kernel), the
    step's temporaries stay under 0.45 GiB (0.394 GiB at the chunk's width
    since the pool is written in place; 2.342 GiB, most of it a copy of
    the 2.11 GiB pool, before), and the weights, the pool and the step fit
    the chip."""
    compiled = _compile_mixed_step(
        one_chip, _latent_cfg(), MLA_SLOTS, MLA_MAX_LEN, width, chunk=512
    )
    assert "paged_flash_decode_fwd" in _kernels(compiled)
    assert not re.search(r"bf16\[(1|26),513,1,128,640\]\{3,4,", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 0.45 * GiB
    assert _in_hbm(compiled) < HBM_BYTES


# What a value shaped like a layer's pages or a stack of them may be in the
# compiled step: the entry's pool, the scan's carry, views and the kernels
# that read and write it in place. A copy, slice or fusion is a copy.
POOL_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "custom-call"}


def _pool_shapes(pages: dict) -> set:
    """Each stack's shape, one layer's, and the [L * n_pages] view's."""
    out = set()
    for leaf in pages.values():
        n, rest = leaf.shape[0], leaf.shape[1:]
        out |= {leaf.shape, rest, (n * rest[0],) + rest[1:]}
    return out


MIXED_STEPS = {
    # name: (config, slots, max_len, chunk, temporaries bound at either width)
    "deepseek-7b": (lambda: get_config("deepseek-7b"), SLOTS, MAX_LEN, 256, 0.25),
    "olmoe-1b-7b": (lambda: get_config("olmoe-1b-7b"), MOE_SLOTS, MOE_MAX_LEN, 512, 0.05),
    "deepseek-v2-lite": (_latent_cfg, MLA_SLOTS, MLA_MAX_LEN, 512, 0.45),
}


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "chunk"])
@pytest.mark.parametrize("model", list(MIXED_STEPS))
def test_mixed_step_writes_pool_in_place(one_chip, model, wide):
    """Each cell's mixed step carries the paged pool whole through the layer
    scan: the new rows are written into the donated stack by the in-place
    write (``paged_write``) and read from it by the paged kernel, so no
    value shaped like one layer's pages or the stack is a copy, a slice, a
    write-back or a fusion; the pool is aliased input to output, and the
    step's temporaries are a small part of one pool (0.196 / 0.026 / 0.394
    GiB at the chunk's width when this test was written, against 1.080 /
    1.189 / 2.342 with the pool sliced per layer)."""
    make, slots, max_len, chunk, bound = MIXED_STEPS[model]
    cfg = make().with_(attn_impl="pallas")
    compiled = _compile_mixed_step(
        one_chip, cfg, slots, max_len, chunk if wide else 1, chunk=chunk
    )
    pages = jax.eval_shape(
        lambda: T.init_pages(cfg, cfg.n_layers, slots * max_len // PAGE + 1, PAGE)
    )
    shapes = _pool_shapes(pages)
    made = {
        (op, dims)
        for dims, op in (
            (tuple(map(int, m.group(1).split(","))), m.group(2))
            for m in re.finditer(r"= \w+\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(", compiled.as_text())
        )
        if dims in shapes
    }
    assert made and {op for op, _ in made} <= POOL_OPS, sorted(made)
    assert {"paged_write", "paged_flash_decode_fwd"} <= _kernels(compiled)
    m = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pages))
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < bound * GiB
