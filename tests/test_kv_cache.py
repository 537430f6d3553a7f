"""KV caches: int8 quantization quality, decode consistency, sharding rules,
and paged-layout parity (block-table decode vs the contiguous oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ParallelConfig, all_configs, get_config
from repro.core.attention import decode_attention
from repro.dist import sharding as shd
from repro.kernels.flash_decode import flash_decode_fwd
from repro.models import build_model
from repro.models.transformer import _dequantize_kv, _quantize_kv, fill_cache, init_cache


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 64)) * 3.0
    q, scale = _quantize_kv(x)
    back = _dequantize_kv(q, scale, jnp.float32)
    # symmetric per-vector int8: |err| <= scale/2 elementwise
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert (np.abs(np.asarray(back - x)) <= bound).all()


def test_init_and_fill_int8_cache():
    cfg = get_config("deepseek-7b").reduced().with_(kv_cache_dtype="int8")
    cache = init_cache(cfg, batch=2, max_len=32)
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].shape == (2, 32, cfg.n_kv_heads)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.n_kv_heads, cfg.hd))
    cache = fill_cache(cfg, cache, k, k)
    back = _dequantize_kv(cache["k"][:, :16], cache["k_scale"][:, :16], jnp.float32)
    np.testing.assert_allclose(np.asarray(back), np.asarray(k), atol=0.05)


@pytest.mark.parametrize("arch", ["deepseek-7b", "mixtral-8x7b"])
def test_int8_decode_close_to_bf16(arch):
    cfg = all_configs()[arch].reduced()
    lm16 = build_model(cfg)
    lm8 = build_model(cfg.with_(kv_cache_dtype="int8"))
    params = lm16.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    lg16, c16 = jax.jit(lambda p, b: lm16.prefill(p, b, 48))(params, {"tokens": toks})
    lg8, c8 = jax.jit(lambda p, b: lm8.prefill(p, b, 48))(params, {"tokens": toks})
    nxt = jnp.argmax(lg16[:, -1], -1)[:, None]
    d16, _ = jax.jit(lm16.decode_step)(params, nxt, c16)
    d8, _ = jax.jit(lm8.decode_step)(params, nxt, c8)
    rel = float(jnp.abs(d8 - d16).max() / (jnp.abs(d16).max() + 1e-9))
    assert rel < 0.1, rel
    # memory halves (8-bit payload + small scales)
    b16 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(c16))
    b8 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(c8))
    assert b8 < 0.75 * b16


# ---- paged layout ----------------------------------------------------------


def _paged_problem(seed=0, b=3, hq=8, hkv=2, d=16, page=8, nb=4):
    """Random pool + shuffled block table + ragged lens + contiguous oracle."""
    rng = np.random.default_rng(seed)
    n_pages = b * nb + 1
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, page, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, page, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    perm = rng.permutation(np.arange(1, n_pages))[: b * nb].reshape(b, nb)
    bt = jnp.asarray(perm.astype(np.int32))
    lens = jnp.asarray(np.array([5, 17, nb * page], np.int32))  # ragged
    kc = kp[bt].transpose(0, 1, 3, 2, 4).reshape(b, nb * page, hkv, d)
    vc = vp[bt].transpose(0, 1, 3, 2, 4).reshape(b, nb * page, hkv, d)
    return q, kp, vp, bt, lens, kc, vc


@pytest.mark.parametrize("order", ["cyclic", "sawtooth", "block_snake"])
@pytest.mark.parametrize("window", [None, 7])
def test_paged_decode_matches_contiguous_oracle(order, window):
    q, kp, vp, bt, lens, kc, vc = _paged_problem()
    ref = decode_attention(q, kc, vc, lens, window=window)
    out = decode_attention(
        q, kp, vp, lens, block_table=bt, window=window, order=order
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    outk = flash_decode_fwd(
        q, kp, vp, lens, block_table=bt, window=window, order=order, interpret=True
    )
    np.testing.assert_allclose(np.asarray(outk), np.asarray(ref), atol=2e-5)


def test_paged_decode_free_slot_rows_are_zero():
    """len=0 rows (free continuous-batching slots) read back exact zeros."""
    q, kp, vp, bt, lens, _, _ = _paged_problem()
    lens = lens.at[0].set(0)
    for fn in (
        lambda: decode_attention(q, kp, vp, lens, block_table=bt, order="sawtooth"),
        lambda: flash_decode_fwd(
            q, kp, vp, lens, block_table=bt, order="sawtooth", interpret=True
        ),
    ):
        out = np.asarray(fn())
        assert not np.isnan(out).any()
        assert np.abs(out[0]).max() == 0.0


def test_paged_init_and_fill():
    cfg = get_config("deepseek-7b").reduced().with_(kv_layout="paged", page_size=8)
    cache = init_cache(cfg, batch=2, max_len=20)  # 3 pages per row
    assert cache["k_pages"].shape == (6, cfg.n_kv_heads, 8, cfg.hd)
    np.testing.assert_array_equal(
        np.asarray(cache["block_table"]), np.arange(6).reshape(2, 3)
    )
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 13, cfg.n_kv_heads, cfg.hd))
    cache = fill_cache(cfg, cache, k, k)
    np.testing.assert_array_equal(np.asarray(cache["len"]), [13, 13])
    got = (
        np.asarray(cache["k_pages"])
        .reshape(2, 3, cfg.n_kv_heads, 8, cfg.hd)
        .transpose(0, 1, 3, 2, 4)
        .reshape(2, 24, cfg.n_kv_heads, cfg.hd)
    )
    np.testing.assert_allclose(got[:, :13], np.asarray(k), rtol=1e-6)
    assert np.abs(got[:, 13:]).max() == 0.0  # tail pages zero-padded


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_paged_model_decode_matches_contiguous(kv_dtype):
    """Same params, paged vs contiguous layout: greedy decode must agree."""
    cfg = get_config("deepseek-7b").reduced().with_(kv_cache_dtype=kv_dtype)
    cfgp = cfg.with_(kv_layout="paged", page_size=16)
    lm, lmp = build_model(cfg), build_model(cfgp)
    params = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    lg, c = jax.jit(lambda p, b: lm.prefill(p, b, 48))(params, {"tokens": toks})
    lgp, cp = jax.jit(lambda p, b: lmp.prefill(p, b, 48))(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lgp), atol=1e-5)
    nxt = jnp.argmax(lg[:, -1], -1)[:, None]
    for _ in range(3):
        lg, c = jax.jit(lm.decode_step)(params, nxt, c)
        lgp, cp = jax.jit(lmp.decode_step)(params, nxt, cp)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lgp), atol=1e-4)
        nxt = jnp.argmax(lg[:, -1], -1)[:, None]


def test_paged_layout_rejects_swa():
    cfg = get_config("mixtral-8x7b").reduced().with_(kv_layout="paged")
    with pytest.raises(ValueError, match="full attention"):
        init_cache(cfg, batch=1, max_len=32)


def test_cache_seq_shard_fallback_for_gqa():
    """hkv=8 doesn't divide model=16 -> the cache shards its seq dim."""
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    pcfg = ParallelConfig(fsdp_axes=("data",), data_axes=("data",))
    caches = {
        "k": jax.ShapeDtypeStruct((126, 128, 32768, 8, 128), jnp.bfloat16),
        "k_scale": jax.ShapeDtypeStruct((126, 128, 32768, 8), jnp.float32),
    }
    sh = shd.cache_shardings(caches, pcfg, mesh)
    assert sh["k"].spec == jax.sharding.PartitionSpec(None, "data", "model", None, None)
    assert sh["k_scale"].spec == jax.sharding.PartitionSpec(None, "data", "model", None)
    # divisible heads keep head sharding
    caches2 = {"k": jax.ShapeDtypeStruct((30, 128, 32768, 32, 128), jnp.bfloat16)}
    sh2 = shd.cache_shardings(caches2, pcfg, mesh)
    assert sh2["k"].spec == jax.sharding.PartitionSpec(None, "data", None, "model", None)
