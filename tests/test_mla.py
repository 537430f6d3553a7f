"""Latent attention (DeepSeek-V2's MLA) in the program at small sizes on
the CPU: YaRN's frequencies and score scale, the latent paged pool, and the
paged kernel on a latent pool (interpret mode, one and several query
blocks) against plain attention. The comparison with the plain reference
is in ``tests/bench/test_bench_mla.py``."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.attention import paged_decode_attention
from repro.kernels import flash_decode as fd
from repro.models import mla
from repro.models import transformer as T
from repro.serve.kv_pool import PagedKVPool


def test_yarn_frequencies_and_scale_are_the_published_ones():
    m = get_config("deepseek-v2-lite").mla
    inv = mla.yarn_inv_freq(m, 10000.0)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    r = 1 - np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(inv, (1 - r) * base / 40 + r * base, rtol=1e-6)
    assert mla.softmax_scale(m) == pytest.approx(0.114721, abs=5e-7)


def test_the_pool_holds_one_latent_per_token_and_layer():
    """One latent row a token a layer, in a pool of its own for the leading
    dense stack, each row zero-padded to whole 128-lane tiles: 576 latent
    values in 640 lanes at DeepSeek-V2-Lite's widths (31,104 B of latent a
    token over 27 layers in bf16, 34,560 B of pool)."""
    cfg = get_config("deepseek-v2-lite").reduced()
    pool = PagedKVPool(cfg.with_(kv_layout="paged", page_size=8), cfg.n_layers, 2, 32)
    assert {n: a.shape for n, a in pool.pages.items()} == {
        "dense_latent_pages": (1, 9, 1, 8, 128), "latent_pages": (1, 9, 1, 8, 128)}
    full = get_config("deepseek-v2-lite")
    assert full.mla.latent_dim * 27 * 2 == 31_104
    leaves = jax.eval_shape(lambda: T.init_pages(full, 27, 1, 1))
    assert {n: a.shape for n, a in leaves.items()} == {
        "dense_latent_pages": (1, 1, 1, 1, 640), "latent_pages": (26, 1, 1, 1, 640)}


def test_a_latent_write_pads_the_row_with_zeros():
    cfg = get_config("deepseek-v2-lite").reduced().with_(kv_layout="paged", page_size=8)
    cache = T.init_cache(cfg, batch=1, max_len=16)
    cache["block_table"] = cache["block_table"] + 1  # page 0 is the dummy page
    cache["latent_pages"] = jnp.zeros((3, 1, 8, 128))
    lat = jnp.arange(1.0, 41.0).reshape(1, 1, 1, 40)
    out = T._paged_write(cfg, cache, {"latent_pages": lat}, jnp.array([3]), jnp.array([1]))
    row = np.asarray(out["latent_pages"][1, 0, 3])
    assert (row[:40] == np.arange(1, 41)).all() and not row[40:].any()


def _oracle(q, pool, bt, lens, q_lens, dv, scale):
    """Plain attention of each valid query row over its row's keys."""
    b, c = q.shape[:2]
    kv = np.asarray(pool)[np.asarray(bt)].reshape(b, -1, pool.shape[-1])[..., : q.shape[-1]]
    out = np.zeros((b, c, q.shape[2], dv), np.float32)
    for i in range(b):
        for t in range(q_lens[i]):
            pos = lens[i] - q_lens[i] + t
            s = np.einsum("hd,kd->hk", np.asarray(q[i, t]), kv[i, : pos + 1]) * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            out[i, t] = (p / p.sum(-1, keepdims=True)) @ kv[i, : pos + 1, :dv]
    return out


@pytest.mark.parametrize("c", [5, 65], ids=["one-block", "two-query-blocks"])
def test_latent_paged_kernel_matches_plain_attention(c):
    """The Pallas kernel (interpret mode) on a latent pool: one page tile is
    K in its 40 latent columns (zero-padded to 128 lanes) and V in its first
    32; ragged rows and 16 heads on the one latent head, so a 65-position
    chunk folds to 1,040 query rows, two blocks of ``MAX_Q_ROWS``."""
    rng = np.random.default_rng(0)
    b, hq, dl, dv, page, nb = 3, 16, 40, 32, 8, 12
    pool = rng.normal(size=(b * nb + 1, 1, page, dl)).astype(np.float32)
    pool = jnp.asarray(np.pad(pool, ((0, 0), (0, 0), (0, 0), (0, 128 - dl))))
    q = jnp.asarray(rng.normal(size=(b, c, hq, dl)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, b * nb + 1)).reshape(b, nb).astype(np.int32))
    lens = np.array([5, 70, 96], np.int32)
    q_lens = np.array([1, min(c, 70), 3], np.int32)
    assert (c * hq > fd.MAX_Q_ROWS) == (c == 65)
    out = fd.paged_flash_decode_fwd(q, pool, None, lens, bt, q_lens=q_lens, order="sawtooth",
                                    scale=0.3, v_dim=dv, interpret=True)
    assert out.shape == (b, c, hq, dv)
    valid = np.arange(c) < q_lens[:, None]
    want = _oracle(q, pool, bt, lens, q_lens, dv, 0.3)
    # float32 online softmax page by page against a one-pass softmax
    np.testing.assert_allclose(np.asarray(out)[valid], want[valid], rtol=1e-5, atol=1e-5)
    xla = paged_decode_attention(q, pool, None, lens, bt, q_lens=q_lens, scale=0.3, v_dim=dv)
    np.testing.assert_allclose(np.asarray(xla)[valid], want[valid], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("per", [3, 5], ids=["whole-groups", "padded-group"])
def test_latent_kernel_over_page_groups_matches_plain_attention(monkeypatch, per):
    """Several pages a grid step: 12 pages in groups of 3, or of 5 with the
    last group padded by pages no query sees; two query blocks and a
    sawtooth visit order, so a group mixes pages some queries see with
    pages none does."""
    monkeypatch.setattr(fd, "KV_ROWS", per * 8)
    rng = np.random.default_rng(3)
    b, c, hq, dl, dv, page, nb = 3, 65, 16, 40, 32, 8, 12
    pool = rng.normal(size=(b * nb + 1, 1, page, dl)).astype(np.float32)
    pool = jnp.asarray(np.pad(pool, ((0, 0), (0, 0), (0, 0), (0, 128 - dl))))
    q = jnp.asarray(rng.normal(size=(b, c, hq, dl)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, b * nb + 1)).reshape(b, nb).astype(np.int32))
    lens = np.array([17, 90, 96], np.int32)
    q_lens = np.array([1, 65, 3], np.int32)
    # The jitted function's body, traced afresh, reads the patched KV_ROWS.
    out = fd.paged_flash_decode_fwd.__wrapped__(
        q, pool, None, lens, bt, q_lens=q_lens, order="sawtooth", scale=0.3, v_dim=dv,
        interpret=True)
    valid = np.arange(c) < q_lens[:, None]
    want = _oracle(q, pool, bt, lens, q_lens, dv, 0.3)
    # float32 online softmax group by group against a one-pass softmax
    np.testing.assert_allclose(np.asarray(out)[valid], want[valid], rtol=1e-5, atol=1e-5)


def test_kv_pool_kernel_with_query_blocks_matches_plain_attention():
    """K and V pools whose folded query rows (520 positions x 2 GQA rows)
    take two blocks: the blocked grid gives plain attention."""
    rng = np.random.default_rng(2)
    b, c, hq, hkv, d, page, nb = 2, 520, 4, 2, 16, 8, 70
    kp, vp = (jnp.asarray(rng.normal(size=(b * nb + 1, hkv, page, d)).astype(np.float32))
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)).astype(np.float32))
    bt = jnp.asarray(np.arange(1, b * nb + 1).reshape(b, nb).astype(np.int32))
    lens, q_lens = np.array([9, 530], np.int32), np.array([2, 520], np.int32)
    assert c * hq // hkv > fd.MAX_Q_ROWS
    out = fd.paged_flash_decode_fwd(q, kp, vp, lens, bt, q_lens=q_lens, interpret=True)
    want = paged_decode_attention(q, kp, vp, lens, bt, q_lens=q_lens)
    valid = np.arange(c) < q_lens[:, None]
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(want)[valid], rtol=1e-5,
                               atol=1e-5)
