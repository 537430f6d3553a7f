"""The serve step's in-place page write (``kernels/paged_write.py``).

The layer scan carries each page array whole, stacked over the layers,
and writes every layer's new rows into the stack in place. These hold
that write (the Pallas kernel in interpret mode, and the scatter that
stands in for it off the TPU) to what the step did when each layer's
pages were sliced out of the stack, scattered into and stacked back:
bit-equal in every page the step owns, with every other layer's and
page's bytes untouched; and a few whole steps through ``ServeEngine`` to
the served tokens and pool of that sliced scan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import Request, ServeEngine

N_LAYERS, PAGE, BLOCKS, ROWS = 3, 16, 4, 3
N_PAGES = ROWS * BLOCKS + 1  # page 0 is the dummy page


def _sliced_write(cfg, cache, vals, starts, q_lens):
    """The write as the step made it before the pool was carried: the
    layer's pages sliced out of the stack, one clamped scatter per array
    (invalid rows into the dummy page 0), the layer stacked back."""
    layer = cache["layer"]
    bt = cache["block_table"]
    first = next(iter(vals))
    b, c = vals[first].shape[:2]
    page = cache[first].shape[-2]
    tq = jnp.arange(c, dtype=jnp.int32)[None, :]
    pos = jnp.minimum(starts[:, None] + tq, bt.shape[1] * page - 1)
    phys = jnp.where(tq < q_lens[:, None], jnp.take_along_axis(bt, pos // page, axis=1), 0)
    offset = pos % page
    out = dict(cache)
    for name, val in vals.items():
        pad = out[name].shape[-1] - val.shape[-1]
        val = jnp.pad(val, ((0, 0),) * (val.ndim - 1) + ((0, pad),))
        new = {name: val}
        if cfg.kv_cache_dtype == "int8":
            new[name], new[name + "_scale"] = T._quantize_kv(val)
        for n, v in new.items():
            pages = out[n][layer].at[phys, :, offset].set(v.astype(out[n].dtype))
            out[n] = out[n].at[layer].set(pages)
    return out


def _cfg(kind):
    if kind == "latent":
        return get_config("deepseek-v2-lite").reduced().with_(kv_layout="paged")
    cfg = get_config("deepseek-7b").reduced().with_(kv_layout="paged")
    return cfg.with_(kv_cache_dtype="int8") if kind == "int8" else cfg


def _vals(cfg, rng, c):
    if cfg.mla is not None:  # latents narrower than the page rows
        return {"latent_pages": rng.normal(size=(ROWS, c, 1, cfg.mla.latent_dim))}
    shape = (ROWS, c, cfg.n_kv_heads, cfg.hd)
    return {"k_pages": rng.normal(size=shape), "v_pages": rng.normal(size=shape)}


# (kind, chunk width C, rows' cache lengths, rows' valid chunk positions)
CASES = {
    "decode": ("bf16", 1, [5, 16, 47], [1, 1, 1]),
    "ragged-chunks": ("bf16", 21, [0, 11, 30], [21, 7, 18]),  # each crosses a page end
    "free-rows": ("bf16", 9, [3, 0, 20], [9, 0, 0]),
    "int8": ("int8", 21, [2, 15, 31], [21, 20, 5]),
    "latent": ("latent", 21, [4, 29, 14], [19, 21, 1]),
}


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("case", list(CASES))
def test_in_place_write_matches_the_sliced_scatter(case, impl):
    """Every page the rows own is bit-equal to the sliced scatter's, and
    nothing else changes: not the dummy page, not a page no row writes, not
    another layer (the sliced scatter's dummy page takes the invalid rows,
    which the in-place write drops)."""
    kind, c, lens, q_lens = CASES[case]
    cfg = _cfg(kind).with_(attn_impl=impl)
    rng = np.random.default_rng(sorted(CASES).index(case))
    pages = T.page_leaves(cfg, N_PAGES, PAGE, lead=(N_LAYERS,))
    pages = {
        n: jnp.asarray(rng.integers(-100, 100, p.shape), p.dtype)
        if p.dtype == jnp.int8 else jnp.asarray(rng.normal(size=p.shape), p.dtype)
        for n, p in pages.items()
    }
    bt = jnp.asarray(rng.permutation(np.arange(1, N_PAGES)).reshape(ROWS, BLOCKS), jnp.int32)
    cache = {**pages, "block_table": bt, "layer": jnp.int32(1)}
    vals = {n: jnp.asarray(v, cfg.activation_dtype()) for n, v in _vals(cfg, rng, c).items()}
    starts, qls = jnp.asarray(lens, jnp.int32), jnp.asarray(q_lens, jnp.int32)

    got = T._paged_write(cfg, cache, vals, starts, qls)
    want = _sliced_write(cfg, cache, vals, starts, qls)

    written = {
        int(bt[b, p // PAGE])
        for b in range(ROWS) for p in range(lens[b], lens[b] + q_lens[b])
    }
    assert set(got) == set(cache) and written
    for n in pages:
        g, w, before = (np.asarray(x[n]) for x in (got, want, cache))
        np.testing.assert_array_equal(g[1, 1:], w[1, 1:])   # the layer's real pages
        np.testing.assert_array_equal(g[1, 0], before[1, 0])  # the dummy page
        np.testing.assert_array_equal(np.delete(g, 1, 0), np.delete(before, 1, 0))
        untouched = [p for p in range(1, N_PAGES) if p not in written]
        np.testing.assert_array_equal(g[1, untouched], before[1, untouched])
        if n in vals:
            assert not np.array_equal(g[1, sorted(written)], before[1, sorted(written)])
    if kind == "latent":  # the row past the latent's width stays zero
        row = np.asarray(got["latent_pages"])[1, int(bt[0, 0]), 0, lens[0]]
        assert not row[cfg.mla.latent_dim:].any()


def _sliced_stack_decode(params, cfg, x, caches, *, ffn_apply_fn=None):
    """The serve layer scan before the pool was carried: every cache leaf,
    pages included, scanned per layer and stacked back."""
    ffn_fn = ffn_apply_fn or (lambda p, c, h: T.ffn_apply(p, c, h))
    scanned, stacks, layers = T._split_expert_stacks(cfg, params)
    if layers is None:
        layers = jnp.arange(jax.tree.leaves(scanned)[0].shape[0], dtype=jnp.int32)

    def body(h, xs):
        lp, cache, layer = xs
        a, cache = T.attn_decode(lp["attn"], cfg, L.rmsnorm(lp["ln_attn"], h, cfg.norm_eps), cache)
        h = h + a
        y = T._serve_ffn(ffn_fn, cfg, lp, stacks, layer, L.rmsnorm(lp["ln_ffn"], h, cfg.norm_eps))
        return h + y, cache

    return T.layer_scan(cfg, body, x, (scanned, caches, layers))


def _layer_write(cfg, cache, vals, starts, q_lens):
    """``_sliced_write`` on one layer's pages, as the sliced scan hands them."""
    stacked = {n: v[None] if T.is_page_leaf(n) else v for n, v in cache.items()}
    out = _sliced_write(cfg, {**stacked, "layer": 0}, vals, starts, q_lens)
    del out["layer"]
    return {n: v[0] if T.is_page_leaf(n) else v for n, v in out.items()}


def _serve(cfg, params):
    rng = np.random.default_rng(5)
    reqs = [
        Request(tokens=rng.integers(2, cfg.vocab, size=n).astype(np.int32),
                max_new_tokens=4, rid=i, arrival=i)
        for i, n in enumerate([19, 5, 26])
    ]
    eng = ServeEngine(
        build_model(cfg), params, batch_size=2, max_len=48, scheduler="continuous",
        page_size=8, prefill_chunk=8,
    )
    out = eng.generate(reqs)
    return [r.tokens for r in out], {n: np.asarray(p) for n, p in eng.last_pool.pages.items()}


@pytest.mark.parametrize("arch", ["deepseek-7b", "deepseek-v2-lite"])
def test_serve_steps_match_the_sliced_scan(arch, monkeypatch):
    """Chunked prefill beside decode rows for several steps, the paged
    kernel and the in-place write in interpret mode: the served tokens, and
    every page but the dummy one, equal those of the sliced scan with its
    scatter."""
    cfg = get_config(arch).reduced().with_(attn_impl="pallas_interpret")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    toks, pool = _serve(cfg, params)
    monkeypatch.setattr(T, "stack_decode", _sliced_stack_decode)
    monkeypatch.setattr(T, "_paged_write", _layer_write)
    want_toks, want_pool = _serve(cfg, params)
    for a, b in zip(toks, want_toks):
        np.testing.assert_array_equal(a, b)
    assert set(pool) == set(want_pool)
    for n in pool:
        assert pool[n][:, 1:].any()
        np.testing.assert_array_equal(pool[n][:, 1:], want_pool[n][:, 1:])
