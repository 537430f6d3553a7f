"""DeepSeek-V2-Lite's block in the program against its plain reference
(``bench/reference/mla_moe.py``) at the program's reduced size on the CPU:
prefill then ragged paged decode through the latent pool, and the
continuous engine's greedy tokens and query-key pair count. The reference
keeps the contract every reference keeps (it pairs every matrix it draws
with the program's, its sizing probes compile), and a chip's shares of
the routed experts add up to the whole layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, serve
from repro.models import build_model
from repro.serve import Request, ServeEngine

SEED = 2**31 + 17


@pytest.fixture(scope="module")
def cfg():
    """The benchmark's configuration (16 of 64 experts held) at reduced size."""
    return serve.model_config(harness.load_config("deepseek-v2-lite")["model"]).reduced()


def test_reduced_size_keeps_the_block(cfg):
    m = cfg.mla
    assert (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim) == (32, 16, 8, 16)
    assert cfg.first_dense_layers == 1 and cfg.n_layers == 2
    assert (cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.n_shared_experts) == (4, 2, 2)
    assert not cfg.moe.norm_topk_prob


def _dummy_page_free(caches):
    """The prefill cache with every page moved up by one: a ragged step
    writes its padding rows to page 0, the serving pool's dummy page, which
    the prefill cache's identity block table gives to row 0."""
    out = dict(caches)
    for name, leaf in caches.items():
        if name.endswith("_pages"):
            out[name] = jnp.pad(leaf, ((0, 0), (1, 0)) + ((0, 0),) * (leaf.ndim - 2))
    out["block_table"] = caches["block_table"] + 1
    return out


def test_prefill_then_ragged_paged_decode_matches_the_reference(cfg):
    """Prefill of 2 x 12 tokens into the latent pool, then two ragged chunk
    steps (C = 3, rows taking 3 and 1, then 2 and 3 positions) through the
    paged path: every valid position's logits agree with the reference's
    forward over the row's whole sequence."""
    ref = harness.load_reference("mla_moe")
    pcfg = cfg.with_(kv_layout="paged", page_size=8)
    lm = build_model(pcfg)
    params = lm.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(1)
    s, c = 12, 3
    toks = rng.integers(2, cfg.vocab, size=(2, s)).astype(np.int32)
    steps = [np.array([3, 1]), np.array([2, 3])]
    chunks = [rng.integers(2, cfg.vocab, size=(2, c)).astype(np.int32) for _ in steps]
    prefill = jax.jit(lambda p, t: lm.prefill(p, {"tokens": t}, 32))
    decode = jax.jit(lm.decode_step)
    with jax.default_matmul_precision("highest"):
        _, caches = prefill(params, jnp.asarray(toks))
        caches = _dummy_page_free(caches)
        got = [[], []]
        for q_lens, chunk in zip(steps, chunks):
            caches["q_len"] = jnp.broadcast_to(jnp.asarray(q_lens, jnp.int32),
                                               caches["len"].shape)
            logits, caches = decode(params, jnp.asarray(chunk), caches)
            for b in range(2):
                got[b] += [np.asarray(logits[b, t]) for t in range(q_lens[b])]
    # Both rows' whole sequences, the shorter right-padded (harmless under
    # the causal mask), in one reference pass.
    seqs = np.zeros((2, s + 5), np.int32)
    for b in range(2):
        row = np.concatenate([toks[b]] + [ch[b, :q[b]] for q, ch in zip(steps, chunks)])
        seqs[b, : len(row)] = row
    want = ref.logits(serve.model_dict(cfg), SEED, seqs)
    for b in range(2):
        # The program in float32 at HIGHEST precision against the float32
        # reference: the absorbed and the decompressed attention round in
        # different orders (the cell agreement test's tolerance).
        np.testing.assert_allclose(np.stack(got[b]), want[b, s : s + len(got[b])],
                                   rtol=2e-4, atol=2e-4)
    assert [len(g) for g in got] == [5, 4]


def test_engine_serves_through_the_latent_pool_and_counts_pairs(cfg):
    """The continuous engine on the latent pool: the greedy tokens are the
    ones the reference puts first, and the query-key pairs counted at each
    step's commit are each request's causal pairs."""
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(SEED))
    eng = ServeEngine(lm, params, batch_size=2, max_len=48, scheduler="continuous",
                      page_size=8, prefill_chunk=8)
    rng = np.random.default_rng(3)
    reqs = [Request(tokens=rng.integers(2, cfg.vocab, size=n).astype(np.int32),
                    max_new_tokens=k, rid=i, eos_id=-1) for i, (n, k) in
            enumerate([(13, 4), (5, 6), (20, 3)])]
    with jax.default_matmul_precision("highest"):
        results = eng.generate(reqs)
    assert set(eng.last_pool.pages) == {"dense_latent_pages", "latent_pages"}
    ref = harness.load_reference("mla_moe")
    pairs = 0
    for r, res in zip(reqs, results):
        assert res.status == "ok" and len(res.tokens) == r.max_new_tokens
        seq = np.concatenate([r.tokens, res.tokens[:-1]])[None]
        want = ref.logits(serve.model_dict(cfg), SEED, seq)[0, len(r.tokens) - 1:]
        assert (want.argmax(-1) == res.tokens).all()
        n = len(seq[0])
        pairs += n * (n + 1) // 2
    assert eng.obs.value("serve.step.qk_pairs") == pairs


def _reduced():
    return serve.model_config(harness.load_config("deepseek-v2-lite")["model"]).reduced()


MLA_MOE = harness.load_reference("mla_moe")


def test_mla_moe_pairs_every_matrix_it_draws():
    """Every matrix of the program's parameters (every leaf but the norms'
    scales) is paired once, by name, layer by layer across both stacks."""
    from repro.models import build_model

    cfg = _reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(7))
    got = [what for what, _, _ in MLA_MOE.weight_pairs(serve.model_dict(cfg), 7, params)]
    attn = ["wq", "wkva", "wkvb", "wo"]
    swiglu = ["wg", "wu", "wd"]
    moe = swiglu + ["router"] + [f"shared {k}" for k in swiglu]
    assert got == ["embed", "lm_head"] + [f"layer 0 {k}" for k in attn + swiglu] + [
        f"layer 1 {k}" for k in attn + moe]
    matrices = [path for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
                if path[-1].key != "scale"]
    assert len(matrices) == 2 + len(attn + swiglu) + len(attn + moe)


def test_mla_moe_compile_probes_compile():
    model = serve.model_dict(_reduced())
    probes = list(MLA_MOE.compile_probes(model, 32))
    assert [p[0] for p in probes] == ["reference layer weights", "reference layer, f32",
                                      "reference layer, fp8", "reference logits"]
    for _, fn, *shapes in probes:
        jax.jit(fn).lower(*shapes).compile()


def test_mla_moe_shares_add_up_to_the_uncut_layer():
    """Four chips each holding 2 of 8 routed experts (chip i: ids [2i, 2i+2)):
    their routed parts, plus the shared experts counted once, equal the
    uncut reference's expert layer."""
    cfg = _reduced()
    whole = serve.model_dict(cfg.with_(moe=dataclasses.replace(
        cfg.moe, num_experts=8, top_k=2, experts_held=None)))
    dm = MLA_MOE.Dims.of(whole)
    w = MLA_MOE._layer_weights(jax.random.PRNGKey(3), dm, False)
    y = jax.random.normal(jax.random.PRNGKey(4), (13, dm.d))
    with jax.default_matmul_precision("highest"):
        want = MLA_MOE.ffn(y, w, dm, "f32")
        shared = MLA_MOE._swiglu(y, w["shared"], "f32")
        parts = [MLA_MOE.routed(y, {**w, **{n: w[n][2 * i : 2 * i + 2] for n in ("wg", "wu", "wd")}},
                                dm, "f32", first=2 * i) for i in range(4)]
    # float32 sums of the same terms in another order
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
