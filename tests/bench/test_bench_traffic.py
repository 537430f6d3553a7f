"""The traffic generator reads mixes from files and keeps to their limits."""

import json

import numpy as np
import pytest

from bench import traffic

MIXES = sorted(p.stem for p in traffic.TRAFFIC_DIR.glob("*.json"))
VOCAB = 50304


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    seed = 2**31 + 99
    for i in range(3):
        a, b = traffic.burst(mix, VOCAB, seed, i), traffic.burst(mix, VOCAB, seed, i)
        assert len(a) == len(b) == mix["burst"]
        for x, y in zip(a, b):
            assert x.max_new == y.max_new and np.array_equal(x.prompt, y.prompt)
    other = traffic.burst(mix, VOCAB, seed + 1, 0)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(traffic.burst(mix, VOCAB, seed, 0), other))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_ids_within_limits(name):
    mix = traffic.load_mix(name)
    p, o = mix["prompt"], mix["output"]
    gen = traffic.bursts(mix, VOCAB, 12345)
    for _ in range(20):
        for r in next(gen):
            assert p["min"] <= len(r.prompt) <= p["max"]
            assert o["min"] <= r.max_new <= o["max"]
            assert len(r.prompt) + r.max_new <= mix["max_len"]
            assert r.prompt.dtype == np.int32
            assert r.prompt.min() >= traffic.FIRST_ID and r.prompt.max() < VOCAB


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_sizes(name):
    """The seed draws the token ids only: every burst of every seed sends
    the same (prompt, output) lengths, the stratified quantiles of the
    mix's distributions in a fixed order."""
    mix = traffic.load_mix(name)
    sizes = lambda seed, i: [(len(r.prompt), r.max_new) for r in traffic.burst(mix, VOCAB, seed, i)]
    for i in range(4):
        assert sizes(1, i) == sizes(2**31 + 5, i) == traffic.sizes(mix)
    prompts = [p for p, _ in traffic.sizes(mix)]
    assert sorted(prompts) == traffic.stratified_lengths(mix["prompt"], mix["burst"]).tolist()
    assert prompts != sorted(prompts)


@pytest.mark.parametrize("name", MIXES)
def test_medians_follow_the_mix(name):
    mix = traffic.load_mix(name)
    for part in ("prompt", "output"):
        lengths = traffic.stratified_lengths(mix[part], mix["burst"])
        want = traffic.median(mix[part])
        assert abs(np.median(lengths) - want) <= 0.1 * want


def test_a_published_mean_sets_the_median():
    dist = {"mean": 214.5, "sigma": 1.0, "min": 1, "max": 10**6}
    assert traffic.median(dist) == pytest.approx(214.5 * np.exp(-0.5))
    lengths = traffic.stratified_lengths(dist, 4096)
    assert lengths.mean() == pytest.approx(214.5, rel=0.02)


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_names_its_source_and_its_cuts(name):
    mix = traffic.load_mix(name)
    assert "arXiv:" in mix["source"] and mix["cuts"] and mix["assumed"]


@pytest.mark.parametrize("name", MIXES)
def test_enough_bursts_serve_the_tokens_asked_for(name):
    mix = traffic.load_mix(name)
    per_burst = sum(o for _, o in traffic.sizes(mix))
    for want in (1, 320, 1000):
        n = traffic.bursts_serving(mix, want)
        assert n * per_burst >= want > (n - 1) * per_burst


def test_a_new_mix_is_found_by_name(tmp_path):
    mix = {"kind": "bursts", "slots": 2, "max_len": 64, "page": 16, "chunk": 32, "burst": 4,
           "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
           "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}
    (tmp_path / "tiny-new.json").write_text(json.dumps(mix))
    got = traffic.load_mix("tiny-new", root=tmp_path)
    reqs = traffic.burst(got, 100, 0, 0)
    assert len(reqs) == 4 and all(4 <= len(r.prompt) <= 16 for r in reqs)
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no-such-mix", root=tmp_path)


def test_a_mix_that_cannot_fit_is_refused(tmp_path):
    mix = {"kind": "bursts", "slots": 2, "max_len": 8, "page": 8, "chunk": 8, "burst": 4,
           "prompt": {"median": 8, "sigma": 0.5, "min": 6, "max": 16},
           "output": {"median": 4, "sigma": 0.5, "min": 4, "max": 8}}
    (tmp_path / "bad.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load_mix("bad", root=tmp_path)
