"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``.

A mix of kind ``bursts`` describes back-to-back bursts of requests. Every
request of a burst arrives when the burst starts. Prompt and output lengths
follow two clipped lognormals, each given by the statistic its source
publishes (``median``, or ``mean`` with the median then ``mean *
exp(-sigma^2 / 2)``), a ``sigma``, and a clip (``min``, ``max``); an output
is further clipped to ``max_len`` less its prompt.

Every burst replays one fixed set of sizes: the stratified quantiles
``(i + 1/2) / burst`` of each distribution, prompts and outputs each in an
order drawn from a fixed seed of the generator's own. So each whole burst
of a window holds the same requests, and a window that completes one more
burst adds the same latencies again rather than new ones. The run's seed
draws the token ids (from ``[2, vocab)``) and, elsewhere, the weights; it
does not change the sizes, because with end-of-sequence off the ids do not
change the work, and the order in which long and short requests meet in
the slots changes the work a window completes by several percent, which
would read as noise between seeds.
"""

from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Iterator, NamedTuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
FIRST_ID = 2  # ids 0 and 1 are kept for padding and end-of-sequence
ORDER_SEED = {"prompt": 1, "output": 2}  # the stream's fixed order, the same for every run


class Req(NamedTuple):
    prompt: np.ndarray  # int32 token ids
    max_new: int


def median(dist: dict) -> float:
    """The lognormal's median, from the statistic the mix gives."""
    if "median" in dist:
        return float(dist["median"])
    return float(dist["mean"]) * math.exp(-dist["sigma"] ** 2 / 2)


def load_mix(name: str, root: Path = TRAFFIC_DIR) -> dict:
    """The mix stored as ``<root>/<name>.json``."""
    path = Path(root) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("kind") != "bursts":
        raise ValueError(f"traffic mix {name!r}: unknown kind {mix.get('kind')!r}")
    for part in ("prompt", "output"):
        d = mix[part]
        if ("median" in d) == ("mean" in d):
            raise ValueError(f"traffic mix {name!r}: {part} needs one of median or mean")
        if not 1 <= d["min"] <= d["max"] or d["sigma"] < 0:
            raise ValueError(f"traffic mix {name!r}: {part} needs 1 <= min <= max, sigma >= 0")
    if mix["prompt"]["max"] + mix["output"]["min"] > mix["max_len"]:
        raise ValueError(f"traffic mix {name!r}: the longest prompt leaves no room for an output")
    return mix


def stratified_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 1/2) / n`` of the clipped lognormal."""
    z = statistics.NormalDist()
    mu = math.log(median(dist))
    out = [math.exp(mu + dist["sigma"] * z.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(out), dist["min"], dist["max"]).astype(np.int64)


def sizes(mix: dict) -> list[tuple[int, int]]:
    """(prompt, output) lengths of every burst, in order: the same for every seed."""
    prompts, outputs = (
        np.random.default_rng(ORDER_SEED[part]).permutation(stratified_lengths(mix[part], mix["burst"]))
        for part in ("prompt", "output"))
    outputs = np.minimum(outputs, mix["max_len"] - prompts)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def bursts_serving(mix: dict, tokens: int) -> int:
    """How many bursts it takes to serve ``tokens`` output tokens."""
    return -(-tokens // sum(o for _, o in sizes(mix)))


def burst(mix: dict, vocab: int, seed: int, index: int) -> list[Req]:
    """Burst ``index`` of the mix for ``seed``."""
    ids = np.random.default_rng([seed, index])
    return [Req(ids.integers(FIRST_ID, vocab, size=p).astype(np.int32), o)
            for p, o in sizes(mix)]


def bursts(mix: dict, vocab: int, seed: int) -> Iterator[list[Req]]:
    """Bursts 0, 1, 2, ... of the mix for ``seed``."""
    index = 0
    while True:
        yield burst(mix, vocab, seed, index)
        index += 1
