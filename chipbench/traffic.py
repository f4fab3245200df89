"""One general traffic generator, driven by the data files in ``traffic/``.

A mix file gives the loop (open with Poisson arrivals at a fixed rate, or
closed with a fixed number of clients), the prompt and output length
distributions, and the engine slots and ``max_len`` the cell serves them
on. Every seed gets the same multiset of lengths and inter-arrival gaps
(drawn at fixed quantiles of the distributions), in an order and with
token ids of its own, so seeds change what is computed but not how much.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
_PAIRING_SEED = 20240229     # fixed: which output length goes with which prompt


@dataclasses.dataclass(frozen=True)
class Request:
    due: float          # seconds after the window opens (open loop); 0 closed
    prompt: np.ndarray  # int32 token ids
    max_new: int


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Generator for one purpose of one run; any non-negative seed."""
    return np.random.default_rng([int(seed), stream])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def pool_size(mix: dict, seconds: float) -> int:
    """Requests drawn for one run. Open loop: as many as the rate offers in
    the pre-roll and the window, so that every one of them is due before
    the window closes and every seed sends the same multiset. Closed loop:
    enough for every client to finish many requests."""
    if mix["loop"] == "open":
        return int(mix["rate_per_s"] * (seconds + mix.get("preroll_s", 0.0)))
    return 64 * int(mix["clients"])


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The run's requests in sending order. Open loop: ``due`` times count
    from the start of the pre-roll; closed loop: ``due`` is 0 and clients
    take requests in list order."""
    n = pool_size(mix, seconds)
    prompts = quantile_lengths(mix["prompt"], n)
    outputs = quantile_lengths(mix["output"], n)
    outputs = outputs[rng_for(_PAIRING_SEED, 0).permutation(n)]
    order = rng_for(seed, 1).permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    if prompts.max() + outputs.max() - 1 > mix["max_len"]:
        raise ValueError("mix lengths do not fit its max_len")
    due = np.zeros(n)
    if mix["loop"] == "open":
        if mix.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u) / float(mix["rate_per_s"])
        due = np.cumsum(gaps[rng_for(seed, 2).permutation(n)])
    tok_rng = rng_for(seed, 3)
    return [Request(float(due[i]),
                    tok_rng.integers(0, vocab, size=int(prompts[i]),
                                     dtype=np.int32),
                    int(outputs[i]))
            for i in range(n)]
