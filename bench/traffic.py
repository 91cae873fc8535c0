"""The one traffic generator: turns a mix's data file and a seed into work.

Every seed gets the same set of sizes; the seed only orders them and draws
the token ids.  So two seeds do the same amount of work, and a difference
between their runs is noise, not a different mix.

The ``serve_waves`` mixes are closed loops of full waves of ``batch``
requests.  A cycle of ``waves_per_cycle`` waves holds prompt and output
lengths taken at evenly spaced quantiles of clipped lognormals, so every
cycle has the distribution's shape exactly.  With ``one_prompt_length_per_wave``
each wave's prompts share one length, taken at one of ``waves_per_cycle``
quantiles, and the engine pads nothing; without it every request has its
own prompt length, taken at one of ``waves_per_cycle * batch`` quantiles, and
the engine left-pads a wave's shorter prompts.  The seed shuffles the waves
of each cycle and the requests inside a wave.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of ``seed`` (any non-negative integer)."""
    return np.random.default_rng([int(seed), int(stream)])


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> list[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n, clipped to [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


@dataclasses.dataclass(frozen=True)
class WaveSpec:
    prompt_lens: tuple[int, ...]    # prompt length of each request
    output_lens: tuple[int, ...]    # max_new_tokens of each request

    @property
    def padded_len(self) -> int:
        """The prompt length the engine pads the wave to."""
        return max(self.prompt_lens)


def _deal(values: list[int], waves: int, per_wave: int,
          stream: int) -> list[tuple[int, ...]]:
    """A fixed deal of ``values`` over the waves, the same for every seed."""
    order = np.random.default_rng(stream).permutation(len(values))
    return [tuple(int(values[j]) for j in order[i * per_wave:
                                                (i + 1) * per_wave])
            for i in range(waves)]


def wave_cycle(mix: dict) -> list[WaveSpec]:
    """The seed-independent set of waves of one cycle, in a fixed order."""
    b, w = int(mix["batch"]), int(mix["waves_per_cycle"])
    p, o = mix["prompt_len"], mix["output_len"]
    outputs = _deal(lognormal_quantiles(w * b, o["median"], o["sigma"],
                                        o["min"], o["max"]), w, b, 0)
    if mix["one_prompt_length_per_wave"]:
        prompts = [(n,) * b for n in lognormal_quantiles(
            w, p["median"], p["sigma"], p["min"], p["max"])]
    else:
        prompts = _deal(lognormal_quantiles(w * b, p["median"], p["sigma"],
                                            p["min"], p["max"]), w, b, 1)
    waves = [WaveSpec(ps, os) for ps, os in zip(prompts, outputs)]
    for wv in waves:
        if wv.padded_len + max(wv.output_lens) > int(mix["max_len"]):
            raise ValueError(f"wave {wv} does not fit max_len "
                             f"{mix['max_len']}")
    return waves


def wave_stream(mix: dict, seed: int, vocab: int):
    """Endless (WaveSpec, prompts, output lengths) in the seed's order:
    each cycle is the same set of waves, shuffled anew; ``prompts`` is a
    list of int32 arrays, one per request."""
    cycle = wave_cycle(mix)
    order_rng, token_rng = rng_for(seed, 1), rng_for(seed, 2)
    while True:
        for i in order_rng.permutation(len(cycle)):
            wv = cycle[i]
            perm = order_rng.permutation(len(wv.output_lens))
            lens = [wv.output_lens[j] for j in perm]
            prompts = [token_rng.integers(0, vocab, size=wv.prompt_lens[j],
                                          dtype=np.int32) for j in perm]
            yield wv, prompts, lens
