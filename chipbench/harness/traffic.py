"""One generator for every traffic mix, driven by the mix's data file.

A mix file (``traffic/<mix>.json``) gives gamma-shaped prompt and output
lengths in tokens with their clips, the loop (``open``: arrivals at a
rate in requests per second, with gamma-shaped gaps whose coefficient of
variation is ``arrivals.gamma_cv``: 1 is a Poisson process, above 1
bursty; ``closed``: a fixed number of outstanding requests), the ramp
before the window, and ``set_seed``.

Every run seed gets the same schedule: the same sizes, arriving at the
same times, in the same order.  The set of sizes and gaps is drawn once,
at evenly spaced quantiles of each distribution, and put in order, both
from the mix's own ``set_seed``; the run's ``--seed`` draws the prompts'
token ids (and the weights).  So two seeds do the same work, and a spread
between seeds is the system's, not the draw's: with ~25 requests in a
window, an order drawn per seed moves a TTFT tail by far more than the
system's own noise does.

Request ``i`` is a pure function of (mix, seed, rate, seconds, vocab, i),
and its size and due time of (mix, rate, seconds, i) alone.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np
from scipy.special import gammaincinv

#: sample size for the quantiles of each length distribution
_QUANTILE_SAMPLE = 200_000


@dataclasses.dataclass(frozen=True)
class Item:
    """One request: when it is due (seconds after the traffic starts;
    None in a closed loop, where it is due when a slot of the loop frees),
    its prompt, and how many tokens it asks for."""

    index: int
    due_s: float | None
    prompt: np.ndarray
    n_new: int


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([w % 2**64 for w in words]))


def quantile_set(dist: dict, n: int, set_seed: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of the clipped gamma
    ``dist`` (``gamma_shape``, ``gamma_scale`` in tokens, ``min``, ``max``)."""
    sample = np.sort(_rng(set_seed).gamma(dist["gamma_shape"],
                                          dist["gamma_scale"],
                                          size=_QUANTILE_SAMPLE))
    pick = ((np.arange(n) + 0.5) / n * _QUANTILE_SAMPLE).astype(np.int64)
    return np.clip(np.rint(sample[pick]), dist["min"],
                   dist["max"]).astype(np.int64)


def gamma_gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles (i + 1/2) / n of a gamma
    with mean ``1 / rate`` seconds and coefficient of variation ``cv``
    (shape ``1 / cv**2``; ``cv`` 1 is the exponential, a Poisson process)."""
    shape = 1.0 / cv ** 2
    u = (np.arange(n) + 0.5) / n
    return gammaincinv(shape, u) / (shape * rate)


class Traffic:
    """The requests of one run, generated on demand by index."""

    def __init__(self, mix: dict, *, seed: int, vocab: int, seconds: float,
                 rate: float | None = None):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.closed = mix["loop"] == "closed"
        self.ramp_s = float(mix["ramp_s"])
        if mix["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {mix['loop']!r} in mix "
                             f"{mix.get('name')!r}")
        if self.closed:
            self.outstanding = int(mix["outstanding"])
            n = int(mix["set_size"])
            self.gaps = None
        else:
            arrivals = mix.get("arrivals")
            if not isinstance(arrivals, dict) or set(arrivals) != {"gamma_cv"} \
                    or not arrivals["gamma_cv"] > 0:
                raise ValueError(f"arrivals must be {{'gamma_cv': > 0}}, not "
                                 f"{arrivals!r}")
            if not rate or rate <= 0:
                raise ValueError("an open-loop mix needs a rate > 0 per s")
            self.rate = float(rate)
            # one set covers the ramp and the window; the drain after the
            # window runs through the same set again, in another order
            n = max(8, math.ceil(self.rate * (self.ramp_s + seconds)))
            self.gaps = gamma_gaps(self.rate, float(arrivals["gamma_cv"]), n)
        self.n = n
        self.prompt_lens = quantile_set(mix["prompt"], n, mix["set_seed"])
        self.output_lens = quantile_set(mix["output"], n, mix["set_seed"] + 1)
        self._cycles: dict[int, tuple] = {}

    def _cycle(self, c: int) -> tuple:
        """Orders of lengths and gaps for pass ``c`` through the set; the
        same for every run seed."""
        if c not in self._cycles:
            rng = _rng(self.mix["set_seed"], c, 1)
            order = (rng.permutation(self.n), rng.permutation(self.n),
                     rng.permutation(self.n))
            due = None
            if self.gaps is not None:
                due = np.cumsum(self.gaps[order[2]]) + c * self.gaps.sum()
            self._cycles[c] = order + (due,)
        return self._cycles[c]

    def item(self, i: int) -> Item:
        c, j = divmod(i, self.n)
        p_order, o_order, _, due = self._cycle(c)
        plen = int(self.prompt_lens[p_order[j]])
        n_new = min(int(self.output_lens[o_order[j]]),
                    int(self.mix["max_total"]) - plen)
        prompt = _rng(self.seed, i, 2).integers(
            self.vocab, size=plen, dtype=np.int32)
        return Item(i, None if due is None else float(due[j]), prompt, n_new)
