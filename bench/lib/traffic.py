"""One generator for every traffic mix: a mix is a data file of parameters.

A mix file (``bench/traffic/<name>.json``) gives the arrivals (``poisson``
at a fixed rate, or an offline ``backlog``), the prompt and output length
distributions, the admission-mode schedule and the serving deployment's
batch and cache sizes. The generator turns it into requests from a seed.

Every seed gets the same work. Lengths and inter-arrival gaps are fixed
quantiles of their distributions, mode-schedule slots a fixed multiset of
modes, and the seed only permutes them and draws the token ids. So two
seeds differ in order and content, never in how much there is to do, and
a spread between seeds measures the system rather than the draw.

The order is stratified, so that every stretch of the window gets the same
work too, and not only the window as a whole: a tail latency near the knee
follows where the long requests and the short gaps fall. A cycle's requests
come in strata of about ``stratum`` requests (the mix's key). The sorted
lengths, and the sorted gaps, are cut into bands of one value per
stratum, and each stratum takes one value of every band, the larger ones
to the strata whose sums are least, so each stratum holds the same spread
of prompts, outputs and gaps and about the same sum of each; the seed
breaks the ties and shuffles each. The mode
schedule comes in blocks of the fewest slots that hold the mix's shares
exactly (4 for 50/25/25), each block a seeded permutation.

Work comes in cycles of one window each. A cycle of ``poisson`` traffic
holds ``round(rate * seconds)`` arrivals whose gaps add up to the window
exactly; a ``backlog`` cycle holds ``backlog_cycle`` requests, all due at
its start. Cycles after the first keep the load up after the window closes
(requests due in the window still wait for their first token then).
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass(frozen=True)
class Planned:
    """One request as the traffic offers it."""

    idx: int
    due_s: float  # seconds after the window opens
    prompt: Tuple[int, ...]
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    # any whole number (negative or past 64 bits) is a valid seed
    return np.random.default_rng([seed % 2**64, stream])


def _quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of a lognormal
    with the given median and sigma, clipped to [min, max], each rounded
    up to the next entry of ``ladder`` when the mix has one."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    qs = [(i + 0.5) / n for i in range(n)]
    raw = [math.exp(math.log(dist["median"]) + dist["sigma"] * _NORMAL.inv_cdf(q))
           for q in qs]
    out = np.clip(np.ceil(raw), dist["min"], dist["max"]).astype(np.int64)
    ladder = dist.get("ladder")
    if ladder:
        lad = np.asarray(sorted(ladder))
        if out.max() > lad[-1]:
            raise ValueError(f"ladder {ladder} does not reach max {dist['max']}")
        out = lad[np.searchsorted(lad, out)]
    return out


def _exp_gaps(rate: float, n: int, seconds: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at mid-quantiles, scaled to add
    up to ``seconds`` exactly (their sum is within a few per cent of
    ``n / rate`` already)."""
    gaps = np.asarray([-math.log(1 - (i + 0.5) / n) for i in range(n)]) / rate
    return gaps * (seconds / gaps.sum())


def _shares(mix: Sequence[Dict], n: int) -> List[int]:
    """Largest-remainder apportionment of ``n`` slots over the mix shares."""
    total = sum(m["share"] for m in mix)
    exact = [m["share"] / total * n for m in mix]
    counts = [int(math.floor(e)) for e in exact]
    order = sorted(range(len(mix)), key=lambda i: -(exact[i] - counts[i]))
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def _strata_sizes(n: int, per: int, rng: np.random.Generator) -> List[int]:
    """Sizes of the ``round(n / per)`` strata of ``n`` requests: all the
    same, and one more in as many strata (drawn) as the division leaves."""
    k = max(1, min(n, round(n / per)))
    base, extra = divmod(n, k)
    sizes = [base] * k
    for s in rng.choice(k, extra, replace=False):
        sizes[s] += 1
    return sizes


def _deal(values: np.ndarray, sizes: Sequence[int],
          rng: np.random.Generator) -> np.ndarray:
    """``values`` in stratified order. Sorted from the largest and cut into
    bands of ``len(sizes)``, each band is dealt out one value to a stratum,
    the largest to the stratum whose sum is then least (ties broken by the
    seed, so the seed decides which stratum gets which share); the values
    left over (the smallest) go to the strata that are one larger. So every
    stratum holds one value of each band and about the same sum, whatever
    the seed, and the seed shuffles each."""
    v = np.sort(values)[::-1]
    k, base = len(sizes), min(sizes)
    strata: List[List] = [[] for _ in range(k)]
    sums = np.zeros(k)
    tie = rng.permutation(k)
    for j in range(base):
        for x, s in zip(v[j * k:(j + 1) * k], np.lexsort((tie, sums))):
            strata[s].append(x)
            sums[s] += x
    rest = v[base * k:]
    big = [s for s in range(k) if sizes[s] > base]
    for x, s in zip(rest, sorted(big, key=lambda s: (sums[s], tie[s]))):
        strata[s].append(x)
    return np.concatenate([np.asarray(st)[rng.permutation(len(st))]
                           for st in strata])


def _block(shares: Sequence[float], most: int = 16) -> int:
    """The fewest slots (up to ``most``) in which every share is a whole
    number of slots."""
    total = sum(shares)
    for b in range(1, most + 1):
        if all(abs(s / total * b - round(s / total * b)) < 1e-9 for s in shares):
            return b
    raise ValueError(f"mode shares {list(shares)} fit no block of up to "
                     f"{most} slots")


class Traffic:
    """The requests and admission-mode schedule of one mix for one seed."""

    def __init__(self, mix: Dict, *, seed: int, seconds: float, vocab: int):
        self.mix = mix
        self.seed = seed
        self.seconds = float(seconds)
        self.vocab = vocab
        self.kind = mix["arrivals"]["kind"]
        if self.kind == "poisson":
            self.cycle_n = max(1, round(mix["arrivals"]["rate_per_s"] * seconds))
        elif self.kind == "backlog":
            self.cycle_n = int(mix["arrivals"]["backlog_cycle"])
        else:
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self.prompt_lens = _quantile_lengths(mix["prompt"], self.cycle_n)
        self.output_lens = _quantile_lengths(mix["output"], self.cycle_n)
        self.stratum = int(mix["stratum"])
        sched = mix["modes"]
        self.every_s = float(sched["every_s"])
        self.block = _block([m["share"] for m in sched["mix"]])
        counts = _shares(sched["mix"], self.block)
        self._slot_pool = [i for i, c in enumerate(counts) for _ in range(c)]

    # -- shapes -------------------------------------------------------------

    def prompt_shapes(self) -> List[int]:
        """Every prompt length this mix can send (the set to warm up)."""
        return sorted({int(x) for x in self.prompt_lens})

    def mode_indices(self) -> List[int]:
        """Indices into ``mix["modes"]["mix"]`` the schedule can select."""
        return sorted(set(self._slot_pool))

    # -- the schedule ---------------------------------------------------------

    def mode_at(self, t: float) -> int:
        """Index of the admission mode in force ``t`` s after the window
        opened (a seeded permutation of the slot multiset per block)."""
        slot = max(0, int(t // self.every_s))
        block, k = divmod(slot, self.block)
        perm = _rng(self.seed, 1000 + block).permutation(self.block)
        return self._slot_pool[perm[k]]

    def requests(self) -> Iterator[Planned]:
        """Requests in due order, cycle after cycle, without end."""
        idx = 0
        cycle = 0
        while True:
            rng = _rng(self.seed, cycle)
            sizes = _strata_sizes(self.cycle_n, self.stratum, rng)
            p_len = _deal(self.prompt_lens, sizes, rng)
            o_len = _deal(self.output_lens, sizes, rng)
            if self.kind == "poisson":
                gaps = _deal(_exp_gaps(self.mix["arrivals"]["rate_per_s"],
                                       self.cycle_n, self.seconds), sizes, rng)
                dues = cycle * self.seconds + np.concatenate(
                    [[0.0], np.cumsum(gaps)[:-1]])
            else:
                dues = np.zeros(self.cycle_n)
            for i in range(self.cycle_n):
                toks = rng.integers(1, self.vocab, int(p_len[i]))
                yield Planned(idx, float(dues[i]), tuple(int(t) for t in toks),
                              int(o_len[i]))
                idx += 1
            cycle += 1

    def window_count(self) -> Optional[int]:
        """Requests due inside the window (poisson); None for a backlog."""
        return self.cycle_n if self.kind == "poisson" else None
