"""The one traffic generator: reads a mix file and yields waves of requests.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

    {"prompt": {"dist": "lognormal", "median": 200, "sigma": 0.7,
                "min": 65, "max": 512},
     "output": {"dist": "uniform", "min": 8, "max": 32},
     "pool": 32, "wave": 8, "greedy": true,
     "link": {"bandwidth_kbps": 250, "rtt_ms": 20}}

Every seed serves the same work.  The ``pool`` (prompt length, output
length) pairs are fixed quantiles of the two distributions, paired by a
fixed permutation, and dealt into ``pool / wave`` waves that each hold
one pair of every stratum of output lengths: sorted by output length,
the pool is cut into strata of ``pool / wave`` pairs, and each stratum
is dealt across the waves, in reverse on every other stratum.  So every
wave holds about the same tokens and ends with a request of about the
same length (a wave's latency is that of its longest request), and any
run of whole waves is about the same work.  A seed only shuffles the
order in which the waves are sent (afresh on each pass over the pool)
and draws the token ids.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

PAIRING_SEED = 20181219          # fixed: the pairing never depends on --seed


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Any whole number (negative or past 64 bits too) -> a SeedSequence."""
    return np.random.SeedSequence(int(seed) % (1 << 64))


def _quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified draws: the value at quantile (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        vals = lo + u * (hi - lo + 1)
        return np.clip(np.floor(vals), lo, hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(x) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(vals), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    spec: Dict

    @property
    def wave(self) -> int:
        return int(self.spec["wave"])

    @property
    def link(self) -> Dict:
        return self.spec["link"]

    def lengths(self) -> np.ndarray:
        """[pool, 2] (prompt length, output length) pairs, seed-free."""
        n = int(self.spec["pool"])
        if n % self.wave:
            raise ValueError(f"mix {self.name!r}: pool {n} is not a "
                             f"multiple of the wave {self.wave}")
        rng = np.random.default_rng(PAIRING_SEED)
        p = _quantiles(self.spec["prompt"], n)[rng.permutation(n)]
        o = _quantiles(self.spec["output"], n)[rng.permutation(n)]
        return np.stack([p, o], axis=1)

    def waves(self) -> List[np.ndarray]:
        """The pool dealt into waves: a list of [wave, 2] arrays, each
        with one pair of every stratum of output lengths."""
        pairs = self.lengths()
        n = len(pairs) // self.wave
        order = np.lexsort((-pairs[:, 0], -pairs[:, 1]))
        dealt: List[List[int]] = [[] for _ in range(n)]
        for s in range(self.wave):
            stratum = order[s * n:(s + 1) * n]
            for w, i in enumerate(stratum if s % 2 == 0 else stratum[::-1]):
                dealt[w].append(int(i))
        return [pairs[d] for d in dealt]

    def max_prompt(self) -> int:
        return int(self.lengths()[:, 0].max())

    def max_output(self) -> int:
        return int(self.lengths()[:, 1].max())

    def median_prompt(self) -> int:
        return int(np.median(self.lengths()[:, 0]))


def load_mix(root: Path, name: str) -> Mix:
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    spec = json.loads(path.read_text())
    if not spec.get("greedy", False):
        raise ValueError(f"mix {name!r}: only greedy traffic can be held "
                         f"to the reference's logits")
    return Mix(name=name, spec=spec)


@dataclasses.dataclass
class Job:
    """One request as the benchmark knows it (the program sees a
    ``Request`` built from it)."""
    uid: int
    prompt: np.ndarray           # int32 token ids
    max_new: int


class Stream:
    """Endless waves of jobs from one seed: the mix's waves in a seeded
    order, then again in a fresh order, and so on."""

    def __init__(self, mix: Mix, vocab: int, seq: np.random.SeedSequence):
        self.mix = mix
        self.vocab = vocab
        self._rng = np.random.default_rng(seq)
        self._waves = mix.waves()
        self._order: List[int] = []
        self._uid = 0

    def tokens(self, n: int) -> np.ndarray:
        return self._rng.integers(0, self.vocab, n, dtype=np.int32)

    def job(self, plen: int, max_new: int) -> Job:
        j = Job(uid=self._uid, prompt=self.tokens(plen), max_new=max_new)
        self._uid += 1
        return j

    def wave(self) -> List[Job]:
        if not self._order:
            self._order = list(self._rng.permutation(len(self._waves)))
        return [self.job(int(p), int(o))
                for p, o in self._waves[self._order.pop()]]


def bucket_len(plen: int, max_len: int) -> int:
    """The scheduler's prefill bucket as documented: the next power of
    two from 8, capped at ``max_len``."""
    b = 8
    while b < plen:
        b *= 2
    return min(b, max_len)


def buckets(mix: Mix, max_len: int) -> List[int]:
    return sorted({bucket_len(int(p), max_len) for p in mix.lengths()[:, 0]})


def max_len_for(mix: Mix, spec_k: int) -> int:
    """Cache length: longest prompt + longest output + 24, the launcher's
    sizing rule, which also covers the k - 1 positions a draft round may
    write past a request's budget."""
    return mix.max_prompt() + mix.max_output() + max(24, spec_k - 1)
