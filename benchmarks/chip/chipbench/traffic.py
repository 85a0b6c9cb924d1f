"""The one request generator: a traffic mix file plus ``--seed`` in,
requests with scheduled arrival times out.

A mix file (``traffic/<name>.json``) holds only parameters:

* ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}``, or
  ``{"kind": "square_wave", "rate_per_s": r, "burst_factor": b,
  "burst_fraction": f, "period_s": T}`` (Poisson whose rate is ``b``
  times the base rate for the first ``f`` of every ``T`` seconds, the
  mean being ``r``), or ``{"kind": "backlog", "depth": d}`` (offline: at
  least ``d`` requests wait in the queue at every step);
* ``prompt`` / ``output``: lognormal lengths ``{"mean", "sigma", "min",
  "max"}`` (the mean is the arithmetic mean before clipping);
* ``shared_prefix``: tokens every prompt starts with (0 = none);
* ``shape_seed``: the fixed multiset of sizes and inter-arrival gaps.  A
  timed mix offers ``round(rate_per_s * (preroll_s + seconds))``
  requests, the mean rate over its pre-roll and window; the unit-rate
  gaps are scaled to mean 1, so its arrivals end within half a mean gap
  of the window's end, whatever the seed.  A backlog mix draws from a
  pool of ``n_requests``;
* ``check``: how many finished requests the verdict samples
  (``sample_requests``) and the fewest served tokens they must hold
  (``min_served_tokens``), sized to the mix's output lengths.

Every seed gets the same multiset of sizes and of unit inter-arrival gaps,
drawn from ``shape_seed``, in an order drawn from ``--seed``; the seed
also draws every token id.  So two seeds of a timed mix offer the same
work, in another order and with other contents.  A backlog serves only the
start of its pool, so there the seed also picks which sizes are served.

The lengths follow ``repro.tune.workloads.bursty_trace`` (lognormal with
the mean solved for mu, clipped); its square-wave rate is kept here too,
with arrival times made by time-rescaling a unit-rate Poisson process.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np

#: numpy seeds must be non-negative; a run's seed may be any int
_SEED_MOD = 2 ** 64


def lognormal_lengths(rng, n: int, mean: float, sigma: float,
                      lo: int, hi: int) -> np.ndarray:
    """``n`` long-tail lengths with arithmetic ``mean`` (mu solved from
    mean and sigma), rounded and clipped to ``[lo, hi]``."""
    mu = np.log(max(mean, 1.0)) - sigma ** 2 / 2.0
    return np.clip(np.round(rng.lognormal(mu, sigma, n)),
                   lo, hi).astype(int)


@dataclasses.dataclass
class Arrival:
    """One request as the generator offers it.

    ``t`` is the scheduled arrival in seconds from the window's start
    (negative in the pre-roll); ``None`` for backlog traffic, which the
    client submits whenever the queue runs short."""

    idx: int
    t: Optional[float]
    prompt_len: int
    max_new: int


def base_rate(arrivals: Dict[str, Any]) -> float:
    """The square wave's off-burst rate that gives mean ``rate_per_s``."""
    r = float(arrivals["rate_per_s"])
    if arrivals["kind"] == "poisson":
        return r
    b, f = float(arrivals["burst_factor"]), float(arrivals["burst_fraction"])
    return r / (1.0 + (b - 1.0) * f)


def _square_wave_times(u: np.ndarray, t0: float, lo: float, hi: float,
                       period: float, frac: float) -> np.ndarray:
    """Invert the cumulative rate of a square wave (``hi`` for the first
    ``frac`` of every ``period`` counted from t = 0, ``lo`` otherwise),
    starting at ``t0``: unit-rate arrival epochs ``u`` -> real times."""
    out = np.empty_like(u)
    t, acc = t0, 0.0
    for i, target in enumerate(u):
        while True:
            k = np.floor(t / period)
            phase = t - k * period
            if phase < frac * period:
                rate, seg_end = hi, k * period + frac * period
            else:
                rate, seg_end = lo, (k + 1) * period
            cap = acc + (seg_end - t) * rate
            if target <= cap:
                t = t + (target - acc) / rate
                acc = target
                break
            t, acc = seg_end, cap
        out[i] = t
    return out


class Traffic:
    """The requests of one run: arrival order and sizes from
    ``(mix, seed)``; token ids made on demand."""

    def __init__(self, mix: Dict[str, Any], seed: int, vocab: int,
                 preroll_s: float, seconds: float):
        self.mix = mix
        self.seed = int(seed) % _SEED_MOD
        self.vocab = int(vocab)
        arr = mix["arrivals"]
        n = (int(mix["n_requests"]) if arr["kind"] == "backlog" else
             int(math.floor(float(arr["rate_per_s"]) * (preroll_s + seconds)
                            + 0.5)))
        shape = np.random.default_rng(int(mix["shape_seed"]))
        p, o = mix["prompt"], mix["output"]
        plens = lognormal_lengths(shape, n, p["mean"], p["sigma"],
                                  p["min"], p["max"])
        gens = lognormal_lengths(shape, n, o["mean"], o["sigma"],
                                 o["min"], o["max"])
        gaps = shape.exponential(1.0, n)
        gaps /= gaps.mean()          # n arrivals span exactly n / rate
        order = np.random.default_rng([self.seed, 1]).permutation(n)
        gap_order = np.random.default_rng([self.seed, 2]).permutation(n)
        if arr["kind"] == "backlog":
            times = [None] * n
        else:
            u = np.cumsum(gaps[gap_order])
            lo = base_rate(arr)
            if arr["kind"] == "poisson":
                t = -preroll_s + u / lo
            elif arr["kind"] == "square_wave":
                t = _square_wave_times(u, -preroll_s, lo,
                                       lo * float(arr["burst_factor"]),
                                       float(arr["period_s"]),
                                       float(arr["burst_fraction"]))
            else:
                raise ValueError(f"unknown arrival kind {arr['kind']!r}")
            times = [float(x) for x in t]
        self.arrivals: List[Arrival] = [
            Arrival(idx=i, t=times[i], prompt_len=int(plens[order[i]]),
                    max_new=int(gens[order[i]])) for i in range(n)]
        self.shared_prefix = int(mix.get("shared_prefix", 0))
        self._prefix = (np.random.default_rng([self.seed, 3]).integers(
            0, self.vocab, self.shared_prefix).tolist()
            if self.shared_prefix else [])

    @property
    def prefix(self) -> List[int]:
        """The tokens every prompt of this run starts with."""
        return list(self._prefix)

    def prompt(self, a: Arrival) -> List[int]:
        """Request ``a``'s prompt: the shared prefix, then its own
        ``prompt_len`` tokens, all drawn from the seed."""
        own = np.random.default_rng([self.seed, 4, a.idx]).integers(
            0, self.vocab, a.prompt_len).tolist()
        return self.prefix + own
