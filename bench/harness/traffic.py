"""Traffic generation: arrival processes, request mixes, percentiles.

``poisson_arrivals``, ``bursty_arrivals`` and ``percentile`` are copies of
the program's sound generators (``repro.traffic.workload``) and arithmetic
(``repro.traffic.harness``), kept here so the yardstick cannot move with the
program.

A mix file (``bench/traffic/<mix>.json``) holds parameters only; this one
generator reads every mix. Every seed gets the same work in another order:

* the keep rates are an exact split of the requests by the mix's shares,
  shuffled by the seed;
* the images are a pool drawn from the seed;
* an open loop's inter-arrival gaps are one fixed sequence drawn from the
  mix's ``arrival_seed`` and rotated by a seed-chosen offset, so every seed
  offers the same number of requests over the same span, with the bursts
  (for the bursty process) kept whole.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import numpy as np


def poisson_arrivals(n: int, rate_rps: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Homogeneous Poisson process: exponential inter-arrivals at
    ``rate_rps`` requests per second; arrival times in ms."""
    if rate_rps <= 0.0:
        raise ValueError(f"rate_rps must be positive, got {rate_rps}")
    gaps_ms = rng.exponential(1e3 / rate_rps, size=n)
    return np.cumsum(gaps_ms)


def bursty_arrivals(n: int, rate_rps: float, rng: np.random.Generator,
                    burst_factor: float = 8.0, p_enter: float = 0.08,
                    p_exit: float = 0.25) -> np.ndarray:
    """Two-state Markov-modulated Poisson process (per-arrival chain): a
    calm state and a burst state ``burst_factor`` times hotter; after each
    arrival the state flips with probability ``p_enter`` (calm -> burst) or
    ``p_exit`` (burst -> calm). The calm rate is set so that the long-run
    mean rate is ``rate_rps``. Arrival times in ms."""
    if rate_rps <= 0.0:
        raise ValueError(f"rate_rps must be positive, got {rate_rps}")
    if burst_factor < 1.0:
        raise ValueError(f"burst_factor must be >= 1, got {burst_factor}")
    pi_burst = p_enter / max(p_enter + p_exit, 1e-12)
    calm_rate = rate_rps * ((1.0 - pi_burst) + pi_burst / burst_factor)
    t = 0.0
    out = np.empty(n, np.float64)
    burst = False
    for i in range(n):
        rate = calm_rate * (burst_factor if burst else 1.0)
        t += rng.exponential(1e3 / rate)
        out[i] = t
        if rng.random() < (p_exit if burst else p_enter):
            burst = not burst
    return out


PROCESSES = {"poisson": poisson_arrivals, "bursty": bursty_arrivals}


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (deterministic, no interpolation)."""
    if not values:
        return float("nan")
    v = sorted(values)
    idx = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[idx])


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def arrivals_ms(mix: dict, rate_rps: float, seconds: float,
                seed: int) -> np.ndarray:
    """Due times (ms from the window's start) of an open loop's requests:
    the fixed gap sequence of the mix that fits in ``seconds``, rotated by
    the seed."""
    proc = PROCESSES[mix["arrivals"]]
    kw = mix.get("process", {})
    n_gen = int(rate_rps * seconds * 2) + 64
    rng = np.random.default_rng(mix["arrival_seed"])
    t = proc(n_gen, rate_rps, rng, **kw)
    gaps = np.diff(np.concatenate([[0.0], t]))
    n = int(np.searchsorted(np.cumsum(gaps), seconds * 1e3, side="right"))
    gaps = gaps[:n]
    if n:
        gaps = np.roll(gaps, int(seed_rng(seed, 1).integers(n)))
    return np.cumsum(gaps)


def keep_rates(mix: dict, n: int, seed: int) -> np.ndarray:
    """Per-request keep rates: the mix's shares split exactly over ``n``
    requests (largest remainders), in a seed-shuffled order."""
    rates = [float(r) for r, _ in mix["keep_rates"]]
    w = np.asarray([float(s) for _, s in mix["keep_rates"]])
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    out = np.repeat(np.asarray(rates), counts)
    seed_rng(seed, 2).shuffle(out)
    return out


def cyclic_keep_rates(mix: dict, seed: int) -> List[float]:
    """A closed loop's repeating keep-rate cycle: one period of the exact
    split, seed-shuffled."""
    period = sum(int(s) for _, s in mix["keep_rates"])
    return [float(r) for r in keep_rates(mix, period, seed)]


def image_pool(mix: dict, n_patches: int, patch_dim: int,
               seed: int) -> np.ndarray:
    """``[pool, n_patches, patch_dim]`` float32 images drawn from the seed
    (normalised pixels: standard normal)."""
    rng = seed_rng(seed, 3)
    return rng.standard_normal((mix["image_pool"], n_patches, patch_dim),
                               dtype=np.float32)
