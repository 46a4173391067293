"""The one traffic generator: a mix file of parameters -> requests.

Every run of a mix offers the same work: the arrival gaps, prompt
lengths and output lengths are each the stratified quantiles of their
distribution (value k of n at probability (k + 0.5) / n), dealt out in a
fixed well-mixed order (request i takes rank ``frac((i + 1) * alpha)``,
a different irrational ``alpha`` per quantity). The seed draws the
prompt tokens, and with them everything the model computes. So seeds
differ in what is computed and not in how much, and the spread between
runs is the system's own.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

# golden ratio, sqrt(2) and sqrt(3), fractional parts: three orders that
# share no structure, so long prompts do not line up with long outputs
# or with short gaps
ALPHA = {"gap": (math.sqrt(5) - 1) / 2, "prompt": math.sqrt(2) - 1,
         "output": math.sqrt(3) - 1}


@dataclasses.dataclass(frozen=True)
class Spec:
    id: int
    arrival_s: float
    prompt: np.ndarray
    max_new_tokens: int


def _normal_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9), so the generator needs no SciPy."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q / \
        (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1)
    return out


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length or gap distribution."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        s = dist["sigma_log"]
        mu = math.log(dist["mean"]) - s * s / 2     # mean of the unclipped law
        v = np.exp(mu + s * _normal_ppf(p))
        return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)
    if kind == "exponential":
        return -np.log1p(-p) * dist["mean"]
    if kind == "fixed":
        return np.full(n, dist["value"])
    raise ValueError(f"unknown distribution {kind!r}")


def dealt(values: np.ndarray, alpha: float) -> np.ndarray:
    """``values`` (sorted) in the order of the ranks of frac((i+1)·alpha)."""
    n = len(values)
    u = np.modf((np.arange(n) + 1) * alpha)[0]
    return np.sort(values)[np.argsort(np.argsort(u))]


def requests(mix: dict, seconds: float, vocab: int, seed: int) -> List[Spec]:
    """The requests of one run: arrivals in [0, seconds] at the mix's rate,
    the first at 0, prompts of random tokens from ``seed``."""
    rate = float(mix["arrivals"]["rate_per_s"])
    n = max(1, int(math.floor(rate * seconds)))
    gaps = dealt(quantiles({"dist": "exponential", "mean": 1.0 / rate}, n),
                 ALPHA["gap"])
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    plens = dealt(quantiles(mix["prompt_tokens"], n), ALPHA["prompt"])
    glens = dealt(quantiles(mix["output_tokens"], n), ALPHA["output"])
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if arrivals[i] > seconds:
            break
        out.append(Spec(i, float(arrivals[i]),
                        rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                        int(glens[i])))
    return out
