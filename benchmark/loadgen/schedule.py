"""The one general traffic generator: reads a traffic mix (the ``traffic``
object of a cell's file) and makes the whole schedule from the seed.

No JAX, no numpy: the load generator's process imports this, and so does
the run's process, which rebuilds the same prompts from the same seed for
the comparison with the reference.

Every seed gets the same set of prompt lengths, output lengths and
inter-arrival gaps — the quantiles of the mix's distributions — in
another order, so that the seed changes which request meets which and
never how much work a run holds.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist


def lognormal_set(n: int, spec: dict) -> list:
    """``n`` whole lengths: the quantiles ``(i + 1/2) / n`` of a lognormal
    with the given median and sigma, clipped to ``[lo, hi]``."""
    z = NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        v = math.exp(mu + spec["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["hi"], max(spec["lo"], round(v)))))
    return out


def exponential_set(n: int, rate: float) -> list:
    """``n`` gaps: the quantiles of the exponential with mean 1 / rate,
    scaled so that they add up to exactly ``n / rate``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate * sum(raw))
    return [g * scale for g in raw]


def build(traffic: dict, vocab: int, seed: int, seconds: float) -> dict:
    """The schedule of one run.

    ``traffic["arrivals"]`` is ``{"kind": "poisson", "rate": r}`` (open
    loop: each request has a ``due`` instant, counted from the start of
    the ramp) or ``{"kind": "closed", "clients": c, "per_client": k}``
    (closed loop: each request belongs to a client, which sends its next
    one when the last has ended).  The measured window is
    ``[ramp_s, ramp_s + seconds)``.
    """
    rng = random.Random(seed)
    arr = traffic["arrivals"]
    ramp = float(traffic["ramp_s"])
    span = ramp + float(seconds)
    if arr["kind"] == "poisson":
        n = max(1, round(arr["rate"] * span))
    elif arr["kind"] == "closed":
        n = arr["clients"] * arr["per_client"]
    else:
        raise ValueError(f"unknown arrivals {arr['kind']!r}")
    prompts = lognormal_set(n, traffic["prompt_len"])
    outputs = lognormal_set(n, traffic["output_len"])
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    requests = [{"id": i, "prompt_len": p, "max_new": o,
                 "tokens": [rng.randrange(vocab) for _ in range(p)]}
                for i, (p, o) in enumerate(zip(prompts, outputs))]
    if arr["kind"] == "poisson":
        gaps = exponential_set(n, arr["rate"])
        rng.shuffle(gaps)
        t = 0.0
        for r, g in zip(requests, gaps):
            t += g
            r["due"] = t - gaps[0]          # the first request is due at 0
    else:
        for r in requests:
            r["client"] = r["id"] % arr["clients"]
    return {"kind": arr["kind"], "ramp_s": ramp, "window_s": float(seconds),
            "requests": requests}
