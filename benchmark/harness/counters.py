"""The program's counters, read through its registry's JSON snapshot
(``telemetry.get_registry().snapshot()``): any series by name and labels,
so a reader needs no accessor of its own."""
from __future__ import annotations

import math


def snapshot() -> dict:
    from deeplearning4j_tpu.telemetry import get_registry
    return get_registry().snapshot()


def _cells(snap: dict, name: str, labels: dict):
    metric = snap.get(name)
    if metric is None:
        return None, []
    names = metric["labelnames"]
    out = []
    for key, data in metric["cells"]:
        have = dict(zip(names, key))
        if all(have.get(k) == str(v) for k, v in labels.items()):
            out.append(data)
    return metric, out


def scalar(snap: dict, name: str, **labels):
    """Sum of the counter's or gauge's cells that carry ``labels``; None
    when the series does not exist (yet)."""
    _metric, cells = _cells(snap, name, labels)
    if not cells:
        return None
    return float(sum(cells))


def hist(snap: dict, name: str, **labels):
    """``{"sum", "count", "buckets": [(upper bound, count), ...]}`` of the
    histogram's matching cells, buckets not cumulative; None if absent."""
    metric, cells = _cells(snap, name, labels)
    if not cells:
        return None
    bounds = list(metric["buckets"]) + [math.inf]
    counts = [0] * len(bounds)
    for c in cells:
        for i, n in enumerate(c["counts"]):
            counts[i] += n
    return {"sum": sum(c["sum"] for c in cells),
            "count": sum(c["count"] for c in cells),
            "buckets": list(zip(bounds, counts))}


def hist_delta(before: dict, after: dict, name: str, **labels):
    """What the histogram gained between two snapshots; None when it does
    not exist after, or gained nothing."""
    b = hist(after, name, **labels)
    if b is None:
        return None
    a = hist(before, name, **labels) or {
        "sum": 0.0, "count": 0, "buckets": [(le, 0) for le, _ in b["buckets"]]}
    out = {"sum": b["sum"] - a["sum"], "count": b["count"] - a["count"],
           "buckets": [(le, nb - na) for (le, nb), (_le, na)
                       in zip(b["buckets"], a["buckets"])]}
    return out if out["count"] > 0 else None


def hist_quantile(h: dict, q: float) -> float:
    """The ``q`` quantile (0..1) of a bucketed sample, interpolated inside
    its bucket as Prometheus' ``histogram_quantile`` does; the last finite
    bound stands for the overflow bucket."""
    rank = q * h["count"]
    seen = 0.0
    lower = 0.0
    for le, n in h["buckets"]:
        if n and seen + n >= rank:
            if math.isinf(le):
                return lower
            return lower + (le - lower) * (rank - seen) / n
        seen += n
        if not math.isinf(le):
            lower = le
    return lower


def read_scalar(name: str, **labels):
    """The counter's or gauge's value now (one metric, no snapshot of the
    whole registry): for sampling.  None while the series does not exist."""
    from deeplearning4j_tpu.telemetry import get_registry
    metric = get_registry().get(name)
    if metric is None:
        return None
    return scalar({name: metric.data()}, name, **labels)


class Sampler:
    """Reads gauges ``{key: (name, labels)}`` at ``hz`` from a thread of
    its own until stopped; ``samples[key]`` holds what it saw."""

    def __init__(self, gauges: dict, hz: float = 20.0):
        import threading
        self.gauges, self.period = gauges, 1.0 / hz
        self.samples = {k: [] for k in gauges}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-sampler")

    def _loop(self):
        while not self._stop.wait(self.period):
            for key, (name, labels) in self.gauges.items():
                v = read_scalar(name, **labels)
                if v is not None:
                    self.samples[key].append(v)

    def start(self):
        self._thread.start()
        return self

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(5.0)
        return self.samples
