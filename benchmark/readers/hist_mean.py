"""Mean, in ms, over the window: the seconds that the series
``args.sum_of`` of the program's histogram ``args.histogram`` gained
(each a selection by labels, ``{}`` for all of them), over the
observations that the series ``args.count_of`` gained.  With
``args.split_by`` (a label of the histogram) it also says, on an earlier
line, every value of that label in ms per observation of ``count_of``,
the share of the window's wall time that all of them account for, and
the mean period between two such observations.  A histogram that the
program does not have, or a ``count_of`` that the window never observed,
gives nothing to read."""
import json

from harness import counters, device


def label_values(snap: dict, name: str, label: str) -> list:
    """The values ``label`` takes in the histogram's cells, in order."""
    metric = snap.get(name)
    if metric is None or label not in metric["labelnames"]:
        return []
    at = metric["labelnames"].index(label)
    return sorted({key[at] for key, _cell in metric["cells"]})


def read(metric: dict, ctx: dict):
    a, w = metric["args"], ctx["window"]
    name = a["histogram"]
    gained = lambda labels: counters.hist_delta(
        w["before"], w["after"], name, **labels)
    n = gained(a["count_of"])
    if n is None:
        return None
    label = a.get("split_by")
    if label:
        split = {v: gained({label: v})
                 for v in label_values(w["after"], name, label)}
        split = {v: d["sum"] for v, d in split.items() if d is not None}
        device.say(
            f"{metric['name']}: {name} by {label}, ms per "
            f"{json.dumps(a['count_of'])} ({n['count']} in the window): "
            + json.dumps({v: round(1e3 * s / n["count"], 4)
                          for v, s in split.items()})
            + f"; all of them {100 * sum(split.values()) / w['seconds']:.2f}"
            f"% of the window's {w['seconds']:.2f} s; period "
            f"{1e3 * w['seconds'] / n['count']:.3f} ms")
    total = 0.0
    for labels in a["sum_of"]:
        d = gained(labels)
        if d is not None:
            total += d["sum"]
    return 1e3 * total / n["count"]
