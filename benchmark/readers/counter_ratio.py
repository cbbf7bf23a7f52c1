"""Ratio of two sums of the program's counters over the window, times
``args.scale``: what the series ``args.sum_of`` gained (each ``{"name",
"labels"}``, a selection by labels, ``{}`` for all of a counter's cells)
over what ``args.over`` (one such selection) gained.  Seconds over steps
or tokens with ``scale`` 1000 read ms a step or a token: a ratio, so the
length of the window between the two snapshots does not bend it.  On an
earlier line it says what both gained.  A counter the program does not
have (the parent of the PR that brought it), or an ``over`` that gained
nothing in the window, gives nothing to read; a ``sum_of`` that is there
and gained nothing reads 0."""
import json

from harness import cells, device

# what a counter's selection gained between the two snapshots (None where
# it has no such cell after): ``hist_share``'s
gained = cells.load_module("readers", "hist_share").scalar_gained


def read(metric: dict, ctx: dict):
    a, w = metric["args"], ctx["window"]
    over = gained(w, a["over"]["name"], **a["over"]["labels"])
    parts = [gained(w, s["name"], **s["labels"]) for s in a["sum_of"]]
    if over is None or over <= 0 or any(p is None for p in parts):
        return None
    scale = a.get("scale", 1.0)
    device.say(f"{metric['name']}: {sum(parts):.6g} x {scale:g} over "
               f"{over:g} of {a['over']['name']}"
               f"{json.dumps(a['over']['labels'])}")
    return scale * sum(parts) / over
