"""Share of the window's wall time, in %, that the series ``args.sum_of``
of the program's histogram ``args.histogram`` gained in seconds (each a
selection by labels).  A program without the histogram (the parent of the
PR that brought it) gives nothing to read; a histogram that gained nothing
in the window reads 0.

On an earlier line it says the whole split by ``args.split_by`` (a label of
the histogram): seconds, observations and ms an observation for every
value, those outside ``sum_of`` included, and the share of the window all
of them cover together (for ``dl4j_tpu_serving_device_idle_seconds`` that
share is to be read beside ``device_idle_pct.*``, which a profiler trace
of a few seconds gives).  On another, what a stall can be held against:
the observations of ``args.stall_s`` seconds or more, of this histogram by
that label and of every histogram under ``args.stalls_of`` by the label
``by`` it names (``but`` the values that are no stall: the loop's wait
slices end after 0.1 s by design) with the seconds each of those gained
over all values (the loop's phases cover its thread's wall time, so theirs
is the time that really lay between the two snapshots: in a traced run the
second comes after ``stop_trace``, 10-44 s past the window's nominal end,
and every share of "the window" reads that much high), and the seconds and
collections that the garbage collector's two process counters gained."""
import json

from harness import cells, counters, device

GC_SECONDS = "dl4j_tpu_process_gc_pause_seconds_total"
GC_COLLECTIONS = "dl4j_tpu_process_gc_collections_total"

# the values a label takes in a histogram's cells: ``hist_mean``'s, which
# splits by a label as this reader does
label_values = cells.load_module("readers", "hist_mean").label_values


def split(window: dict, name: str, label: str) -> dict:
    """``{value of label: what the histogram gained under it}``, values
    that gained nothing left out."""
    out = {}
    for v in label_values(window["after"], name, label):
        d = counters.hist_delta(window["before"], window["after"], name,
                                **{label: v})
        if d is not None:
            out[v] = d
    return out


def at_least(gained: dict, seconds: float) -> int:
    """Observations in the buckets above ``seconds``: with a bucket
    bound at ``seconds``, those longer than it."""
    return sum(n for le, n in gained["buckets"] if le > seconds)


def scalar_gained(window: dict, name: str, **labels):
    after = counters.scalar(window["after"], name, **labels)
    if after is None:
        return None
    return after - (counters.scalar(window["before"], name, **labels) or 0.0)


def read(metric: dict, ctx: dict):
    a, w = metric["args"], ctx["window"]
    name, label = a["histogram"], a["split_by"]
    if w["after"].get(name) is None:
        return None
    by = split(w, name, label)
    seconds = w["seconds"]
    device.say(
        f"{metric['name']}: {name} by {label} over the window's "
        f"{seconds:.2f} s: "
        + json.dumps({v: {"s": round(d["sum"], 6), "n": d["count"],
                          "ms_each": round(1e3 * d["sum"] / d["count"], 4)}
                      for v, d in by.items()})
        + f"; all of them {100 * sum(d['sum'] for d in by.values()) / seconds:.3f}"
        "% of the window")
    stall_s = a.get("stall_s", 0.1)
    long = {name + "{" + label + "}":
            {v: n for v, d in by.items() if (n := at_least(d, stall_s))}}
    spans = {}
    for other, o in a.get("stalls_of", {}).items():
        parts = split(w, other, o["by"])
        long[other + "{" + o["by"] + "}"] = {
            v: n for v, d in parts.items()
            if v not in o.get("but", ()) and (n := at_least(d, stall_s))}
        spans[other] = round(sum(d["sum"] for d in parts.values()), 3)
    gc = {"seconds": scalar_gained(w, GC_SECONDS),
          "collections": scalar_gained(w, GC_COLLECTIONS),
          "seconds_gen2": scalar_gained(w, GC_SECONDS, generation="2"),
          "collections_gen2": scalar_gained(w, GC_COLLECTIONS,
                                            generation="2")}
    device.say(f"{metric['name']}: observations over {stall_s} s: "
               + json.dumps(long) + "; seconds all of it gained between "
               "the two snapshots: " + json.dumps(spans)
               + "; garbage collection in the window: "
               + json.dumps({k: round(v, 6) for k, v in gc.items()
                             if v is not None}))
    total = 0.0
    for labels in a["sum_of"]:
        d = counters.hist_delta(w["before"], w["after"], name, **labels)
        if d is not None:
            total += d["sum"]
    return 100.0 * total / seconds
