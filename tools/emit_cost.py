#!/usr/bin/env python
"""What the delivery of one decode step costs the loop thread, by sink, in
microseconds a token — by hand:

    python tools/emit_cost.py            # on the chip's host: chiprun -- python tools/emit_cost.py

``ContinuousBatcher._emitStep`` over 64 streaming slots, every stream's
consumer a thread of its own blocked in ``streamQ.get`` (a handler thread's
wake-up without its write), timed as the loop times it (wall and the
thread's own CPU clock) with all four per-token sinks on, and with each in
turn stubbed out:

- ``decode_tokens``: ``serving_metrics().decode_tokens().inc``;
- ``inter_token``: ``observe_exemplar`` of the inter-token gap;
- ``timeline``: ``timeline_store().note`` (the per-step note of
  ``/v1/requests/<id>``);
- ``queue_put``: ``streamQ.put`` (with it stubbed no consumer wakes).

What a sink costs is the whole less the run without it.  It is measured
once and written down (``PERF.md`` §5), not counted while serving: timing
256 calls a step would cost more than the calls.  No device is touched.
One JSON line.
"""
import contextlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.remote import scheduler
from deeplearning4j_tpu.telemetry import RequestContext, serving_metrics

SINKS = ("decode_tokens", "inter_token", "timeline", "queue_put")


class _Nothing:
    """Stands in for a counter, the timeline store and a stream's queue."""

    def inc(self, *_a, **_k):
        pass

    note = put = inc


@contextlib.contextmanager
def stubbed(sink, seqs):
    """Take one of the four sinks out of ``_emit`` / ``_emitStep``."""
    nothing = _Nothing()
    if sink == "decode_tokens":
        cls = type(serving_metrics())
        was, cls.decode_tokens = cls.decode_tokens, lambda self: nothing
        restore = lambda: setattr(cls, "decode_tokens", was)
    elif sink == "inter_token":
        was = scheduler.observe_exemplar
        scheduler.observe_exemplar = lambda *_a, **_k: None
        restore = lambda: setattr(scheduler, "observe_exemplar", was)
    elif sink == "timeline":
        was = scheduler.timeline_store
        scheduler.timeline_store = lambda: nothing
        restore = lambda: setattr(scheduler, "timeline_store", was)
    elif sink == "queue_put":
        queues = [s.streamQ for s in seqs]
        for s in seqs:
            s.streamQ = nothing

        def restore():
            for s, q in zip(seqs, queues):
                s.streamQ = q
    else:
        restore = lambda: None
    try:
        yield
    finally:
        restore()


def _batcher(slots: int):
    """As much of a batcher as ``_emitStep`` touches, every slot held by
    a streaming sequence with a trace id and no end in sight."""
    cb = scheduler.ContinuousBatcher.__new__(scheduler.ContinuousBatcher)
    cb.name, cb.maxSlots, cb.eosToken = "m", slots, None
    cb._parted = []
    cb._tok = np.zeros(slots, np.int32)
    seqs = []
    for s in range(slots):
        parent = scheduler._Pending(1, 1 << 30, ctx=RequestContext.new())
        seq = scheduler._Seq(np.zeros((1, 4), np.int32), 8, 1 << 30, 1,
                             parent, 0)
        seq.streamQ = scheduler._stdqueue.SimpleQueue()
        seqs.append(seq)
    cb._slotSeq = list(seqs)
    return cb, seqs


def _consume(q) -> None:
    while q.get() is not None:
        pass


def measure(slots: int = 64, steps: int = 200, rounds: int = 5) -> dict:
    """``{variant: {"wall_us_a_token", "cpu_us_a_token"}}`` for ``all``
    and for each sink stubbed out (the median of ``rounds`` rounds that
    take the variants in turn, ``steps`` steps each: under the timeline
    store's 256 events a request), and ``sink_us_a_token`` = all less the
    variant's wall."""
    serving_metrics().inter_token_seconds()     # registered, as start() does
    serving_metrics().ttft_seconds()
    cb, seqs = _batcher(slots)
    consumers = [threading.Thread(target=_consume, args=(s.streamQ,),
                                  daemon=True, name=f"emit-cost-{i}")
                 for i, s in enumerate(seqs)]
    for th in consumers:
        th.start()
    flight = scheduler._Flight(None, list(range(slots)), list(seqs),
                               np.zeros(slots, np.int64))
    g = np.arange(slots, dtype=np.int32)[:, None]
    variants = ("all",) + SINKS
    seen = {v: [] for v in variants}
    n = steps * slots
    try:
        for _ in range(rounds):
            for variant in variants:
                for seq in seqs:    # a new request: an empty timeline
                    seq.ctx = seq.parent.ctx = RequestContext.new()
                    seq.parent.firstTokenAt = seq.lastTokT = None
                    del seq.emitted[:]
                with stubbed(variant, seqs):
                    cb._emitStep(flight, g)     # its first token
                    wall, cpu = time.perf_counter(), time.thread_time()
                    for _ in range(steps):
                        cb._emitStep(flight, g)
                    cpu = time.thread_time() - cpu
                    wall = time.perf_counter() - wall
                seen[variant].append((wall / n * 1e6, cpu / n * 1e6))
    finally:
        for seq in seqs:
            seq.streamQ.put(None)
        for th in consumers:
            th.join(10.0)
    out = {}
    for variant, runs in seen.items():
        wall, cpu = sorted(runs)[len(runs) // 2]
        out[variant] = {"wall_us_a_token": wall, "cpu_us_a_token": cpu}
    out["sink_us_a_token"] = {
        sink: out["all"]["wall_us_a_token"] - out[sink]["wall_us_a_token"]
        for sink in SINKS}
    out.update(slots=slots, steps=steps, rounds=rounds,
               consumers_ended=not any(th.is_alive() for th in consumers))
    return out


if __name__ == "__main__":
    print(json.dumps(measure()))
