"""``schedule.build`` with the order of the lengths made part of the mix.

``schedule.py`` gives every seed the same multiset of lengths in another
order, "so that the seed changes which request meets which and never how
much work a run holds".  That holds for a run that sends the whole
multiset.  A closed loop sends what its window has time for: where a
window holds some 70 requests of 240, and their prefills take two fifths
of it, which 70 is the seed's draw and the tokens per second follow it
(``olmo_hybrid_7b.docqa_closed16``: 2.1-3.5% between the quartiles,
PERF.md section 4).

Here the mix names its shuffle, ``traffic["order_seed"]``: every run sends
the same lengths in the same order to the same clients, and ``--seed``
draws what the lengths are filled with, each prompt's tokens (and, in the
run's process, the weights).  No JAX, no numpy, as ``schedule.py``.
"""
from __future__ import annotations

import random

import schedule


def build(traffic: dict, vocab: int, seed: int, seconds: float) -> dict:
    """The schedule of one run: ``schedule.build``'s for the mix's own
    ``order_seed``, its prompts filled with tokens drawn from ``seed``."""
    # vocab 1: the lengths, the clients and the order are all that is
    # kept of this schedule, so its tokens are not drawn over the vocabulary
    sched = schedule.build(traffic, 1, int(traffic["order_seed"]), seconds)
    rng = random.Random(seed)
    for r in sched["requests"]:
        r["tokens"] = [rng.randrange(vocab) for _ in range(r["prompt_len"])]
    return sched
