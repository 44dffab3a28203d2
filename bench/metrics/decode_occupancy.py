"""Scheduler: the share of the decode batch's rows that hold a request,
over every decode step of the window (real request ids per decode step,
from the wall-clock observer, over n_slots)."""


def read(ctx):
    rows = steps = 0
    for w in ctx.waves:
        for kind, _, _, rids in w["observer"].steps:
            if kind.startswith("decode"):
                rows += len(rids)
                steps += 1
    if not steps:
        return None
    return 100.0 * rows / (steps * ctx.n_slots)
