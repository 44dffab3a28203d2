"""Adapter memory manager: host time of one adapter load (the pool
rewrite, fetch and dispatch), the mean length of the traced wave's
``adapter.load`` spans."""

from bench.metrics import _spans


def read(ctx):
    spans = _spans.of(ctx)
    if spans is None:
        return None
    loads = _spans.named(spans, "adapter.load")
    if not loads:
        return None
    return 1e-6 * sum(s[4] - s[3] for s in loads) / len(loads)
