"""Adapter memory manager: adapter loads a request's own acquire caused
(``adapter.load`` spans under ``adapter.acquire``; prefetches excluded)
per request of the traced wave."""

from bench.metrics import _spans


def read(ctx):
    spans = _spans.of(ctx)
    if spans is None or not ctx.traced["requests"]:
        return None
    demand = sum(_spans.parent_name(spans, s) == "adapter.acquire"
                 for s in _spans.named(spans, "adapter.load"))
    return demand / len(ctx.traced["requests"])
