"""Scheduler: host time of one pass of ``serve()``'s loop, the mean over
the traced wave's ``iter`` spans of their length less the ``wait`` spans
(``block_until_ready`` on a step) inside them."""

from bench.metrics import _spans


def read(ctx):
    spans = _spans.of(ctx)
    if spans is None:
        return None
    host = {s[0]: s[4] - s[3] for s in _spans.named(spans, "iter")}
    if not host:
        return None
    for s in _spans.named(spans, "wait"):
        it = _spans.ancestor(spans, s, "iter")
        if it is not None:
            host[it[0]] -= s[4] - s[3]
    return 1e-6 * sum(host.values()) / len(host)
