"""Scheduler: the 95th percentile over the traced wave's requests of the
wall time from the start of ``serve()`` to the start of the ``admit``
span that gave the request a slot."""

from bench.harness.stats import percentile
from bench.metrics import _spans


def read(ctx):
    spans = _spans.of(ctx)
    if spans is None:
        return None
    serve = _spans.named(spans, "serve")
    if not serve:
        return None
    waits = [1e-6 * (s[3] - serve[0][3])
             for s in _spans.named(spans, "admit")
             for _ in s[5].get("requests") or ()]
    return percentile(waits, 95) if waits else None
