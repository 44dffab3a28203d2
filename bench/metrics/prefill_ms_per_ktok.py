"""Model step: device time of the ``prefill_fn`` module's events in the
traced wave per thousand real (unpadded) prompt tokens that they
prefilled."""

from bench.metrics import _steps


def read(ctx):
    ns = ctx.dev["module_ns"].get("prefill_fn", 0.0)
    tokens = _steps.prompt_tokens(ctx.traced)
    if not ns or not tokens:
        return None
    return ns * 1e-6 / (tokens / 1000.0)
