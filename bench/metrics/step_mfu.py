"""Device / whole step: model operations of the real tokens that the
traced wave's prefill and decode steps ran (``bench/harness/flops.py``),
over the device time of the ``prefill_fn`` and ``decode_fn`` modules times
the chip's peak bf16 rate."""

from bench.metrics import _steps


def read(ctx):
    ns = sum(ctx.dev["module_ns"].get(k, 0.0)
             for k in ("prefill_fn", "decode_fn"))
    if not ns:
        return None
    work = _steps.model_flops(ctx.cfg, ctx.traced)
    return 100.0 * work / (ns * 1e-9 * ctx.peaks["bf16_flops"])
