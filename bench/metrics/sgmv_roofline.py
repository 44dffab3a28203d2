"""Kernels: the SGMV kernels' share of their roofline in the traced wave.

The least time the chip needs for every SGMV call of the wave's steps
(``bench/harness/flops.py``: real tokens, the A and B of the distinct
adapters those rows use, x and y; each call bound by the larger of its
operations over the peak rate and its bytes over the bandwidth), over the
device time of the SGMV Pallas kernels (``_shrink_kernel``,
``_expand_kernel``): in the trace, the ``tpu_custom_call`` ops that
``kernels/ops.sgmv`` puts in, named ``%sgmv.<n>``."""

from bench.harness import flops
from bench.metrics import _steps

from bench.harness.devtrace import PALLAS


def read(ctx):
    kernel_ns = sum(ns for name, ns in ctx.dev["op_ns"].items()
                    if name.split(":", 1)[1].startswith("%sgmv")
                    and name.endswith(PALLAS))
    if not kernel_ns:
        return None
    pk = ctx.peaks
    need = sum(flops.sgmv_step_bound(ctx.cfg, tokens, n_adapters,
                                      pk["bf16_flops"], pk["hbm_bytes_per_s"])
               for tokens, n_adapters in _steps.sgmv_calls(ctx.traced))
    return 100.0 * need / (kernel_ns * 1e-9)
