"""The reduction from a device trace to the per-layer numbers."""
import json
import os

import pytest

from bench.harness import devtrace

DATA = os.path.join(os.path.dirname(__file__), "data")

# a hand-made trace: window [0, 100] ns; two decode modules and one
# prefill module, their ops (two overlapping), host annotations
SYNTH = {
    "window": [0.0, 100.0],
    "modules": [["jit_decode_fn(3)", 10.0, 20.0],
                ["jit_prefill_fn(7)", 40.0, 30.0],
                ["jit_decode_fn(3)", 80.0, 10.0],
                ["jit_decode_fn(3)", 120.0, 10.0]],   # after the window
    "ops": [["fusion.1", 10.0, 15.0], ["fusion.2", 20.0, 10.0],
            ["_shrink_kernel", 40.0, 10.0], ["_expand_kernel", 50.0, 20.0],
            ["fusion.1", 80.0, 10.0], ["fusion.1", 120.0, 10.0]],
    "host": [["bench.wave", 0.0, 100.0], ["bench.after decode", 31.0, 0.0],
             ["bench.after prefill", 71.0, 0.0],
             ["bench.after decode", 91.0, 0.0]],
}


def test_synthetic_reduction():
    red = devtrace.reduce(SYNTH)
    assert red["window_ns"] == 100.0
    # busy: [10, 30] + [40, 70] + [80, 90]
    assert red["busy_ns"] == 60.0
    assert red["module_ns"] == {"decode_fn": 30.0, "prefill_fn": 30.0}
    assert red["module_calls"] == {"decode_fn": 2, "prefill_fn": 1}
    assert red["op_ns"]["prefill_fn:_expand_kernel"] == 20.0
    assert red["op_ns"]["decode_fn:fusion.1"] == 25.0
    assert red["gaps"] == {"wave start -> decode_fn": [10.0, 1],
                           "after decode -> prefill_fn": [10.0, 1],
                           "after prefill -> decode_fn": [10.0, 1],
                           "after decode -> wave end": [10.0, 1]}
    bd = devtrace.breakdown(red, top=2)
    assert bd["device_ops"][0][0] == "decode_fn:fusion.1"
    assert bd["device_ops"][0][1] == pytest.approx(25e-9)
    assert len(bd["device_ops"]) == 2 and len(bd["idle_gaps"]) == 2
    assert bd["idle_gaps"][0][0].endswith("(x1)")


def test_module_kind():
    assert devtrace.module_kind("jit_decode_fn(12)") == "decode_fn"
    assert devtrace.module_kind("jit_write_slots") == "write_slots"
    assert devtrace.module_kind("jit_prefill_fn.3") == "prefill_fn"


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce(dict(SYNTH, window=None))


def test_op_name_from_hlo_text():
    text = ('%sgmv.22 = f32[4096,16]{1,0:T(8,128)S(1)} custom-call(s32[64]{0} '
            '%broadcast_clamp_fusion.12, bf16[4096,896]{1,0} %fusion.467), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={s32[64]{0}}')
    assert devtrace.op_name(text) == "%sgmv.22 tpu_custom_call"
    assert devtrace.op_name("%convert.43 = bf16[24,32,896,16]{2,3,1,0} "
                            "convert(f32[24,32,896,16]{2,3,1,0} %c)") \
        == "%convert.43 convert"


def test_recorded_chip_trace():
    # the start of a qwen2-0.5b.churn wave, measured on one TPU v5e:
    # adapter loads as eager programs, then the first prefill group
    with open(os.path.join(DATA, "qwen2_churn_wave_start.json")) as f:
        rec = json.load(f)
    red = devtrace.reduce(rec)
    assert red["busy_ns"] == pytest.approx(111488782.0)
    assert red["window_ns"] == pytest.approx(282631395.0)
    assert red["module_calls"]["prefill_fn"] == 1
    assert red["module_ns"]["prefill_fn"] == pytest.approx(91475048.0)
    kernels = [k for k in red["op_ns"] if k.endswith("tpu_custom_call")]
    assert kernels and all(k.startswith("prefill_fn:%sgmv") for k in kernels)
    assert sum(n for _, n in red["gaps"].values()) > 100
    assert 0 < red["busy_ns"] < red["window_ns"]
