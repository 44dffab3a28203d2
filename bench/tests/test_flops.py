"""Operation and byte counts against hand counts at qwen2-0.5b's widths
(24 layers, d 896, 14/2 heads of 64, d_ff 4864, vocab 151936, rank 16).
The block's counts come from the configuration's model module, as
``bench/harness/flops.py`` reaches them."""
import json
import os

from bench.harness import flops
from bench.models import dense_decoder, module_of

CFG = json.load(open(os.path.join(os.path.dirname(__file__), "..", "configs",
                                  "qwen2-0.5b.json")))
BLOCK = module_of(CFG)


def test_dispatch_reaches_the_configurations_module():
    assert BLOCK is dense_decoder


def test_layer_weights():
    # q 896x896, k and v 896x128, o 896x896, gate/up/down 3 x 896x4864
    assert BLOCK.layer_weight_params(CFG) == (
        896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864)


def test_token_and_head_flops():
    lora = 2 * 16 * ((896 + 896) * 2 + (896 + 128) * 2 + (896 + 4864) * 3)
    attn = 4 * 10 * 14 * 64
    per_layer = 2 * 14_909_440 + lora + attn
    assert BLOCK.token_flops(CFG, 10) == 24 * per_layer
    assert flops.head_flops(CFG) == 2 * 896 * 151936
    assert flops.decode_flops(CFG, 9) == 24 * per_layer + 2 * 896 * 151936


def test_prefill_counts_every_position_once_and_one_head_row():
    p = 5
    want = sum(BLOCK.token_flops(CFG, i) for i in range(1, p + 1)) \
        + flops.head_flops(CFG)
    assert flops.prefill_flops(CFG, p) == want


def test_sgmv_need():
    fl, nb = flops.sgmv_need(tokens=16, n_adapters=3, d_in=896, d_out=4864,
                             rank=16)
    assert fl == 2 * 16 * 16 * (896 + 4864)
    assert nb == 2 * (16 * 896 + 3 * 16 * (896 + 4864) + 16 * 4864)


def test_sgmv_step_bound_is_bandwidth_bound_at_decode():
    peak, bw = 197e12, 819e9
    t = flops.sgmv_step_bound(CFG, 16, 4, peak, bw)
    want = 0.0
    for d_in, d_out in BLOCK.lora_modules(CFG).values():
        fl, nb = flops.sgmv_need(16, 4, d_in, d_out, 16)
        assert nb / bw > fl / peak
        want += nb / bw
    assert abs(t - 24 * want) < 1e-15
