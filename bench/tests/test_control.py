"""The control: the reference computed in int8 (weights per output channel,
activations per token), put in the program's place, must come out not
correct, while the program's own served tokens are correct.

At a size a CPU test can hold (4 layers, d 256, vocab 4096, 16-request
waves with outputs longer than the tiny cell's) the limits below are set
from CPU readings of seeds 11-14 (this test's, and one more): the served
tokens' mean gap read 0.00015-0.00049 and the int8 control's
0.00145-0.00297; the widest gaps read 0.024-0.047 and 0.060-0.122. Only
the mean separates by 3x at this size, so only it gets a limit between
the two; the widest gap keeps a limit above both, that faults still pass.
"""
import copy

import pytest

from bench.harness import core, correct
from bench.tests import tiny

CONFIG = dict(copy.deepcopy(tiny.CONFIG), n_layers=4, d_model=256,
              n_heads=4, n_kv_heads=2, head_dim=64, d_ff=1024,
              vocab_size=4096,
              correct={"logit_gap_max": 0.25, "logit_gap_mean": 0.001})
MIX = copy.deepcopy(tiny.MIX)
MIX.update(wave_requests=16, input_range=[8, 64], output_range=[8, 32])
MIX["engine"].update(max_ctx=96, prompt_buckets=[32, 64])


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("small")), CONFIG, MIX)
    return core.load_cell(root, "tiny.churn")


def _check(numbers):
    return all(numbers[k] <= lim for k, lim in CONFIG["correct"].items())


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_program_correct_control_not(cell, seed):
    env = core.build(cell, seed, require_tpu=False)
    win = core.window(env, 0.0, min_waves=1)
    got = core.served(win, env.vocab)
    assert got.failed == 0
    rows = correct.sample(got.done, seed, win.loaded)
    g = correct.gaps(env.model, CONFIG, seed, rows, control=True)
    assert _check(correct.numbers(g["served"]))
    assert not _check(correct.numbers(g["int8"]))
