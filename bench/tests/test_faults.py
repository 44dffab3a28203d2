"""A run with its timed path broken underneath must come out not correct.

Each case drives the whole harness (set-up, warm-up, window, sample,
reference) at the tiny size on the CPU, skipping only the look for a chip,
with the engine broken after it is built: a decode step that returns its
cache unchanged, a token altered where the decode step produces it, and
adapter loads that leave the pool unchanged. The unbroken run is correct.
"""
import io
import json

import pytest

from bench.harness import core
from bench.tests import tiny


def _run(tmp_path, hook=None):
    root = tiny.make_root(str(tmp_path))
    out, err = io.StringIO(), io.StringIO()
    rc = core.run(root, "tiny.churn", 2 ** 33 + 17, 1.0, False, 0.0,
                  require_tpu=False, hook=hook, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _stale_cache(engine):
    step = engine._decode

    def decode(params, pool, tokens, cache, pos, sids):
        toks, _ = step(params, pool, tokens, cache, pos, sids)
        return toks, cache
    engine._decode = decode


def _altered_token(engine):
    step = engine._decode
    vocab = engine.cfg.vocab_size

    def decode(params, pool, tokens, cache, pos, sids):
        toks, cache = step(params, pool, tokens, cache, pos, sids)
        return toks.at[0].set((toks[0] + 1) % vocab), cache
    engine._decode = decode


def _no_load(engine):
    engine.manager.load_fn = lambda adapter_id, slot: None


def test_unbroken_run_is_correct(tmp_path):
    res = _run(tmp_path)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_stale_cache, _altered_token, _no_load],
                         ids=["state_unchanged", "token_altered",
                              "adapter_not_loaded"])
def test_fault_is_not_correct(tmp_path, fault):
    res = _run(tmp_path, fault)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]
