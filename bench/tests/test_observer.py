"""The wall-clock observer: one first-token stamp per request from its
prefill step, one stamp per further token from decode steps, and a compile
count that is the number of new step shapes (0 on a repeated serve)."""
import numpy as np

from bench.harness import traffic
from bench.harness.core import _requests
from bench.harness.engine import (WallObserver, engine_config, make_engine,
                                  model_config)
from bench.models import dense_decoder
from bench.tests import tiny


def _engine():
    cfg = tiny.CONFIG
    params = dense_decoder.make_params(cfg, 3)
    return make_engine(model_config(cfg), engine_config(tiny.MIX, 3), params,
                       dense_decoder.adapter_fn(cfg, 3))


def test_stamps_and_compiles():
    engine = _engine()
    dicts = traffic.wave(tiny.MIX, 16, 512, 3, 0, 0)
    for attempt in range(2):
        obs = WallObserver()
        engine.tracer = obs
        reqs = _requests(dicts)
        engine.serve(reqs)
        engine.tracer = None
        for d, r in zip(dicts, reqs):
            stamps = obs.token_stamps(d["request_id"])
            assert len(stamps) == r.generated == d["output_len"]
            assert np.all(np.diff(stamps) > 0)
            assert stamps[0] == obs.prefill[d["request_id"]]
        kinds = {s[0] for s in obs.steps}
        assert kinds == {"prefill", "decode"}
        decode_rows = sum(len(s[3]) for s in obs.steps if s[0] == "decode")
        assert decode_rows == sum(d["output_len"] - 1 for d in dicts)
        shapes = {s[1] for s in obs.steps}
        if attempt == 0:
            assert obs.compiles == len(shapes)
        else:
            assert obs.compiles == 0


def test_observer_records_nothing_else():
    obs = WallObserver()
    obs.begin(0.0, 4)
    obs.transition(0.0, 0, "idle", "selecting", None)
    obs.sched(0.0, "admit")
    obs.sample(0.0, queue_depth=1)
    assert obs.events == [] and obs.steps == []
    assert obs.finish(1.0) == {} and obs.breakdown_summary() is None
