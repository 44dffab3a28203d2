"""Idle time put down to the program's host spans, and the readers of
the program's span list."""
from types import SimpleNamespace

import pytest

from bench.harness import spantrace
from bench.metrics import (adapter_load_ms, demand_loads_per_req,
                           host_ms_per_iter, queue_wait_p95_ms)

# window [0, 110] ns; the device busy over [10, 30], [40, 70], [80, 90]
REC = {"window": [0.0, 110.0], "modules": [],
       "ops": [["fusion.1", 10.0, 20.0], ["fusion.2", 40.0, 30.0],
               ["fusion.1", 80.0, 10.0]],
       "host": []}
# serve [0, 100] > iter [2, 50] > decode [20, 50] > wait [25, 35];
# serve > iter [55, 95] > admit [72, 78]
HOST = [["serve", 0.0, 100.0], ["iter", 2.0, 48.0], ["decode", 20.0, 30.0],
        ["wait", 25.0, 10.0], ["iter", 55.0, 40.0], ["admit", 72.0, 6.0]]


def test_idle_goes_to_the_innermost_open_span():
    by_span = spantrace.idle_by_span(REC, HOST)
    # idle [0, 10]: serve 2, iter 8; [30, 40]: wait 5, decode 5;
    # [70, 80]: iter 2, admit 6, iter 2; [90, 110]: iter 5, serve 5, none 10
    assert by_span == {"serve": 7.0, "iter": 17.0, "wait": 5.0,
                       "decode": 5.0, "admit": 6.0, "none": 10.0}
    assert sum(by_span.values()) == 110.0 - 60.0
    assert spantrace.host_idle_ns(by_span) == 45.0


def test_a_child_that_outlasts_its_parent_is_cut_at_the_parent():
    host = [["iter", 0.0, 50.0], ["decode", 40.0, 30.0]]
    rec = dict(REC, ops=[], window=[0.0, 100.0])
    assert spantrace.idle_by_span(rec, host) == {
        "iter": 40.0, "decode": 10.0, "none": 50.0}


def test_spans_from_a_profile():
    ev = SimpleNamespace
    line = ev(name="python", events=[
        ev(name="edgelora.decode", start_ns=5, duration_ns=3),
        ev(name="bench.after decode", start_ns=9, duration_ns=0),
        ev(name="edgelora.iter", start_ns=5, duration_ns=9)])
    device = ev(name="/device:TPU:0", lines=[ev(name="XLA Ops", events=[
        ev(name="edgelora.nothing", start_ns=1, duration_ns=1)])])
    profile = ev(planes=[ev(name="/host:CPU", lines=[line]), device])
    assert spantrace.spans(profile) == [["iter", 5.0, 9.0],
                                        ["decode", 5.0, 3.0]]


MS = 1_000_000
PROGRAM = [
    [0, None, "serve", 0, 100 * MS, {"n_requests": 3}],
    [1, 0, "iter", 1 * MS, 11 * MS, {}],
    [2, 1, "admit", 1 * MS, 2 * MS, {"requests": [7, 8]}],
    [3, 1, "select", 2 * MS, 5 * MS, {}],
    [4, 3, "adapter.acquire", 2 * MS, 4 * MS, {"adapter": 3}],
    [5, 4, "adapter.load", int(2.5 * MS), int(3.5 * MS), {"adapter": 3}],
    [6, 1, "decode", 5 * MS, 11 * MS, {"requests": [7]}],
    [7, 6, "wait", 6 * MS, 10 * MS, {"key": ["decode"]}],
    [8, 0, "iter", 20 * MS, 24 * MS, {}],
    [9, 8, "adapter.prefetch", 20 * MS, 23 * MS, {}],
    [10, 9, "adapter.load", 20 * MS, 23 * MS, {"adapter": 5}],
    [11, 8, "admit", 23 * MS, 24 * MS, {"requests": [9]}],
]


def _ctx(spans, n_requests=3):
    obs = SimpleNamespace(spans=spans) if spans is not None \
        else SimpleNamespace()
    return SimpleNamespace(traced={"observer": obs,
                                   "requests": [{}] * n_requests})


def test_readers_on_a_span_list():
    ctx = _ctx(PROGRAM)
    # (10 - 4 waited) and 4 ms of host time per pass
    assert host_ms_per_iter.read(ctx) == pytest.approx(5.0)
    # admitted 1, 1 and 23 ms after serve() began
    assert queue_wait_p95_ms.read(ctx) == pytest.approx(20.8)
    assert adapter_load_ms.read(ctx) == pytest.approx(2.0)
    # one load under an acquire; the prefetched one does not count
    assert demand_loads_per_req.read(ctx) == pytest.approx(1 / 3)


@pytest.mark.parametrize("reader", [host_ms_per_iter, queue_wait_p95_ms,
                                    adapter_load_ms, demand_loads_per_req])
def test_readers_without_program_spans_read_nothing(reader):
    # a program without host spans (its tracer has no `spans`), or with
    # spans off (an empty list)
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx([])) is None


def test_no_loads_reads_zero_demand_and_no_load_time():
    ctx = _ctx([s for s in PROGRAM[:4] if not s[2].startswith("adapter")])
    assert adapter_load_ms.read(ctx) is None
    assert demand_loads_per_req.read(ctx) == 0.0


def test_recorded_chip_wave():
    # the first 300 ms of a qwen2-0.5b.churn wave on one TPU v5e with host
    # spans on: the first pass of serve()'s loop, in which select loads
    # nine adapters (16 requests admitted at once), then prefill and
    # decode; the device record and the profile's spans cover 300 ms
    import json
    import os

    from bench.harness import devtrace

    path = os.path.join(os.path.dirname(__file__), "data",
                        "qwen2_churn_spans_wave_start.json")
    with open(path) as f:
        rec = json.load(f)
    # the profile's annotations are the program's spans, in order
    assert [s[0] for s in rec["spans"]] == \
        [s[2] for s in rec["program_spans"][:len(rec["spans"])]]
    by_span = spantrace.idle_by_span(rec, rec["spans"])
    red = devtrace.reduce(rec)
    assert sum(by_span.values()) == pytest.approx(
        red["window_ns"] - red["busy_ns"])
    # the chip waits on the adapter loads' host work
    assert by_span["adapter.load"] == pytest.approx(263011236.0)
    assert by_span["adapter.load"] > 0.95 * sum(by_span.values())
    ctx = _ctx(rec["program_spans"], rec["requests"])
    # one pass: 459.7 ms, of which 92.7 + 20.5 ms waited on prefill/decode
    assert host_ms_per_iter.read(ctx) == pytest.approx(346.548013)
    assert queue_wait_p95_ms.read(ctx) == pytest.approx(0.31092)
    assert adapter_load_ms.read(ctx) == pytest.approx(36.415868, rel=1e-6)
    assert demand_loads_per_req.read(ctx) == pytest.approx(9 / 64)
