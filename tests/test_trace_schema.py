"""Trace export schema tests: the ``serve --trace`` artifact contract.

A real traced serve exports JSON that (a) passes the shared schema
check in ``benchmarks/schema.py``, (b) loads in a Chrome-trace viewer
(phases/timestamps well-formed), and (c) round-trips through the two
CLI tools. Corrupted variants of the same artifact must each fail the
check — a validator that accepts everything protects nothing.
"""
import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))  # benchmarks/ is imported from the root

from benchmarks.schema import (validate_trace_file,  # noqa: E402
                               validate_trace_json)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced serve, exported; shared by every test here."""
    import dataclasses

    from repro.configs import get_config, reduced_config
    from repro.serving.engine import EdgeLoRAEngine, EngineConfig
    from repro.serving.trace import EngineTracer
    from repro.serving.workload import WorkloadConfig, generate_trace

    cfg = reduced_config(get_config("qwen2-0.5b"))
    cfg = dataclasses.replace(
        cfg, lora=dataclasses.replace(cfg.lora, n_adapters=5))
    tracer = EngineTracer()
    eng = EdgeLoRAEngine(cfg, EngineConfig(
        n_slots=2, max_ctx=48, prompt_buckets=(16, 32),
        policy="edgelora", kv_backend="paged"), tracer=tracer)
    trace = generate_trace(WorkloadConfig(
        n_adapters=5, request_rate=3.0, duration=2.0,
        input_range=(4, 20), output_range=(3, 6),
        vocab_size=cfg.vocab_size, seed=0))
    eng.serve(trace)
    path = tmp_path_factory.mktemp("trace") / "TRACE_test.json"
    tracer.export(path)
    return path, json.loads(path.read_text())


def test_exported_trace_validates(traced):
    path, data = traced
    assert validate_trace_file(path) == []
    assert validate_trace_json(data) == []
    # Chrome-trace surface Perfetto needs
    assert data["displayTimeUnit"] == "ms"
    phases = {ev["ph"] for ev in data["traceEvents"]}
    assert "M" in phases and "X" in phases and "C" in phases
    # host spans: in the raw section, and as their own Perfetto process
    # (pid 1, wall clock) apart from the virtual-clock tracks (pid 0)
    spans = data["edgelora"]["spans"]
    assert "serve" in [s[2] for s in spans]
    host = [ev for ev in data["traceEvents"]
            if ev["pid"] == 1 and ev["ph"] == "X"]
    assert [ev["name"] for ev in host] == [s[2] for s in spans]
    assert all(ev["cat"] == "host" for ev in host)
    assert not any(ev.get("cat") == "host" for ev in data["traceEvents"]
                   if ev["pid"] == 0)


def test_missing_file_and_bad_json(tmp_path):
    assert validate_trace_file(tmp_path / "absent.json") != []
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert any("invalid JSON" in e for e in validate_trace_file(bad))


@pytest.mark.parametrize("corrupt", [
    "drop_trace_events", "empty_trace_events", "bad_phase", "nan_ts",
    "negative_dur", "drop_section", "wrong_version", "break_sum",
    "nan_segment", "drop_breakdowns", "nonfinite_duration",
    "empty_raw_events", "drop_spans", "span_open", "span_bad_parent",
    "span_escapes_parent",
])
def test_corrupted_traces_fail(traced, corrupt):
    _, original = traced
    data = copy.deepcopy(original)
    if corrupt == "drop_trace_events":
        del data["traceEvents"]
    elif corrupt == "empty_trace_events":
        data["traceEvents"] = []
    elif corrupt == "bad_phase":
        data["traceEvents"][1]["ph"] = "Z"
    elif corrupt == "nan_ts":
        for ev in data["traceEvents"]:
            if ev["ph"] != "M":
                ev["ts"] = float("nan")
                break
    elif corrupt == "negative_dur":
        for ev in data["traceEvents"]:
            if ev["ph"] == "X":
                ev["dur"] = -1.0
                break
    elif corrupt == "drop_section":
        del data["edgelora"]
    elif corrupt == "wrong_version":
        data["edgelora"]["version"] = 2
    elif corrupt == "break_sum":
        bd = next(iter(data["edgelora"]["breakdowns"].values()))
        bd["e2e"] += 1.0
    elif corrupt == "nan_segment":
        bd = next(iter(data["edgelora"]["breakdowns"].values()))
        bd["decode"] = float("nan")
    elif corrupt == "drop_breakdowns":
        del data["edgelora"]["breakdowns"]
    elif corrupt == "nonfinite_duration":
        data["edgelora"]["duration"] = float("inf")
    elif corrupt == "empty_raw_events":
        data["edgelora"]["events"] = []
    elif corrupt == "drop_spans":
        del data["edgelora"]["spans"]
    elif corrupt == "span_open":
        data["edgelora"]["spans"][-1][4] = None
    elif corrupt == "span_bad_parent":
        data["edgelora"]["spans"][1][1] = 5
    elif corrupt == "span_escapes_parent":
        data["edgelora"]["spans"][1][4] = data["edgelora"]["spans"][0][4] + 1
    assert validate_trace_json(data) != [], corrupt


# ---------------------------------------------------------------------------
# the CLI tools, end to end
# ---------------------------------------------------------------------------


def _run_tool(script, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}{os.pathsep}{ROOT}"
    return subprocess.run(
        [sys.executable, str(ROOT / "tools" / script), *map(str, args)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)


def test_trace_export_cli(traced, tmp_path):
    path, _ = traced
    res = _run_tool("trace_export.py", path)
    assert res.returncode == 0, res.stderr
    assert "watchdog=ok" in res.stderr

    out = tmp_path / "viewer.json"
    res = _run_tool("trace_export.py", path, "-o", out, "--strip-raw")
    assert res.returncode == 0, res.stderr
    stripped = json.loads(out.read_text())
    assert "edgelora" not in stripped
    assert stripped["traceEvents"]

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": []}))
    assert _run_tool("trace_export.py", bad).returncode == 1


def test_trace_report_cli(traced, tmp_path):
    path, _ = traced
    res = _run_tool("trace_report.py", path, "--top", "3")
    assert res.returncode == 0, res.stderr
    for needle in ("slowest", "mean breakdown", "busiest compute spans",
                   "utilization", "jit-recompile watchdog", "ok:"):
        assert needle in res.stdout, (needle, res.stdout)

    # a stripped trace has no raw section to analyze: fail loudly
    out = tmp_path / "viewer.json"
    _run_tool("trace_export.py", path, "-o", out, "--strip-raw")
    res = _run_tool("trace_report.py", out)
    assert res.returncode == 1
    assert "strip-raw" in res.stderr
