"""The last line of a run: its keys and their shapes, and the compared
numbers as the last lines on standard error.  The run is driven on the
CPU at the tiny size with the look for a chip skipped."""
import io
import json
from contextlib import redirect_stderr, redirect_stdout

import pytest

import chipbench_tiny
from chipbench import run

E2E = {"images_per_s": "images/s", "setup_s": "s"}


def drive(root, workload, trace):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", "2147483711",
                       "--seconds", "0.5", "--trace", str(trace)],
                      root=root, require_tpu=False)
    return rc, out.getvalue().splitlines(), err.getvalue().splitlines()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with chipbench_tiny.kept_cache_config():
        yield chipbench_tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("workload,trace", [("tiny-train", 0), ("tiny-infer", 0),
                                            ("tiny-infer", 1)])
def test_last_line_schema(root, workload, trace):
    rc, out, err = drive(root, workload, trace)
    assert rc == 0
    res = json.loads(out[-1])
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert isinstance(res["correct"], bool)
    assert res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert dev["window_s"] > 0
    else:
        assert {k: v["unit"] for k, v in res["metrics"].items()} == E2E
        assert all(v["value"] > 0 for v in res["metrics"].values())
    checks = res["checks"]
    assert checks and all(set(c) == {"value", "limit"} for c in checks.values())
    tail = err[-len(checks):]
    for line, name in zip(tail, checks):
        assert line.startswith(f"[chipbench] check {name}: ") and "(limit " in line
