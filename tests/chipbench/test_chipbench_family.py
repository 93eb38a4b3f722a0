"""A model family joins the benchmark by new files alone, and the
harness's generic modules import no model code.

The checkout is the tiny one (``chipbench_tiny.py``) with a toy family
added: its module (``data/toy-msda.py``, a stack of MSDA self-attention
layers through the program's ``msda_attention`` with a float32 reference
of its own), its configuration, traffic and limits files, and its
entries in ``BENCHMARK.json``.  No file that was there changes.  The run
goes through ``chipbench.run.main`` on the CPU with the look for a chip
skipped."""
import ast
import glob
import hashlib
import io
import json
import os
import shutil
from contextlib import redirect_stderr, redirect_stdout

import pytest

import chipbench_tiny
from chipbench import run

REPO = chipbench_tiny.REPO
HERE = os.path.dirname(os.path.abspath(__file__))

TOY_CONFIG = {"name": "toy-msda", "family": "toy-msda", "source": "test",
              "levels": [[8, 8], [4, 4]], "d_model": 64, "num_heads": 2,
              "num_points": 2, "layers": 2}
TOY_TRAFFIC = {"mode": "infer", "batch": 2, "distinct_batches": 3,
               "feature_std": 1.0, "checked_batches": 2}
# Set like a cell's limit (lower^0.4 x upper^0.6), on the CPU: the
# program's largest ``out_gap`` over seeds 11..22, and the smallest of an
# output 0.1 off where it is produced (``_perturbed``) over seeds 11..13:
#   out_gap  lower 1.45e-6, upper 0.0892
TOY_LIMITS = {"out_gap": 1.1e-3}


def _perturbed(forward):
    """One output of one answer 0.1 off where it is produced."""
    return lambda p, x: forward(p, x).at[0, 0, 0].add(0.1)


def _digest(root):
    out = {}
    for path in glob.glob(os.path.join(root, "**", "*"), recursive=True):
        if os.path.isfile(path) and "__pycache__" not in path:
            with open(path, "rb") as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def add_toy_family(root: str) -> str:
    """Add the toy family to the checkout at ``root``, by new files and
    new entries in ``BENCHMARK.json`` alone."""
    cb = os.path.join(root, "chipbench")
    shutil.copy(os.path.join(HERE, "data", "toy-msda.py"),
                os.path.join(cb, "families", "toy-msda.py"))
    for rel, obj in (("configs/toy-msda.json", TOY_CONFIG),
                     ("traffic/toy-infer.json", TOY_TRAFFIC),
                     ("limits/toy-infer.json", TOY_LIMITS)):
        with open(os.path.join(cb, rel), "x") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-msda", "source": "test",
                             "file": "chipbench/configs/toy-msda.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-infer", "config": "toy-msda",
                               "traffic": "toy-infer", "chips": 1,
                               "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    with chipbench_tiny.kept_cache_config():
        root = chipbench_tiny.make_root(str(tmp_path_factory.mktemp("family")))
        before = _digest(root)
        yield add_toy_family(root), before


def drive(root, workload, trace=0, hooks=None, seed=11):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "0.3", "--trace", str(trace)],
                      root=root, require_tpu=False, hooks=hooks)
    lines = out.getvalue().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 else None), err.getvalue()


@pytest.mark.parametrize("trace", [0, 1])
def test_new_family_runs_and_is_correct(checkout, trace):
    root, before = checkout
    rc, res, err = drive(root, "toy-infer", trace, seed=2147483777)
    assert rc == 0, err[-2000:]
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"out_gap"}
    assert res["attempted"] > 0 and res["failed"] == 0
    after = _digest(root)
    changed = [p for p, h in before.items() if after[p] != h]
    assert changed == [os.path.join(root, "BENCHMARK.json")]


def test_new_family_perturbed_forward_is_not_correct(checkout):
    rc, res, err = drive(checkout[0], "toy-infer",
                         hooks={"forward_fn": _perturbed})
    assert rc == 0, err[-2000:]
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("family", [None, "no-such-family"])
def test_configuration_without_a_known_family_fails(tmp_path, family):
    root = chipbench_tiny.make_root(str(tmp_path))
    cfg = {k: v for k, v in chipbench_tiny.TINY_CONFIG.items() if k != "family"}
    if family:
        cfg["family"] = family
    with open(os.path.join(root, "chipbench", "configs", "tiny-detr.json"),
              "w") as f:
        json.dump(cfg, f)
    rc, res, err = drive(root, "tiny-infer")
    assert rc == 1 and res is None
    last = err.splitlines()[-1]
    assert last.startswith("[chipbench] FAIL: CatalogError") and "family" in last


GENERIC = ["run.py", "catalog.py", "trace.py", "device_scopes.py",
           "harness.py", "checks.py", "work.py", "peaks.py"] + sorted(
    os.path.relpath(p, os.path.join(REPO, "chipbench")) for p in
    glob.glob(os.path.join(REPO, "chipbench", "metrics", "*.py")))
MODEL_CODE = {"repro.core.deformable_transformer", "chipbench.weights",
              "chipbench.generate", "chipbench.reference"}


@pytest.mark.parametrize("rel", GENERIC)
def test_generic_modules_import_no_model_code(rel):
    with open(os.path.join(REPO, "chipbench", rel)) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
            imported |= {f"{node.module}.{a.name}" for a in node.names}
    assert not imported & MODEL_CODE
