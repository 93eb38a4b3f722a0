"""A run whose timed path is broken underneath comes out not correct.

The run is driven as the benchmark drives it (weights and batches from
the seed, the compiled entry, the checked steps or the window's answers,
the reference and the limits), at the test size on the CPU with the look
for a chip skipped; only the program's step or forward pass is wrapped
with a fault of ``chipbench/faults.py`` before it is compiled.  Seed 11
is the first of the test size's calibration seeds.  Not every seed shows
every fault: a half batch whose two images have nearly the same loss
escapes (PERF.md, section 6, gives the counts)."""
import pytest

import chipbench_tiny
from chipbench import faults, run


FAULTS = [("tiny-train", "train", n) for n in faults.TRAIN] + [
    ("tiny-infer", "infer", n) for n in faults.INFER]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with chipbench_tiny.kept_cache_config():
        yield chipbench_tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


def _run(root, workload, hooks=None, seed=11):
    args = run.parse_args(["--workload", workload, "--seed", str(seed),
                           "--seconds", "0.3"])
    return run.run(args, root=root, require_tpu=False, hooks=hooks)


@pytest.mark.parametrize("workload", ["tiny-train", "tiny-infer"])
def test_sound_run_is_correct(root, workload):
    assert _run(root, workload)["correct"] is True


@pytest.mark.parametrize("workload,mode,fault", FAULTS,
                         ids=[f"{f[1]}-{f[2]}" for f in FAULTS])
def test_fault_is_not_correct(root, workload, mode, fault):
    res = _run(root, workload, faults.hooks(mode, fault))
    assert res["correct"] is False, res["checks"]
