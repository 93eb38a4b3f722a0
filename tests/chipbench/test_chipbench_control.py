"""The control comes out not correct.

The control is the plain reference computed in float8 (e4m3), the step
below the configuration's bfloat16, put in the program's place: its
readings against the float32 reference are judged by the limits of the
test size (``chipbench_tiny.LIMITS``, set like a cell's from twelve
seeds).  The inputs are the benchmark's own, made from each seed.  A
training step is caught on every seed by the first gradient of the class
head's bias, whose gap reads the rounding of the whole forward pass.  The
program's own runs come out correct in ``test_chipbench_faults.py``.
"""
import pytest

import chipbench_tiny
from chipbench import catalog, checks, generate, reference, weights

CFG = chipbench_tiny.TINY_CONFIG
DETR = catalog.family(CFG, chipbench_tiny.REPO)


def control_correct(mode, seed):
    traffic = chipbench_tiny.TRAIN if mode == "train" else chipbench_tiny.INFER
    params = weights.make(seed, CFG, served=mode == "infer")
    batches = generate.make(seed, CFG, traffic)
    if mode == "train":
        inputs = {"params0": params,
                  "batches": batches[:traffic["checked_steps"]]}
    else:
        inputs = {"params": params, "batches": batches, "answered": [0, 1]}
    ref = DETR.REFERENCE[mode](CFG, traffic, inputs)
    low = DETR.REFERENCE[mode](CFG, traffic, inputs, reference.FLOAT8)
    limits = chipbench_tiny.LIMITS[f"tiny-{mode}"]
    return checks.judge(DETR.NUMBERS[mode](low, ref), limits)["correct"]


@pytest.mark.parametrize("seed", chipbench_tiny.CONTROL_SEEDS[:3])
def test_inference_control_is_not_correct(seed):
    assert control_correct("infer", seed) is False


def test_training_control_is_not_correct_on_most_seeds():
    verdicts = [control_correct("train", s) for s in chipbench_tiny.CONTROL_SEEDS]
    assert verdicts.count(False) == len(verdicts), verdicts
