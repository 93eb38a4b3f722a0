"""The benchmark's FLOP and byte counts against hand-worked counts at
the paper's widths (5 levels 256^2..16^2, 87,296 queries, 8 heads of 32,
4 points, d=256, FFN 1024, 300 object queries, 91 classes)."""
import json
import os

import pytest

import chipbench_tiny
from chipbench import catalog, peaks, work

REPO = chipbench_tiny.REPO
DETR = catalog.family(chipbench_tiny.TINY_CONFIG, REPO)
LEVELS = ((256, 256), (128, 128), (64, 64), (32, 32), (16, 16))
Q = 87296                    # 65536 + 16384 + 4096 + 1024 + 256
SAMPLES = Q * 8 * 5 * 4      # queries x heads x levels x points = 13,967,360


def _config(name):
    with open(os.path.join(REPO, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_msda_fwd_work_bf16_encoder():
    flops, nbytes = work.msda_fwd_work(LEVELS, 1, Q, 8, 32, 4, "bfloat16")
    assert flops == 10 * SAMPLES * 32 == 4_469_555_200
    value = Q * 256 * 2          # 44,695,552
    loc = SAMPLES * 2 * 4        # 111,738,880
    attn = SAMPLES * 2           # 27,934,720
    out = Q * 256 * 2            # 44,695,552
    assert nbytes == value + loc + attn + out == 229_064_704


def test_msda_fwd_work_float32_batch2():
    flops, nbytes = work.msda_fwd_work(LEVELS, 2, Q, 8, 32, 4, "float32")
    assert flops == 2 * 4_469_555_200
    assert nbytes == 2 * (Q * 256 * 4 + SAMPLES * 8 + SAMPLES * 4 + Q * 256 * 4)


def test_msda_bwd_work_bf16_encoder():
    flops, nbytes = work.msda_bwd_work(LEVELS, 1, Q, 8, 32, 4, "bfloat16")
    assert flops == 26 * SAMPLES * 32 == 11_620_843_520
    # operands and gradients (value, loc, attn) twice, plus the cotangent
    assert nbytes == 2 * (44_695_552 + 111_738_880 + 27_934_720) + 44_695_552
    assert nbytes == 413_433_856


def test_least_seconds_is_bytes_bound_for_msda():
    peak = peaks.peaks("TPU v5 lite")
    t, bound = work.least_seconds(4_469_555_200, 229_064_704, peak)
    assert bound == "bytes"
    assert t == pytest.approx(229_064_704 / 819e9)   # 0.28 ms
    t, bound = work.least_seconds(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "flops")


def test_model_forward_flops_paper_width():
    cfg = _config("deformable-detr")
    S, d, ff = Q, 256, 1024
    # encoder layer: value 11,442,061,312 + offsets 14,302,576,640 +
    # weights 7,151,288,320 + interpolation 4,469,555,200 + output
    # 11,442,061,312 + FFN 91,536,490,496 = 140,344,033,280
    enc_layer = 140_344_033_280
    assert enc_layer == (2 * S * d * d + 2 * S * d * 320 + 2 * S * d * 160
                         + 10 * S * 160 * 32 + 2 * S * d * d + 4 * S * d * ff)
    # decoder layer: self-attention 249,446,400; MSDA 11,570,470,912 (its
    # value projection runs over all 87,296 memory tokens); FFN 314,572,800
    dec_layer = 249_446_400 + 11_570_470_912 + 314_572_800
    heads = 307_200 + 13_977_600 + 39_321_600 + 614_400
    total = 6 * enc_layer + 6 * dec_layer + heads
    assert total == 914_925_361_152
    assert DETR.model_forward_flops(cfg) == total
    assert DETR.flops_per_image(cfg, "train") == 3 * total
    assert DETR.flops_per_image(cfg, "infer") == total


def test_model_forward_flops_coco800_levels():
    cfg = _config("deformable-detr-coco800")
    assert sum(h * w for h, w in cfg["levels"]) == 22_223
    S = 22_223
    enc_layer = (2 * S * 256 * 256 * 2 + 2 * S * 256 * 128 * 3
                 + 10 * S * 128 * 32 + 4 * S * 256 * 1024)
    assert DETR.model_forward_flops(cfg) > 6 * enc_layer


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
