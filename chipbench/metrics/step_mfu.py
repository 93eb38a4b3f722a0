"""The whole step's share of the chip's bf16 peak: model FLOPs per image
(``chipbench/work.py``; a training image counts 3x its forward pass,
recomputation not at all) times the traced window's images per second,
over the peak."""
from chipbench import work


def read(run):
    if run.peak is None:
        return None
    flops = work.flops_per_image(run.cfg, run.mode)
    return 100.0 * flops * run.images_per_s / run.peak["bf16_flop_per_s"]
