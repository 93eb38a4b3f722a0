"""The whole step's share of the chip's bf16 peak: model FLOPs per image
(``flops_per_image`` of the configuration's family; a training image
counts 3x its forward pass, recomputation not at all) times the traced
window's images per second, over the peak."""


def read(run):
    if run.peak is None or run.flops_per_image is None:
        return None
    return (100.0 * run.flops_per_image * run.images_per_s
            / run.peak["bf16_flop_per_s"])
