"""Roofline share of the fused DSC + int8 wire kernel: the HBM bytes its
passes over every segmented leaf need in the traced rounds, at the chip's
HBM bandwidth, over the device time its calls took."""
from bench import counting


def read(ctx):
    return counting.kernel_share(ctx, "dsc_quantize._kernel",
                                 counting.dsc_quantize_bytes)
