"""Roofline share of the int8 dequantize kernel on the aggregator side:
the HBM bytes of one pass over every segmented leaf per traced round, at
the chip's HBM bandwidth, over the device time its calls took."""
from bench import counting


def read(ctx):
    return counting.kernel_share(ctx, "quantize._dequant_kernel",
                                 counting.dequantize_bytes)
