"""Pallas TPU kernels for the compute hot spots: fused DSC update, int8
wire quantization, the fused DSC->int8 wire step, flash attention and
paged decode attention.  ``common.interpret_mode`` decides at call time
whether they compile for the TPU or run in interpret mode on the CPU."""
