"""Paged-attention kernel: roofline time of the attention the traced
steps needed, over the device time of the kernel's ops in the trace, in
percent. Silent when the trace holds no such op."""
from bench.measure import KERNEL_OP


def read(run):
    return run.kernel_roofline_pct(KERNEL_OP)
