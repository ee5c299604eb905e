"""The fused forward kernel's roofline share in the training step, %."""
from bench import readers


def read(run):
    return readers.train_kernel_roofline(run, "fused_fwd")
