"""Pallas TPU kernels (flash attention, paged-attention decode)."""
import collections

# kernel name -> how many times its pallas_call was traced into a program
# in this process. What a caller can assert instead of trusting a dispatch
# gate's opinion: a jitted step whose trace bumped "flash_fwd" has the
# kernel in it (``chip_smoke.py`` checks exactly that).
kernel_traces: collections.Counter = collections.Counter()
