"""SA-FC kernel: the FC layers' least time over the kernel's device time, percent."""
from bench.readers import kernel_roofline

#: the SA-FC kernel's custom call (kernels/sa_fc.py), as the trace names
#: it: ``%sa_fc_matmul.1 = f32[...] custom-call(...)``
PATTERN = r"^%sa_fc_matmul(\.\d+)? = "


def read(run):
    return kernel_roofline(run, "fc", PATTERN)
