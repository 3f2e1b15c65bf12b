"""SA-CONV kernel: the conv layers' least time over the kernel's device time, percent."""
from bench.readers import kernel_roofline

#: the SA-CONV kernel's custom call (kernels/sa_conv_implicit.py), as the
#: trace names it: ``%sa_conv_implicit.1 = f32[...] custom-call(...)``
PATTERN = r"^%sa_conv_implicit(\.\d+)? = "


def read(run):
    return kernel_roofline(run, "conv", PATTERN)
