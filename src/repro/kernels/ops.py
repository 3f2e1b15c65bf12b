"""Public jit'd kernel API.

Each op has two execution paths with identical semantics:

* ``backend="pallas"``  — the Pallas kernels (compiled to Mosaic on a TPU
  backend, run in the Pallas interpreter on the CPU — the platform decides,
  :func:`repro.kernels.geometry.interpret_mode`).  Used by kernel tests,
  the CNN examples and single-chip benchmarking.
* ``backend="xla"``     — the pure-jnp oracles from :mod:`repro.kernels.ref`.
  Used under pjit/shard_map (Pallas TPU kernels cannot lower on the CPU
  backend of the dry-run) and as the autodiff-native path.

The selection lives in :mod:`repro.core.engine`; this module only wires.
"""
from __future__ import annotations

from repro.kernels.attention import flash_attention
from repro.kernels.conv2d import conv2d_im2col, conv2d_mpna
from repro.kernels.pool_act import maxpool_act
from repro.kernels.sa_conv import sa_conv_matmul
from repro.kernels.sa_conv_implicit import sa_conv_implicit
from repro.kernels.sa_fc import sa_fc_matmul
from repro.kernels import ref

__all__ = [
    "flash_attention", "conv2d_mpna", "conv2d_im2col", "sa_conv_implicit",
    "maxpool_act", "sa_conv_matmul", "sa_fc_matmul", "ref",
]
