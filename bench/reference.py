"""The plain reference: a CNN forward in straightforward ``jax.numpy``.

It reads the layer table of a configuration file and nothing else: no
module of the program is imported and nothing the program made is used.
Convolutions are ``lax.conv_general_dilated`` (NHWC, HWIO), each conv is
followed by ReLU, each pool is a VALID max over ``k x k`` windows, the
feature map is flattened in NHWC order, and the FC layers apply their
table's activation.  Matmul precision is an argument: ``"highest"`` is the
reference, a lower one (``"high"``: three bf16 passes) is the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _layer(spec: dict, p: dict, x, precision):
    if spec["kind"] == "conv":
        pad = spec["pad"]
        y = jax.lax.conv_general_dilated(
            x, p["f"], (spec["stride"], spec["stride"]),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        return jax.nn.relu(y + p["b"])
    if spec["kind"] == "pool":
        k, s = spec["k"], spec["stride"]
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, k, k, 1), (1, s, s, 1), "VALID")
    y = jnp.dot(x.reshape(x.shape[0], -1), p["w"],
                precision=precision) + p["b"]
    return jax.nn.relu(y) if spec["act"] == "relu" else y


def forward(layers: list[dict], params: list[dict], images,
            precision: str = "highest"):
    """Logits of ``images`` (N, H, W, C) float32 under ``params``: one
    dict per layer, ``{"f", "b"}`` for a conv, ``{}`` for a pool,
    ``{"w", "b"}`` for an FC layer, all float32."""
    x = images
    for spec, p in zip(layers, params):
        x = _layer(spec, p, x, precision)
    return x


def logits(layers: list[dict], params: list[dict], images: np.ndarray, *,
           precision: str = "highest", block: int = 16) -> np.ndarray:
    """Host logits of every image, computed ``block`` images at a time so
    the reference fits beside whatever the process still holds."""
    fn = jax.jit(lambda p, x: forward(layers, p, x, precision))
    out = [np.asarray(fn(params, jnp.asarray(images[i:i + block])))
           for i in range(0, len(images), block)]
    return np.concatenate(out)
