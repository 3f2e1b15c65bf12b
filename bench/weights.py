"""Seeded weights and images, made by the benchmark and handed to the program.

The weights of one configuration come from one jitted call on the device,
in the type they are served in: float32, or symmetric per-output-channel
int8 with float32 scales.  Widths follow the program's ``width_mult`` rule
(``max(8, int(c * width_mult))``, the 1000-way classifier kept) so a
configuration at another width gets the shapes the program expects.  The
reference is given the same weights, dequantized by this module's own
arithmetic, never by the program's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _width(c: int, width_mult: float, keep: bool = False) -> int:
    return c if keep else max(8, int(c * width_mult))


def shapes(cfg: dict) -> list[dict]:
    """Per layer of ``cfg["layers"]``: the input and output feature maps
    and the weight shape the program serves at ``cfg["width_mult"]``."""
    res, ch = cfg["in_res"], cfg["in_ch"]
    out = []
    for s in cfg["layers"]:
        if s["kind"] == "conv":
            oc = _width(s["out"], cfg["width_mult"])
            o = (res + 2 * s["pad"] - s["k"]) // s["stride"] + 1
            out.append(dict(s, ifm=(res, res, ch), ofm=(o, o, oc),
                            w=(s["k"], s["k"], ch, oc)))
            res, ch = o, oc
        elif s["kind"] == "pool":
            o = (res - s["k"]) // s["stride"] + 1
            out.append(dict(s, ifm=(res, res, ch), ofm=(o, o, ch), w=None))
            res = o
        else:
            oc = _width(s["out"], cfg["width_mult"], keep=s["out"] == 1000)
            fan_in = res * res * ch
            out.append(dict(s, ifm=(1, 1, fan_in), ofm=(1, 1, oc),
                            w=(fan_in, oc)))
            res, ch = 1, oc
    return out


def _key(seed: int, salt: int):
    state = np.random.SeedSequence([seed, salt]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


def _quantize(w):
    amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, wshapes: tuple, int8: bool):
    params = []
    for k, shp in zip(jax.random.split(key, len(wshapes)), wshapes):
        if shp is None:
            params.append({})
            continue
        kw, kb = jax.random.split(k)
        fan_in = int(np.prod(shp[:-1]))
        w = jax.random.normal(kw, shp, jnp.float32) * (2.0 / fan_in) ** 0.5
        b = 0.05 * jax.random.normal(kb, (shp[-1],), jnp.float32)
        name = "f" if len(shp) == 4 else "w"
        params.append({name: _quantize(w) if int8 else w, "b": b})
    return params


def make(cfg: dict, seed: int) -> list[dict]:
    """The weights of ``cfg`` for ``seed``.  A layer's weight is a float32
    array, or for an int8 configuration a ``(q, scale)`` pair."""
    wshapes = tuple(None if s["w"] is None else tuple(s["w"])
                    for s in shapes(cfg))
    return _make(_key(seed, 0), wshapes, cfg["weights"] == "int8")


def for_reference(params: list[dict]) -> list[dict]:
    """The same weights as float32 arrays: ``q * scale`` for int8."""
    out = []
    for p in params:
        d = {}
        for k, v in p.items():
            d[k] = v[0].astype(jnp.float32) * v[1] if isinstance(v, tuple) \
                else v
        out.append(d)
    return out


def images(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` distinct standard-normal images of the configuration's
    resolution, the pool every request of a run draws from."""
    rng = np.random.default_rng([seed, 1])
    return rng.standard_normal((n, cfg["in_res"], cfg["in_res"],
                                cfg["in_ch"]), dtype=np.float32)
