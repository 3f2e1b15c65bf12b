"""Operations and compulsory bytes of each layer, from a configuration's shapes.

A conv layer is counted with the pool that follows it, as one fused
dispatch: it must read its input feature map and its weights once and
write the pooled map once.  An FC layer must read its input rows, stream
its weight matrix once at the served width (4 bytes, or 1 byte plus a
float32 scale per output channel for int8) and write its output rows.
Activations are float32.  Operations are 2 per multiply-accumulate.
"""
from __future__ import annotations

from bench.weights import shapes

ACT_BYTES = 4


def layers(cfg: dict, batch: int) -> list[dict]:
    """``[{"name", "kind", "macs", "flops", "bytes"}]`` for the conv and FC
    layers of ``cfg`` at ``batch`` images."""
    wbytes = 1 if cfg["weights"] == "int8" else 4
    sh = shapes(cfg)
    out: list[dict] = []
    n = {"conv": 0, "fc": 0}
    for i, s in enumerate(sh):
        if s["kind"] == "pool":
            continue
        n[s["kind"]] += 1
        h, w, c = s["ifm"]
        oh, ow, oc = s["ofm"]
        if s["kind"] == "conv":
            k = s["k"]
            macs = oh * ow * oc * k * k * c
            nxt = sh[i + 1] if i + 1 < len(sh) else None
            if nxt is not None and nxt["kind"] == "pool":
                oh, ow, oc = nxt["ofm"]
            weights = k * k * c * s["ofm"][2]
        else:
            macs = c * oc
            weights = c * oc
        extra = s["ofm"][2] * (4 + (4 if wbytes == 1 else 0))  # bias, scale
        out.append(dict(
            name=f"{s['kind']}{n[s['kind']]}", kind=s["kind"],
            macs=macs, flops=2 * macs * batch,
            bytes=(batch * (h * w * c + oh * ow * oc) * ACT_BYTES
                   + weights * wbytes + extra)))
    return out


def flops_per_image(cfg: dict) -> int:
    return sum(layer["flops"] for layer in layers(cfg, 1))


def least_seconds(cfg: dict, batch: int, kind: str, peak: dict) -> float:
    """The least time the chip could take for the ``kind`` ("conv" or
    "fc") layers of one wave of ``batch`` images: per layer the larger of
    operations over peak FLOP/s and compulsory bytes over HBM bandwidth."""
    return sum(max(layer["flops"] / peak["mxu_flops_per_s"],
                   layer["bytes"] / peak["hbm_bytes_per_s"])
               for layer in layers(cfg, batch) if layer["kind"] == kind)
