"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

Keys of a mix:

* ``loop``: ``"closed"`` (one client hands ``chunk`` images to each
  ``serve()`` call and sends the next chunk when it returns) or
  ``"open"`` (clumps of ``clump`` requests due at Poisson instants at
  ``rate_per_s`` clumps per second, whatever the server does);
* ``pool``: how many distinct seeded images the requests draw from;
* ``server``: ``{"kind": "zoo"}`` or ``{"kind": "fleet", "replicas": n,
  "shard_waves": bool}``.

Every seed gets the same inter-arrival gaps, drawn once from a fixed
stream and scaled to fill the window exactly, in an order drawn from the
seed within each stretch of ``SHUFFLE_S`` seconds: every half second of
every run offers the same load, and only the order inside it and the
images change.  The gaps are exponential inter-arrivals, as
``benchmarks/timing.py``'s ``poisson_arrivals`` draws them.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

#: the stream the gap set is drawn from: fixed, so it is not the run's seed
GAP_STREAM = 20181030
#: the seed reorders gaps only inside stretches of this many seconds
SHUFFLE_S = 0.5


@dataclass(frozen=True)
class Mix:
    name: str
    loop: str
    pool: int
    server: dict = field(default_factory=lambda: {"kind": "zoo"})
    chunk: int = 0
    rate_per_s: float = 0.0
    clump: int = 1

    @property
    def replicas(self) -> int:
        return int(self.server.get("replicas", 1))


def load(path: Path) -> Mix:
    d = json.loads(Path(path).read_text())
    mix = Mix(name=Path(path).stem, loop=d["loop"], pool=int(d["pool"]),
              server=d.get("server", {"kind": "zoo"}),
              chunk=int(d.get("chunk", 0)),
              rate_per_s=float(d.get("rate_per_s", 0.0)),
              clump=int(d.get("clump", 1)))
    if mix.loop == "closed" and mix.chunk < 1:
        raise ValueError(f"{path}: a closed loop needs chunk >= 1")
    if mix.loop == "open" and (mix.rate_per_s <= 0 or mix.clump < 1):
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0 "
                         "and clump >= 1")
    if mix.loop not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return mix


def poisson_gaps(n: int, rate_hz: float, rng) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at ``rate_hz``."""
    return rng.exponential(1.0 / rate_hz, size=n)


def due_times(mix: Mix, seconds: float, seed: int) -> np.ndarray:
    """Due time of every request of an open loop, in seconds from the
    window's start, sorted; a clump's requests share one due time."""
    n = max(1, round(mix.rate_per_s * seconds))
    gaps = poisson_gaps(n, mix.rate_per_s, np.random.default_rng(GAP_STREAM))
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng([seed, 2])
    block = max(1, round(mix.rate_per_s * SHUFFLE_S))
    gaps = np.concatenate([rng.permutation(gaps[i:i + block])
                           for i in range(0, n, block)])
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return np.repeat(starts, mix.clump)


def image_ids(n: int, pool: int, seed: int, salt: int = 3) -> np.ndarray:
    """Which pool image each of ``n`` requests carries."""
    return np.random.default_rng([seed, salt]).integers(0, pool, size=n)
