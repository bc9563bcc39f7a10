"""The one traffic generator.  A mix is a data file,
``bench/traffic/<name>.json``; nothing here knows a mix by name.

Traffic is a closed loop: ``outstanding`` units in flight, the next
sent when the oldest completes.  A unit is one request per tenant of
the configuration (one request where the configuration has no tenants),
submitted together.

Keys of a mix:

- ``outstanding``: units in flight.
- ``share``: each run of ``share`` consecutive tenants of a unit reads
  one pool entry.  ``share`` equal to the tenant count: every tenant
  searches the same stream (fan-out); 1: every request has a stream of
  its own.  Default 1.
- ``pool``: entries (streams, or batches of ``clips_per_request``
  clips) rendered at set-up; units draw from it in an order drawn from
  the seed, whole permutations one after another.
- ``server``, ``scheduler`` (optional): settings laid over the
  configuration's own keys of the same names, e.g. a buffer length.

Every seed gets the same work; only its order and the rendered content
differ.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    validate(mix)
    return mix


def validate(mix: dict) -> None:
    if int(mix.get("share", 1)) < 1:
        raise ValueError("share must be >= 1")
    if int(mix["outstanding"]) < 1:
        raise ValueError("a closed loop needs outstanding >= 1")


def settings(cfg: dict, mix: dict, key: str) -> dict:
    """The configuration's ``key`` settings with the mix's laid over."""
    return {**cfg.get(key, {}), **mix.get(key, {})}


def rng(seed: int, *tags: int) -> np.random.Generator:
    """An independent stream of randomness per (seed, tags); takes any
    non-negative integer seed, however large."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tags]))


class Schedule:
    """Which pool entries each unit uses.  Deterministic per (mix, seed)."""

    def __init__(self, mix: dict, seed: int, n_tenants: int = 1):
        self.mix = mix
        self.seed = int(seed)
        self.n_tenants = int(n_tenants)
        self.share = min(int(mix.get("share", 1)), self.n_tenants)
        self.per_unit = -(-self.n_tenants // self.share)  # entries per unit
        self.pool = int(mix["pool"])
        if self.pool % self.per_unit:
            raise ValueError(
                f"a unit reads {self.per_unit} pool entries; the pool "
                f"({self.pool}) must be a multiple of that"
            )
        self._order: list[int] = []
        self._perm_rng = rng(self.seed, 1)

    def _entry(self, j: int) -> int:
        """The j-th pool entry drawn: whole permutations of the pool, one
        after another, so every entry is used equally often and a unit
        never reads one entry twice."""
        while len(self._order) <= j:
            self._order.extend(int(i) for i in self._perm_rng.permutation(self.pool))
        return self._order[j]

    def unit(self, i: int) -> list[tuple[int, int]]:
        """Requests of unit i as (tenant, pool entry) pairs."""
        return [(t, self._entry(i * self.per_unit + t // self.share))
                for t in range(self.n_tenants)]
