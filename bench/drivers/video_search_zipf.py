"""Driver for configurations of kind ``video_search_zipf``: many resident
tenants searched under skewed popularity, through the same served path
as ``video_search`` (``MicrobatchScheduler`` → ``VideoSearchServer``).

A unit of traffic is ``unit_requests`` requests, submitted back to back
by one thread: their tenants drawn with replacement from Zipf(``zipf_s``)
over popularity rank (tenant 0 the hottest), their streams from the pool
in whole permutations, so no two requests of a unit share a stream.  A
hot tenant may appear twice in a unit; every unit is a composition of
tenants, rows per pool group and arena offsets that the server has most
likely not seen before, and must be served by a program compiled in
set-up.  With ``max_batch`` equal to ``unit_requests`` every batch is one
unit, as the harness checks.

The cell needs a program that takes a batch's composition as runtime
data, with each pool group's gratings in one resident arena
(``QueryEngine.set_resident``).  On a program without that, set-up stops
with an error before any work: such a program compiles for nearly every
batch of this traffic and would answer next to nothing in the window.
"""

from __future__ import annotations

import numpy as np

from bench import traffic
from bench.drivers import video_search


class ZipfSchedule:
    """Which (tenant, pool entry) pairs each unit sends.  Deterministic
    per (mix, seed): tenants and streams come from streams of randomness
    of their own, drawn unit by unit in order."""

    def __init__(self, mix: dict, seed: int, n_tenants: int):
        self.n_tenants = int(n_tenants)
        self.per_unit = int(mix["unit_requests"])
        self.pool = int(mix["pool"])
        if self.pool % self.per_unit:
            raise ValueError(
                f"a unit reads {self.per_unit} pool entries; the pool "
                f"({self.pool}) must be a multiple of that"
            )
        rank = np.arange(1, self.n_tenants + 1, dtype=np.float64)
        weight = rank ** -float(mix["zipf_s"])
        self.popularity = weight / weight.sum()
        self._tenant_rng = traffic.rng(seed, 6)
        self._perm_rng = traffic.rng(seed, 1)
        self._units: list[list[tuple[int, int]]] = []
        self._order: list[int] = []

    def _entry(self, j: int) -> int:
        """The j-th pool entry drawn: whole permutations of the pool, one
        after another; a unit's entries lie inside one permutation."""
        while len(self._order) <= j:
            self._order.extend(int(i) for i in self._perm_rng.permutation(self.pool))
        return self._order[j]

    def unit(self, i: int) -> list[tuple[int, int]]:
        """Requests of unit i as (tenant, pool entry) pairs."""
        while len(self._units) <= i:
            u = len(self._units)
            tenants = self._tenant_rng.choice(
                self.n_tenants, size=self.per_unit, p=self.popularity
            )
            self._units.append([
                (int(t), self._entry(u * self.per_unit + r))
                for r, t in enumerate(tenants)
            ])
        return self._units[i]


class Cell(video_search.Cell):

    def __init__(self, cfg: dict, mix: dict, seed: int, log=print):
        super().__init__(cfg, mix, seed, log=log)
        self.schedule = ZipfSchedule(mix, seed, self.n_tenants)
        self.pool_window: tuple[dict | None, dict | None] = (None, None)

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from repro.core.engine import QueryEngine

        if not hasattr(QueryEngine, "set_resident"):
            raise RuntimeError(
                "this program has no resident arenas and compiles its pooled "
                "stream path per batch composition; the cell serves a new "
                "composition on nearly every batch"
            )
        super().setup()  # also serves two units of the schedule
        self._warm_buckets()
        self.batches.clear()
        self.requests.clear()
        stats = self.server.sthc.engine.pool_stats()
        self.log(f"set-up: {stats['stream_traces']} pooled stream traces, "
                 f"{stats['arena_builds']} arena builds")

    def _warm_buckets(self) -> None:
        """Serve, for each pool group alone, batches of every size from 1
        to ``max_batch`` requests of distinct tenants on distinct
        streams: every (pool group, row bucket) program the window can
        need is compiled here, and every padded batch shape served once,
        whatever mix the window's units bring."""
        max_batch = int(traffic.settings(self.cfg, self.mix, "scheduler")
                        .get("max_batch", self.schedule.per_unit))
        by_fid: dict[str, list[int]] = {}
        for t, fid in enumerate(self.fids):
            by_fid.setdefault(fid["name"], []).append(t)
        for tenants in by_fid.values():
            for n in range(1, max_batch + 1):
                futs = [
                    self.sched.submit(
                        self.names[tenants[r % len(tenants)]],
                        self.streams[r % len(self.streams)], block=True,
                    )
                    for r in range(n)
                ]
                for fut in futs:
                    fut.result(timeout=1200)

    # -- the measured window -----------------------------------------------

    def run(self, seconds: float) -> None:
        engine = self.server.sthc.engine
        before = engine.pool_stats()
        super().run(seconds)
        after = engine.pool_stats()
        self.pool_window = (before, after)
        self.log(
            "pooled stream traces in window: "
            f"{after['stream_traces'] - before['stream_traces']}, arena "
            f"builds: {after['arena_builds'] - before['arena_builds']}, "
            f"rows: {after['rows_dispatched'] - before['rows_dispatched']} "
            f"carrying a request, {after['rows_padded'] - before['rows_padded']}"
            " padding"
        )

    # -- correctness --------------------------------------------------------

    def sample(self) -> list:
        """Requests to check, drawn from the seed: half on ideal tenants,
        half on physical ones, each of a different tenant where the
        window answered enough of them — tenants drawn uniformly, so cold
        tenants, whose arena slots a hot-only sample never reads, are
        checked too."""
        n = int(self.cfg["check_requests"])
        rng = traffic.rng(self.seed, 4)
        by_fid: dict[str, dict[int, list]] = {}
        for r in self.in_window():
            if r.result is not None:
                by_fid.setdefault(self.fids[r.tenant]["name"], {}) \
                    .setdefault(r.tenant, []).append(r)
        picked = []
        for by_tenant in by_fid.values():
            want = max(1, n // len(by_fid))
            tenants = sorted(by_tenant)
            order = [tenants[i] for i in rng.permutation(len(tenants))]
            # distinct tenants first; a tenant again only when the window
            # answered fewer tenants than the sample wants
            for t in (order * want)[:want]:
                reqs = [r for r in by_tenant[t]
                        if all(r is not p for p in picked)]
                if reqs:
                    picked.append(reqs[int(rng.integers(len(reqs)))])
        return picked
