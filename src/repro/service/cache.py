"""LRU result cache for RLC query answers.

Keys are ``(s, t, mr_id)`` triples; values are booleans — *both* positive
and negative answers are cached (a false reachability answer is exactly as
expensive to recompute as a true one; the index is immutable between
rebuilds/deltas, so staleness is driven by explicit invalidation, not
time — but an optional TTL is available for deployments that prefer
bounded staleness over precise invalidation). Hit/miss/eviction counters
feed the service stats and the Zipf-workload benchmark.

Graphs became mutable with the delta-build engine
(:mod:`repro.build.delta`): a delta changes the answers of exactly the
queries whose source row (``L_out(s)``) or target row (``L_in(t)``) went
dirty, so :meth:`ResultCache.invalidate_rows` evicts only those keys and
every other cached answer survives.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

from repro.obs import NULL_OBS, Reservoir

Key = Tuple[int, int, int]  # (s, t, mr_id)


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    expirations: int = 0
    invalidations: int = 0
    # per-MR-length (hits, misses) — the warming-priority input: MR
    # lengths that miss more benefit more from pre-materialization
    by_mr_len: Dict[int, Tuple[int, int]] = field(default_factory=dict)

    @property
    def lookups(self) -> int:
        # expirations are lookups too (an entry was found but stale) —
        # they dilute the hit rate without counting as plain misses
        return self.hits + self.misses + self.expirations

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def record(self, mr_len: Optional[int], hit: bool, n: int = 1) -> None:
        if mr_len is None:
            return
        h, m = self.by_mr_len.get(mr_len, (0, 0))
        self.by_mr_len[mr_len] = (h + n, m) if hit else (h, m + n)

    def hit_rate_by_mr_len(self) -> Dict[int, float]:
        return {ln: h / (h + m) if h + m else 0.0
                for ln, (h, m) in sorted(self.by_mr_len.items())}

    def as_dict(self) -> dict:
        return dict(hits=self.hits, misses=self.misses,
                    evictions=self.evictions,
                    expirations=self.expirations,
                    invalidations=self.invalidations,
                    hit_rate=self.hit_rate,
                    hit_rate_by_mr_len=self.hit_rate_by_mr_len())


class ResultCache:
    """Bounded LRU mapping ``(s, t, mr_id) -> bool``.

    ``ttl_s``: optional time-to-live; an entry older than this counts as
    a miss (and is evicted) on lookup. ``clock`` is injectable for
    tests.
    """

    def __init__(self, capacity: int, ttl_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic, obs=None):
        if capacity < 0:
            raise ValueError(f"capacity must be >= 0, got {capacity}")
        if ttl_s is not None and ttl_s <= 0:
            raise ValueError(f"ttl_s must be > 0, got {ttl_s}")
        self.capacity = capacity
        self.ttl_s = ttl_s
        self.clock = clock
        self._d: "OrderedDict[Key, Tuple[bool, float]]" = OrderedDict()
        self.stats = CacheStats()
        # registry cells mirroring CacheStats (the registry survives
        # service-internal resets and feeds the exporters)
        self.obs = obs or NULL_OBS
        reg = self.obs.registry
        look = reg.counter("rlc_cache_lookups",
                           desc="result-cache lookups by outcome",
                           labelnames=("outcome",))
        self._m_look = {o: look.labels(outcome=o)
                        for o in ("hit", "miss", "expired")}
        self._m_evict = reg.counter(
            "rlc_cache_evictions",
            desc="LRU capacity evictions").labels()
        self._m_inval = reg.counter(
            "rlc_cache_invalidations",
            desc="entries dropped by invalidate_rows/clear").labels()
        self._m_size = reg.gauge("rlc_cache_size",
                                 desc="entries currently cached").labels()
        self._m_mr = reg.counter(
            "rlc_cache_mr_lookups",
            desc="result-cache lookups by outcome and MR length",
            labelnames=("outcome", "mr_len"))
        self._m_evict_age = reg.histogram(
            "rlc_cache_eviction_age_seconds",
            desc="entry age (insert -> LRU eviction) at capacity "
                 "eviction", unit="s").labels()
        # standalone reservoir so eviction_age_summary works with the
        # null registry too (warming reads it without telemetry on)
        self.eviction_ages = Reservoir()

    def __len__(self) -> int:
        return len(self._d)

    def get(self, key: Key, mr_len: Optional[int] = None) -> Optional[bool]:
        """Answer if cached and fresh (refreshing recency), else ``None``."""
        found: Dict[Tuple[str, Optional[int]], int] = {}
        val = self.probe(key, found, mr_len)
        self.count({mr_len: 1}, found)
        return val

    def probe(self, key: Key, found: Dict[Tuple[str, Optional[int]], int],
              mr_len: Optional[int] = None) -> Optional[bool]:
        """:meth:`get` for one of a run of lookups, counted afterwards: a
        hit or an expiry is tallied in ``found`` (``(outcome, mr_len) ->
        n``), a miss not at all; :meth:`count` adds the run to the stats
        and counters in one go."""
        pair = self._d.get(key) if self.capacity else None
        if pair is None:
            return None
        if self.ttl_s is not None and self.clock() - pair[1] >= self.ttl_s:
            # expired is its own outcome: the lookup found a (stale)
            # entry, so it is neither a hit nor a plain miss — it still
            # dilutes hit_rate via CacheStats.lookups
            del self._d[key]
            outcome, val = "expired", None
        else:
            self._d.move_to_end(key)
            outcome, val = "hit", pair[0]
        k = (outcome, mr_len)
        found[k] = found.get(k, 0) + 1
        return val

    def count(self, lookups: Dict[Optional[int], int],
              found: Dict[Tuple[str, Optional[int]], int]) -> None:
        """Add a run of :meth:`probe` calls to the stats and the
        ``rlc_cache_lookups`` / ``rlc_cache_mr_lookups`` counters:
        ``lookups`` is the number of probes by MR length, ``found`` their
        hits and expiries; the rest missed."""
        misses = dict(lookups)
        for (outcome, mr_len), n in found.items():
            misses[mr_len] -= n
            self._count(outcome, mr_len, n)
        for mr_len, n in misses.items():
            if n:
                self._count("miss", mr_len, n)

    def _count(self, outcome: str, mr_len: Optional[int], n: int) -> None:
        st = self.stats
        if outcome == "hit":
            st.hits += n
        elif outcome == "miss":
            st.misses += n
        else:
            st.expirations += n
        st.record(mr_len, outcome == "hit", n)
        self._m_look[outcome].inc(n)
        if mr_len is not None:
            self._m_mr.labels(outcome=outcome, mr_len=mr_len).inc(n)

    def peek(self, key: Key) -> Optional[bool]:
        """Non-mutating probe: the cached answer if present and fresh,
        else ``None``. No recency refresh, no stats, no counters —
        EXPLAIN's cache-disposition probe must not perturb the serving
        LRU or the hit-rate series it reports on."""
        if self.capacity == 0:
            return None
        pair = self._d.get(key)
        if pair is None:
            return None
        val, stamp = pair
        if self.ttl_s is not None and self.clock() - stamp >= self.ttl_s:
            return None
        return val

    def put(self, key: Key, value: bool,
            mr_len: Optional[int] = None) -> None:
        if self.capacity == 0:
            return
        if key in self._d:
            self._d.move_to_end(key)
        now = self.clock()
        self._d[key] = (bool(value), now)
        while len(self._d) > self.capacity:
            _k, (_v, stamp) = self._d.popitem(last=False)
            self.stats.evictions += 1
            self._m_evict.inc()
            age = max(now - stamp, 0.0)
            self.eviction_ages.add(age)
            self._m_evict_age.observe(age)
        self._m_size.set(len(self._d))

    def hit_rate_by_mr_len(self) -> Dict[int, float]:
        """Per-MR-length hit rates — the warmer's priority input."""
        return self.stats.hit_rate_by_mr_len()

    def eviction_age_summary(self) -> dict:
        """Percentiles of entry age at LRU eviction: a low p50 means the
        capacity is churning entries before they can be re-hit."""
        return self.eviction_ages.summary()

    def invalidate_rows(self, dirty_s=None, dirty_t=None) -> int:
        """Evict every key whose source row is in ``dirty_s`` or target
        row is in ``dirty_t`` (containers supporting ``in``); returns the
        eviction count. The targeted flavor of :meth:`clear` for delta
        updates: untouched keys keep serving."""
        dirty_s = dirty_s if dirty_s is not None else ()
        dirty_t = dirty_t if dirty_t is not None else ()
        doomed = [k for k in self._d
                  if k[0] in dirty_s or k[1] in dirty_t]
        for k in doomed:
            del self._d[k]
        self.stats.invalidations += len(doomed)
        self._m_inval.inc(len(doomed))
        self._m_size.set(len(self._d))
        return len(doomed)

    def clear(self) -> None:
        self.stats.invalidations += len(self._d)
        self._m_inval.inc(len(self._d))
        self._d.clear()
        self._m_size.set(0)
