"""Self-metrics: counters / gauges / histograms with Prometheus text
exposition.

Role of the reference's Prometheus self-monitoring (mixer/pkg/runtime/
monitor.go:34-88, pilot discovery.go:53-113). Host-side only — device-side
perf comes from `benchmark/` (a profiler trace on the chip).
"""
from __future__ import annotations

import bisect
import collections
import itertools
import threading
from typing import Iterable

_DEFAULT_BUCKETS = (
    1e-6, 2.5e-6, 5e-6, 1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
    1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)


def _label_key(labels: dict[str, str] | None) -> tuple:
    return tuple(sorted((labels or {}).items()))


_LABEL_ESCAPES = str.maketrans({"\\": r"\\", '"': r"\"", "\n": r"\n"})


def _fmt_labels(key: tuple) -> str:
    """A label value escaped as the text format asks (backslash, double
    quote, newline): a DFA bank's subject is an expression and holds
    quotes and commas."""
    if not key:
        return ""
    return "{" + ",".join(
        f'{k}="{str(v).translate(_LABEL_ESCAPES)}"' for k, v in key) + "}"


def quantile_from_counts(buckets: tuple[float, ...],
                         counts: list[int], n: int, q: float) -> float:
    """Quantile (bucket upper bound) from a per-bucket count vector —
    shared by Histogram.quantile and delta-window readers that
    subtract two Histogram.state() snapshots."""
    if not counts or n <= 0:
        return 0.0
    target = q * n
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= target:
            return buckets[i] if i < len(buckets) else float("inf")
    return float("inf")


class Counter:
    def __init__(self, name: str, help_: str = ""):
        self.name, self.help = name, help_
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: str) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def label_sets(self) -> list[dict]:
        with self._lock:
            return [dict(k) for k in self._values]

    def expose(self) -> Iterable[str]:
        if self.help:
            yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} counter"
        with self._lock:
            snapshot = sorted(self._values.items())
        for key, v in snapshot:
            yield f"{self.name}{_fmt_labels(key)} {v}"


class Gauge:
    def __init__(self, name: str, help_: str = ""):
        self.name, self.help = name, help_
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels: str) -> None:
        with self._lock:
            self._values[_label_key(labels)] = value

    def value(self, **labels: str) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def label_sets(self) -> list[dict]:
        with self._lock:
            return [dict(k) for k in self._values]

    def expose(self) -> Iterable[str]:
        if self.help:
            yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} gauge"
        with self._lock:
            snapshot = sorted(self._values.items())
        for key, v in snapshot:
            yield f"{self.name}{_fmt_labels(key)} {v}"


class Histogram:
    def __init__(self, name: str, help_: str = "",
                 buckets: tuple[float, ...] = _DEFAULT_BUCKETS):
        self.name, self.help = name, help_
        self._buckets = tuple(sorted(buckets))
        self._counts: dict[tuple, list[int]] = {}
        self._sum: dict[tuple, float] = {}
        self._n: dict[tuple, int] = {}
        # reentrant: a gc callback observes (monitor._on_gc), and a
        # collection can start on a thread that is inside this
        # histogram's own state(): a plain lock would have that thread
        # wait for itself
        self._lock = threading.RLock()

    def observe(self, value: float, **labels: str) -> None:
        self.observe_key(_label_key(labels), value)

    def observe_key(self, key: tuple, value: float, n: int = 1) -> None:
        """observe() for a caller that keeps its label key (the
        sorted (name, value) pairs) — per-batch span sites must not
        rebuild and sort it on every observation. `n`: that many
        observations of the one value (a batch's rows share its
        wall), as n calls would count them."""
        idx = bisect.bisect_left(self._buckets, value)
        with self._lock:
            if key not in self._counts:
                self._counts[key] = [0] * (len(self._buckets) + 1)
                self._sum[key] = 0.0
                self._n[key] = 0
            self._counts[key][idx] += n
            self._sum[key] += value * n
            self._n[key] += n

    @property
    def buckets(self) -> tuple[float, ...]:
        return self._buckets

    def count(self, **labels: str) -> int:
        return self._n.get(_label_key(labels), 0)

    def sum(self, **labels: str) -> float:
        return self._sum.get(_label_key(labels), 0.0)

    def label_sets(self) -> list[dict]:
        with self._lock:
            return [dict(k) for k in self._counts]

    def state(self, **labels: str) -> tuple[list[int], float, int]:
        """(per-bucket counts copy, sum, n) for one label set — the
        subtraction token for windowed readings: two states taken
        around a phase delta to that phase's own histogram (histograms
        are process-lifetime cumulative by design)."""
        key = _label_key(labels)
        with self._lock:
            counts = list(self._counts.get(key, ()))
            return (counts, self._sum.get(key, 0.0),
                    self._n.get(key, 0))

    def quantile(self, q: float, **labels: str) -> float:
        """Approximate quantile from bucket counts (upper bound of the
        bucket containing the q-th observation)."""
        counts, _, n = self.state(**labels)
        return quantile_from_counts(self._buckets, counts, n, q)

    def expose(self) -> Iterable[str]:
        if self.help:
            yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} histogram"
        with self._lock:
            items = sorted((k, list(v), self._sum[k], self._n[k])
                           for k, v in self._counts.items())
        if not items:
            # exposition conformance: a histogram with no observations
            # must still emit its full zero series (_bucket ladder with
            # le="+Inf", _sum, _count) — scrapers treat a bare # TYPE
            # line with no samples as a malformed family
            items = [((), [0] * (len(self._buckets) + 1), 0.0, 0)]
        for key, counts, total, n in items:
            cum = 0
            for i, c in enumerate(counts[:-1]):
                cum += c
                lk = dict(key)
                lk["le"] = repr(self._buckets[i])
                yield f"{self.name}_bucket{_fmt_labels(_label_key(lk))} {cum}"
            lk = dict(key)
            lk["le"] = "+Inf"
            yield f"{self.name}_bucket{_fmt_labels(_label_key(lk))} {n}"
            yield f"{self.name}_sum{_fmt_labels(key)} {total}"
            yield f"{self.name}_count{_fmt_labels(key)} {n}"


class SlidingWindow:
    """Rolling window over the last `capacity` observations with exact
    quantiles computed on read (the live-p99 counterpart of Histogram's
    bucket-bounded quantile()). observe() is hot-path cheap (deque
    append under a lock); quantile() sorts a snapshot and is meant for
    scrape-rate readers (the introspect server)."""

    def __init__(self, capacity: int = 4096):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self._buf: collections.deque[float] = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self._total = 0

    def observe(self, value: float, n: int = 1) -> None:
        """`n` observations of the one value: the window holds its
        capacity at most, so no more copies than that are appended."""
        with self._lock:
            self._buf.extend(
                itertools.repeat(value, min(n, self._buf.maxlen)))
            self._total += n

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    @property
    def total(self) -> int:
        """Observations ever seen (not just the ones still windowed)."""
        with self._lock:
            return self._total

    def reset(self) -> None:
        with self._lock:
            self._buf.clear()

    def quantile(self, q: float) -> float:
        qs = self.quantiles((q,))
        return qs[0]

    def quantiles(self, qs: Iterable[float]) -> list[float]:
        """Exact quantiles over the current window (one sort for all of
        them); empty window → zeros."""
        with self._lock:
            data = sorted(self._buf)
        if not data:
            return [0.0 for _ in qs]
        n = len(data)
        return [data[min(int(q * n), n - 1)] for q in qs]


class Registry:
    """Collects metrics for a /metrics endpoint (reference: mixer
    monitoring server on :9093, mixer/pkg/server/monitoring.go)."""

    def __init__(self) -> None:
        self._metrics: list[Counter | Gauge | Histogram] = []
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        m = Counter(name, help_)
        with self._lock:
            self._metrics.append(m)
        return m

    def gauge(self, name: str, help_: str = "") -> Gauge:
        m = Gauge(name, help_)
        with self._lock:
            self._metrics.append(m)
        return m

    def histogram(self, name: str, help_: str = "",
                  buckets: tuple[float, ...] = _DEFAULT_BUCKETS) -> Histogram:
        m = Histogram(name, help_, buckets)
        with self._lock:
            self._metrics.append(m)
        return m

    def expose_text(self) -> str:
        lines: list[str] = []
        with self._lock:
            for m in self._metrics:
                lines.extend(m.expose())
        return "\n".join(lines) + "\n"


default_registry = Registry()
