"""Thread-safe, constant-memory metrics registry (ISSUE 8 tentpole).

Three instrument kinds behind one :class:`MetricsRegistry`:

* **counter** — monotone float/int accumulator (``inc``).
* **gauge** — last-write-wins value (``set``).
* **histogram** — log-bucketed latency/size distribution with constant
  memory per child: values land in geometric buckets spaced
  ``GROWTH = 2**(1/8)`` apart (~9% max relative quantile error from bucket
  midpoints), plus exact ``count``/``sum``/``min``/``max``. Quantiles
  geometric-interpolate inside the crossing bucket and clamp to the
  observed [min, max], so single-valued and narrow distributions report
  exact percentiles.

Every family can carry label dimensions (``labels=("engine", ...)``);
children materialize lazily per label-value tuple and all mutation goes
through one registry lock (the instruments are far off any kernel hot path
— the service touches them a handful of times per *batch*, not per row).

Exposition:

* :meth:`MetricsRegistry.render_prometheus` — Prometheus text format
  (histograms as cumulative ``_bucket{le=...}`` + ``_sum`` / ``_count``).
* :meth:`MetricsRegistry.export_jsonl` — one JSON line per child with the
  quantile summary and sparse bucket map; ``benchmarks/check_obs_schema.py``
  (via :mod:`repro.obs.schema`) validates the shape.

``NULL_METRICS`` is a no-op registry with the same surface — the
``metrics=False`` service path swaps it in so call sites never branch.
"""
from __future__ import annotations

import json
import math
import threading
from pathlib import Path

import numpy as np

GROWTH = 2.0 ** 0.125          # 8 buckets per doubling (~9% quantile error)
_LOG_GROWTH = math.log(GROWTH)
HIST_LO = 1e-3                 # smallest resolved value (ms space: 1 us)
HIST_HI = 1e8                  # largest  (ms space: ~28 h)
N_BUCKETS = int(math.ceil(math.log(HIST_HI / HIST_LO) / _LOG_GROWTH)) + 1


def _bucket_index(value: float) -> int:
    if value <= HIST_LO:
        return 0
    i = int(math.log(value / HIST_LO) / _LOG_GROWTH) + 1
    return min(i, N_BUCKETS - 1)


def bucket_upper(i: int) -> float:
    """Upper bound of bucket ``i`` (``le`` edge in the exposition)."""
    if i >= N_BUCKETS - 1:
        return float("inf")
    return HIST_LO * GROWTH ** i


class _Child:
    __slots__ = ("labels",)

    def __init__(self, labels: dict):
        self.labels = labels


class _Counter(_Child):
    __slots__ = ("value",)
    kind = "counter"

    def __init__(self, labels):
        super().__init__(labels)
        self.value = 0.0


class _Gauge(_Child):
    __slots__ = ("value",)
    kind = "gauge"

    def __init__(self, labels):
        super().__init__(labels)
        self.value = 0.0


class _Histogram(_Child):
    __slots__ = ("buckets", "count", "sum", "min", "max")
    kind = "histogram"

    def __init__(self, labels):
        super().__init__(labels)
        self.buckets = np.zeros(N_BUCKETS, dtype=np.int64)
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    def _observe(self, value: float) -> None:
        self.buckets[_bucket_index(value)] += 1
        self.count += 1
        self.sum += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value


def quantile_from_buckets(buckets: np.ndarray, count: int, q: float,
                          lo: float | None = None,
                          hi: float | None = None) -> float | None:
    """Estimate the ``q``-quantile from log-bucket counts: find the bucket
    the target rank lands in, geometric-interpolate inside it, clamp to the
    observed [lo, hi] when given. ``None`` when empty."""
    if count <= 0:
        return None
    target = max(1, int(math.ceil(q * count)))
    cum = 0
    for i, c in enumerate(buckets):
        if not c:
            continue
        if cum + c >= target:
            frac = (target - cum) / c
            b_lo = HIST_LO * GROWTH ** (i - 1) if i > 0 else 0.0
            b_hi = HIST_LO * GROWTH ** i
            est = b_lo + (b_hi - b_lo) * frac
            if lo is not None:
                est = max(est, lo)
            if hi is not None:
                est = min(est, hi)
            return est
        cum += c
    return hi


class Family:
    """One named metric family; holds the per-label-tuple children."""

    _KINDS = {"counter": _Counter, "gauge": _Gauge, "histogram": _Histogram}

    def __init__(self, registry, name: str, kind: str, help: str,
                 label_names: tuple):
        self._registry = registry
        self.name = name
        self.kind = kind
        self.help = help
        self.label_names = tuple(label_names)
        self._children: dict[tuple, _Child] = {}

    def _child(self, label_values: tuple) -> _Child:
        c = self._children.get(label_values)
        if c is None:
            c = self._KINDS[self.kind](dict(zip(self.label_names,
                                                label_values)))
            self._children[label_values] = c
        return c

    def _resolve(self, labels: dict) -> _Child:
        if set(labels) != set(self.label_names):
            raise ValueError(
                f"{self.name}: labels {sorted(labels)} != declared "
                f"{sorted(self.label_names)}")
        return self._child(tuple(str(labels[n]) for n in self.label_names))

    # -- mutation (each takes the registry lock) ----------------------------
    def touch(self, **labels) -> None:
        """Materialize a labeled child at its zero value without recording
        an event — pre-seeding known label sets so exports (and
        required-family CI floors) see the family before the first hit."""
        with self._registry._lock:
            self._resolve(labels)

    def inc(self, value: float = 1.0, **labels) -> None:
        with self._registry._lock:
            self._resolve(labels).value += value

    def set(self, value: float, **labels) -> None:
        with self._registry._lock:
            self._resolve(labels).value = float(value)

    def observe(self, value: float, **labels) -> None:
        with self._registry._lock:
            self._resolve(labels)._observe(float(value))

    # -- read side ----------------------------------------------------------
    def value(self, **labels) -> float:
        """Current value of one counter/gauge child (0 if never touched)."""
        with self._registry._lock:
            c = self._children.get(
                tuple(str(labels[n]) for n in self.label_names))
            return getattr(c, "value", 0.0) if c is not None else 0.0

    def total(self) -> float:
        """Sum of every child's value (counters/gauges)."""
        with self._registry._lock:
            return sum(c.value for c in self._children.values())

    def count(self) -> int:
        """Total observations across children (histograms)."""
        with self._registry._lock:
            return sum(c.count for c in self._children.values()
                       if isinstance(c, _Histogram))

    def quantile(self, q: float, **labels) -> float | None:
        """Aggregate quantile estimate across children (or one child when
        ``labels`` pin it). ``None`` when no observations."""
        with self._registry._lock:
            if labels:
                key = tuple(str(labels[n]) for n in self.label_names)
                kids = [self._children[key]] if key in self._children else []
            else:
                kids = [c for c in self._children.values()
                        if isinstance(c, _Histogram)]
            if not kids:
                return None
            buckets = np.zeros(N_BUCKETS, dtype=np.int64)
            count, lo, hi = 0, math.inf, -math.inf
            for c in kids:
                buckets += c.buckets
                count += c.count
                lo, hi = min(lo, c.min), max(hi, c.max)
            return quantile_from_buckets(buckets, count, q, lo, hi)

    def mean(self, **labels) -> float | None:
        with self._registry._lock:
            kids = [c for c in self._children.values()
                    if isinstance(c, _Histogram)]
            total = sum(c.sum for c in kids)
            count = sum(c.count for c in kids)
            return (total / count) if count else None

    def _reset(self) -> None:
        self._children.clear()


class MetricsRegistry:
    """Create-or-get metric families; render / export the whole set."""

    def __init__(self):
        self._lock = threading.RLock()
        self._families: dict[str, Family] = {}

    enabled = True

    def _family(self, name: str, kind: str, help: str,
                labels: tuple) -> Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = Family(self, name, kind, help, labels)
                self._families[name] = fam
            elif fam.kind != kind or fam.label_names != tuple(labels):
                raise ValueError(
                    f"metric {name!r} re-registered as {kind}/{labels} "
                    f"(was {fam.kind}/{fam.label_names})")
            return fam

    def counter(self, name, help: str = "", labels: tuple = ()):
        return self._family(name, "counter", help, labels)

    def gauge(self, name, help: str = "", labels: tuple = ()):
        return self._family(name, "gauge", help, labels)

    def histogram(self, name, help: str = "", labels: tuple = ()):
        return self._family(name, "histogram", help, labels)

    def family(self, name: str) -> Family | None:
        return self._families.get(name)

    def reset(self) -> None:
        """Zero every child (family declarations survive)."""
        with self._lock:
            for fam in self._families.values():
                fam._reset()

    # -- exposition ---------------------------------------------------------
    def collect(self) -> list[dict]:
        """Snapshot every child as a plain dict (the JSONL line shape)."""
        out = []
        with self._lock:
            for fam in self._families.values():
                for c in fam._children.values():
                    row = {"name": fam.name, "type": fam.kind,
                           "labels": dict(c.labels)}
                    if isinstance(c, _Histogram):
                        row.update(
                            count=int(c.count), sum=float(c.sum),
                            min=(float(c.min) if c.count else None),
                            max=(float(c.max) if c.count else None),
                            p50=quantile_from_buckets(
                                c.buckets, c.count, 0.5, c.min, c.max),
                            p99=quantile_from_buckets(
                                c.buckets, c.count, 0.99, c.min, c.max),
                            buckets={f"{bucket_upper(i):.6g}": int(n)
                                     for i, n in enumerate(c.buckets) if n})
                    else:
                        row["value"] = float(c.value)
                    out.append(row)
        return out

    def render_prometheus(self) -> str:
        lines = []
        with self._lock:
            for fam in self._families.values():
                if not fam._children:
                    continue
                if fam.help:
                    lines.append(f"# HELP {fam.name} {fam.help}")
                lines.append(f"# TYPE {fam.name} {fam.kind}")
                for c in fam._children.values():
                    lab = ",".join(
                        f'{k}="{v}"' for k, v in c.labels.items())
                    if isinstance(c, _Histogram):
                        cum = 0
                        for i, n in enumerate(c.buckets):
                            if not n:
                                continue
                            cum += int(n)
                            le = bucket_upper(i)
                            le_s = "+Inf" if math.isinf(le) else f"{le:.6g}"
                            blab = (f'{lab},le="{le_s}"' if lab
                                    else f'le="{le_s}"')
                            lines.append(
                                f"{fam.name}_bucket{{{blab}}} {cum}")
                        blab = (f'{lab},le="+Inf"' if lab else 'le="+Inf"')
                        if cum != c.count:    # ensure the +Inf edge exists
                            lines.append(
                                f"{fam.name}_bucket{{{blab}}} {c.count}")
                        sfx = f"{{{lab}}}" if lab else ""
                        lines.append(f"{fam.name}_sum{sfx} {c.sum:.6g}")
                        lines.append(f"{fam.name}_count{sfx} {c.count}")
                    else:
                        sfx = f"{{{lab}}}" if lab else ""
                        lines.append(f"{fam.name}{sfx} {c.value:.6g}")
        return "\n".join(lines) + "\n"

    def export_jsonl(self, path, ts: float | None = None,
                     append: bool = False) -> int:
        """Write one JSON line per child; returns the line count."""
        rows = self.collect()
        if ts is not None:
            for r in rows:
                r["ts"] = ts
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        mode = "a" if append else "w"
        with open(path, mode) as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        return len(rows)


class _NullFamily:
    """Accepts every instrument call and does nothing."""
    __slots__ = ()

    def touch(self, **k):
        pass

    def inc(self, *a, **k):
        pass

    def set(self, *a, **k):
        pass

    def observe(self, *a, **k):
        pass

    def value(self, **k):
        return 0.0

    def total(self):
        return 0.0

    def count(self):
        return 0

    def quantile(self, q, **k):
        return None

    def mean(self, **k):
        return None


_NULL_FAMILY = _NullFamily()


class NullMetrics:
    """Registry-shaped no-op (the ``metrics=False`` overhead baseline)."""

    enabled = False

    def counter(self, *a, **k):
        return _NULL_FAMILY

    gauge = histogram = counter

    def family(self, name):
        return None

    def reset(self):
        pass

    def collect(self):
        return []

    def render_prometheus(self):
        return ""

    def export_jsonl(self, path, ts=None, append=False):
        return 0


NULL_METRICS = NullMetrics()
