"""Lightweight metrics registry: counters and gauges, sampled into time
series on the engine's virtual clock.

This is the in-process analogue of a Prometheus client: the engine (via
``serving/trace.py``) sets gauges once per scheduler step — queue depth,
active slots, arena blocks in use, resident/loading adapters, decode
batch occupancy — and counts adapter loads by cause (``loads.demand``,
``loads.prefetch``) and bytes (``load.bytes``); ``sample(t)`` snapshots
every metric's current value into its series. The exporter turns each
series into a Perfetto counter track, so arena pressure and queue
depth are visible *on the same timeline* as the slot/channel spans.

Sampling the same virtual timestamp twice keeps only the latest
snapshot (scheduler iterations that charge no compute do not advance
the clock, and duplicate points at one ``t`` would draw as a vertical
smear in Perfetto).

The registry is engine-agnostic and jax-free: it can be unit-tested and
reused by any component that wants cheap time-series accounting.
"""
from __future__ import annotations

from typing import Dict, List, Tuple, Type, TypeVar, Union

Number = Union[int, float]
_M = TypeVar("_M", "Counter", "Gauge")


class Counter:
    """Monotonically increasing count (``inc`` only)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value: float = 0

    def inc(self, n: Number = 1) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name}: negative increment {n}")
        self.value += n


class Gauge:
    """Point-in-time value (``set`` to anything)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value: float = 0

    def set(self, v: Number) -> None:
        self.value = v


class MetricsRegistry:
    """Named metrics + their sampled time series.

    ``counter``/``gauge`` get-or-create by name (a name is
    bound to one metric type for the registry's lifetime);
    ``sample(t)`` appends ``(t, value)`` to every metric's series,
    replacing the last point when ``t`` repeats.
    """

    def __init__(self) -> None:
        self._metrics: Dict[str, Union[Counter, Gauge]] = {}
        self.series: Dict[str, List[Tuple[float, float]]] = {}

    def _get(self, name: str, cls: Type[_M]) -> _M:
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = cls(name)
            self.series[name] = []
        elif not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} is a {type(m).__name__}, "
                f"not a {cls.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def sample(self, t: float) -> None:
        for name, metric in self._metrics.items():
            series = self.series[name]
            point = (float(t), float(metric.value))
            if series and series[-1][0] == point[0]:
                series[-1] = point
            else:
                series.append(point)

    def as_dict(self) -> Dict[str, List[List[float]]]:
        """Series as plain lists (JSON-ready)."""
        return {k: [list(p) for p in v] for k, v in self.series.items()}
