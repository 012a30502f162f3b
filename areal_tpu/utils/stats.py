"""Scoped, denominator-normalized statistics tracking.

Capability counterpart of the reference's `DistributedStatsTracker`
(areal/utils/stats_tracker.py:30-290) and `StatsLogger` (stats_logger.py).
torch-free: values are numpy arrays; cross-host reduction (multi-host TPU)
goes through an optional reduce hook instead of torch.distributed.
"""

import math
from collections import defaultdict
from contextlib import contextmanager
from enum import Enum
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from areal_tpu.utils import logging, telemetry

logger = logging.getLogger("stats")


class ReduceType(Enum):
    AVG = "avg"
    SUM = "sum"
    MIN = "min"
    MAX = "max"


def _asarray(x) -> np.ndarray:
    if hasattr(x, "addressable_shards") or hasattr(x, "device_buffer"):
        x = np.asarray(x)  # jax array
    arr = np.asarray(x)
    return arr


class PendingTrainStats:
    """Train-step stats whose device→host fetch is deferred (Mapping-like).

    A per-step blocking stats fetch serialises the trainer on dispatch
    latency: the host cannot enqueue step N+1 until step N's scalars have
    come back to the host.  Deferring the fetch lets XLA
    pipeline steps back-to-back; reading any key materialises the stats (one
    batched transfer) and runs the registered finalizers (normalisation +
    tracker commit), preserving the sync path's observable behavior, just
    later.
    """

    def __init__(self, device_stats: Dict[str, Any], fetch: Callable):
        # issue async copies now so the transfer overlaps device compute
        for v in device_stats.values():
            if hasattr(v, "copy_to_host_async"):
                try:
                    v.copy_to_host_async()
                except Exception:  # noqa: BLE001 — optional fast path
                    pass
        self._device_stats = device_stats
        self._fetch = fetch
        self._finalizers: List[Callable] = []
        self._result: Optional[Dict[str, float]] = None

    def then(self, fn: Callable) -> "PendingTrainStats":
        """Register `fn(stats_dict) -> stats_dict` to run at materialisation."""
        if self._result is not None:
            self._result = fn(self._result)
        else:
            self._finalizers.append(fn)
        return self

    def materialize(self) -> Dict[str, float]:
        if self._result is None:
            out = self._fetch(self._device_stats)
            self._device_stats = None
            for fn in self._finalizers:
                out = fn(out)
            self._finalizers = []
            self._result = out
        return self._result

    # Mapping surface — any read materialises
    def __getitem__(self, key):
        return self.materialize()[key]

    def __contains__(self, key):
        return key in self.materialize()

    def __iter__(self):
        return iter(self.materialize())

    def __len__(self):
        return len(self.materialize())

    def keys(self):
        return self.materialize().keys()

    def values(self):
        return self.materialize().values()

    def items(self):
        return self.materialize().items()

    def get(self, key, default=None):
        return self.materialize().get(key, default)

    def pop(self, key, *default):
        return self.materialize().pop(key, *default)

    def __setitem__(self, key, value):
        # callers annotate stats in place (e.g. sft/rw engines' ppl/acc);
        # writing forces materialisation so ordering stays deterministic
        self.materialize()[key] = value

    def __repr__(self):
        state = "pending" if self._result is None else repr(self._result)
        return f"PendingTrainStats({state})"


class StatsTracker:
    """Accumulates masked statistics under hierarchical scopes.

    - `denominator(name=mask)` registers boolean masks.
    - `stat(denominator="mask", key=value, ...)` records per-element values
      normalized by a mask at reduce time.
    - `scalar(key=value)` records plain scalars (averaged over records).
    - `scope(name)` nests key prefixes.
    - `export()` reduces everything to flat {key: float} and clears.
    """

    def __init__(self, name: str = ""):
        self.name = name
        self._scopes: List[str] = []
        self._denoms: Dict[str, List[np.ndarray]] = defaultdict(list)
        # each stat record carries the mask it was validated against, so
        # values and denominators can never be mis-paired positionally
        self._stats: Dict[str, List[tuple]] = defaultdict(list)
        self._reduce: Dict[str, ReduceType] = {}
        self._scalars: Dict[str, List[float]] = defaultdict(list)
        self._timing: Dict[str, List[float]] = defaultdict(list)

    # --- scoping ---
    @contextmanager
    def scope(self, name: str):
        self._scopes.append(name)
        try:
            yield self
        finally:
            self._scopes.pop()

    def _key(self, key: str) -> str:
        parts = [p for p in ([self.name] + self._scopes + [key]) if p]
        return "/".join(parts)

    # --- recording ---
    def denominator(self, **kwargs):
        for key, mask in kwargs.items():
            arr = _asarray(mask)
            if arr.dtype != np.bool_:
                raise ValueError(f"denominator {key!r} must be boolean, got {arr.dtype}")
            self._denoms[self._key(key)].append(arr.reshape(-1))

    def stat(
        self,
        denominator: str,
        reduce_type: ReduceType = ReduceType.AVG,
        **kwargs,
    ):
        denom_key = self._key(denominator)
        if denom_key not in self._denoms:
            raise ValueError(f"unknown denominator {denominator!r}")
        for key, value in kwargs.items():
            arr = _asarray(value).astype(np.float32).reshape(-1)
            full = self._key(key)
            mask = self._denoms[denom_key][-1]
            if arr.shape != mask.shape:
                raise ValueError(
                    f"stat {key!r} shape {arr.shape} != denominator shape {mask.shape}"
                )
            self._stats[full].append((arr, mask))
            self._reduce[full] = reduce_type

    def scalar(self, **kwargs):
        for key, value in kwargs.items():
            self._scalars[self._key(key)].append(float(value))

    @contextmanager
    def record_timing(self, key: str):
        """A `telemetry.span` named `key` (so the phase shows in a device
        profile as `areal/<key>`), its seconds appended to the timings."""
        took: Dict[str, float] = {}
        try:
            with telemetry.span(key, took):
                yield
        finally:
            self._timing[self._key(key)].append(took[f"t_{key}_s"])

    # --- reduction ---
    def export(
        self,
        key: Optional[str] = None,
        reduce_hook: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None,
        reset: bool = True,
    ) -> Dict[str, float]:
        """Reduce to flat floats.  `reduce_hook` may implement cross-host
        aggregation: it receives {key: (num, denom)|value} partial sums."""
        out: Dict[str, float] = {}
        for full, records in self._stats.items():
            if key is not None and not full.startswith(key):
                continue
            vals = np.concatenate([v for v, _ in records])
            mask = np.concatenate([m for _, m in records])
            rt = self._reduce[full]
            if mask.sum() == 0:
                continue
            sel = vals[mask]
            if rt == ReduceType.AVG:
                out[full] = float(sel.mean())
            elif rt == ReduceType.SUM:
                out[full] = float(sel.sum())
            elif rt == ReduceType.MIN:
                out[full] = float(sel.min())
            elif rt == ReduceType.MAX:
                out[full] = float(sel.max())
        for full, masks in self._denoms.items():
            if key is not None and not full.startswith(key):
                continue
            tot = int(sum(m.sum() for m in masks))
            out.setdefault(f"{full}/count", float(tot))
        for full, vals in self._scalars.items():
            if key is not None and not full.startswith(key):
                continue
            out[full] = float(np.mean(vals))
        for full, vals in self._timing.items():
            if key is not None and not full.startswith(key):
                continue
            out[f"time_perf/{full}"] = float(np.sum(vals))
        if reduce_hook is not None:
            out = reduce_hook(out)
        if reset:
            if key is None:
                self._denoms.clear()
                self._stats.clear()
                self._scalars.clear()
                self._timing.clear()
                self._reduce.clear()
            else:
                for d in (self._denoms, self._stats, self._scalars, self._timing):
                    for k in [k for k in d if k.startswith(key)]:
                        del d[k]
        return {k: (0.0 if (isinstance(v, float) and math.isnan(v)) else v) for k, v in out.items()}


# Module-level default tracker, mirroring the reference's module-level API.
DEFAULT_TRACKER = StatsTracker()
denominator = DEFAULT_TRACKER.denominator
stat = DEFAULT_TRACKER.stat
scalar = DEFAULT_TRACKER.scalar
scope = DEFAULT_TRACKER.scope
record_timing = DEFAULT_TRACKER.record_timing
export = DEFAULT_TRACKER.export
