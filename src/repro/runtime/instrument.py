"""Host<->device dispatch accounting, program spans, request-engine
counters.

The fused wave program's whole point is eliminating host round-trips
(DESIGN.md §3 / §9 item 6 resolution), so the benchmark needs a number
to show for it.  ``counting()`` installs a process-local counter; every
host->device program dispatch and device->host materialization on the
search path calls :func:`record` with an event tag.  Outside a
``counting()`` block recording is a no-op (one ``is None`` check — the
hot path pays nothing).

Tags follow ``<direction>:<site>``: ``h2d`` = a program dispatch,
``d2h`` = a blocking device-to-host materialization; ``filter:<stage>``
counts a finished tile's candidates per filter stage (the paper's
Tables II/IV/V funnel); ``verify:<quantity>`` counts the host verifier's
solver work (``verify:solver_rows``, ``verify:solver_cells_logical`` and
``verify:solver_cells_padded``, ``verify:solver_steps``: the exact
solver's lockstep scan iterations).  The benchmark (``bench/run.py``) copies the
counter into every result; its per-layer metrics read the tags.

:func:`span` marks a phase of the host's work.  Each span is a
``jax.profiler.TraceAnnotation`` (so a profiler trace shows it on the
host plane, on the device trace's clock) and, inside ``counting()``,
three counter entries: ``span_n:<name>`` (entries), ``span_ns:<name>``
(total nanoseconds) and ``self_ns:<name>`` (nanoseconds not covered by
a child span of the same thread).  Every program span name starts with
``koios.`` and is listed in :data:`SPANS`.  With no counter and no
profiler a span costs two checks and returns a shared no-op.

:class:`EngineCounters` is the request engine's per-request / per-wave
instrumentation (DESIGN.md §3.2): true admit->respond latencies (the
number ``serve_batch`` reports per request — NOT one amortized batch
figure), queue-depth samples at every continuous-batching step, and the
stream-cache hit/miss/eviction tallies of the serving window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import Counter
from typing import Iterator, List, Optional

from jax.profiler import TraceAnnotation

_ACTIVE: Optional[Counter] = None
_clock = time.perf_counter_ns
_local = threading.local()

# every program span, outermost first (PERF.md §3 names the metric that
# reads each)
SPANS = ("koios.step", "koios.join", "koios.stream", "koios.wave",
         "koios.wave.launch", "koios.device_wait", "koios.resume",
         "koios.verify", "koios.verify.weights", "koios.verify.pack",
         "koios.verify.solve", "koios.finish", "koios.respond")


def record(event: str, n: int = 1) -> None:
    """Count ``n`` occurrences of ``event`` if a counter is installed."""
    if _ACTIVE is not None:
        _ACTIVE[event] += n


@contextlib.contextmanager
def counting() -> Iterator[Counter]:
    """Install a fresh dispatch counter for the enclosed block (reentrant:
    an inner block shadows, then restores, the outer one)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = Counter()
    try:
        yield _ACTIVE
    finally:
        _ACTIVE = prev


class _Span:
    """One entered span: the annotation and, inside ``counting()``, its
    timing against the thread's stack of open spans."""

    __slots__ = ("name", "attrs", "ann", "counts", "t0", "child")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "_Span":
        self.ann = None
        if TraceAnnotation.is_enabled():
            self.ann = TraceAnnotation(self.name, **self.attrs)
            self.ann.__enter__()
        self.counts = _ACTIVE
        if self.counts is not None:
            stack = getattr(_local, "stack", None)
            if stack is None:
                stack = _local.stack = []
            stack.append(self)
            self.child = 0
            self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        if self.counts is not None:
            dt = _clock() - self.t0
            stack = _local.stack
            stack.pop()
            if stack:
                stack[-1].child += dt
            c, n = self.counts, self.name
            c["span_n:" + n] += 1
            c["span_ns:" + n] += dt
            c["self_ns:" + n] += dt - self.child
        if self.ann is not None:
            self.ann.__exit__(*exc)

    def annotate(self, **attrs) -> None:
        """Attach attributes known only inside the span."""
        if self.ann is not None:
            self.ann.set_metadata(**attrs)


class _Off:
    """The span of a process with no counter and no profiler."""

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def annotate(self, **attrs) -> None:
        pass


_OFF = _Off()


def span(name: str, **attrs):
    """Context manager marking a phase of host work (module docstring).
    Use per phase or per round, never per candidate."""
    if _ACTIVE is None and not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, attrs)


def totals(counts: Counter) -> dict:
    """Per-direction sums plus the grand total of a counter's events."""
    h2d = sum(v for k, v in counts.items() if k.startswith("h2d:"))
    d2h = sum(v for k, v in counts.items() if k.startswith("d2h:"))
    return {"h2d_dispatches": h2d, "d2h_transfers": d2h,
            "total": h2d + d2h}


# --------------------------------------------------------- request engine
@dataclasses.dataclass
class RequestTrace:
    """One request's lifecycle timestamps (engine clock seconds)."""

    rid: int
    t_admit: float
    t_first_wave: float = 0.0      # first wave that included the request
    t_respond: float = 0.0
    stream_hit: bool = False
    waves: int = 0                 # waves the request participated in
    deadline: Optional[float] = None
    status: str = "ok"             # 'ok' | 'shed' | 'failed'

    @property
    def latency_s(self) -> float:
        return self.t_respond - self.t_admit

    @property
    def queue_s(self) -> float:
        """Admission-queue wait: admit -> first wave."""
        return self.t_first_wave - self.t_admit

    @property
    def deadline_met(self) -> Optional[bool]:
        if self.deadline is None:
            return None
        return self.t_respond <= self.deadline


def _quantile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[min(len(s) - 1, int(q * len(s)))]


class EngineCounters:
    """Rolling request-engine metrics: per-request traces, queue-depth
    samples (one per continuous-batching step), wave sizes, and the
    stream-cache window deltas."""

    def __init__(self) -> None:
        self.traces: List[RequestTrace] = []
        self.queue_depth: List[int] = []
        self.wave_sizes: List[int] = []
        self.steps = 0
        self.overloaded = 0        # refused: admission queue full
        self.invalid = 0           # refused: failed query validation
        self.resyncs = 0           # epoch resyncs performed

    def observe_step(self, queue_depth: int, wave_size: int) -> None:
        self.steps += 1
        self.queue_depth.append(int(queue_depth))
        self.wave_sizes.append(int(wave_size))

    def observe_respond(self, trace: RequestTrace) -> None:
        self.traces.append(trace)

    def observe_overload(self) -> None:
        self.overloaded += 1

    def observe_invalid(self) -> None:
        self.invalid += 1

    def observe_resync(self) -> None:
        self.resyncs += 1

    def summary(self, cache_stats: Optional[dict] = None) -> dict:
        """Deadline accounting rides along (DESIGN.md §6): latency
        quantiles cover SERVED requests only (a shed request's 'latency'
        is time-to-shed, not service), while the shed tally and the
        deadline-met ratio cover every respond."""
        served = [t for t in self.traces if t.status == "ok"]
        lats = [t.latency_s for t in served]
        queues = [t.queue_s for t in served]
        met = [t.deadline_met for t in self.traces
               if t.deadline_met is not None]
        out = {
            "requests": len(self.traces),
            "served": len(served),
            "shed": sum(t.status == "shed" for t in self.traces),
            "failed": sum(t.status == "failed" for t in self.traces),
            "overloaded": self.overloaded,
            "invalid": self.invalid,
            "resyncs": self.resyncs,
            "steps": self.steps,
            "mean_latency_s": sum(lats) / len(lats) if lats else 0.0,
            "p50_latency_s": _quantile(lats, 0.50),
            "p95_latency_s": _quantile(lats, 0.95),
            "p99_latency_s": _quantile(lats, 0.99),
            "max_latency_s": max(lats) if lats else 0.0,
            "mean_queue_s": sum(queues) / len(queues) if queues else 0.0,
            "mean_queue_depth": (sum(self.queue_depth)
                                 / len(self.queue_depth)
                                 if self.queue_depth else 0.0),
            "max_queue_depth": max(self.queue_depth, default=0),
            "mean_wave_size": (sum(self.wave_sizes) / len(self.wave_sizes)
                               if self.wave_sizes else 0.0),
            "stream_hits": sum(t.stream_hit for t in self.traces),
            "deadlines_met": sum(met),
            "deadlines_missed": len(met) - sum(met),
            "deadline_met_ratio": (sum(met) / len(met)) if met else 1.0,
        }
        if cache_stats is not None:
            out["stream_cache"] = dict(cache_stats)
        return out
