"""Per-request SLO accounting for the serving engine.

The serving contract the ROADMAP's "millions of users" story is measured
against is not a single batched call — it is *sustained* service under a
dynamic request stream: at what tick latency, where each tick's time
goes, and what happened to every request that did NOT get served
(expired past its deadline, rejected at admission, recovered mid-stream).
:class:`SLOTracker` is the one place those numbers accumulate; the
engine calls ``count``/``record_tick`` (and records phases through a
:class:`TickSpans` per tick) and everything else
(tests, the examples, operator dashboards) reads ``summary()``.

Latencies are recorded per engine *tick* — one fixed-shape device call —
because that is the quantum the slot loop schedules in: a request's
end-to-end latency is (queue wait in ticks) x (tick latency), and the
two factors are exactly the knobs an operator has (slots/admission vs
kernel/batch shape).

Each tick is also cut into back-to-back phases (:data:`PHASES`) by a
:class:`TickSpans`.  The tracker sums every phase's seconds and count
(``phase_s``/``phase_n``), which is what an operator without a profiler
reads.  While the JAX profiler records, each phase is besides a
``TraceMe`` on the host plane of the device trace, named after the phase
and carrying the tick number as ``tick=`` metadata, so that the idle gaps
of the device can be charged to the phase the host was in.
"""

from __future__ import annotations

import time

import jax
import numpy as np

#: counter names the tracker maintains (all start at 0):
#:   submitted — requests accepted into the queue;
#:   served    — requests completed with a result;
#:   expired   — requests that overran ``deadline_ticks`` while queued
#:               and completed as failed;
#:   rejected  — requests refused (or timed out) at admission because the
#:               bounded queue was full;
#:   recovered — mid-stream program swaps after a ``tile_down`` failure;
#:   failed    — queued or in-flight requests completed as failed because
#:               a tick raised (the dispatch thread then stops).
COUNTERS = ("submitted", "served", "expired", "rejected", "recovered",
            "failed")

#: the phases of a tick that runs a device step, in order:
#:   engine.expire   — failure poll and deadline expiry of queued requests;
#:   engine.admit    — queued requests into free slots (metadata: ``n``
#:                     admitted, their summed queue ``wait_s``, the queue
#:                     ``depth`` left);
#:   engine.panel    — the host-side input panel filled;
#:   engine.put      — the panel's copy to the device;
#:   engine.launch   — the device call dispatched (asynchronous);
#:   engine.fetch    — the result copied back: the host blocked on the
#:                     device and the device-to-host copy;
#:   engine.complete — results scattered, counters, futures completed.
PHASES = ("engine.expire", "engine.admit", "engine.panel", "engine.put",
          "engine.launch", "engine.fetch", "engine.complete")


class TickSpans:
    """One tick's phases, back to back on one thread.

    ``phase(name)`` ends the open phase and opens ``name``; ``end()`` ends
    the last.  Whether the profiler records is asked once, when the tick
    starts: with it off no annotation is built, and a phase costs a clock
    read and two dict updates.
    """

    __slots__ = ("_tracker", "tick", "traced", "_name", "_t", "_span")

    def __init__(self, tracker: "SLOTracker", tick: int):
        self._tracker = tracker
        self.tick = tick
        self.traced = jax.profiler.TraceAnnotation.is_enabled()
        self._name: str | None = None
        self._t = 0.0
        self._span = None

    def phase(self, name: str) -> None:
        now = time.perf_counter()
        self._close(now)
        self._name, self._t = name, now
        if self.traced:
            self._span = jax.profiler.TraceAnnotation(name, tick=self.tick)
            self._span.__enter__()

    def note(self, **meta) -> None:
        """Metadata on the open phase's span (a traced tick only)."""
        if self._span is not None:
            self._span.set_metadata(**meta)

    def end(self) -> None:
        self._close(time.perf_counter())

    def _close(self, now: float) -> None:
        if self._name is None:
            return
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self._tracker.add_phase(self._name, now - self._t)
        self._name = None


class SLOTracker:
    """Counters, tick-latency percentiles and phase totals for one
    serving engine."""

    def __init__(self):
        self.counters: dict[str, int] = dict.fromkeys(COUNTERS, 0)
        self.tick_latencies: list[float] = []   # seconds per engine tick
        self.phase_s: dict[str, float] = {}     # summed seconds per phase
        self.phase_n: dict[str, int] = {}       # times each phase ran

    # ------------------------------------------------------------------
    def count(self, name: str, k: int = 1) -> None:
        if name not in self.counters:
            raise KeyError(f"unknown SLO counter {name!r} "
                           f"(have {sorted(self.counters)})")
        self.counters[name] += k

    def record_tick(self, seconds: float) -> None:
        self.tick_latencies.append(seconds)

    def add_phase(self, name: str, seconds: float) -> None:
        self.phase_s[name] = self.phase_s.get(name, 0.0) + seconds
        self.phase_n[name] = self.phase_n.get(name, 0) + 1

    # ------------------------------------------------------------------
    def percentile_us(self, p: float) -> float | None:
        """``p``-th percentile tick latency in microseconds (None when no
        tick has been recorded yet)."""
        if not self.tick_latencies:
            return None
        return float(np.percentile(np.asarray(self.tick_latencies), p)) * 1e6

    def summary(self) -> dict:
        """Counters + ticks + p50/p99 tick latency, and the phase totals
        (``phase_s``: seconds, ``phase_n``: count, by phase name)."""
        out = dict(self.counters)
        out["ticks"] = len(self.tick_latencies)
        out["p50_tick_us"] = self.percentile_us(50)
        out["p99_tick_us"] = self.percentile_us(99)
        out["phase_s"] = dict(self.phase_s)
        out["phase_n"] = dict(self.phase_n)
        return out
