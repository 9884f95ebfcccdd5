"""The program's own spans in a traced window.

The serving engine puts each phase of a tick on the host plane of the
trace as an ``engine.<phase>`` event, with the tick number (and, for
``engine.admit``, the requests admitted ``n``, their summed queue wait
``wait_s`` and the queue ``depth`` left) as stats; the compilation
counter puts a ``repro.compile`` event there for every executable built
or loaded in the window.  ``read`` returns those events from the newest
``*.xplane.pb`` under a trace directory, and ``of_cell`` those of the
cell's, read once per run.  A program without these spans gives an
empty list, and each reader then returns nothing.
"""

from __future__ import annotations

import dataclasses
import pathlib

import run

trace = run.load_module(".", "trace")

PREFIXES = ("engine.", "repro.compile")
FETCH = "engine.fetch"


@dataclasses.dataclass(frozen=True)
class Span:
    start_ns: float
    end_ns: float
    name: str
    thread: int          # index of the host line the span is on
    stats: dict


def read(trace_dir) -> list[Span]:
    """The program's spans on the host's Python threads in the newest
    ``*.xplane.pb`` under ``trace_dir``, by start; none without one."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return []
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for k, line in enumerate(plane.lines):
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    ev.name, k, dict(ev.stats)))
    out.sort(key=lambda s: (s.start_ns, s.end_ns))
    return out


def of_cell(ctx) -> list[Span]:
    if "spans" not in ctx.observed:
        ctx.observe(spans=read(ctx.trace_dir))
    return ctx.observed["spans"]


@dataclasses.dataclass(frozen=True)
class Ticks:
    n: int               # ticks that ran a device step (engine.fetch spans)
    host_s: float        # union of every other engine phase, seconds
    wait_s: float        # summed engine.fetch, seconds


def ticks(spans: list[Span]) -> Ticks | None:
    """The engine's ticks in the window; None where no tick fetched."""
    fetch = [s for s in spans if s.name == FETCH]
    if not fetch:
        return None
    host, _ = trace.union((s.start_ns, s.end_ns) for s in spans
                          if s.name.startswith("engine.")
                          and s.name != FETCH)
    return Ticks(n=len(fetch), host_s=host * 1e-9,
                 wait_s=sum(s.end_ns - s.start_ns for s in fetch) * 1e-9)
