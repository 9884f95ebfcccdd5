"""The unified analog serving engine.

One engine now serves both request families that used to have separate
loops (`ContinuousBatcher` for LM decode, `AnalogTickBatcher` for analog
ticks), in the shape of MaxText's ``offline_inference.py``:

  * an (optional) background **dispatch thread** pulls from a bounded
    request queue and drives the device, so callers just ``submit()`` and
    wait on the request's result future;
  * a bounded **admission queue** with a choice of backpressure policy —
    ``"block"`` (submit waits for space) or ``"reject"`` (submit fails
    fast and the request completes as failed);
  * a fixed-slot **tick loop**: every tick admits queued requests into
    free slots and runs ONE fixed-shape device call — a single fused
    megakernel ``pallas_call`` for a compiled analog program, one decode
    step for the LM — then frees finished slots immediately (no
    head-of-line blocking);
  * per-request **SLO accounting** (:class:`repro.runtime.slo.SLOTracker`):
    deadlines, served/expired/rejected/recovered counters, p50/p99 tick
    latency, and each tick cut into phases (``engine.expire``,
    ``engine.admit``, ``engine.panel``, ``engine.put``, ``engine.launch``,
    ``engine.fetch``, ``engine.complete``) whose seconds are summed and,
    while the JAX profiler records, put on the device trace's clock;
    every request is stamped when it is submitted, admitted and served;
  * the mid-stream **failure-recovery** hooks from the fault-tolerance
    work: a fired ``tile_down`` swaps in a recovered program between
    ticks and in-flight requests keep draining.

The engine consumes any compiled program through the
:class:`~repro.serving.servable.ServableProgram` protocol — the three
``Compiled*Program`` classes, a ``TiledAnalogLinear``/``AnalogSequence``
with ``params``, or anything else with ``apply``/``n_in``/``n_out``.  A
model exposing ``decode_step`` is served through the LM slot family
instead; both families share the same admission queue, tick loop, SLO
tracker and failure hooks.

Tick ordering is load-bearing for deadline/recovery semantics and is
kept bit-identical to the retired ``AnalogTickBatcher``: failures are
polled and deadlines expired against the *pre-increment* tick counter,
then the counter advances, then admission and the device call happen.
A request submitted at tick t with ``deadline_ticks=k`` therefore
expires at the top of tick t+k+1 if still queued — the head of a
slots=1 queue gets exactly k service opportunities.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable

import jax.numpy as jnp
import numpy as np

from repro.runtime.slo import SLOTracker, TickSpans
from repro.serving.servable import ServableProgram, as_servable

__all__ = ["Request", "ServingEngine"]


class Request:
    """One unit of serving work — analog feature vector OR LM prompt.

    ``payload`` is the request body: a ``[d]`` float feature vector for
    an analog program, a ``[prompt_len]`` int32 token array for the LM.
    The ``features=`` / ``prompt=`` keywords are readable aliases for the
    same slot (exactly one of the three may be given).

    ``deadline_ticks``: optional per-request tick budget — a request
    still *queued* that many engine ticks after submission completes as
    failed instead of waiting forever behind an outage.

    The result is a future: ``wait()`` blocks until the engine completes
    the request (from the dispatch thread or a synchronous ``run()``),
    ``done`` is non-blocking.  On success ``result`` holds the output
    panel row (analog) or the generated token array (LM); on expiry or
    rejection ``failed`` is True and ``result`` stays None.

    The engine stamps each request on the host clock
    (``time.perf_counter``) and in ticks: ``submitted_at``/
    ``submitted_tick`` when it is queued, ``admitted_at``/
    ``admitted_tick`` when it takes a slot (one stamp for every request
    of a tick), ``completed_at``/``completed_tick`` when it is served.
    ``completed_at`` is set before ``_finish()``, so a subclass that
    stamps its own completion there keeps its value.  The tick numbers
    are the ``tick=`` of the engine's phase spans.
    """

    def __init__(self, rid: int, payload: Any = None, *,
                 features: Any = None, prompt: Any = None,
                 deadline_ticks: int | None = None,
                 max_new: int = 32, eos_id: int | None = None):
        given = [v for v in (payload, features, prompt) if v is not None]
        if len(given) != 1:
            raise ValueError(
                "Request takes exactly one of payload=/features=/prompt= "
                f"(got {len(given)})")
        self.rid = rid
        self.payload = given[0]
        self.deadline_ticks = deadline_ticks
        self.max_new = max_new
        self.eos_id = eos_id
        # filled by the engine:
        self.result: Any = None
        self.output: list[int] = []          # LM path: tokens as they decode
        self.failed = False
        self.submitted_tick = 0
        self.submitted_at: float | None = None
        self.admitted_tick: int | None = None
        self.admitted_at: float | None = None
        self.completed_tick: int | None = None
        self.completed_at: float | None = None
        self._event = threading.Event()

    @property
    def features(self) -> Any:
        return self.payload

    @property
    def prompt(self) -> Any:
        return self.payload

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: float | None = None) -> bool:
        """Block until the engine completes this request (True) or the
        timeout elapses (False)."""
        return self._event.wait(timeout)

    def _finish(self, failed: bool = False) -> None:
        if failed:
            self.failed = True
        self._event.set()

    def __repr__(self):
        state = ("failed" if self.failed else
                 "done" if self.done else "pending")
        return f"Request(rid={self.rid}, {state})"


# ---------------------------------------------------------------------------
# slot families: the per-tick device step for each request kind
# ---------------------------------------------------------------------------

class _AnalogSlots:
    """Fixed-slot panel ticks through a :class:`ServableProgram`.

    The analog network is stateless, so a tick is: pack up to
    ``n_slots`` admitted requests into a zero-padded ``[n_slots, n_in]``
    panel, ONE ``apply`` (a single megakernel ``pallas_call`` for a
    compiled program), scatter rows back, free every slot.  Unfilled
    slots ride as zero rows — the kernels' ragged-batch padding
    semantics.  With ``mesh=`` the same apply is sharded over the batch
    grid via :func:`repro.parallel.sharding.data_parallel`.
    """

    def __init__(self, servable: ServableProgram, n_slots: int, *,
                 mesh=None, data_axis: str = "data"):
        self.n_slots = n_slots
        self.mesh = mesh
        self.data_axis = data_axis
        self.active: list[Request] = []
        self.rebind(servable)

    def rebind(self, servable: ServableProgram) -> None:
        """(Re)bind the device call — also the mid-stream recovery swap."""
        self.servable = servable

        def apply(p, x):
            return servable.apply(x)

        if self.mesh is not None:
            from repro.parallel.sharding import data_parallel

            apply = data_parallel(apply, self.mesh,
                                  axis_name=self.data_axis)
        self._apply = apply

    def free_slots(self) -> int:
        return self.n_slots - len(self.active)

    def n_active(self) -> int:
        return len(self.active)

    def admit(self, req: Request) -> None:
        self.active.append(req)

    def step(self, spans: TickSpans) -> list[Request]:
        spans.phase("engine.panel")
        active, self.active = self.active, []
        try:
            d = int(self.servable.n_in)
        except (AttributeError, TypeError):
            d = len(np.asarray(active[0].payload))
        panel = np.zeros((self.n_slots, d), np.float32)
        for i, req in enumerate(active):
            panel[i] = req.payload
        spans.phase("engine.put")
        x = jnp.asarray(panel)
        spans.phase("engine.launch")
        y = self._apply(None, x)
        spans.phase("engine.fetch")
        out = np.asarray(y)
        spans.phase("engine.complete")
        for i, req in enumerate(active):
            req.result = out[i]
        return active


class _LMSlot:
    __slots__ = ("req", "pos", "pending")

    def __init__(self):
        self.req: Request | None = None
        self.pos = 0                # next cache position for this slot
        self.pending = 0            # last token, fed on the next tick


class _DecodeSlots:
    """Fixed-slot continuous batching over the LM decode step.

    Slot state lives host-side; the device state is the shared KV cache
    pytree.  Admission prefills the prompt slot-serially (decode_step is
    the uniform per-token primitive), the tick decodes one token for all
    active slots at the shared max position, and finished requests (eos,
    max tokens, cache full) free their slot immediately.
    """

    def __init__(self, model, params, n_slots: int, max_len: int,
                 sample: Callable | None = None):
        if max_len is None:
            raise ValueError("LM serving needs max_len= (KV cache length)")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.sample = sample
        self.slots = [_LMSlot() for _ in range(n_slots)]
        self.cache = model.init_cache(n_slots, max_len)
        self._decode = model.bind_decode(params)

    def rebind(self, servable) -> None:
        raise ValueError("mid-stream program recovery is an analog-path "
                         "feature; the LM decode path has no tile grid")

    def free_slots(self) -> int:
        return sum(1 for s in self.slots if s.req is None)

    def n_active(self) -> int:
        return sum(1 for s in self.slots if s.req is not None)

    def admit(self, req: Request) -> None:
        i = next(j for j, s in enumerate(self.slots) if s.req is None)
        slot = self.slots[i]
        slot.req, slot.pos = req, 0
        prompt = np.asarray(req.payload, np.int32)
        for tok in prompt[:-1]:
            self._step_one(i, int(tok))
        # the last prompt token is fed on the next engine tick
        slot.pending = int(prompt[-1])

    def _step_one(self, i: int, token: int) -> None:
        """Advance a single slot by one position (prefill path)."""
        slot = self.slots[i]
        toks = np.zeros((self.n_slots,), np.int32)
        toks[i] = token
        _, self.cache = self._decode(
            jnp.asarray(toks), self.cache, jnp.asarray(slot.pos, jnp.int32))
        slot.pos += 1

    def step(self, spans: TickSpans) -> list[Request]:
        spans.phase("engine.panel")
        active = [i for i, s in enumerate(self.slots) if s.req is not None]
        toks = np.zeros((self.n_slots,), np.int32)
        for i in active:
            slot = self.slots[i]
            toks[i] = slot.pending if slot.pos < self.max_len else 0
        # positions: slots advance in lockstep from the shared max offset
        # (prefill above is slot-serial, so admitted slots start aligned)
        pos = max(self.slots[i].pos for i in active)
        spans.phase("engine.put")
        toks, pos = jnp.asarray(toks), jnp.asarray(pos, jnp.int32)
        spans.phase("engine.launch")
        logits, self.cache = self._decode(toks, self.cache, pos)
        nxt = (jnp.argmax(logits, -1) if self.sample is None
               else self.sample(logits))
        spans.phase("engine.fetch")
        arr = np.asarray(nxt)
        spans.phase("engine.complete")
        completed = []
        for i in active:
            slot = self.slots[i]
            slot.pos = pos + 1
            tok = int(arr[i])
            req = slot.req
            req.output.append(tok)
            slot.pending = tok
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.output) >= req.max_new
                    or slot.pos >= self.max_len - 1):
                req.result = np.asarray(req.output, np.int32)
                completed.append(req)
                slot.req = None   # slot freed immediately
        return completed


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """Continuous batching + async dispatch over one compiled program.

    ``program`` is anything servable: a compiled analog program
    (`CompiledProgram`/`CompiledTiledProgram`/`CompiledDeepProgram`), an
    analog model with ``params=``, or an LM :class:`repro.models.Model`
    (detected by its ``decode_step``; needs ``params=`` and ``max_len=``).

    Admission: ``max_queue=None`` leaves the queue unbounded; with a
    bound, ``admission="block"`` makes ``submit`` wait for space (up to
    its ``timeout=``) while ``admission="reject"`` fails the request
    fast.  Either way a refused request completes as failed and counts
    as ``rejected``.

    Synchronous use: ``submit(...)`` then ``run()`` drains the queue on
    the caller's thread.  Async use: ``start()`` (or the context
    manager) spins up the dispatch thread; ``submit`` from any thread
    and ``req.wait()`` for the result future; ``stop()`` drains and
    joins.  A tick that raises on the dispatch thread completes every
    queued and in-flight request as failed, stops the thread, and
    ``stop()`` (or leaving the context) re-raises the exception; later
    ``submit`` calls raise too.

    Fault tolerance (analog path): with ``failure_injector=`` the engine
    polls the injector every tick; a fired ``tile_down`` swaps the
    program mid-stream — via the ``recovery(dead_tiles)`` callable when
    given, else the servable's own ``recover(dead_tiles)`` — and serving
    continues on the recovered grid.  ``events`` logs each swap.
    """

    def __init__(self, program, params=None, *, slots: int,
                 max_len: int | None = None,
                 sample: Callable | None = None,
                 max_queue: int | None = None,
                 admission: str = "block",
                 mesh=None, data_axis: str = "data",
                 failure_injector=None, recovery=None):
        if admission not in ("block", "reject"):
            raise ValueError(f"admission must be 'block' or 'reject', "
                             f"got {admission!r}")
        self.n_slots = slots
        self.max_queue = max_queue
        self.admission = admission
        self.injector = failure_injector
        self.recovery = recovery
        self.ticks = 0
        self.slo = SLOTracker()
        self.events: list[dict] = []
        if hasattr(program, "decode_step"):
            self._impl = _DecodeSlots(program, params, slots, max_len,
                                      sample=sample)
        else:
            self._impl = _AnalogSlots(as_servable(program, params), slots,
                                      mesh=mesh, data_axis=data_axis)
        self._pending: deque[Request] = deque()
        self._deadlined: set[Request] = set()  # queued with a deadline
        self._inflight: set[Request] = set()   # admitted, not completed
        self._cond = threading.Condition()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._error: Exception | None = None   # dispatch-thread failure

    # -- admission ------------------------------------------------------
    def submit(self, req: Request, timeout: float | None = None) -> bool:
        """Enqueue a request; returns False if it was rejected.

        Thread-safe.  With a bounded queue, ``admission="block"`` waits
        up to ``timeout`` seconds for space (None = forever);
        ``admission="reject"`` returns immediately.  A refused request
        completes as failed so ``req.wait()`` never hangs on it.
        """
        with self._cond:
            if self.max_queue is not None:
                if self.admission == "reject":
                    if len(self._pending) >= self.max_queue:
                        return self._refuse(req)
                else:
                    ok = self._cond.wait_for(
                        lambda: (self._error is not None
                                 or len(self._pending) < self.max_queue),
                        timeout=timeout)
                    if not ok:
                        return self._refuse(req)
            if self._error is not None:
                req._finish(failed=True)
                raise RuntimeError("serving dispatch thread failed") \
                    from self._error
            req.submitted_tick = self.ticks
            req.submitted_at = time.perf_counter()
            self._pending.append(req)
            if req.deadline_ticks is not None:
                self._deadlined.add(req)
            self.slo.count("submitted")
            self._cond.notify_all()
        return True

    def _refuse(self, req: Request) -> bool:
        self.slo.count("rejected")
        req._finish(failed=True)
        return False

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._pending)

    # -- the tick loop --------------------------------------------------
    def _check_failures(self) -> None:
        """Poll the injector against the pre-increment tick counter; a
        fired ``tile_down`` swaps in the recovered program mid-stream."""
        if self.injector is None:
            return
        fired = self.injector.at_step(self.ticks)
        if not any(f.kind == "tile_down" for f in fired):
            return
        dead = tuple(sorted(self.injector.dead_tiles))
        if self.recovery is not None:
            prog = self.recovery(dead)
        else:
            prog = self._impl.servable.recover(dead)
        self._impl.rebind(as_servable(prog))
        self.slo.count("recovered")
        self.events.append({"tick": self.ticks, "kind": "tile_recovery",
                            "dead_tiles": dead})

    def _expire(self) -> None:
        """Complete overdue *queued* requests as failed, against the
        pre-increment tick counter (never silently stuck behind an
        outage).  Only the queued requests that carry a deadline are
        looked at, so a deep queue of requests without one costs
        nothing here."""
        with self._cond:
            late = {req for req in self._deadlined
                    if self.ticks - req.submitted_tick >= req.deadline_ticks}
            if not late:
                return
            self._deadlined -= late
            live: deque[Request] = deque()
            for req in self._pending:
                if req in late:
                    self.slo.count("expired")
                    req._finish(failed=True)
                else:
                    live.append(req)
            self._pending = live
            self._cond.notify_all()   # queue shrank: wake blocked submits

    def tick(self) -> int:
        """One engine iteration; returns the number of requests completed.

        Ordering (load-bearing, see module docstring): poll failures and
        expire deadlines at the old tick number, advance the counter,
        admit into free slots, then one fixed-shape device call.  The
        phases are recorded under the advanced tick number.
        """
        spans = TickSpans(self.slo, self.ticks + 1)
        try:
            return self._tick(spans)
        finally:
            spans.end()

    def _tick(self, spans: TickSpans) -> int:
        spans.phase("engine.expire")
        self._check_failures()
        self._expire()
        self.ticks += 1
        spans.phase("engine.admit")
        with self._cond:
            batch: list[Request] = []
            free = self._impl.free_slots()
            while free > 0 and self._pending:
                batch.append(self._pending.popleft())
                free -= 1
            depth = len(self._pending)
            if self._deadlined:
                self._deadlined.difference_update(batch)
            self._inflight.update(batch)
            if batch:
                self._cond.notify_all()   # queue shrank: wake blocked submits
        now = time.perf_counter()
        for req in batch:
            req.admitted_tick, req.admitted_at = self.ticks, now
            self._impl.admit(req)         # device work outside the lock
        if spans.traced:
            spans.note(n=len(batch), depth=depth,
                       wait_s=sum(now - r.submitted_at for r in batch))
        if self._impl.n_active() == 0:
            return 0
        t0 = time.perf_counter()
        completed = self._impl.step(spans)
        now = time.perf_counter()
        self.slo.record_tick(now - t0)
        with self._cond:
            self._inflight.difference_update(completed)
        for req in completed:
            req.completed_tick, req.completed_at = self.ticks, now
            self.slo.count("served")
            req._finish()
        return len(completed)

    def run(self, max_ticks: int = 10_000) -> None:
        """Drain synchronously: tick until every submitted request is
        done (served, or completed-as-failed past its deadline)."""
        for _ in range(max_ticks):
            served = self.tick()
            if served == 0 and not self._has_work():
                return
        raise RuntimeError("serving engine did not drain")

    # -- background dispatch -------------------------------------------
    def _has_work(self) -> bool:
        with self._cond:
            return bool(self._pending) or self._impl.n_active() > 0

    def _dispatch_loop(self) -> None:
        try:
            while True:
                if self._has_work():
                    self.tick()
                elif self._stop.is_set():
                    return
                else:
                    with self._cond:
                        if not self._pending:
                            self._cond.wait(timeout=0.02)
        except Exception as err:  # noqa: BLE001 - re-raised from stop()
            self._fail_all(err)

    def _fail_all(self, err: Exception) -> None:
        """Record a dispatch failure and complete every queued and
        in-flight request as failed, so no ``wait()`` blocks on a thread
        that is gone."""
        with self._cond:
            self._error = err
            doomed = [*self._pending, *self._inflight]
            self._pending.clear()
            self._deadlined.clear()
            self._inflight.clear()
            self._cond.notify_all()
        for req in doomed:
            self.slo.count("failed")
            req._finish(failed=True)

    def start(self) -> "ServingEngine":
        """Spin up the background dispatch thread."""
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._stop.clear()
        self._thread = threading.Thread(target=self._dispatch_loop,
                                        name="serving-dispatch", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the dispatch thread; by default after draining the queue.
        Re-raises the exception of a tick that failed on the thread."""
        if self._thread is None:
            return
        if not drain:
            with self._cond:
                for req in self._pending:
                    self.slo.count("rejected")
                    req._finish(failed=True)
                self._pending.clear()
                self._deadlined.clear()
                self._cond.notify_all()
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        self._thread.join()
        self._thread = None
        if self._error is not None:
            raise self._error

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=exc == (None, None, None))

    # -- accounting -----------------------------------------------------
    @property
    def stats(self) -> dict:
        """SLO summary: counters, tick count, p50/p99 tick latency, the
        summed seconds and count of each tick phase, plus the current
        queue depth."""
        out = self.slo.summary()
        out["queue_depth"] = self.queue_depth
        return out
