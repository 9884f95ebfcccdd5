"""Unified serving-engine tests: protocol conformance, admission
backpressure, SLO accounting, async dispatch, and the public surface."""

import threading

import jax
import numpy as np
import pytest

import repro
from repro import serving
from repro.serving import Request, ServableProgram, ServingEngine, as_servable

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# fixtures: one small compiled program of each variant
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiled_prog():
    from repro import compile as compile_mod

    w = np.random.default_rng(11).normal(size=(8, 8)) / np.sqrt(8)
    tp = compile_mod.program_tiled(
        compile_mod.synthesize_tiled(w, tile=4), method="reck")
    return w, compile_mod.lower_tiled(tp)


@pytest.fixture(scope="module")
def all_compiled():
    from repro import compile as compile_mod

    rng = np.random.default_rng(7)
    w = rng.normal(size=(8, 8)) / np.sqrt(8)
    single = compile_mod.lower(compile_mod.program(
        compile_mod.synthesize(w, n=8), method="reck"))
    tp = compile_mod.program_tiled(
        compile_mod.synthesize_tiled(w, tile=4), method="reck")
    tiled = compile_mod.lower_tiled(tp)
    deep = compile_mod.lower_deep([tp, tp])
    return single, tiled, deep


# ---------------------------------------------------------------------------
# ServableProgram protocol
# ---------------------------------------------------------------------------

def test_all_compiled_programs_are_servable(all_compiled):
    """The three Compiled* variants present one apply/metadata surface."""
    for prog in all_compiled:
        assert isinstance(prog, ServableProgram), type(prog).__name__
        assert prog.n_in == 8 and prog.n_out == 8
        # placement is part of the metadata surface (None when unplaced)
        _ = prog.placement
        y = np.asarray(prog.apply(np.ones((2, 8), np.float32)))
        assert y.shape == (2, 8)


def test_as_servable_passthrough_and_wrap(all_compiled):
    from repro.core.analog_linear import AnalogSequence

    single, tiled, deep = all_compiled
    for prog in all_compiled:
        assert as_servable(prog) is prog   # already conformant: no wrapper
    model = AnalogSequence(n=8, depth=1, backend="reference")
    params = model.init(jax.random.PRNGKey(0))
    bound = as_servable(model, params)
    assert isinstance(bound, ServableProgram)
    assert bound.n_in == 8 and bound.n_out == 8
    x = np.ones((2, 8), np.float32)
    np.testing.assert_allclose(np.asarray(bound.apply(x)),
                               np.asarray(model.apply(params, x)))
    with pytest.raises(ValueError, match="recover"):
        bound.recover(((0, 0),))


def test_single_mesh_program_refuses_tile_recovery(all_compiled):
    single, _, _ = all_compiled
    with pytest.raises(ValueError, match="tile grid"):
        single.recover(((0, 0),))


# ---------------------------------------------------------------------------
# admission backpressure: bounded queue rejects vs blocks
# ---------------------------------------------------------------------------

def _feature_reqs(count, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, features=rng.normal(size=8).astype(np.float32),
                    **kw) for i in range(count)]


def test_bounded_queue_rejects_when_full(tiled_prog):
    _, comp = tiled_prog
    eng = ServingEngine(comp, slots=1, max_queue=2, admission="reject")
    reqs = _feature_reqs(4)
    accepted = [eng.submit(r) for r in reqs]
    assert accepted == [True, True, False, False]
    # a rejected request completes as failed — wait() never hangs on it
    assert reqs[2].failed and reqs[2].done and reqs[2].wait(timeout=0)
    assert eng.stats["rejected"] == 2
    eng.run()
    assert eng.stats["served"] == 2


def test_bounded_queue_blocks_until_space(tiled_prog):
    """admission="block": a full queue stalls submit until a tick drains
    it (here: the dispatch thread), instead of dropping the request."""
    _, comp = tiled_prog
    eng = ServingEngine(comp, slots=2, max_queue=2, admission="block")
    reqs = _feature_reqs(8)
    with eng:
        for r in reqs:
            assert eng.submit(r, timeout=30)
        assert all(r.wait(timeout=30) for r in reqs)
    assert eng.stats["served"] == 8
    assert eng.stats["rejected"] == 0


def test_blocking_submit_times_out_as_rejected(tiled_prog):
    _, comp = tiled_prog
    eng = ServingEngine(comp, slots=1, max_queue=1, admission="block")
    assert eng.submit(_feature_reqs(1)[0])
    late = _feature_reqs(1, seed=1)[0]
    # no dispatch thread is running, so the queue can never drain
    assert not eng.submit(late, timeout=0.05)
    assert late.failed and late.done
    assert eng.stats["rejected"] == 1


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------

def test_stats_counters_and_latency_percentiles(tiled_prog):
    w, comp = tiled_prog
    eng = ServingEngine(comp, slots=2)
    reqs = _feature_reqs(5)
    for r in reqs:
        eng.submit(r)
    eng.run()
    s = eng.stats
    assert s["submitted"] == 5 and s["served"] == 5
    assert s["expired"] == 0 and s["rejected"] == 0 and s["recovered"] == 0
    assert s["ticks"] == 3 and s["queue_depth"] == 0
    assert s["p50_tick_us"] > 0 and s["p99_tick_us"] >= s["p50_tick_us"]
    assert s["qps"] > 0
    # arrival/completion metadata stamped per request
    assert all(r.submitted_at is not None for r in reqs)
    assert [r.completed_tick for r in reqs] == [1, 1, 2, 2, 3]


def test_unknown_counter_rejected():
    from repro.runtime import SLOTracker

    t = SLOTracker()
    with pytest.raises(KeyError):
        t.count("nope")
    assert t.percentile_us(50) is None and t.qps() is None


# ---------------------------------------------------------------------------
# async dispatch thread
# ---------------------------------------------------------------------------

def test_dispatch_thread_serves_submissions_from_other_threads(tiled_prog):
    w, comp = tiled_prog
    eng = ServingEngine(comp, slots=4)
    reqs = _feature_reqs(12, seed=2)

    def producer(chunk):
        for r in chunk:
            eng.submit(r)

    with eng:
        threads = [threading.Thread(target=producer, args=(reqs[i::3],))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r.wait(timeout=30) for r in reqs)
    for r in reqs:
        np.testing.assert_allclose(r.result, np.abs(r.features @ w.T),
                                   atol=1e-4)
    assert eng.stats["served"] == 12


def test_stop_without_drain_fails_pending(tiled_prog):
    _, comp = tiled_prog
    eng = ServingEngine(comp, slots=1)
    reqs = _feature_reqs(3)
    # never started: stop(drain=False) must still fail queued requests
    for r in reqs:
        eng.submit(r)
    eng.start()
    eng.stop(drain=False)
    assert all(r.done for r in reqs)
    served = sum(1 for r in reqs if not r.failed)
    assert served + eng.stats["rejected"] == 3


def test_dispatch_failure_fails_requests_and_reraises_on_stop():
    """A tick that raises on the dispatch thread must not strand callers:
    queued and in-flight requests complete as failed, later submissions
    raise, and stop() re-raises the tick's exception."""

    class Broken:
        n_in = n_out = 4

        def apply(self, x):
            raise FloatingPointError("device call failed")

    eng = ServingEngine(Broken(), slots=2)
    reqs = [Request(rid=i, features=np.ones(4, np.float32))
            for i in range(5)]
    for r in reqs:              # queued before the thread can fail
        eng.submit(r)
    eng.start()
    assert all(r.wait(timeout=30) for r in reqs)
    assert all(r.failed and r.result is None for r in reqs)
    assert eng.stats["failed"] == 5 and eng.stats["served"] == 0
    late = Request(rid=9, features=np.ones(4, np.float32))
    with pytest.raises(RuntimeError, match="dispatch thread failed"):
        eng.submit(late)
    assert late.failed
    with pytest.raises(FloatingPointError, match="device call failed"):
        eng.stop()


# ---------------------------------------------------------------------------
# LM-vs-analog parity on the shared slot loop
# ---------------------------------------------------------------------------

def test_lm_and_analog_paths_share_slot_loop_semantics(tiled_prog):
    """Same engine class, same admission/deadline machinery: a queued
    request past its deadline expires identically on both paths."""
    from repro import configs
    from repro.models import Model

    _, comp = tiled_prog
    e_analog = ServingEngine(comp, slots=1)

    cfg = configs.get_reduced("tinyllama-1.1b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    e_lm = ServingEngine(model, params, slots=1, max_len=32)

    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(3, 4)).astype(np.int32)
    lm_reqs = [Request(rid=i, prompt=prompts[i], max_new=2,
                       deadline_ticks=2) for i in range(3)]
    an_reqs = _feature_reqs(3, deadline_ticks=2)
    for r in lm_reqs:
        e_lm.submit(r)
    for r in an_reqs:
        e_analog.submit(r)
    e_lm.run()
    e_analog.run()
    # slots=1: on both paths the first request serves and the last
    # expires; the LM path holds its slot for max_new=2 ticks, so its
    # queue drains slower and expires MORE — never fewer — requests
    for stats in (e_lm.stats, e_analog.stats):
        assert stats["served"] >= 1
        assert stats["served"] + stats["expired"] == 3
    assert e_lm.stats["expired"] >= e_analog.stats["expired"]
    assert all(r.done for r in lm_reqs + an_reqs)


# ---------------------------------------------------------------------------
# public surface audit
# ---------------------------------------------------------------------------

def test_serving_public_surface_is_exactly_the_engine_api():
    assert serving.__all__ == ["Request", "ServableProgram",
                               "ServingEngine", "as_servable"]
    for name in serving.__all__:
        assert getattr(serving, name) is not None
    assert "ServingEngine" in repro.__all__ and "Request" in repro.__all__
    assert repro.ServingEngine is ServingEngine
    assert repro.Request is Request

