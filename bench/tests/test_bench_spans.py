"""The program's spans on the trace (``bench/spans.py``), the readers of
the engine and compilation metrics, and the compilation counter."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import spans
import tiny
from repro.runtime import compile_cache

class Ctx:
    """What a reader is handed: the trace directory and the run's notes."""

    def __init__(self, trace_dir):
        self.trace_dir = pathlib.Path(trace_dir)
        self.observed = {}

    def observe(self, **kw):
        self.observed.update(kw)


def read(name, ctx):
    return run.metric_reader(name).read(ctx, {"name": name})


# two ticks on the engine's thread (line 0), one 5 us event of JAX's on
# the same line, and an engine span on a line that is not Python's
EVENTS = [  # (line, name, start us, duration us, stats)
    (0, "engine.expire", 0, 10, {"tick": 1}),
    (0, "engine.admit", 10, 20, {"tick": 1, "n": 3, "wait_s": 0.006,
                                 "depth": 1}),
    (0, "engine.panel", 30, 10, {"tick": 1}),
    (0, "engine.put", 40, 10, {"tick": 1}),
    (0, "engine.launch", 50, 10, {"tick": 1}),
    (0, "engine.fetch", 60, 1000, {"tick": 1}),
    (0, "np.asarray(jax.Array)", 70, 5, {}),
    (0, "engine.complete", 1060, 40, {"tick": 1}),
    (0, "engine.expire", 1100, 10, {"tick": 2}),
    (0, "engine.admit", 1110, 20, {"tick": 2, "n": 1, "wait_s": 0.002,
                                   "depth": 0}),
    (0, "engine.panel", 1130, 10, {"tick": 2}),
    (0, "engine.put", 1140, 10, {"tick": 2}),
    (0, "engine.launch", 1150, 10, {"tick": 2}),
    (0, "engine.fetch", 1160, 3000, {"tick": 2}),
    (0, "repro.compile", 2000, 1, {"fun_name": "jit(f)", "seconds": 0.5}),
    (0, "engine.complete", 4160, 40, {"tick": 2}),
    (1, "engine.fetch", 0, 99, {"tick": 9}),
]


def _text_proto(events) -> str:
    names = sorted({e[1] for e in events})
    stats = sorted({k for e in events for k in e[4]})
    lines = []
    for k, line_name in enumerate(("python3", "tf_compile")):
        evs = []
        for line, name, start, dur, st in events:
            if line != k:
                continue
            body = "".join(
                f" stats {{ metadata_id: {100 + stats.index(s)} "
                + (f"int64_value: {v} }}" if isinstance(v, int) else
                   f"double_value: {v} }}" if isinstance(v, float) else
                   f'str_value: "{v}" }}')
                for s, v in st.items())
            evs.append(f"events {{ metadata_id: {1 + names.index(name)} "
                       f"offset_ps: {start * 10**6} "
                       f"duration_ps: {dur * 10**6}{body} }}")
        lines.append(f'lines {{ id: {k} name: "{line_name}" '
                     f'timestamp_ns: 0 {" ".join(evs)} }}')
    meta = "".join(f'event_metadata {{ key: {1 + i} value {{ id: {1 + i} '
                   f'name: "{n}" }} }} ' for i, n in enumerate(names))
    meta += "".join(f'stat_metadata {{ key: {100 + i} value {{ id: '
                    f'{100 + i} name: "{s}" }} }} '
                    for i, s in enumerate(stats))
    return (f'planes {{ id: 1 name: "/host:CPU" {" ".join(lines)} '
            f'{meta} }}')


@pytest.fixture
def hand_built(tmp_path):
    from jax.profiler import ProfileData

    pb = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _text_proto(EVENTS)))
    return Ctx(tmp_path)


def test_spans_of_a_hand_built_profile(hand_built):
    got = spans.of_cell(hand_built)
    assert [s.name for s in got if s.thread == 0][:3] == [
        "engine.expire", "engine.admit", "engine.panel"]
    # only the program's spans on Python lines: JAX's event and the span
    # on the compiler's line are left out
    assert len(got) == 15
    assert {s.thread for s in got} == {0}
    assert got[1].stats == {"tick": 1, "n": 3, "wait_s": 0.006, "depth": 1}
    t = spans.ticks(got)
    assert t.n == 2
    assert t.host_s == pytest.approx(2 * 100e-6)
    assert t.wait_s == pytest.approx(4000e-6)


def test_readers_on_a_hand_built_profile(hand_built):
    assert read("tick_host_ms.serve", hand_built) == pytest.approx(0.1)
    assert read("tick_wait_ms.backlog", hand_built) == pytest.approx(2.0)
    # (6 ms + 2 ms) of queue wait over 4 requests
    assert read("queue_wait_ms.serve", hand_built) == pytest.approx(2.0)
    assert read("window_compiles.train", hand_built) == 1


def test_readers_return_nothing_without_the_programs_spans(tmp_path,
                                                          monkeypatch):
    ctx = Ctx(tmp_path)            # no trace at all
    for name in ("tick_host_ms", "tick_wait_ms", "queue_wait_ms"):
        assert read(f"{name}.serve", ctx) is None
    assert read("window_compiles.serve", ctx) == 0
    monkeypatch.delattr(compile_cache, "COMPILES")
    assert read("window_compiles.serve", Ctx(tmp_path)) is None


def _traced(trace_dir, fn):
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return Ctx(trace_dir)


def test_window_compiles_counts_compiles_inside_the_window(tmp_path):
    compile_cache.use_compile_cache()
    f = jax.jit(lambda x: x * 2 + 1)
    old, new = jnp.ones(3), jnp.ones(5)
    f(old).block_until_ready()
    before = _traced(tmp_path / "a", lambda: f(old).block_until_ready())
    assert read("window_compiles.serve", before) == 0
    inside = _traced(tmp_path / "b", lambda: f(new).block_until_ready())
    assert read("window_compiles.serve", inside) == 1
    (compile,) = [s for s in spans.of_cell(inside)
                  if s.name == "repro.compile"]
    assert "lambda" in compile.stats["fun_name"]
    assert compile.stats["seconds"] > 0


def test_compiles_counts_a_persistent_cache_load(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    compile_cache.use_compile_cache()
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    hits = []

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    try:
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0)
        jax.config.update(keys[2], -1)
        compilation_cache.reset_cache()
        x = np.arange(7, dtype=np.float32)

        def build():
            return jax.jit(lambda v: v * 3 - 2)(x).block_until_ready()

        name = "jit(<lambda>)"
        n0 = compile_cache.COMPILES.get(name, 0)
        build()
        assert compile_cache.COMPILES[name] == n0 + 1 and not hits
        jax.clear_caches()
        build()
        assert hits, "the second build did not load from the cache"
        assert compile_cache.COMPILES[name] == n0 + 2
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_idle_gaps_are_charged_to_the_engine_phase(hand_built):
    """A device gap inside a fetch goes to ``engine.fetch``, which holds
    JAX's own event there, and not to that event."""
    from jax.profiler import ProfileData

    trace = spans.trace
    pb = next(hand_built.trace_dir.rglob("*.xplane.pb"))
    host = trace._host_events(ProfileData.from_file(str(pb)).planes, 1e12)
    gaps = [(65_000, 75_000), (1_065_000, 1_095_000)]
    assert trace.charge(gaps, host) == pytest.approx(
        {"engine.fetch": 10e-6, "engine.complete": 30e-6})


@pytest.mark.parametrize("name,kind", [("deep64_serve_poisson", "serve"),
                                       ("deep64_serve_backlog", "backlog"),
                                       ("deep64_train_b1024", "train")])
def test_traced_tiny_cell_reads_the_new_metrics(name, kind):
    result = tiny.run_tiny(name, trace=True)
    assert result["correct"], result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics[f"window_compiles.{kind}"] == 0
    if kind == "train":
        return
    assert metrics[f"tick_host_ms.{kind}"] > 0
    assert metrics[f"tick_wait_ms.{kind}"] > 0
    if kind == "serve":
        assert metrics["queue_wait_ms.serve"] > 0
