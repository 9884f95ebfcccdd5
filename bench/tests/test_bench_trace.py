"""The trace reduction, on a trace recorded on a v5e (three ticks of the
deep-grid inference kernel at 64 rows) and on hand-made intervals."""

import json
import pathlib

import pytest

import run

trace = run.load_module(".", "trace")
DATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


def test_leaves_union_and_charge_by_hand():
    # a loop (0..100) around two operations; one more at 150..160
    events = [(0, 100, "while.1", False), (10, 40, "fusion.2", False),
              (60, 90, "k.3", True), (150, 160, "fusion.4", False)]
    kept = trace.leaves(events)
    assert [e[2] for e in kept] == ["fusion.2", "k.3", "fusion.4"]
    busy, gaps = trace.union(kept)
    assert busy == 70 and gaps == [(40, 60), (90, 150)]
    host = [(35, 70, "PjitFunction(f)"), (80, 200, "np.asarray")]
    assert trace.charge([(300, 20_300), (90, 150)], host) == pytest.approx(
        {"no host event": 20e-6, trace.SHORT_GAPS: 60e-9})
    assert trace.charge([(60, 20_060)], host) == pytest.approx(
        {"np.asarray": 20e-6})
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(...)") == "fusion.12"
    assert trace.kernel_name("jvp_jit__deep_apply_impl__.1") == \
        "jvp_jit__deep_apply_impl__"


def test_recorded_chip_trace():
    pb = DATA / "deepgrid_3_ticks.xplane.pb"
    if not pb.is_file():
        pytest.fail(f"missing recorded trace {pb}")
    from jax.profiler import ProfileData

    meta = json.loads((DATA / "deepgrid_3_ticks.json").read_text())
    red = trace.reduce_profile(ProfileData.from_file(str(pb)),
                               window_s=meta["window_s"], n_devices=1)
    assert red.n_devices == 1
    calls = red.kernels["_deep_apply_impl"]
    assert len(calls) == 3
    assert 0 < sum(calls) <= red.busy_s < red.window_s
    assert all(" = " not in name for name in red.ops)
    br = red.breakdown()
    assert br["device_ops"][0][0].startswith("_deep_apply_impl")
    assert sum(v for _, v in br["idle_gaps"]) <= red.window_s - red.busy_s
