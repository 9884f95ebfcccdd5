"""Every cell, end to end at a tiny size on the CPU, comes out correct,
and its traced run reads its per-layer metrics without a device."""

import pytest

import tiny

CELLS = ["deep64_train_b1024", "deep64_serve_poisson",
         "deep64_serve_backlog", "mnist_fig14_train_b10"]


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_is_correct(name):
    result = tiny.run_tiny(name)
    assert result["correct"], result["checks"]
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "setup_s" in result["metrics"]
    assert all(v["value"] > 0 for v in result["metrics"].values())
