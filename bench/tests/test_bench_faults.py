"""The timed path broken underneath: ``correct`` has to come out false."""

import pytest

import tiny


def _unchanged(orig):
    def make(loss_fn, lr, **kw):
        step = orig(loss_fn, lr, **kw)

        def stuck(params, *batch):
            return params, step(params, *batch)[1]
        return stuck
    return make


def _half_batch(orig):
    def make(loss_fn, lr, **kw):
        def half(params, *batch):
            b = batch[0].shape[0]
            return loss_fn(params, *[
                a[: b // 2] if getattr(a, "shape", ())[:1] == (b,) else a
                for a in batch])
        return orig(half, lr, **kw)
    return make


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("name", ["deep64_train_b1024",
                                  "mnist_fig14_train_b10"])
def test_training_fault_is_caught(monkeypatch, name, fault):
    from repro.train import step

    monkeypatch.setattr(step, "make_sgd_step", fault(step.make_sgd_step))
    assert not tiny.run_tiny(name)["correct"]


@pytest.mark.parametrize("name", ["deep64_serve_poisson",
                                  "deep64_serve_backlog"])
def test_altered_answer_is_caught(monkeypatch, name):
    from repro.compile.program import CompiledDeepProgram

    orig = CompiledDeepProgram.apply

    def altered(self, x):
        y = orig(self, x)
        return y.at[0].multiply(1.01)

    monkeypatch.setattr(CompiledDeepProgram, "apply", altered)
    result = tiny.run_tiny(name)
    assert not result["correct"]
    assert result["checks"]["out_gap"]["value"] > \
        result["checks"]["out_gap"]["limit"]
