"""Faults planted in the engine's output, underneath the harness."""
import pytest

#: each takes the rows the engine produced and returns them altered
FAULTS = {
    "alter_one_answer": lambda y: y.at[0].add(0.01),
    "leave_out_half": lambda y: y.at[y.shape[0] // 2:].set(0.0),
}


@pytest.fixture
def break_engine(monkeypatch):
    """``break_engine(name)`` makes every batch the engine applies come
    back with the fault ``FAULTS[name]`` in it."""
    from repro.core.engine import InferenceEngine
    apply = InferenceEngine.apply_batched

    def install(name):
        fault = FAULTS[name]

        def broken(self, x, **kw):
            return fault(apply(self, x, **kw))

        monkeypatch.setattr(InferenceEngine, "apply_batched", broken)

    return install
