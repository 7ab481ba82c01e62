"""The readers of the program's region, bridge and submit spans, on a
hand-made record of a traced run."""
import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import harness  # noqa: E402
from repro.obs import Span  # noqa: E402


def _span(name, t0, t1):
    return Span(name, "test", t0, t1, None, None, 0, "MainThread")


# one caller's spans of one step, in ms: region.call 2 (engine.get 0.25,
# bridge_in 0.5, queue.submit 1), region.result 3 (wait 1, bridge_out 1.5)
STEP = [("region.call", 0.0, 2.0), ("engine.get", 0.0, 0.25),
        ("region.bridge_in", 0.25, 0.75), ("queue.submit", 0.75, 1.75),
        ("batch.apply", 2.0, 9.0), ("serve.request", 0.75, 9.5),
        ("region.result", 10.0, 13.0), ("region.wait", 10.0, 11.0),
        ("region.bridge_out", 11.0, 12.5)]
PER_CALLER = {"region_ms_per_step": 5.0, "bridge_ms_per_step": 2.0,
              "submit_ms_per_step": 1.0}


def _rec(callers, steps):
    spans = [_span(name, 1e-3 * a, 1e-3 * b)
             for _ in range(callers * steps) for name, a, b in STEP]
    return {"steps": [(0.0, 1.0, 0.0)] * steps, "spans": spans,
            "trace": None}


@pytest.mark.parametrize("metric", sorted(PER_CALLER))
@pytest.mark.parametrize("callers,steps", [(1, 1), (4, 1), (64, 3)])
def test_reader_sums_its_spans_per_step(metric, callers, steps):
    got = harness.load_reader(metric)(_rec(callers, steps))
    assert got == pytest.approx(callers * PER_CALLER[metric])


@pytest.mark.parametrize("metric", sorted(PER_CALLER))
def test_reader_finds_nothing_without_its_spans(metric):
    read = harness.load_reader(metric)
    rec = _rec(4, 2)
    assert read({**rec, "spans": None}) is None
    assert read({**rec, "steps": []}) is None
    # a program that writes only the batch spans (as before the region
    # path had spans of its own) leaves the metric out
    names = {"batch.apply", "serve.request"}
    assert read({**rec, "spans": [s for s in rec["spans"]
                                  if s.name in names]}) is None

