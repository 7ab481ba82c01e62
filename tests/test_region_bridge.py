"""The data bridge's identity path: a region whose functor maps the
caller's array onto itself takes the array as its rows and gives the
served rows back without a bridge program; every other functor, and a
numpy input, keeps the program, and both paths agree bit for bit."""
import gc
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import binomial, bonds, minibude, miniweather, particlefilter
from repro.core import approx_ml, tensor_functor
from repro.core.engine import InferenceEngine
from repro.core.tensor_map import TensorMap
from repro.nn import MLP
from repro.nn.serialize import save_model
from repro.obs import default_registry
from repro.serve import FlushPolicy, ServeQueue

N = 6
QUICKSTART_IN = "ifnctr: [i, j, 0:5] = ([i-1,j],[i+1,j],[i,j-1:j+2])"


def _side(region, side):
    (functor, ranges), = getattr(region, side).values()
    return functor, ranges


# ------------------------------------------------------------ predicate ---
_APP_IDENTITIES = {
    "binomial-in": (lambda: _side(binomial.make_region(N), "inputs"),
                    (N, 5)),
    "binomial-out": (lambda: _side(binomial.make_region(N), "outputs"),
                     (N, 1)),
    "minibude-in": (lambda: _side(minibude.make_region(N), "inputs"),
                    (N, 6)),
    "minibude-out": (lambda: _side(minibude.make_region(N), "outputs"),
                     (N, 1)),
    "bonds-in": (lambda: _side(bonds.make_region(N), "inputs"), (N, 4)),
    "bonds-out": (lambda: _side(bonds.make_region(N), "outputs"), (N, 2)),
    "particlefilter-in": (
        lambda: _side(particlefilter.make_region(N), "inputs"),
        (N, particlefilter.H * particlefilter.W)),
    "particlefilter-out": (
        lambda: _side(particlefilter.make_region(N), "outputs"), (N, 2)),
    "miniweather-point-whole-grid": (
        lambda: (miniweather.point_fn,
                 {"i": (0, miniweather.NY), "j": (0, miniweather.NX)}),
        (miniweather.NY, miniweather.NX, miniweather.NF)),
}

_NOT_IDENTITIES = {
    "miniweather-point-interior": (miniweather.point_fn, miniweather.RANGES),
    "miniweather-stencil": (miniweather.stencil_fn, miniweather.RANGES),
    "quickstart-stencil": (tensor_functor(QUICKSTART_IN),
                           {"i": (1, 5), "j": (1, 5)}),
    "offset": (tensor_functor("f: [i, 0:5] = ([i+1, 0:5])"), {"i": (0, N)}),
    "feature-offset": (tensor_functor("f: [i, 0:1] = ([i, 1])"),
                       {"i": (0, N)}),
    "strided-sweep": (tensor_functor("f: [i, 0:5] = ([2*i, 0:5])"),
                      {"i": (0, N)}),
    "strided-range": (tensor_functor("f: [i, 0:5] = ([i, 0:5])"),
                      {"i": (0, N, 2)}),
    "strided-features": (tensor_functor("f: [i, 0:3] = ([i, 0:6:2])"),
                         {"i": (0, N)}),
    # features ahead of the sweep, at a square shape the map transposes
    "permuted-dims": (tensor_functor("f: [i, 0:5] = ([0:5, i])"),
                      {"i": (0, 5)}),
    "permuted-features": (tensor_functor("f: [i, 0:2] = ([i, 1], [i, 0])"),
                          {"i": (0, N)}),
    "column-subset": (tensor_functor("f: [i, 0:3] = ([i, 1:4])"),
                      {"i": (0, N)}),
    "sweep-window": (tensor_functor("f: [i, 0:2] = ([i:i+2])"),
                     {"i": (0, N)}),
}


@pytest.mark.parametrize("case", sorted(_APP_IDENTITIES))
def test_identity_shape_of_the_apps_declarations(case):
    make, shape = _APP_IDENTITIES[case]
    functor, ranges = make()
    tm = TensorMap(functor, None, ranges)
    assert tm.identity_shape() == shape
    # the claim itself: the map returns an array of that shape bit for bit
    x = jnp.asarray(np.random.default_rng(0).normal(size=shape)
                    .astype(np.float32))
    assert np.array_equal(np.asarray(TensorMap(functor, x, ranges)
                                     .to_tensor()), np.asarray(x))
    back = TensorMap(functor, jnp.zeros_like(x), ranges, "from")
    assert np.array_equal(np.asarray(back.from_tensor(x)), np.asarray(x))


@pytest.mark.parametrize("case", sorted(_NOT_IDENTITIES))
def test_identity_shape_is_none_for_other_functors(case):
    functor, ranges = _NOT_IDENTITIES[case]
    assert TensorMap(functor, None, ranges).identity_shape() is None


# --------------------------------------------------- both paths, served ---
def _bundle(tmp, f_in, f_out, name):
    net = MLP((1, f_in), [8], f_out)
    return save_model(tmp / name, net, net.init(jax.random.PRNGKey(0)))


def _no_accurate_path(**arrays):
    raise AssertionError("the surrogate serves every call here")


def _region(case, mode, model, queue=None, program=False):
    reg = approx_ml(
        _no_accurate_path, name=f"bridge-{case['name']}",
        inputs={k: (tensor_functor(d), r) for k, (d, r) in
                case["inputs"].items()},
        outputs={case["out"][0]: (tensor_functor(case["out"][1]),
                                  case["out"][2])},
        mode=mode, model=model, serving=queue)
    if program:  # the twin that always takes the bridge programs
        reg.__dict__["_identity_in"] = reg.__dict__["_identity_out"] = None
    return reg


def _arrays(case, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for k, shape in case["arrays"].items():
        x = rng.normal(size=shape).astype(np.float32)
        out[k] = x if case.get("numpy") else jnp.asarray(x)
    return out


_ROWS = {"i": (0, N)}
_CASES = [
    dict(name="identity", inputs={"x": ("a: [i, 0:5] = ([i, 0:5])", _ROWS)},
         arrays={"x": (N, 5)}, out=("y", "b: [i, 0:1] = ([i, 0:1])", _ROWS),
         f=(5, 1), paths=("identity", "identity")),
    dict(name="numpy", inputs={"x": ("a: [i, 0:5] = ([i, 0:5])", _ROWS)},
         arrays={"x": (N, 5)}, out=("y", "b: [i, 0:1] = ([i, 0:1])", _ROWS),
         f=(5, 1), paths=("program", "identity"), numpy=True),
    dict(name="offset", inputs={"x": ("a: [i, 0:5] = ([i+1, 0:5])", _ROWS)},
         arrays={"x": (N + 1, 5)},
         out=("y", "b: [i, 0:1] = ([i, 0:1])", _ROWS),
         f=(5, 1), paths=("program", "identity")),
    dict(name="strided", inputs={"x": ("a: [i, 0:5] = ([2*i, 0:5])", _ROWS)},
         arrays={"x": (2 * N, 5)},
         out=("y", "b: [i, 0:1] = ([i, 0:1])", _ROWS),
         f=(5, 1), paths=("program", "identity")),
    dict(name="permuted", inputs={"x": ("a: [i, 0:5] = ([0:5, i])",
                                        {"i": (0, 5)})},
         arrays={"x": (5, 5)},
         out=("y", "b: [i, 0:1] = ([i, 0:1])", {"i": (0, 5)}),
         f=(5, 1), paths=("program", "identity")),
    dict(name="columns", inputs={"x": ("a: [i, 0:3] = ([i, 1:4])", _ROWS)},
         arrays={"x": (N, 5)}, out=("y", "b: [i, 0:1] = ([i, 0:1])", _ROWS),
         f=(3, 1), paths=("program", "identity")),
    # identity functors, but the caller's arrays are wider than the window
    dict(name="wider", inputs={"x": ("a: [i, 0:3] = ([i, 0:3])", _ROWS)},
         arrays={"x": (N, 5), "y": (N, 2)},
         out=("y", "b: [i, 0:1] = ([i, 0:1])", _ROWS),
         f=(3, 1), paths=("program", "program")),
    dict(name="stencil", inputs={"t": (QUICKSTART_IN,
                                       {"i": (1, 5), "j": (1, 5)})},
         arrays={"t": (6, 6)},
         out=("t", "o: [i, j] = ([i, j])", {"i": (1, 5), "j": (1, 5)}),
         f=(5, 1), paths=("program", "program")),
    dict(name="multi-input",
         inputs={"a": ("a: [i, 0:2] = ([i, 0:2])", _ROWS),
                 "b": ("b: [i, 0:3] = ([i, 0:3])", _ROWS)},
         arrays={"a": (N, 2), "b": (N, 3)},
         out=("y", "c: [i, 0:1] = ([i, 0:1])", _ROWS),
         f=(5, 1), paths=("program", "identity")),
    # a field-to-field region that writes its input array back in place
    dict(name="field", inputs={"s": ("a: [i, j, 0:4] = ([i, j, 0:4])",
                                     {"i": (0, 3), "j": (0, 4)})},
         arrays={"s": (3, 4, 4)},
         out=("s", "b: [i, j, 0:4] = ([i, j, 0:4])",
              {"i": (0, 3), "j": (0, 4)}),
         f=(4, 4), paths=("identity", "identity")),
]


def _bridges(region: str) -> dict:
    snap = default_registry().collect().get("repro_region_bridge_total",
                                            {"values": []})
    return {(v["labels"]["direction"], v["labels"]["path"]): v["value"]
            for v in snap["values"] if v["labels"]["region"] == region}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("mode", ["infer", "infer_async"])
@pytest.mark.parametrize("case", _CASES, ids=[c["name"] for c in _CASES])
def test_served_outputs_match_the_program_path(tmp_path, case, mode):
    """Both modes give the program path's outputs bit for bit, and count
    one bridge a direction a call, on the path the functor allows."""
    path = _bundle(tmp_path, *case["f"], name=case["name"])
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30)) \
        if mode == "infer_async" else None
    fast = _region(case, mode, path, q)
    slow = _region(case, mode, path, q, program=True)

    def call(reg, arrays):
        out = reg(**arrays)
        if mode == "infer_async":
            q.flush(path)
            out = out.result(30)
        return out

    for seed in (1, 2):
        arrays = _arrays(case, seed)
        before = _bridges(fast.name)
        got = call(fast, arrays)
        assert _delta(_bridges(fast.name), before) == {
            ("in", case["paths"][0]): 1, ("out", case["paths"][1]): 1}
        want = call(slow, arrays)
        assert sorted(got) == sorted(want)
        for k in want:
            assert isinstance(got[k], jax.Array)
            assert got[k].dtype == want[k].dtype
            assert got[k].shape == want[k].shape
            assert np.array_equal(np.asarray(got[k]), np.asarray(want[k]))


IDENTITY = _CASES[0]


def test_identity_input_is_the_callers_array(tmp_path):
    case = IDENTITY
    path = _bundle(tmp_path, 5, 1, "m")
    reg = _region(case, "infer", path)
    x = _arrays(case)["x"]
    assert reg._rows_in(InferenceEngine.get(path), {"x": x}) is x


def test_numpy_input_is_copied_before_the_flush(tmp_path):
    """A numpy input keeps the program, which copies it: the app may
    change its array between the region call and the flush."""
    case = dict(IDENTITY, numpy=True)
    path = _bundle(tmp_path, 5, 1, "m")
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    reg = _region(case, "infer_async", path, q)
    x = _arrays(case)["x"]
    want = reg(x=x.copy())
    q.flush(path)
    want = np.asarray(want.result(30)["y"])
    before = _bridges(reg.name)
    handle = reg(x=x)
    x[:] = 1e3
    q.flush(path)
    got = handle.result(30)["y"]
    assert _delta(_bridges(reg.name), before) == {("in", "program"): 1,
                                                  ("out", "identity"): 1}
    assert np.array_equal(np.asarray(got), want)


def test_identity_output_outlives_the_reused_scratch_buffer(tmp_path):
    """The output's one put copies the served rows: the batcher's pooled
    buffer they came from serves the next batch, and the first output
    keeps its values."""
    case = IDENTITY
    path = _bundle(tmp_path, 5, 1, "m")
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    reg = _region(case, "infer_async", path, q)
    scratch = q._batcher.scratch

    def served(seed):
        handles = [reg(**_arrays(case, seed + k)) for k in range(2)]
        q.flush(path)
        return [h.result(30)["y"] for h in handles]

    first = served(10)
    kept = [np.array(y) for y in first]
    gc.collect()  # no view of the first batch's buffer is left
    hits = scratch.stats()["hits"]
    second = served(20)
    assert scratch.stats()["hits"] > hits
    assert not any(np.array_equal(a, np.asarray(b))
                   for a, b in zip(kept, second))
    for y, k in zip(first, kept):
        assert isinstance(y, jax.Array)
        assert np.array_equal(np.asarray(y), k)


def test_first_identity_call_compiles_no_bridge(tmp_path):
    """With the engine warm, a fresh identity region's first call
    compiles nothing; its program twin compiles its bridges."""
    compiles = default_registry().counter(
        "repro_xla_compile_total", "XLA backend compiles in this process")
    case = IDENTITY
    path = _bundle(tmp_path, 5, 1, "m")
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    arrays = _arrays(case)

    def first_call(**kw):
        reg = _region(case, "infer_async", path, q, **kw)
        before = compiles.value()
        h = reg(**arrays)
        q.flush(path)
        jax.block_until_ready(h.result(30))
        return compiles.value() - before

    first_call()  # warms the engine's apply at this bucket
    assert first_call() == 0
    assert first_call(program=True) > 0


_DEVICES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import jax, numpy as np
from test_region_bridge import _CASES, _arrays, _bundle, _region
from repro.serve import FlushPolicy, ServeQueue
import pathlib, tempfile
tmp = pathlib.Path(tempfile.mkdtemp())
q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
dev = jax.devices()[1]
cases = {{c["name"]: c for c in _CASES}}
for name, f in (("identity", (5, 1)), ("field", (4, 4))):
    case = cases[name]
    path = _bundle(tmp, *f, name=name)
    for committed in (True, False):
        arrays = {{k: jax.device_put(x, dev) if committed else x
                   for k, x in _arrays(case).items()}}
        outs = []
        for program in (False, True):
            reg = _region(case, "infer_async", path, q, program=program)
            h = reg(**arrays)
            q.flush(path)
            outs.append(h.result(30)[case["out"][0]])
        fast, slow = outs
        assert fast.devices() == slow.devices(), (fast.devices(),
                                                  slow.devices())
        assert fast.committed == slow.committed
        assert np.array_equal(np.asarray(fast), np.asarray(slow))
        # the program writes into the caller's array, so lands beside it
        if name == "field" and committed:
            assert fast.devices() == {{dev}} and fast.committed
print("ok")
"""


def test_identity_output_lands_where_the_program_would(tmp_path):
    """Inputs committed to a non-default device, or not: the identity
    output lands on the program's output's device, committed alike; an
    output written into the caller's committed array lands beside it."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = _DEVICES.format(src=os.path.join(os.path.dirname(here), "src"),
                           tests=here)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("ok")
