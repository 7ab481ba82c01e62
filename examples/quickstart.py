"""Quickstart: the HPAC-ML programming model in 60 lines.

Mirrors the paper's Fig. 2: a 2-D stencil region annotated with tensor
functors, run in collect mode, then replaced by a surrogate.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import pathlib
import tempfile

import jax
import jax.numpy as jnp

from repro.core import SurrogateDB, approx_ml, tensor_functor
from repro.launch.compile_cache import enable_compile_cache
from repro.nas.train_surrogate import fit
from repro.nn import MLP
from repro.nn.serialize import save_model

N = M = 34

# --- declare the data bridge (paper Fig. 2 syntax) -------------------------
ifn = tensor_functor("ifnctr: [i, j, 0:5] = ([i-1,j],[i+1,j],[i,j-1:j+2])")
ofn = tensor_functor("ofnctr: [i, j] = ([i,j])")
RANGES = {"i": (1, N - 1), "j": (1, M - 1)}


# --- the accurate execution path -------------------------------------------
def smooth_step(t):
    """5-point smoothing: the computation the surrogate will replace."""
    interior = 0.2 * (t[1:-1, 1:-1] + t[:-2, 1:-1] + t[2:, 1:-1]
                      + t[1:-1, :-2] + t[1:-1, 2:])
    return {"t": t.at[1:-1, 1:-1].set(interior)}


def main():
    enable_compile_cache()
    tmp = pathlib.Path(tempfile.mkdtemp())
    t = jax.random.normal(jax.random.PRNGKey(0), (N, M))

    # 1) collect training data while running the real code
    region = approx_ml(smooth_step, name="smooth",
                       inputs={"t": (ifn, RANGES)},
                       outputs={"t": (ofn, RANGES)},
                       mode="collect", database=str(tmp / "db"))
    state = t
    for _ in range(64):
        state = region(t=state)["t"]
    region.db.flush()

    # 2) train a surrogate offline from the database
    d = region.db.group("smooth").load()
    X = d["inputs"].reshape(-1, 5)
    Y = d["outputs"].reshape(-1, 1)
    net = MLP((1, 5), [32], 1)
    params, rmse, stats = fit(net, X, Y, epochs=40)
    mp = save_model(tmp / "model", net, params, extra=stats)
    print(f"collected {X.shape[0]} samples; surrogate val RMSE={rmse:.5f}")

    # 3) same region, now predicated: accurate and surrogate paths coexist
    region2 = approx_ml(smooth_step, name="smooth",
                        inputs={"t": (ifn, RANGES)},
                        outputs={"t": (ofn, RANGES)},
                        mode="predicated", model=str(mp))
    ref = smooth_step(t)["t"]
    ml = region2(predicate=True, t=t)["t"]
    acc = region2(predicate=False, t=t)["t"]
    print("surrogate RMSE vs accurate:",
          float(jnp.sqrt(jnp.mean((ml - ref) ** 2))))
    print("accurate path exact:", bool(jnp.allclose(acc, ref)))


if __name__ == "__main__":
    main()
