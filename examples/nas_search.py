"""Standalone nested-BO surrogate search (paper §V-C) for any benchmark.

Run:  PYTHONPATH=src python examples/nas_search.py --app binomial --n 2048
"""
import argparse
import pathlib
import tempfile

from repro.apps import ALL_APPS
from repro.launch.compile_cache import enable_compile_cache
from repro.nas.nested import best_trial, nested_search, save_trial


def collect(app_name, app, n, db_path):
    if app_name == "miniweather":
        region = app.make_region(mode="collect", database=db_path)
        s = app.init_state()
        for _ in range(n):
            s = region(state=s)["state"]
    elif app_name == "particlefilter":
        frames, _ = app.make_video(n)
        region = app.make_region(n, mode="collect", database=db_path)
        region(frames=frames.reshape(n, -1))
    else:
        x = app.make_inputs(n)
        region = app.make_region(n, mode="collect", database=db_path)
        key = [k for k in region.inputs][0]
        region(**{key: x})
    region.db.flush()
    return region.db


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="binomial", choices=list(ALL_APPS))
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--outer", type=int, default=8)
    ap.add_argument("--inner", type=int, default=4)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    app = ALL_APPS[args.app]
    tmp = pathlib.Path(args.out or tempfile.mkdtemp())
    db = collect(args.app, app, args.n, str(tmp / "db"))
    res = nested_search(app, db.group(args.app),
                        outer_iters=args.outer, inner_iters=args.inner)
    print(f"\nexplored {len(res['trials'])} architectures; Pareto front:")
    for i in res["pareto"]:
        t = res["trials"][i]
        print(f"  {t['arch']}  rmse={t['val_rmse']:.5f} "
              f"lat={t['latency']*1e3:.2f}ms")
    bt = best_trial(res)
    mp = save_trial(bt, tmp / "model")
    print(f"best model saved to {mp}")


if __name__ == "__main__":
    main()
