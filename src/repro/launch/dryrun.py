import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. compiles the full scanned-layer program on the production mesh and
     prints ``memory_analysis()`` (fits per chip?) and ``cost_analysis()``;
  2. compiles R=1 and R=2 *unrolled* calibration variants: XLA's cost
     analysis counts a `while` body once, so per-layer FLOPs/bytes/
     collective-bytes are obtained as the difference, and totals as
     ``outside + R * per_layer`` (SSM chunk scans stay as inner while loops;
     their loop-body compute is <2% of total FLOPs — documented);
  3. emits the three roofline terms + dominant bottleneck to a JSON artifact
     consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs.base import (SHAPES, all_configs, cell_supported,
                                get_config, with_repeats)
from repro.dist.hlo_analysis import (Roofline, collective_stats)
from repro.dist.sharding import use_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _compile(cfg, shape, mesh, multi_pod):
    with use_mesh(mesh, multi_pod):
        cell = build_cell(cfg, shape, mesh, multi_pod)
        jitted = jax.jit(cell["fn"], donate_argnums=cell["donate"],
                         out_shardings=cell["out_shardings"])
        lowered = jitted.lower(*cell["args"])
        compiled = lowered.compile()
    return lowered, compiled


def _cost(compiled):
    ca = compiled.cost_analysis() or {}
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def _model_flops(cfg, shape):
    n_active = cfg.param_counts()["active"]
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: pathlib.Path,
             force: bool = False, variant: str = "baseline",
             cfg_override=None, shape_override=None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}__{variant}"
    outpath = outdir / f"{tag}.json"
    if outpath.exists() and not force:
        return json.loads(outpath.read_text())

    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = shape_override if shape_override is not None else SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant}
    if not ok:
        rec["status"] = why
        outdir.mkdir(parents=True, exist_ok=True)
        outpath.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for v in mesh.shape.values():
        chips *= v
    try:
        # --- full compile: proves the cell lowers/partitions/fits ---
        t0 = time.time()
        lowered, compiled = _compile(cfg, shape, mesh, multi_pod)
        rec["compile_s"] = round(time.time() - t0, 1)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_chip_bytes": (ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
        }
        # exact per-chip resident bytes from the sharded input spec trees
        # (HLO temp bytes are inflated on the CPU backend, which emulates
        # bf16 arithmetic via f32 converts; see EXPERIMENTS.md methodology)
        with use_mesh(mesh, multi_pod):
            cell_shapes = build_cell(cfg, shape, mesh, multi_pod)["args"]

        def _shard_bytes(leaf):
            if not hasattr(leaf, "sharding") or leaf.sharding is None:
                return leaf.size * leaf.dtype.itemsize
            shard = leaf.sharding.shard_shape(leaf.shape)
            n = 1
            for s in shard:
                n *= s
            return n * leaf.dtype.itemsize

        rec["resident_per_chip_bytes"] = int(sum(
            _shard_bytes(l) for l in jax.tree.leaves(cell_shapes)))
        # analytic activation estimate: remat saves one residual-stream
        # carry per pattern repeat (bf16), sharded over batch (+seq for
        # attention archs)
        dshard = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        seq_shardable = not any(
            s.mixer in ("rwkv6", "mamba")
            for s in list(cfg.pattern) + list(cfg.prefix))
        sshard = mesh.shape.get("model", 1) if seq_shardable else 1
        if shape.kind == "train":
            carry = (shape.global_batch * shape.seq_len * cfg.d_model * 2
                     / (dshard * sshard))
            saved = carry * cfg.pattern_repeats
        else:
            saved = 0.0
        rec["analytic"] = {
            "resident_bytes": rec["resident_per_chip_bytes"],
            "saved_carries_bytes": int(saved),
            "est_hbm_per_chip": int(rec["resident_per_chip_bytes"] + saved),
        }
        rec["fits_16GB_analytic"] = rec["analytic"]["est_hbm_per_chip"] < 16e9
        rec["fits_16GB_hlo_cpu_inflated"] = (
            rec["memory"]["peak_per_chip_bytes"] < 16e9)
        f_full, b_full = _cost(compiled)
        st_full = collective_stats(compiled.as_text())
        rec["raw_full"] = {"flops": f_full, "bytes": b_full,
                           "coll_bytes": st_full.total_bytes,
                           "coll_counts": st_full.per_kind_count}

        # --- calibration: unrolled R=1 / R=2 ---
        R = cfg.pattern_repeats
        cal = {}
        for r in (1, 2):
            c = with_repeats(cfg, r).replace(scan_layers=False,
                                             unroll_inner=True)
            _, comp_r = _compile(c, shape, mesh, multi_pod)
            fl, by = _cost(comp_r)
            st = collective_stats(comp_r.as_text())
            cal[r] = (fl, by, st)
        per_layer_f = max(0.0, cal[2][0] - cal[1][0])
        per_layer_b = max(0.0, cal[2][1] - cal[1][1])
        per_layer_c = {k: max(0.0, cal[2][2].per_kind_bytes.get(k, 0)
                              - cal[1][2].per_kind_bytes.get(k, 0))
                       for k in set(cal[1][2].per_kind_bytes)
                       | set(cal[2][2].per_kind_bytes)}
        flops_dev = cal[1][0] + per_layer_f * (R - 1)
        bytes_dev = cal[1][1] + per_layer_b * (R - 1)
        coll_dev = sum(cal[1][2].per_kind_bytes.values()) + \
            sum(per_layer_c.values()) * (R - 1)
        coll_kinds = {k: cal[1][2].per_kind_bytes.get(k, 0)
                      + per_layer_c.get(k, 0) * (R - 1)
                      for k in set(cal[1][2].per_kind_bytes) | set(per_layer_c)}
        # bf16-on-the-wire correction (see CollectiveStats.corrected_bytes)
        per_layer_corr = max(0.0, cal[2][2].corrected_bytes
                             - cal[1][2].corrected_bytes)
        coll_dev_corr = cal[1][2].corrected_bytes + per_layer_corr * (R - 1)

        roof = Roofline(flops_global=flops_dev * chips,
                        hbm_bytes_global=bytes_dev * chips,
                        coll_bytes_global=coll_dev_corr * chips,
                        chips=chips,
                        model_flops=_model_flops(cfg, shape))
        rec["coll_bytes_raw_per_dev"] = coll_dev
        rec["coll_bytes_corrected_per_dev"] = coll_dev_corr
        rec["roofline"] = roof.to_dict()
        rec["coll_bytes_per_kind_per_dev"] = coll_kinds
        rec["params_total"] = cfg.param_counts()["total"]
        rec["params_active"] = cfg.param_counts()["active"]
        rec["status"] = "ok"
    except Exception as e:  # record the failure; dry-run failures are bugs
        rec["status"] = f"FAIL: {type(e).__name__}: {str(e)[:400]}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    outdir.mkdir(parents=True, exist_ok=True)
    outpath.write_text(json.dumps(rec, indent=1))
    return rec


def run_smoke(outdir: pathlib.Path, force: bool = False) -> dict:
    """Compile one tiny sharded train cell on the 256-chip host mesh.

    Fast proof (CI smoke) that the dist substrate partitions a real
    program: must report non-zero collective bytes or it exits non-zero.
    """
    from repro.configs.base import LayerSpec, ModelConfig, ShapeCell
    tiny = ModelConfig(name="smoke-tiny", n_layers=2, d_model=256,
                       n_heads=16, n_kv_heads=8, head_dim=16, d_ff=512,
                       vocab_size=1024, pattern=(LayerSpec(),))
    shape = ShapeCell("smoke_train", 512, 256, "train")
    rec = run_cell("smoke-tiny", "smoke_train", False, outdir, force=force,
                   variant="smoke", cfg_override=tiny, shape_override=shape)
    coll = rec.get("raw_full", {}).get("coll_bytes", 0)
    print(f"[smoke] status={rec.get('status')} "
          f"compile={rec.get('compile_s', 0)}s "
          f"coll_bytes/dev={coll:.3e} "
          f"counts={rec.get('raw_full', {}).get('coll_counts')}", flush=True)
    if rec.get("status") != "ok" or not coll:
        raise SystemExit(f"smoke cell failed: {rec.get('status')} "
                         f"coll_bytes={coll}")
    return rec


def run_tune(bundle=None, buckets=(64, 256, 1024), force=False,
             kernels="all"):
    """Pre-populate the kernel autotune caches (artifacts/tune/<kernel>.json).

    The registry dispatch consults the kernel-namespaced caches at trace
    time (``repro.kernels.registry.dispatch`` ->
    ``repro.tune.cache.best_params``); running this at deploy — per
    surrogate bundle for fused_mlp, plus every registered kernel's
    representative problems (flash_attention block sizes, stencil_gather
    tiles) — means the first real dispatch already runs the
    measured-best config instead of the hardcoded defaults.
    """
    from repro.tune import autotune, autotune_registered
    names = None if kernels in ("all", None) else \
        [k.strip() for k in kernels.split(",") if k.strip()]
    if names is None or "fused_mlp" in names:
        targets = [bundle] if bundle else [[5, 128, 128, 1],
                                           [16, 256, 256, 4]]
        for t in targets:
            recs = autotune(t, list(buckets), force=force, verbose=True)
            wins = sum(1 for r in recs if r["exact"])
            print(f"[tune] fused_mlp {t}: {wins}/{len(recs)} buckets tuned",
                  flush=True)
        if names is not None:
            names = [k for k in names if k != "fused_mlp"]
            if not names:
                return
    else:
        names = names or []
    recs = autotune_registered(names, force=force, verbose=True)
    wins = sum(1 for r in recs if r["exact"])
    print(f"[tune] registered kernels: {wins}/{len(recs)} problems tuned",
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--pod-smoke", action="store_true",
                    help="spawn a real 2-process pod (jax.distributed on "
                         "CPU) and run the cross-host serve round-trip — "
                         "the multi-process counterpart of --smoke's "
                         "single-process 512-device fiction")
    ap.add_argument("--pod-processes", type=int, default=2)
    ap.add_argument("--obs", action="store_true",
                    help="with --pod-smoke: run the pod with tracing on, "
                         "all-gather every host's spans/metrics "
                         "(obs.pod_snapshot) and write the merged Chrome "
                         "trace to artifacts/obs/pod_trace.json")
    ap.add_argument("--shadow-rate", type=float, default=None,
                    help="with --pod-smoke: shadow-score this fraction of "
                         "served requests per host (default 1.0 with "
                         "--obs) and report cross-host drift state")
    ap.add_argument("--tune", action="store_true",
                    help="pre-populate the kernel autotune cache for the "
                         "serve-path shapes (see repro.tune)")
    ap.add_argument("--tune-bundle", default=None,
                    help="--tune: autotune this bundle's widths instead of "
                         "the NAS-representative defaults")
    ap.add_argument("--tune-buckets", default="64,256,1024",
                    help="--tune: comma-separated batch buckets to sweep")
    ap.add_argument("--tune-kernels", default="all",
                    help="--tune: comma-separated registered kernels to "
                         "pre-populate (default: all)")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()
    outdir = pathlib.Path(args.out)

    if args.tune:
        run_tune(args.tune_bundle,
                 [int(b) for b in args.tune_buckets.split(",")],
                 force=args.force, kernels=args.tune_kernels)
        return

    if args.pod_smoke:
        # children build their own device view (spawn_local_pod overrides
        # XLA_FLAGS per child); the parent never initializes jax here
        from repro.launch.multihost import run_smoke as run_pod_smoke
        obs_out = None
        if args.obs:
            obs_out = str(ARTIFACTS.parent / "obs" / "pod_trace.json")
        run_pod_smoke(processes=args.pod_processes, obs_out=obs_out,
                      shadow_rate=args.shadow_rate)
        return

    if args.obs:
        ap.error("--obs needs --pod-smoke (the flight recorder is a pod "
                 "collective)")

    if args.smoke:
        run_smoke(outdir, force=args.force)
        return

    if args.all:
        jobs = []
        for arch in all_configs():
            for shape in SHAPES:
                for mp in (False, True):
                    jobs.append((arch, shape, mp))
    else:
        jobs = [(args.arch, args.shape, args.multi_pod)]

    for arch, shape, mp in jobs:
        t0 = time.time()
        rec = run_cell(arch, shape, mp, outdir, force=args.force)
        status = rec.get("status", "?")
        roof = rec.get("roofline", {})
        print(f"[{arch} x {shape} x {'2x16x16' if mp else '16x16'}] "
              f"{status} compile={rec.get('compile_s', 0)}s "
              f"mem/chip={rec.get('memory', {}).get('peak_per_chip_bytes', 0)/1e9:.2f}GB "
              f"dom={roof.get('dominant', '-')} "
              f"t_step={roof.get('step_time_s', 0)*1e3:.2f}ms "
              f"useful={roof.get('useful_flops_fraction', 0)*100:.0f}%",
              flush=True)
        if "memory" in rec:
            print(f"   memory_analysis: {rec['memory']}", flush=True)
        if "raw_full" in rec:
            print(f"   cost_analysis(full, per-dev, body-once): "
                  f"{rec['raw_full']['flops']:.3e} flops; collectives: "
                  f"{rec['raw_full']['coll_counts']}", flush=True)


if __name__ == "__main__":
    main()
