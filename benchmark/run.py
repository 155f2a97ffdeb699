"""One cell, once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration
(``benchmark/configs/``), its traffic mix (``benchmark/traffic/<traffic>.json``,
which names the traffic kind under ``benchmark/kinds/``) and each metric
(``benchmark/metrics/<name>.json``, which names its reader under
``benchmark/readers/``) are files found by name. This file has no branch on a
cell's, a configuration's or a metric's name.

Without platform ``tpu`` it refuses to measure. ``--rehearse`` runs the same
code on the CPU at the program's tiny test configuration and prints a line
that says so; that line is never a result.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced).
"""

import time

_T_START = time.monotonic()  # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

from benchmark import arith, harness  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny configuration on the CPU; never a result")
    return p.parse_args(argv)


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def prepare(argv=None, t_start=None, bench=None):
    """Everything before the traffic kind runs: the cell's files, the device
    check, the compile cache, the context. (None, None, None) where the
    device check refuses. ``bench`` is ``BENCHMARK.json`` unless a test hands
    in a dict of the same form."""
    t_start = _T_START if t_start is None else t_start
    args = parse(argv)
    bench = bench or harness.load_bench()
    cell = harness.load_cell(args.workload, bench)
    traffic = cell.traffic
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        traffic = _merge(traffic, traffic.get("rehearse", {}))
    # first: puts the scoped-VMEM flag into LIBTPU_INIT_ARGS before any
    # backend starts
    import areal_tpu  # noqa: F401

    from areal_tpu.utils import compile_cache

    device = harness.device_info()
    if args.rehearse:
        if device["platform"] != "cpu":
            raise SystemExit("--rehearse runs on the CPU only")
    elif device["platform"] != "tpu":
        print(f"benchmark: platform {device['platform']!r} is not a TPU; "
              "nothing is measured off the chip (use --rehearse to try "
              "the code on the CPU)", file=sys.stderr)
        return None, None, None
    elif device["count"] < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, jax sees "
              f"{device['count']}", file=sys.stderr)
        return None, None, None
    watch = harness.CompileWatch()
    if not args.rehearse:
        # the one rule (areal_tpu/utils/compile_cache.py): the inherited
        # JAX_COMPILATION_CACHE_DIR, else a fixed path inside the checkout
        compile_cache.enable_compilation_cache(compile_cache.resolve_cache_dir())
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    ctx = types.SimpleNamespace(
        cell=cell, traffic=traffic, seed=int(args.seed), seconds=seconds,
        trace=bool(args.trace), rehearse=args.rehearse, watch=watch,
        model_config=harness.model_config(cell, args.rehearse),
        reference_config=harness.reference_config(cell, args.rehearse),
        logprob_limits=harness.logprob_limits(cell, args.rehearse),
        peaks=None if args.rehearse else arith.load_peaks(device["kind"]),
        trace_dir=os.path.join(harness.ROOT, "benchmark_out", "trace",
                               cell.name),
        limit_s=600.0, setup_s=None, setup_programs=None,
    )

    def mark_window_start():
        ctx.setup_s = time.monotonic() - t_start
        ctx.setup_programs = watch.snapshot()

    ctx.mark_window_start = mark_window_start
    ctx.args, ctx.device = args, device
    return ctx, harness.load_kind(traffic["kind"]), bench


def main(argv=None, bench=None):
    ctx, kind, bench = prepare(argv, bench=bench)
    if ctx is None:
        return 3
    args, cell, device = ctx.args, ctx.cell, ctx.device
    out = kind.run(ctx)

    names = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for name in names:
        m = harness.read_metric(name, out.obs)
        if m is not None:
            metrics[name] = m
    dev = dict(device, memory_peak_bytes=harness.memory_peak_bytes())
    line = {
        "correct": bool(out.checks) and all(out.checks.values()),
        "attempted": int(out.attempted), "failed": int(out.failed),
        "metrics": metrics, "device": dev,
    }
    if args.trace and out.obs.trace:
        from benchmark import xplane

        t = out.obs.trace
        dev["busy_s"], dev["window_s"] = t["busy_s"], t["window_s"]
        line["breakdown"] = {
            "device_ops": xplane.top_ops(t["op_seconds"]),
            "idle_gaps": [[k, v] for k, v in t["gaps"]],
        }
    # earlier lines: what PERF.md records and the driver ignores
    print(json.dumps({
        "cell": cell.name, "seed": ctx.seed, "window_s": out.obs.window_s,
        "setup_s": ctx.setup_s, "setup_programs": ctx.setup_programs,
        "memory_peak_bytes": dev["memory_peak_bytes"], "checks": out.checks,
        "notes": out.notes,
    }, default=str), flush=True)
    if args.rehearse:
        line["rehearsal"] = (
            "tiny configuration on the CPU: not a measurement"
        )
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
