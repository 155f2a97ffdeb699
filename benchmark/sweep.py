"""Find the knee of an open-loop cell once, on the chip.

    python -m benchmark.sweep --workload serve_0p5b_chat --rates 6,9,12,15,18,22 --seconds 40

One process, one engine, one warm-up; then one window per rate. The knee is
the highest rate at which at least 98% of the requests due in the window
finished and the queue at the window's end was no longer than at its middle.
The table goes to stdout (and to PERF.md and the traffic file's "knee" by
hand); the cell's rate is 0.8 x the knee.
"""

import argparse
import json
import sys
import time

_T_START = time.monotonic()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated, per second")
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--bench", default=None,
                   help="as benchmark.run's: the file that lists the cell")
    args = p.parse_args(argv)
    # the same set-up as a run of the cell, through run.py's own steps
    from benchmark import run

    ctx, kind, bench = run.prepare(
        ["--workload", args.workload, "--seed", str(args.seed), "--trace", "0"]
        + (["--seconds", str(args.seconds)] if args.seconds else [])
        + (["--rehearse"] if args.rehearse else [])
        + (["--bench", args.bench] if args.bench else []),
        t_start=_T_START,
    )
    if ctx is None:
        return 3
    from benchmark import genlib, harness, lengths

    engine, _params = genlib.build_engine(ctx, ctx.traffic["engine"])
    rows = []
    try:
        _checked = genlib.warm_shapes(
            ctx, engine, lengths.pool(ctx.traffic["prompt_len"], 64),
            ctx.traffic["sampling"], ctx.traffic["warmup"],
        )
        for rate in [float(r) for r in args.rates.split(",")]:
            out = kind.measure(ctx, engine, rate)
            n = out.notes
            row = {
                "rate": rate, "sent": n["sent"],
                "finished_share": n["finished_share"],
                "queue_mid": n["queue_mid"], "queue_end": n["queue_end"],
                "compiles_in_window": n["compiles_in_window"],
                "gen_tokens_per_s": out.obs.counters.get(
                    "total_generated_tokens", 0.0) / out.obs.window_s,
            }
            for name in ("ttft_p95_ms", "tpot_p95_ms"):
                m = harness.read_metric(name, out.obs)
                row[name] = m and m["value"]
            row["sustained"] = bool(
                n["finished_share"] >= 0.98
                and (n["queue_end"] or 0) <= max(n["queue_mid"] or 0, 1)
            )
            rows.append(row)
            print(json.dumps(row), flush=True)
            time.sleep(2.0)  # let the last window's tail leave the engine
    finally:
        engine.stop()
    ok = [r["rate"] for r in rows if r["sustained"]]
    print(json.dumps({"knee": max(ok) if ok else None,
                      "cell_rate": 0.8 * max(ok) if ok else None,
                      "device": harness.device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
