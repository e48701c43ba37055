#!/usr/bin/env python3
"""Studies made once, when a cell is defined; the benchmark's runs do not
make them.  Each runs in one process, so compiled programs are shared.

    python3 perfbench/study.py sweep --workload <cell> --seed <n> \
        --seconds <s> --rates 0.5,1,2
        One engine per rate on the same weights: SLO attainment (the paper's
        limits) over the requests due in the window, the waiting queue's
        growth, and the cell's end-to-end metrics.  The knee is the highest
        rate with attainment >= 0.9 and a queue that does not grow.

    python3 perfbench/study.py readings --workload <cell> --seeds 1,2,3 \
        --seconds <s>
        For each seed, weights from the seed and a window at the cell's
        rate, then the numbers ``correct`` compares, read for the program,
        for the float8 control in its place, and for the faults planted in
        the reference's place (``harness.compare`` with ``study``).

One JSON line per rate or seed on stdout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
# room for every program of a cell, whatever cap the machine sets
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(1 << 30)
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
sys.path[:0] = [str(REPO), str(REPO / "src")]


def sweep(cell, seed, seconds, rates, log):
    from perfbench import harness, stats
    compiles = harness.compile_seconds()
    built = harness.build(cell, seed)
    for rate in rates:
        win = harness.serve(cell, built, seed, seconds, rate, False,
                            time.perf_counter(), compiles, log)
        run = win.run
        due = stats.due(run.served, run.w0, run.w1)
        met = sum(stats.meets_slo(r, run.w1) for r in due)
        m = harness.read_metrics(run, "end_to_end")
        print(json.dumps({
            "rate_rps": rate, "attempted": len(due),
            "slo_attainment": met / max(len(due), 1),
            "queue_growth": win.queue_growth, "window_compiles": win.compiles,
            "memory_peak_bytes": win.device["memory_peak_bytes"],
            "metrics": {k: v["value"] for k, v in m.items()}}), flush=True)


def readings(cell, seeds, seconds, log):
    from perfbench import harness
    compiles = harness.compile_seconds()
    for seed in seeds:
        built = harness.build(cell, seed)
        win = harness.serve(cell, built, seed, seconds,
                            float(cell.setup["rate_rps"]), False,
                            time.perf_counter(), compiles, log)
        mp, ma = built.make_params, built.make_adapters
        trained = (built.names.index(harness.TRAINED) if built.trains
                   else -1)
        del built
        gc.collect()
        got = harness.compare(cell, mp, ma, seed, win.picked, win.training,
                              trained, study=True)
        rows = (sum(len(s) for s in win.training["steps"]) if win.training
                else 0)
        print(json.dumps(dict(seed=seed, requests=len(win.picked),
                              tokens=sum(len(p["output"])
                                         for p in win.picked),
                              rows=rows, **got)), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("study", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    import jax
    from perfbench import harness
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("study.py: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    if args.study == "sweep":
        sweep(cell, args.seed, args.seconds,
              [float(r) for r in args.rates.split(",")], log)
    else:
        readings(cell, [int(s) for s in args.seeds.split(",")],
                 args.seconds, log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
