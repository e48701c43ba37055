#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's model and adapters from the seed on the device, warms
every step shape the cell uses, pre-rolls its open-loop traffic, measures
``--seconds`` of it, and checks what the window served against the float32
reference.  The last line of stdout is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` last); the numbers compared are also the last
lines of stderr.  Exits non-zero, printing no result, where JAX finds no TPU
or fewer chips than the cell asks for.  JAX's compilation cache lives in
``.jax_cache`` of this checkout, so only a checkout's first run compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from perfbench import harness
    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    log(f"start: devices found {time.perf_counter() - T_START:.2f} s after "
        f"the process started")

    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, log=log)
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
