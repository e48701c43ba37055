# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV, and writes ``BENCH_summary.json`` — one trend row per bench (headline
# metric + wall time) so CI can publish a single cross-bench artifact.
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import (bench_adapters, bench_dedup, bench_finetune,
                        bench_fleet, bench_inference, bench_kernels,
                        bench_loading, bench_mutable, bench_paged,
                        bench_preempt, bench_prefix, bench_realworld,
                        bench_roofline, bench_spec, bench_tiers,
                        bench_unified)
from benchmarks.gate import GateError, resolve

# (table name, entry point, BENCH artifact the run must (re)write — None
# for CSV-only benches).  A registered artifact that is missing or stale
# after the run is a FAILURE: the CI bench matrix gates on these files, and
# a silently-skipped write would green-pass on yesterday's numbers.
TABLES = [
    ("table2_loading", bench_loading.main, None),
    ("fig2_inference", bench_inference.main, None),
    ("fig3_finetune", bench_finetune.main, None),
    ("fig4_unified", bench_unified.main, None),
    ("fig5_mutable", bench_mutable.main, None),
    ("fig6_realworld", bench_realworld.main, None),
    ("kernels_micro", bench_kernels.main, "BENCH_kernels.json"),
    ("roofline_table", bench_roofline.main, None),
    ("paged_cache", bench_paged.main, "BENCH_paged.json"),
    ("spec_decode", bench_spec.main, "BENCH_spec.json"),
    ("prefix_prefill", bench_prefix.main, "BENCH_prefix.json"),
    ("preempt_overadmit", bench_preempt.main, "BENCH_preempt.json"),
    ("hash_dedup", bench_dedup.main, "BENCH_dedup.json"),
    ("fleet_serving", bench_fleet.main, "BENCH_fleet.json"),
    ("adapter_paging", bench_adapters.main, "BENCH_adapters.json"),
    ("tiers_memory", bench_tiers.main, "BENCH_tiers.json"),
]

# headline metric per artifact: the one number a trend dashboard plots.
# Resolved with the gate's own path resolver so a renamed field fails
# loudly here too instead of silently charting nulls.
HEADLINES = {
    "BENCH_kernels.json": "long_ctx.speedup",
    "BENCH_paged.json": "engine.peak_ratio",
    "BENCH_spec.json": "speedup",
    "BENCH_prefix.json": "speedup",
    "BENCH_preempt.json": "speedup",
    "BENCH_dedup.json": "speedup",
    "BENCH_fleet.json": "speedup",
    "BENCH_adapters.json": "speedup",
    "BENCH_tiers.json": "speedup",
}

SUMMARY = "BENCH_summary.json"


def check_artifact(artifact, started_at: float) -> str:
    """'' when the registered artifact exists and was written during this
    run; otherwise a reason string (missing, or stale from an earlier
    run)."""
    if artifact is None:
        return ""
    if not os.path.exists(artifact):
        return f"benchmark wrote no {artifact}"
    if os.path.getmtime(artifact) < started_at:
        return f"{artifact} is stale (not rewritten by this run)"
    return ""


def headline_of(artifact: str, artifact_dir: str = "."):
    """(path, value) headline for an artifact, or (None, None) when the
    bench has no registered headline or the artifact is absent."""
    path = HEADLINES.get(artifact)
    p = os.path.join(artifact_dir, artifact)
    if path is None or not os.path.exists(p):
        return None, None
    with open(p) as f:
        doc = json.load(f)
    return path, resolve(doc, path)[0]


def write_summary(rows, artifact_dir: str = ".") -> None:
    out = os.path.join(artifact_dir, SUMMARY)
    with open(out, "w") as f:
        json.dump({"benches": rows}, f, indent=1)
    print(f"# wrote {SUMMARY} ({len(rows)} row(s))")


def summarize_only(artifact_dir: str = ".") -> int:
    """Rebuild BENCH_summary.json from whatever artifacts already exist —
    the CI summary job downloads the matrix artifacts and calls this; no
    benchmark runs.  Fails if NO registered artifact is present (a summary
    of nothing is a broken pipeline, not a quiet success)."""
    rows = {}
    for name, _, artifact in TABLES:
        if artifact is None:
            continue
        path, value = headline_of(artifact, artifact_dir)
        if path is None:
            continue
        rows[name] = {"artifact": artifact, "headline": path,
                      "value": value, "wall_s": None}
    if not rows:
        print("no BENCH_*.json artifacts found to summarize",
              file=sys.stderr)
        return 1
    write_summary(rows, artifact_dir)
    return 0


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--summarize-only", action="store_true",
                    help="rebuild BENCH_summary.json from existing "
                         "artifacts without running any benchmark")
    ap.add_argument("--dir", default=".", help="artifact directory")
    args = ap.parse_args(argv)
    if args.summarize_only:
        sys.exit(summarize_only(args.dir))

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    rows = {}
    for name, fn, artifact in TABLES:
        t0 = time.monotonic()
        wall0 = time.time()
        print(f"# --- {name} ---")
        status = "ok"
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures += 1
            status = f"ERROR={type(e).__name__}"
            traceback.print_exc()
            print(f"{name},0.0,{status}")
        else:
            reason = check_artifact(artifact, wall0)
            if reason:
                failures += 1
                status = "ERROR=MissingArtifact"
                print(f"{name},0.0,{status} ({reason})")
        wall = time.monotonic() - t0
        print(f"# {name} took {wall:.1f}s")
        row = {"artifact": artifact, "wall_s": round(wall, 2),
               "status": status}
        if artifact is not None and status == "ok":
            try:
                row["headline"], row["value"] = headline_of(artifact)
            except GateError as e:
                failures += 1
                row["status"] = "ERROR=Headline"
                print(f"{name},0.0,ERROR=Headline ({e})")
        rows[name] = row
    write_summary(rows)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
