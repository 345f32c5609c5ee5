#!/usr/bin/env python3
"""Benchmark of the served path, one cell of `BENCHMARK.json` per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Serves the cell's configuration (int4 weights made on the device from
the seed) through the in-process HTTP gateway, `FleetRouter`,
`PagedServeEngine` and the model's serve step down to the kernels, under
the cell's traffic mix, and measures `--seconds` of it.  With
`--trace 0` the result holds the cell's end-to-end metrics; with
`--trace 1` its per-layer metrics, read from a profiler trace of part of
the window and from the program's spans and counters.  Every run checks
a sample of the served tokens against a plain float32 reference.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`: each compared number beside its limit).
The checks are also the last lines on standard error.  With no TPU, or
fewer chips than the cell needs, or a chip not in `bench/peaks.json`, the
run exits 2 and prints no result.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(os.path.dirname(HERE), "src"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(result: dict) -> None:
    """Print the result line (checks last) and the checks on stderr."""
    extra = result.pop("_extra", {})
    checks = result.pop("checks")
    result["checks"] = checks
    print(json.dumps(extra), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    import cells
    import device
    import harness
    bench = cells.benchmark()
    cell = cells.workload(args.workload, bench)
    try:
        result = harness.run_cell(
            cell, cells.config(cell["config"]), cells.traffic(cell["traffic"]),
            cells.limits(cell["name"]), args.seed, args.seconds,
            bool(args.trace), per_layer=cells.per_layer(cell["name"], bench),
            end_to_end=cells.end_to_end(cell["name"], bench),
            t_process=T_PROCESS)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
