#!/usr/bin/env python3
"""Readings that set the benchmark's fixed numbers; not part of a run.

    python bench/calibrate.py --workload <cell> --sweep 1,2,3 --seconds 20
    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--control]

`--sweep` serves an open-loop cell's mix at each of the given rates
(requests/s), one window each, on one set of weights, and prints a line
per rate: the tails, the requests left unfinished, and the engine's
step times, to find the highest rate the system sustains.

`--seeds` makes one whole run of the cell per seed in this one process
(each with its own weights) and prints a line per seed with the
compared numbers.  With `--control` the plain reference computed one
precision step lower (float8 matmul inputs, int4 K and V) stands in for
the program on the same prompts and is judged as the program would be
(`correct` has to come out false); the program's own gaps are printed
beside it.  Both are the readings a limit is set from.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(os.path.dirname(HERE), "src"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _floats(text):
    return [float(x) for x in text.split(",") if x]


def sweep(cell, rates, seconds, seed) -> None:
    import cells
    import device
    import endtoend
    import harness
    import program
    devs = device.check(cell["chips"])
    harness.enable_compile_cache()
    cfg, traffic = cells.config(cell["config"]), cells.traffic(cell["traffic"])
    vocab = cells.family(cfg).dims(cfg)["v"]
    gen = cells.generator(traffic["generator"])
    model, engines = program.build(cfg, traffic["engine"], seed)
    program.warm_up(engines, traffic["engine"], vocab)
    for rate in rates:
        tr = dict(traffic, rate_rps=rate)
        schedule = gen.build(tr, seed, vocab, seconds)
        with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
            s = asyncio.run(harness.serve(engines, schedule, tr, seconds,
                                          False, tmp))
        e = endtoend.open_loop(s["records"], s["t_stop"])
        a, b = s["telemetry"]["t0"], s["telemetry"]["t1"]
        steps = b["decode_steps"] - a["decode_steps"]
        win = endtoend.window_requests(s["records"])
        print(json.dumps({
            "rate_rps": rate, "due": e["attempted"],
            "unfinished": e["unfinished"], "failed": e["failed"],
            "misses": e["misses"],
            "ttft_p50_ms": 1e3 * endtoend.tail(e["ttft_s"], 50),
            "ttft_p90_ms": 1e3 * endtoend.tail(e["ttft_s"], 90),
            "itl_p50_ms": 1e3 * endtoend.tail(e["itl_s"], 50),
            "itl_p99_ms": 1e3 * endtoend.tail(e["itl_s"], 99),
            "tokens_per_s": endtoend.tokens_between(
                s["records"], s["t0"], s["t1"]) / seconds,
            "decode_step_ms": 1e3 * (b["decode_s"] - a["decode_s"])
            / max(steps, 1),
            "prefill_s_share": (b["prefill_s"] - a["prefill_s"])
            / seconds,
            "batch_mean": (sum(b["batch_samples"][-(b["steps"] - a["steps"]):])
                           / max(b["steps"] - a["steps"], 1)),
            "last_due_finished_s": max(
                (r["times"][-1] - r["due"] for r in win if r["times"]),
                default=None),
            "lateness_s": endtoend.generator_lateness_s(s["records"]),
            "memory_peak_bytes": device.memory_peak_bytes(devs)}),
            flush=True)


def seeds(cell, seed_list, seconds, control) -> None:
    import cells
    import harness
    bench = cells.benchmark()
    for i, seed in enumerate(seed_list):
        t = time.monotonic()
        res = harness.run_cell(
            cell, cells.config(cell["config"]), cells.traffic(cell["traffic"]),
            cells.limits(cell["name"]), seed, seconds, False,
            per_layer=cells.per_layer(cell["name"], bench),
            end_to_end=cells.end_to_end(cell["name"], bench),
            t_process=T_PROCESS if i == 0 else t, control=control)
        res["seed"] = seed
        res["run_s"] = time.monotonic() - t
        print(json.dumps(res), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sweep", type=_floats)
    ap.add_argument("--seeds", type=lambda t: [int(x) for x in t.split(",")])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    import cells
    cell = cells.workload(args.workload)
    if args.sweep:
        sweep(cell, args.sweep, args.seconds, args.seed)
    if args.seeds:
        seeds(cell, args.seeds, args.seconds, args.control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
