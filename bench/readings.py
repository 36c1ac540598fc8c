#!/usr/bin/env python3
"""Readings that the limits of the comparison deciding ``correct`` are
set from, all in one process on the machine it is started on:

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds 201:213 [--control 301:304] [--fault AlteredAnswers:401:404]

Each seed is a whole run of the cell (set-up, window, comparison): the
program on ``--seeds``, the plain reference in bfloat16 in the program's
place on ``--control``, and the program with a fault of
``harness.faults`` planted under the timed path on ``--fault``. Prints one
JSON line per run with every number compared; a run that gives no result
prints its reason instead.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# libtpu otherwise keeps its logs at a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _seeds(text: str) -> range:
    lo, hi = text.split(":")
    return range(int(lo), int(hi))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default=None, help="lo:hi, the program")
    ap.add_argument("--control", default=None, help="lo:hi, the control")
    ap.add_argument("--fault", action="append", default=[],
                    help="Name:lo:hi, a fault of harness.faults")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import gc

    import jax
    from harness import faults
    from harness.cell import Options, run_cell
    from harness.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(args.workload)
    runs = []
    if args.seeds:
        runs += [("program", s, {}) for s in _seeds(args.seeds)]
    if args.control:
        runs += [("control", s, {"control": True})
                 for s in _seeds(args.control)]
    for spec in args.fault:
        name, rng = spec.split(":", 1)
        runs += [(name, s, {"server_wrap": getattr(faults, name)})
                 for s in _seeds(rng)]
    for kind, seed, kw in runs:
        t = time.perf_counter()
        line = {"workload": args.workload, "run": kind, "seed": seed}
        try:
            res = run_cell(cell, Options(seed=seed, seconds=args.seconds,
                                         rehearse=args.rehearse, **kw))
            line.update(correct=res["correct"],
                        checks={k: v["value"]
                                for k, v in res["checks"].items()},
                        metrics={k: v["value"]
                                 for k, v in res["metrics"].items()},
                        memory_peak_bytes=res["device"]["memory_peak_bytes"])
        except SystemExit as e:     # a run that gives no result
            line["no_result"] = str(e)
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
