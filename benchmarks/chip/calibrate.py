#!/usr/bin/env python3
"""Readings the correctness limits of a cell are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 ... [--control-seeds 3]

For each seed, one window of the cell at its own size, the program's
answers compared with the plain reference (the lower readings), and for
the first ``--control-seeds`` seeds the control, the reference in
bfloat16, compared the same way (the upper readings).  One JSON line per
seed; all seeds run in one process, so set-up is paid once per seed but
compilation once.  Runs on the chip it is started on.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(BENCH_DIR.parents[1] / "src"))

from bench import Clock, device_info, load_json, load_module  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = load_json("cells", args.workload)
    config = load_json("configs", cell["config"])
    mix = load_json("traffic", cell["traffic"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    driver = load_module("drivers", mix["driver"])
    for i, seed in enumerate(args.seeds):
        state = driver.setup(config, mix, seed, args.seconds)
        res = driver.window(state, args.seconds, Clock)
        t = time.perf_counter()
        line = {"seed": seed, "attempted": res["attempted"],
                "program": driver.check(state),
                "reference_s": time.perf_counter() - t}
        if i < args.control_seeds:
            line["control"] = state.control_check()
        line["device"] = device_info(int(cell["chips"]))["kind"]
        print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
