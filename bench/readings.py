#!/usr/bin/env python3
"""Read the compared numbers of a cell over many seeds in one process:
the program's (its sound runs, which set each limit's lower reading)
and the control's (the reference computed in bfloat16 in the program's
place, which sets the upper reading).

    python bench/readings.py --workload <name> --seeds 101-112 \\
        --seconds 3 [--control 3] [--out readings.jsonl]

Each seed is a short window at the cell's own size and load, run through
the same path as ``run.py``; the first ``--control`` seeds also read the
control.  One JSON line per seed.  Needs the chips the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import harness, manifest  # noqa: E402


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = manifest.cell(manifest.load(), args.workload)
    harness.place_compile_cache()
    import jax

    devices = jax.devices()
    why = harness.chip_problem(devices, spec["chips"], harness.load_peaks())
    if why:
        print(f"readings: {why}", file=sys.stderr)
        return 2
    out = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(_seeds(args.seeds)):
            t = time.perf_counter()
            rec = harness.run_cell(spec, seed, args.seconds,
                                   devices=devices[:spec["chips"]],
                                   with_control=i < args.control)
            row = {"workload": args.workload, "seed": seed,
                   "correct": rec.correct,
                   "checks": {k: v for k, (v, _) in rec.checks.items()},
                   "control": ({k: v for k, (v, _) in
                                rec.control_checks.items()}
                               if rec.control_checks else None),
                   "setup_s": rec.setup_s, "seconds": time.perf_counter() - t,
                   "plan_builds_window": rec.plan_builds_window}
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
