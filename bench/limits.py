#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/limits.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--out FILE]

In one process, for each seed, the runner of the cell's kind reads the
numbers its comparison uses: the program against the plain reference (the
lower readings) and, on the control seeds, the control against the
reference (the upper readings).  One JSON line per seed; the chip check
is the same as a run's.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = harness.load_json(harness.SPEC_FILE)
    r = harness.resolve(spec, args.workload)
    try:
        harness.device_check(r["cell"]["chips"])
    except harness.NoChip as e:
        print(f"limits: {e}", file=sys.stderr)
        return 3
    harness.import_program()
    harness.enable_compile_cache()
    kind = harness.load_module(r["runner"])
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        line = dict(workload=args.workload, seed=seed,
                    **kind.readings(r["conf"], r["traffic"], seed,
                                    seed in control),
                    seconds=time.perf_counter() - t0)
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
