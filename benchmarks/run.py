#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, which
has to hold the TPU chips the cell asks for, and prints one JSON object as
the last line of its standard output.  Everything else (the program's own
progress lines, the numbers compared beside their limits) goes to standard
error.  See README.md beside this file.
"""

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    out = sys.stdout
    # the program's prints and its logger (which binds sys.stdout when
    # tpusppy is first imported) go to stderr: stdout carries the result
    with contextlib.redirect_stdout(sys.stderr):
        from benchmarks.harness import core

        try:
            line = core.run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), t_start=T_START)
        except core.NoChip as e:
            print(f"benchmarks/run.py: {e}", file=sys.stderr)
            return 3
        core.print_checks(line)
    print(json.dumps(line), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
