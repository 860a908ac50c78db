"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It refuses to start unless JAX's first device
is a TPU and there are as many devices as the cell's ``chips``; it
builds the model from the seed, warms up the cell's own shapes (all of
that is ``setup_s``), measures for ``--seconds`` and prints the
contract's one JSON object as the last line of stdout.  Earlier lines
are free-form JSON (geometry, set-up, the check's numbers).

Nothing here knows a cell, a model or a metric by name.  A cell in
``BENCHMARK.json`` names a config and a traffic mix; those are files
(``configs/``, ``traffic/``), the traffic file names a runner
(``runners/``), the config file a family (``families/``), and every
metric of the manifest has a file of its own (``end_to_end/``,
``layer_metrics/``) that names a reader (``readers/``).  See README.md.
"""
import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness     # noqa: E402

harness.T_START = T_START


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              args.trace)
    print(json.dumps(result), flush=True)
    if not result["correct"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
