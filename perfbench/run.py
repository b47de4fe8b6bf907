"""Run one cell of the benchmark on the chip(s) of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
`BENCHMARK.json` at the root of the checkout. The last line of stdout is
one JSON object (`correct`, `attempted`, `failed`, `metrics`, `device`,
with `--trace 1` also `breakdown`, and last `checks`: each number the
output check compared, with its limit); the checks are also the last
lines of stderr. Without a TPU, with fewer chips than the cell asks for,
or without the program beside this directory, it exits non-zero and
prints no result.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.lib import bench, cell as cell_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cell_mod.load(args.workload)
        out = bench.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_PROCESS)
    except (bench.NoChip, FileNotFoundError, KeyError) as e:
        bench.log(f"no result: {e}")
        return 2
    for name, c in out["checks"].items():
        print(f"[perfbench] check {name}: {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
