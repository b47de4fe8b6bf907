"""Readings for the limits of the output check: what the program reads on
several seeds, what each lower-precision control (`perfbench/lib/control.py`)
reads on the same sampled requests, and what the timed path reads with
each named fault of `perfbench/lib/faults.py` planted in it. Not part of
a benchmark run; run on the chip:

    python3 perfbench/control.py --workload <name> --seconds 30 \
        --seeds 11 12 13 --faults state_unchanged pages_swapped

All runs share one process, so only the first pays the compiles. One
JSON line per run, then a summary line. It exits 1 where a control or a
fault comes out correct under the cell's limits.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.lib import bench, cell as cell_mod, control, faults  # noqa: E402,E501


def _row(seed, fault, out):
    return {"seed": seed, "fault": fault, "correct": out["correct"],
            "checks": {k: c["value"] for k, c in out["checks"].items()},
            "mean_gap": out["stats"]["mean_gap"],
            "widest_gap": out["stats"]["widest_gap"],
            "sampled_tokens": out["stats"]["sampled_tokens"],
            "longest_step_s": out["stats"]["longest_step_s"],
            "repeat_share": out["stats"]["repeat_share"],
            "window_events": out["stats"]["window_events"],
            "controls": out.get("controls")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[],
                    choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = cell_mod.load(args.workload)
    rows = []
    for seed in args.seeds:
        out = bench.run(cell, seed, args.seconds, False, time.monotonic(),
                        controls={k: f() for k, f in
                                  control.CONTROLS.items()})
        rows.append(_row(seed, None, out))
        print(json.dumps(rows[-1]), flush=True)
        for name in args.faults:
            out = bench.run(cell, seed, args.seconds, False,
                            time.monotonic(), fault=faults.FAULTS[name])
            rows.append(_row(seed, name, out))
            print(json.dumps(rows[-1]), flush=True)
    sound = [r for r in rows if r["fault"] is None]
    summary = {
        "program_max": {k: max(r["checks"][k] for r in sound)
                        for k in cell.limits},
        "controls_min": {
            c: {k: min(r["controls"][c]["checks"][k]["value"]
                       for r in sound) for k in cell.limits}
            for c in control.CONTROLS},
        "controls_correct": {c: [r["controls"][c]["correct"]
                                 for r in sound] for c in control.CONTROLS},
        "faults_min": {f: {k: min(r["checks"][k] for r in rows
                                  if r["fault"] == f) for k in cell.limits}
                       for f in args.faults},
        "faults_correct": {f: [r["correct"] for r in rows
                               if r["fault"] == f] for f in args.faults},
        "limits": cell.limits}
    print(json.dumps(summary), flush=True)
    caught = not any(any(v) for v in summary["controls_correct"].values()) \
        and not any(any(v) for v in summary["faults_correct"].values())
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
