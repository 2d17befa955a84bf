"""Read, in one process on the chip, what a cell's limits are set from: over
many seeds, the gaps of the served tokens (the lower reading is the largest
of these) and, on the same samples, the gaps of the reference computed in
the next lower precision put in the program's place (the upper reading is
the smallest of these).  The control goes through the harness's own
``checks``: every seed has to come out ``correct: false``, or this exits 1.

    python3 benchmarks/tools/limits.py --workload <cell> --seeds 1,2,3 --seconds 8

Writes chiprun_out/limits_<cell>.json and prints one line per seed."""

import argparse
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    args = parser.parse_args()
    sys.path.insert(0, str(ROOT))

    from benchmarks.harness.cell import passes, run_cell
    from benchmarks.harness.serving_system import persistent_compile_cache

    persistent_compile_cache()

    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = io.StringIO()
        result = run_cell(ROOT, args.workload, seed, args.seconds, False,
                          out=out, err=io.StringIO(), control=True)
        infos = [json.loads(line)["info"]
                 for line in out.getvalue().splitlines()[:-1]]
        checks = result["checks"]
        program = next(i["comparison"] for i in infos if "comparison" in i)
        control = program.pop("control")
        compared = {"gap": "served_gap", "mean_gap": "served_gap_mean"}
        row = {"seed": seed, "control_correct": result["correct"],
               "attempted": result["attempted"], "failed": result["failed"],
               "program": program,
               "control": {k: control[k] for k in compared},
               "program_within_limits": all(
                   program[k] <= checks[name]["limit"]
                   for k, name in compared.items() if name in checks),
               "other_checks_pass": all(
                   passes(c) for name, c in checks.items()
                   if name not in compared.values())}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seconds": args.seconds}
    for key in ("gap", "mean_gap"):
        summary[key] = {
            "lower_reading": max(r["program"][key] for r in rows),
            "upper_reading": min(r["control"][key] for r in rows)}
    summary["control_correct_on_no_seed"] = not any(
        r["control_correct"] for r in rows)
    print(json.dumps(summary))
    summary["rows"] = rows
    target = ROOT / "chiprun_out"
    target.mkdir(exist_ok=True)
    (target / f"limits_{args.workload}.json").write_text(
        json.dumps(summary, indent=1))
    return 0 if summary["control_correct_on_no_seed"] else 1


if __name__ == "__main__":
    sys.exit(main())
