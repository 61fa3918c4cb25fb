"""Two sets of runs of one cell with the same seeds in both, and each
end-to-end metric's spread: the distance between the first and third
quartile (statistics.quantiles(values, n=4)) over the median.  Run it
through the chip tool; it starts run.py once per run and never touches
JAX itself.

    python3 benchmarks/tests/measure_sets.py <cell> <seconds> <out.json> [runs] [first_seed] [traced] [sets]

The second set's runs and the traced runs also put the control in the
program's place (``--control 1``: computed after the window has closed, so
it moves no metric); each has to report ``control_correct`` false.
"""

import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one(cell, seed, seconds, trace, control):
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--control", str(control)], cwd=ROOT,
        capture_output=True, text=True)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    out = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    notes = [ln for ln in p.stderr.splitlines()
             if "compiled inside" in ln or "ttp ms" in ln
             or "control in" in ln or "window opens" in ln
             or "stage-to-stage" in ln
             or "FAILED" in ln or "cycle " in ln or "  only " in ln
             or "  other host" in ln]
    return {"seed": seed, "trace": trace, "rc": p.returncode, "out": out,
            "wall_s": time.time() - t0, "notes": notes,
            "err_tail": p.stderr[-1500:] if out is None
            or not out["correct"] else ""}


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv):
    cell, seconds, out_path = argv[1], argv[2], argv[3]
    runs = int(argv[4]) if len(argv) > 4 else 6
    first = int(argv[5]) if len(argv) > 5 else 2 ** 31 + 1000
    traced = int(argv[6]) if len(argv) > 6 else 3
    sets = int(argv[7]) if len(argv) > 7 else 2
    doc = {"cell": cell, "seconds": seconds, "sets": [], "traced": []}

    def keep(into, run):
        into.append(run)
        with open(out_path, "w") as f:
            json.dump(doc, f)

    for s in range(sets):
        doc["sets"].append([])
        for i in range(runs):
            keep(doc["sets"][s], one(cell, first + i, seconds, 0, int(s > 0)))
    for i in range(traced):
        keep(doc["traced"], one(cell, first + runs + i, seconds, 1, 1))
    summary = {}
    for k, runs_ in enumerate(doc["sets"]):
        good = [r["out"] for r in runs_ if r["out"]]
        for name in (good[0]["metrics"] if good else {}):
            vals = [g["metrics"][name]["value"] for g in good]
            summary.setdefault(name, []).append(
                {"median": statistics.median(vals),
                 "spread": spread(vals) if len(vals) >= 2 else None,
                 "values": vals})
    doc["summary"] = summary
    every = sum(doc["sets"], []) + doc["traced"]
    doc["all_correct"] = all(r["out"] and r["out"]["correct"] for r in every)
    doc["controls_correct"] = [r["out"]["control_correct"] for r in every
                               if r["out"] and "control_correct" in r["out"]]
    with open(out_path, "w") as f:
        json.dump(doc, f)
    for name, sets in summary.items():
        print(name, " | ".join(
            f"median {s['median']:.4f} spread {100 * (s['spread'] or 0):.2f}%"
            for s in sets))
    print("all correct:", doc["all_correct"], "; control_correct of the "
          "runs that read it:", doc["controls_correct"])
    for r in every:
        out = r["out"] or {}
        print(r["seed"], r["trace"], r["rc"], round(r["wall_s"], 1),
              out.get("correct"), out.get("control_correct"),
              out.get("launched_in_window"), r["notes"][:3])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
