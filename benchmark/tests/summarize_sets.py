"""By hand: read what ``full_sets.sh`` wrote and print, per cell, what a
bound is set from — each end-to-end metric's median and spread (distance
between the first and third quartile of ``statistics.quantiles(n=4)``, as
a share of the median) in each set, the wider of the two, the second
set's median against the first's — the same for the rate and the longest
pass that a shorter window (SHORTER seconds, default 30: the passes that
started before it) would have read, and the largest reading of every
number compared, over all the seeds run.

    python3 benchmark/tests/summarize_sets.py chiprun_out/sets/<workload>.jsonl
"""

import json
import os
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path):
    runs = [json.loads(line.replace('"wall_s": ,', '"wall_s": 0,'))
            for line in open(path)]
    good = [r for r in runs if r["line"]]
    print(f"{path}: {len(runs)} runs, {len(good)} with a result line, "
          f"correct on {sum(r['line']['correct'] for r in good)}, "
          f"rcs {sorted({r['rc'] for r in runs})}, wall "
          f"{statistics.median(r['wall_s'] for r in runs):.0f} s median")
    sets = {s: [r["line"]["metrics"] for r in good if r["set"] == s]
            for s in ("set1", "set2")}
    for name in sets["set1"][0] if sets["set1"] else ():
        row = []
        for s in ("set1", "set2"):
            vals = [m[name]["value"] for m in sets[s]]
            if len(vals) >= 2:
                row.append((statistics.median(vals), spread(vals), vals))
        line = f"  {name:26s}"
        for med, sp, vals in row:
            line += f" median {med:.6g} spread {100 * sp:.2f}%"
        if len(row) == 2:
            line += f" | second/first {row[1][0] / row[0][0]:.4f}" \
                    f" | widest {100 * max(row[0][1], row[1][1]):.2f}%"
        print(line)
        for _, _, vals in row:
            print("      " + " ".join(f"{v:.5g}" for v in vals))
    shorter = float(os.environ.get("SHORTER", 30))
    for name, read in (
            ("examples_per_s_per_chip",
             lambda w, ps: len(ps) * w["examples_per_pass"]
             / (ps[-1][0] + ps[-1][1])),
            ("pass_s_max", lambda w, ps: max(p[1] for p in ps))):
        line = f"  at {shorter:g} s: {name:26s}"
        for s in ("set1", "set2"):
            vals = [read(r["window"], [p for p in r["window"]["passes_at"]
                                       if p[0] < shorter])
                    for r in good if r["set"] == s and r.get("window")]
            if len(vals) >= 2:
                line += f" median {statistics.median(vals):.6g} spread " \
                        f"{100 * spread(vals):.2f}%"
        print(line)
    traced = [r["line"] for r in good if r["trace"]]
    for name in traced[0]["metrics"] if traced else ():
        print(f"  {name:26s} traced: " + " ".join(
            f"{t['metrics'][name]['value']:.5g}" for t in traced
            if name in t["metrics"]))
    if traced:
        print("  busy_s/window_s traced: " + " ".join(
            f"{t['device']['busy_s']:.3f}/{t['device']['window_s']:.3f}"
            for t in traced))
        print("  memory_peak_bytes: " + " ".join(
            str(r["line"]["device"]["memory_peak_bytes"]) for r in good))
    names = good[0]["line"]["compared"] if good else {}
    for name in names:
        vals = [r["line"]["compared"][name]["value"] for r in good]
        print(f"  compared {name:18s} max {max(vals):.3g} median "
              f"{statistics.median(vals):.3g} limit "
              f"{good[0]['line']['compared'][name]['limit']} "
              f"seeds {len({r['seed'] for r in good})}")
        print("      " + " ".join(f"{v:.2g}" for v in vals))


if __name__ == "__main__":
    for p in sys.argv[1:]:
        main(p)
