"""By hand: read what ``full_sets.sh`` wrote and print, per cell, what a
bound is held to — each end-to-end metric's median and spread in each set
(distance between the first and third quartile of
``statistics.quantiles(n=4)``, as a share of the median), whole and
without the set's run farthest from its median (what the driver's check
reads for tightness), both beside half the bound ``BENCHMARK.json`` gives
the metric; the second set's median against the first's; the passes each
run's window started; the rate and the longest pass that a shorter window
(SHORTER seconds, default 30: the passes that started before it) would
have read; and the largest reading of every number compared, over all the
seeds run.

    python3 benchmark/tests/summarize_sets.py chiprun_out/sets/<workload>.jsonl
"""

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def without_farthest(values):
    """The set less its run farthest from the median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def bounds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}


def set_line(vals, bound):
    """One set's median and both spreads, beside half the bound."""
    line = f"median {statistics.median(vals):.6g} spread " \
           f"{100 * spread(vals):.2f}%"
    if len(vals) >= 4:
        line += f" ({100 * spread(without_farthest(vals)):.2f}% without " \
                f"its farthest run)"
    if bound is not None:
        line += f" | half the bound {100 * bound / 2:.2f}%"
    return line


def main(path):
    runs = [json.loads(line.replace('"wall_s": ,', '"wall_s": 0,'))
            for line in open(path)]
    good = [r for r in runs if r["line"]]
    print(f"{path}: {len(runs)} runs, {len(good)} with a result line, "
          f"correct on {sum(r['line']['correct'] for r in good)}, "
          f"rcs {sorted({r['rc'] for r in runs})}, wall "
          f"{statistics.median(r['wall_s'] for r in runs):.0f} s median")
    bound = bounds()
    names = sorted({r["set"] for r in good} - {"traced", "extra"})
    sets = {s: [r for r in good if r["set"] == s] for s in names}
    for s, rs in sets.items():
        print(f"  {s}: seeds " + " ".join(str(r["seed"]) for r in rs)
              + " | passes a window " + " ".join(
                  str(r["window"]["passes"]) if r.get("window") else "?"
                  for r in rs))
    for name in (sets[names[0]][0]["line"]["metrics"] if names else ()):
        medians = []
        for s, rs in sets.items():
            vals = [r["line"]["metrics"][name]["value"] for r in rs]
            if len(vals) >= 2:
                medians.append(statistics.median(vals))
                print(f"  {name:24s} {s}: "
                      + set_line(vals, bound.get(name)))
            print("      " + " ".join(f"{v:.6g}" for v in vals))
        if len(medians) == 2:
            print(f"  {name:24s} second/first {medians[1] / medians[0]:.4f}")
    shorter = float(os.environ.get("SHORTER", 30))
    for name, read in (
            ("examples_per_s_per_chip",
             lambda w, ps: len(ps) * w["examples_per_pass"]
             / (ps[-1][0] + ps[-1][1])),
            ("pass_s_max", lambda w, ps: max(p[1] for p in ps))):
        line = f"  at {shorter:g} s: {name:26s}"
        for s, rs in sets.items():
            vals = [read(r["window"], [p for p in r["window"]["passes_at"]
                                       if p[0] < shorter])
                    for r in rs if r.get("window")]
            if len(vals) >= 2:
                line += f" median {statistics.median(vals):.6g} spread " \
                        f"{100 * spread(vals):.2f}%"
        print(line)
    traced = [r["line"] for r in good if r["set"] == "traced"]
    for name in traced[0]["metrics"] if traced else ():
        print(f"  {name:26s} traced: " + " ".join(
            f"{t['metrics'][name]['value']:.5g}" for t in traced
            if name in t["metrics"]))
    if traced:
        print("  busy_s/window_s traced: " + " ".join(
            f"{t['device']['busy_s']:.3f}/{t['device']['window_s']:.3f}"
            for t in traced))
    if good:
        print("  memory_peak_bytes: " + " ".join(
            str(r["line"]["device"]["memory_peak_bytes"]) for r in good))
    compared = good[0]["line"]["compared"] if good else {}
    for name in compared:
        vals = [r["line"]["compared"][name]["value"] for r in good]
        print(f"  compared {name:18s} max {max(vals):.3g} median "
              f"{statistics.median(vals):.3g} limit "
              f"{good[0]['line']['compared'][name]['limit']} "
              f"seeds {len({r['seed'] for r in good})}")
        print("      " + " ".join(f"{v:.2g}" for v in vals))


if __name__ == "__main__":
    for p in sys.argv[1:]:
        main(p)
