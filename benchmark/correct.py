"""The comparison that decides ``correct`` for a training cell.

What the timed path produced in its first steps — through the window's
own call, on the pass's own table — against the plain reference that
followed the same steps from the same seed. Every number compared has a
limit of its own, kept with the traffic mix (``limits`` in the workload's
file; ``PERF.md`` gives the readings each was set from):

  ingest_mismatch   values of the first batches the parser and packer
                    delivered (ids, presence, labels, dense) that differ
                    from what the generator wrote, plus examples loaded
                    that were not written or the reverse. Exact: limit 0.
  counter_mismatch  rows whose show or clk count after the last followed
                    step differs from the reference's. Exact: limit 0.
  loss_gap_<k>      |loss - reference| / reference, step k. (Only the first
                    step's has a limit in the first cells; the later ones
                    are printed: a difference of the first step grows a
                    hundredfold per step, in the program and in the
                    bfloat16 control alike, so neither the control nor a
                    fault reads clear of sound runs there.)
  grad_gap          the first gradient as each optimizer got it, worked
                    out from the state after one step: dense leaves from
                    Adam's first moment, the table's w and embedding from
                    the adagrad accumulators. Worst leaf of
                    | ||g|| - ||g_ref|| | / max(||g_ref||, median leaf's).
  change_gap        the same measure on the change of every leaf over the
                    followed steps (the table's leaves: w, embedding and
                    both accumulators, over the rows of those steps).
                    Leaves whose reference gradient is under a thousandth
                    of the median leaf's are left out: they move by
                    round-off alone.

And what every pass of the run — the warm-up cycle and the window's —
left behind, read back through the store once the window has closed:

  window_counter_mismatch
                    of a sample of keys drawn from the seed (as many of
                    both pass sets, of A alone and of B alone as there
                    are, up to 4096 each: rows that stayed resident, rows
                    that retired to the host store and rows that came
                    back), those whose show or clk
                    count is not the number of their tokens, and of their
                    tokens' clicks, in all the passes run. Exact: limit
                    0. A boundary that reuses a stale row, drops a
                    write-back or loses the last deferred push miscounts.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmark.reference.steps import B1


def _leaves(tree) -> list[tuple[str, np.ndarray]]:
    """(name, leaf) in the tree's order, each leaf as the tree holds it:
    whoever reads one turns that one into float64, so the comparison's
    memory follows the largest leaf and not the model."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("dense" + jax.tree_util.keystr(path), leaf)
            for path, leaf in flat]


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def grad_norms(after1: dict, dim: int) -> dict[str, float]:
    """Norm of the first gradient per leaf, from the state after one step."""
    out = {name: _norm(m) / (1 - B1) for name, m in _leaves(after1["m"])}
    rows = after1["rows"]
    out["table.w"] = float(np.sqrt(np.sum(rows[:, 3 + dim], dtype=np.float64)))
    out["table.embedding"] = float(np.sqrt(
        dim * np.sum(rows[:, 4 + dim], dtype=np.float64)))
    return out


def change_norms(after: dict, params0, rows0: np.ndarray,
                 dim: int) -> dict[str, float]:
    now, before = _leaves(after["params"]), _leaves(params0)
    if [name for name, _ in now] != [name for name, _ in before]:
        raise ValueError("the trees compared do not hold the same leaves")
    out = {name: _norm(np.asarray(p, np.float64) - np.asarray(p0, np.float64))
           for (name, p), (_, p0) in zip(now, before)}
    d = np.asarray(after["rows"], np.float64) - rows0
    out["table.w"] = _norm(d[:, 2])
    out["table.embedding"] = _norm(d[:, 3:3 + dim])
    out["table.g2w"] = _norm(d[:, 3 + dim])
    out["table.g2x"] = _norm(d[:, 4 + dim])
    return out


def worst_leaf_gap(got: dict[str, float], ref: dict[str, float],
                   skip: set[str] = frozenset()) -> tuple[float, str]:
    median = float(np.median(list(ref.values())))
    worst, name = 0.0, ""
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        gap = abs(got[leaf] - r) / max(r, median, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, name = gap, leaf
    return worst, name


def ingest_mismatch(parsed: list[dict], written: list[dict],
                    examples_loaded: list[int],
                    examples_written: list[int]) -> int:
    bad = sum(abs(a - b) for a, b in zip(examples_loaded, examples_written))
    bad += abs(len(parsed) - len(written)) * 10 ** 6
    for p, w in zip(parsed, written):
        if p["ids"].shape != w["ids"].shape:
            bad += int(w["ids"].size)
            continue
        bad += int(np.sum(p["ids"] != w["ids"]))
        bad += int(np.sum(p["mask"] != w["mask"]))
        bad += int(np.sum(p["floats"][:, 0] != w["labels"]))
        bad += int(np.sum(p["floats"][:, 1:] != w["dense"]))
    return bad


def sample_keys(passes, each: int, seed: int) -> np.ndarray:
    """Up to `each` keys of each kind, drawn from the seed: keys both pass
    sets hold (rows that stay resident), keys of A alone and keys of B
    alone (rows that retire to the host store and come back); sorted."""
    rng = np.random.default_rng([int(seed), 0xC])
    held = []
    for p in passes:
        cols = [np.unique(p.ids[:, c]) for c in range(p.ids.shape[1])]
        keys = np.unique(np.concatenate(cols))
        held.append(keys[keys != 0])
    kinds = [np.intersect1d(*held, assume_unique=True),
             np.setdiff1d(held[0], held[1], assume_unique=True),
             np.setdiff1d(held[1], held[0], assume_unique=True)]
    return np.sort(np.concatenate([
        rng.choice(k, size=min(each, len(k)), replace=False) for k in kinds]))


def window_counter_mismatch(rows: np.ndarray, keys: np.ndarray, passes,
                            runs_of: list[int]) -> tuple[int, dict]:
    """`rows` of the sorted `keys` as the store holds them after pass set
    k was trained ``runs_of[k]`` times; the counts the generator's own id
    matrices and labels give."""
    show = np.zeros(len(keys), np.float64)
    clk = np.zeros(len(keys), np.float64)
    in_set = []
    for p, runs in zip(passes, runs_of):
        n = np.zeros(len(keys), np.int64)
        clicks = np.zeros(len(keys), np.int64)
        for c in range(p.ids.shape[1]):
            col = p.ids[:, c]              # 0 where absent: never a key
            lo, hi = np.searchsorted(keys, [col.min(), col.max() + 1])
            if lo == hi:
                continue
            # position + 1 of each token's key among keys[lo:hi], 0 for
            # the others: a table over the column's span of keys where
            # that is small, else a search
            base = int(keys[lo])
            if int(keys[hi - 1]) - base < 1 << 26:
                table = np.zeros(int(keys[hi - 1]) - base + 3, np.int32)
                table[keys[lo:hi] - base + 1] = np.arange(1, hi - lo + 1)
                pos = table[np.clip(col - base + 1, 0, len(table) - 1)]
            else:
                at = np.minimum(np.searchsorted(keys[lo:hi], col),
                                hi - lo - 1)
                pos = np.where(keys[lo:hi][at] == col, at + 1, 0)
            n[lo:hi] += np.bincount(pos, minlength=hi - lo + 1)[1:]
            clicks[lo:hi] += np.bincount(pos, weights=p.labels,
                                         minlength=hi - lo + 1
                                         )[1:].astype(np.int64)
        show += runs * n
        clk += runs * clicks
        in_set.append(n > 0)
    bad = (rows[:, 0] != show) | (rows[:, 1] != clk)
    return int(bad.sum()), {
        "in_both": int(np.sum(in_set[0] & in_set[1])),
        "in_a_alone": int(np.sum(in_set[0] & ~in_set[1])),
        "in_b_alone": int(np.sum(~in_set[0] & in_set[1])),
        "largest_show": float(show.max())}


def compare(got: dict, ref: dict, dim: int) -> tuple[dict, dict]:
    """`got` and `ref` both hold ``losses`` and ``after`` {1, n}: the
    program's readings and the reference's (``ref`` also ``params0`` and
    ``rows0``). Returns the numbers compared and, for the earlier lines,
    which leaf was the worst."""
    n = max(ref["after"])
    numbers, notes = {}, {}
    for k, (a, b) in enumerate(zip(got["losses"], ref["losses"]), start=1):
        numbers[f"loss_gap_{k}"] = abs(a - b) / abs(b)
    g_ref = grad_norms(ref["after"][1], dim)
    numbers["grad_gap"], notes["grad_gap_leaf"] = worst_leaf_gap(
        grad_norms(got["after"][1], dim), g_ref)
    median_g = float(np.median(list(g_ref.values())))
    still = {leaf for leaf, g in g_ref.items() if g < 1e-3 * median_g}
    if "table.w" in still:
        still.add("table.g2w")
    if "table.embedding" in still:
        still.add("table.g2x")
    notes["leaves_left_out_of_change"] = sorted(still)
    numbers["change_gap"], notes["change_gap_leaf"] = worst_leaf_gap(
        change_norms(got["after"][n], ref["params0"], ref["rows0"], dim),
        change_norms(ref["after"][n], ref["params0"], ref["rows0"], dim),
        skip=still)
    numbers["counter_mismatch"] = int(np.sum(np.any(
        got["after"][n]["rows"][:, :2] != ref["after"][n]["rows"][:, :2],
        axis=1)))
    return numbers, notes


def judge(numbers: dict, limits: dict) -> tuple[bool, dict, dict]:
    """Each number the cell's mix gives a limit, beside that limit; one
    that is missing or not finite fails. Numbers the mix gives no limit
    are not compared (``PERF.md`` says why, with their readings) and come
    back apart, for an earlier line."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        ok &= bool(np.isfinite(value) and value <= limit)
        table[name] = {"value": float(value), "limit": limit}
    return ok, table, {k: float(v) for k, v in numbers.items()
                       if k not in limits}
