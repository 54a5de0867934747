"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process loads, warms up, measures and prints earlier lines freely
and one last line on standard output: a JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (a traced run may add
``breakdown``) and, last, ``compared`` — every number that decided
``correct`` beside its limit; the same numbers close standard error.

What a cell is comes from data, found by the name in ``BENCHMARK.json``:
its configuration (``file`` of the entry in ``configs``), its traffic mix
(``benchmark/workloads/<name>.json``), its per-layer metrics (one reader
each, ``benchmark/metrics/<metric>.py``) and its model's plain reference
(``benchmark/reference/<model>.py``). No cell's name is in this code;
``benchmark/README.md`` says what files a new cell brings.

The window drives the user's day loop per pass — ``SlotDataset`` load of
slot-text files, ``BoxPS.begin_pass``, ``Trainer.train_pass``,
``BoxPS.end_pass`` — over two sets of pass files A, B, A, B, ... made from
``--seed`` (two draws of the traffic mix's public distribution; where the
mix states a ``pool_seed`` the draws are that seed's in every run and
``--seed`` orders each pass's examples). The
initial state — the dense weights, a new key's row, the trainer's seed —
comes from the configuration's ``weights_seed`` where its file has one
(training continues from one checkpoint, whatever the day's traffic) and
from ``--seed`` where it has none. Set-up (``setup_s``: process start to
window start) writes the files, builds the trainer once and runs one full
warm-up cycle. A pass starts while the window has time left and fewer
than the mix's ``max_passes_per_window`` have started (``window_has_room``;
no such key: time alone); the window ends when the last started pass's
``end_pass`` has returned and the table and dense state are ready on the
device. Every rate divides by that whole time.

``correct`` compares the first steps of the first pass with the plain
reference (``correct.py``), and, once the window has closed, the show and
click counts that all the passes run left in the rows of a sample of keys.

``--trace 1``: the same set-up, then one whole pass cycle under
``jax.profiler``, reduced by ``trace_reduce.py``; the per-layer metrics
come from that cycle. End-to-end metrics never come from a traced run.

The run fails (non-zero, no last line) when JAX finds no TPU, or fewer
chips than the cell asks for. ``--rehearse`` walks the same control flow
at tiny sizes (``rehearsal_sizes``) on whatever backend JAX has (the
sandbox's CPU) and never prints a line of metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# a rehearsal's sizes, where the cell's own files give no ``rehearsal``
REHEARSAL = {"steps_per_pass": 6, "max_ind_range": 2048, "files_per_pass": 2}
REHEARSAL_BATCH = 256
WARMUP_PASSES = 2      # one full cycle A, B: both row buckets compile
FOLLOWED_STEPS = 3     # steps of the first pass the reference follows
SAMPLED_KEYS = 4096    # of each kind (correct.sample_keys), read back at the end


def say(**obj) -> None:
    print(json.dumps(obj), flush=True)


def load_cell(name: str, waiting: str | None = None
              ) -> tuple[dict, dict, dict, dict]:
    """(the BENCHMARK.json, the cell's entry, its configuration, its mix).
    `waiting` names a file of further ``configs`` and ``workloads`` entries
    — cells not in the benchmark yet, for a trial before they are added."""
    from benchmark import datagen
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs, cells = list(bench["configs"]), list(bench["workloads"])
    if waiting:
        with open(os.path.join(ROOT, waiting)) as f:
            more = json.load(f)
        configs += more.get("configs", [])
        cells += more.get("workloads", [])
    cells = {w["name"]: w for w in cells}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in configs}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    mix = datagen.load_mix(os.path.join(
        ROOT, cell.get("file", f"benchmark/workloads/{name}.json")))
    return bench, cell, cfg, mix


def rehearsal_sizes(cfg: dict, mix: dict) -> tuple[dict, dict]:
    """The cell cut to a rehearsal: the defaults above, then the cell's own
    ``rehearsal`` — in the configuration's file overrides of its groups
    (``model_args``, ``trainer``, ``embedding``: merged key by key) and of
    single keys, in the mix's file overrides of the mix."""
    cfg = {**cfg, "trainer": {**cfg["trainer"],
                              "global_batch_size": REHEARSAL_BATCH}}
    for key, val in cfg.get("rehearsal", {}).items():
        cfg[key] = {**cfg[key], **val} if isinstance(val, dict) else val
    return cfg, {**mix, **REHEARSAL, **mix.get("rehearsal", {})}


def window_has_room(elapsed: float, seconds: float, started: int,
                    cap: int | None) -> bool:
    """The window's rule: another pass starts while the window has time
    left and fewer than `cap` have started; no cap, time alone."""
    return elapsed < seconds and (cap is None or started < cap)


def metrics_of(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The cell's metrics of one kind: those that name it, or name none."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def device_facts(devs) -> tuple[dict, dict]:
    """The device as JAX reports it, and the fullest chip's memory
    statistics. The peak is ``peak_bytes_in_use``: the buffers the program
    holds. (On a TPU the largest program's temporaries are a second part
    of HBM, ``peak_bytes_reserved``; the metric ``reserved_hbm_gb`` reads
    them.)"""
    fullest = max((d.memory_stats() or {} for d in devs),
                  key=lambda st: int(st.get("peak_bytes_in_use", 0)))
    return ({"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs),
             "memory_peak_bytes": int(fullest.get("peak_bytes_in_use", 0))},
            fullest)


def tokens_and_unique(p, batch: int, sample: int = 8) -> tuple[float, float]:
    """Tokens and unique keys per step, over evenly spaced batches."""
    import numpy as np
    steps = p.num // batch
    picks = np.unique(np.linspace(0, steps - 1, min(sample, steps)).astype(int))
    tok, uniq = [], []
    for k in picks:
        ids = p.ids[k * batch:(k + 1) * batch]
        present = ids[ids != 0]
        tok.append(present.size)
        uniq.append(np.unique(present).size)
    return float(np.mean(tok)), float(np.mean(uniq))


def run(args) -> tuple[int, dict | None]:
    bench, cell, cfg, mix = load_cell(args.workload, args.waiting)
    try:
        import paddlebox_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the system under test is not in this directory "
              f"({e})", file=sys.stderr)
        return 3, None
    import jax
    import numpy as np
    devs = jax.devices()
    chips = int(cell["chips"])
    if not args.rehearse and devs[0].platform != "tpu":
        print(f"run.py: no TPU — jax.devices()[0].platform is "
              f"{devs[0].platform!r}; the benchmark measures on the chip "
              f"and does not fall back (--rehearse walks the control flow "
              f"at tiny sizes instead)", file=sys.stderr)
        return 2, None
    if len(devs) < chips and not args.rehearse:
        print(f"run.py: the cell asks for {chips} chip(s), JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 2, None
    devs = devs[:chips]

    from paddlebox_tpu.utils.compile_cache import (CompileMeter,
                                                   enable_compile_cache)
    cache = None if args.rehearse else enable_compile_cache()
    # keep every program, also the sub-second ones, so a second run of a
    # cell in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    meter = CompileMeter()

    from benchmark import correct, datagen, sut, trace_reduce, work
    from benchmark.reference import steps as ref_steps

    if args.rehearse:
        cfg, mix = rehearsal_sizes(cfg, mix)
    batch = int(cfg["trainer"]["global_batch_size"])
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    hot = datagen.slot_hotness(mix, n_sparse)
    n_follow = FOLLOWED_STEPS
    # what is initial state follows the configuration's seed where its
    # file has one; what is traffic always follows the run's
    weights_seed = int(cfg.get("weights_seed", args.seed))
    cap = mix.get("max_passes_per_window")
    say(phase="start", workload=cell["name"], seed=args.seed,
        weights_seed=weights_seed, seconds=args.seconds, trace=args.trace,
        rehearse=args.rehearse, jax=jax.__version__, compile_cache=cache,
        device={"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)})

    tmp = tempfile.mkdtemp(prefix="pbtpu_bench_")
    system = None
    try:
        # ---- set-up: traffic, weights, trainer, one warm-up cycle --------
        t = time.perf_counter()
        passes = datagen.make_passes(mix, n_sparse, dense_dim, batch,
                                     args.seed)
        files = [datagen.write_pass(tmp, tag, p, int(mix["files_per_pass"]))
                 for tag, p in zip("AB", passes)]
        if mix.get("input_format", "text") == "archive":
            from paddlebox_tpu.data.archive import archive_filelist
            schema = sut.build_schema(cfg, hot)
            files = [archive_filelist(f, schema, os.path.join(tmp, f"ar{k}"))
                     for k, f in enumerate(files)]
        written = [p.num for p in passes]
        followed = passes[0].batches(batch, n_follow)
        tok_step, uniq_step = tokens_and_unique(passes[0], batch)
        say(phase="traffic", seconds=round(time.perf_counter() - t, 3),
            examples_per_pass=written, tokens_per_step=tok_step,
            unique_rows_per_step=uniq_step,
            unique_keys_reckoned=round(datagen.expected_unique_keys(
                mix, n_sparse, batch)),
            bytes_written=sum(os.path.getsize(f) for fl in files for f in fl))

        params0 = ref_steps.initial_params(cfg, weights_seed)
        system = sut.System(cfg, hot, weights_seed, dense_params=params0,
                            n_devices=len(devs))
        keys = np.unique(np.concatenate(
            [b["ids"][b["mask"]] for b in followed]))
        probe = sut.StepProbe(keys, (1, n_follow))
        probe.attach(system.trainer, system.box)
        overlap = bool(mix.get("overlap_load", 0))
        n_warm = WARMUP_PASSES
        for k in range(n_warm):
            rec = system.run_pass(
                files[k % 2], files[(k + 1) % 2] if overlap else None,
                keep_batches=n_follow if k == 0 else 0)
            say(phase="warmup", **{"pass": k}, seconds=rec["seconds"],
                steps=rec["steps"], boundary_s=rec["boundary_s"],
                fresh_rows=rec["fresh_rows"], reused_rows=rec["reused_rows"],
                loss_first=rec["losses"][0], loss_last=rec["losses"][-1],
                **meter.snapshot())
        system.block()
        warm = system.passes[:n_warm]
        got = {"losses": warm[0]["losses"][:n_follow], "after": probe.after}
        parsed = warm[0]["first_batches"]
        say(phase="engines", **system.engines())
        compiled0 = meter.snapshot()
        setup_s = time.perf_counter() - T_START

        # ---- the window, or the traced cycle ----------------------------
        reduced = None
        k = n_warm
        t0 = time.perf_counter()
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            trace_dir = os.path.join(tmp, "trace")
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                system.run_pass(files[k % 2],
                                files[(k + 1) % 2] if overlap else None)
                system.block()
            finally:
                jax.profiler.stop_trace()
        else:
            while window_has_room(time.perf_counter() - t0, args.seconds,
                                  k - n_warm, cap):
                system.run_pass(files[k % 2],
                                files[(k + 1) % 2] if overlap else None)
                k += 1
            system.block()
        window_s = time.perf_counter() - t0
        in_window = meter.since(compiled0)
        measured = system.passes[n_warm:]
        for rec in measured:
            say(phase="pass", start_s=rec["t0"] - t0, seconds=rec["seconds"],
                steps=rec["steps"],
                ingest_s=rec["ingest_s"], boundary_s=rec["boundary_s"],
                boundary_split=rec["boundary_split"],
                fresh_rows=rec["fresh_rows"], reused_rows=rec["reused_rows"],
                h2d_bytes=rec["boundary_h2d_bytes"], timers=rec["timers"],
                loss_last=rec["losses"][-1])
        say(phase="window", seconds=window_s, passes=len(measured),
            max_passes_per_window=cap, examples_per_pass=written[0],
            passes_at=[[p["t0"] - t0, p["seconds"]] for p in measured],
            compiled_in_window=in_window)
        if in_window["compilations"] - in_window["cache_hits"] > 0 \
                and not args.rehearse:
            print(f"run.py: {in_window} compiled inside the window — not a "
                  f"steady-state reading; the warm-up has to cover it",
                  file=sys.stderr)
            return 4, None
        device, memory = device_facts(devs)
        say(phase="memory", stats=memory)
        loaded = [p["examples"] for p in system.passes]
        examples = sum(p["examples"] for p in measured)
        trained = sum(p["steps"] for p in measured) * batch
        dropped = sum(p["routed_dropped"] for p in measured)
        if args.trace:
            t = time.perf_counter()
            xplane = trace_reduce.find_xplane(trace_dir)
            reduced = trace_reduce.reduce(xplane)
            say(phase="trace", xplane_bytes=os.path.getsize(xplane),
                reduce_seconds=round(time.perf_counter() - t, 3),
                devices=reduced["devices"],
                by_program=reduced.get("by_program"))
            if args.keep_trace:
                os.makedirs(os.path.dirname(args.keep_trace) or ".",
                            exist_ok=True)
                shutil.copy(xplane, args.keep_trace)

        # ---- what the passes left in the rows of a sample of keys; then
        # free the program; then the reference and the comparison ---------
        t = time.perf_counter()
        sample = correct.sample_keys(passes, SAMPLED_KEYS, args.seed)
        left = system.read_rows(sample)
        say(phase="read_back", keys=len(sample),
            seconds=round(time.perf_counter() - t, 3))
        system.free()
        system = None
        gc.collect()
        t = time.perf_counter()
        ref = ref_steps.follow(cfg, params0, followed, hot, weights_seed)
        numbers, notes = correct.compare(got, ref,
                                         int(cfg["embedding"]["dim"]))
        numbers["ingest_mismatch"] = correct.ingest_mismatch(
            parsed, followed, loaded,
            [written[i % 2] for i in range(len(loaded))])
        runs_of = [(len(loaded) + 1 - k) // 2 for k in range(2)]
        numbers["window_counter_mismatch"], notes["sampled_keys"] = \
            correct.window_counter_mismatch(left, sample, passes, runs_of)
        del passes
        ok, compared, not_compared = correct.judge(numbers, mix["limits"])
        say(phase="reference", seconds=round(time.perf_counter() - t, 3),
            losses=got["losses"], reference_losses=ref["losses"],
            not_compared=not_compared, **notes)

        # ---- the result --------------------------------------------------
        record = {"passes": measured, "trace": reduced, "memory": memory}
        if not args.rehearse:
            peak = work.peaks(devs[0].device_kind)
            record.update(peaks=peak, work={
                "flops": work.step_flops(cfg),
                "bytes": work.step_bytes(cfg, tok_step, uniq_step)})
            say(phase="work", **record["work"], peaks=peak)
        values = {}
        if args.trace:
            for m in metrics_of(bench, cell, "per_layer"):
                reader = importlib.import_module(
                    f"benchmark.metrics.{m['name']}")
                v = reader.read(record)
                if v is not None:
                    values[m["name"]] = {"value": float(v), "unit": m["unit"]}
            if reduced and reduced["devices"]:
                device.update(busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
        else:
            e2e = {
                "examples_per_s_per_chip": trained / window_s / len(devs),
                "pass_s_max": max(p["seconds"] for p in measured),
                "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
                "setup_s": setup_s,
            }
            for m in metrics_of(bench, cell, "end_to_end"):
                values[m["name"]] = {"value": float(e2e[m["name"]]),
                                     "unit": m["unit"]}
        result = {"correct": bool(ok), "attempted": int(examples),
                  "failed": int(examples - trained + dropped),
                  "metrics": values, "device": device}
        if reduced and reduced["devices"]:
            result["breakdown"] = trace_reduce.breakdown(reduced)
        result["compared"] = compared
        return 0, result
    finally:
        if system is not None:
            system.free()
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no metrics")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb to this path")
    ap.add_argument("--waiting", default=None,
                    help="a file of configs and workloads entries not in "
                         "BENCHMARK.json yet, to try such a cell")
    args = ap.parse_args(argv)
    code, result = run(args)
    if result is None:
        return code
    for name, row in result["compared"].items():
        print(f"compared {name} = {row['value']:.6g} (limit {row['limit']})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    if args.rehearse:
        # a rehearsal's numbers are the CPU's: never under a metric's name
        say(rehearsal="passed" if result["correct"] else "not correct",
            correct=result["correct"], attempted=result["attempted"],
            failed=result["failed"], device=result["device"],
            compared=result["compared"])
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
