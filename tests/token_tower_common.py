"""What the token towers' tests share: two passes (files A, then B) of a
cell at its rehearsal sizes through ``Trainer.train_pass``, the first
pass's three first steps followed by the plain reference as ``run.py``
follows them."""

import shutil
import tempfile

import numpy as np

from paddlebox_tpu import monitor


def rehearsal_cell(cell: str):
    from benchmark import run
    _, _, cfg, mix = run.load_cell(cell)
    return run.rehearsal_sizes(cfg, mix)


def follow_two_passes(cell: str, seed: int, n: int = 3) -> dict:
    from benchmark import correct, datagen, sut
    from benchmark.reference import steps
    cfg, mix = rehearsal_cell(cell)
    batch = cfg["trainer"]["global_batch_size"]
    hot = datagen.slot_hotness(mix, 1)
    passes = datagen.make_passes(mix, 1, 0, batch, seed)
    tmp = tempfile.mkdtemp(prefix="pbtpu_tower_")
    try:
        files = [datagen.write_pass(tmp, tag, p, 2)
                 for tag, p in zip("AB", passes)]
        batches = passes[0].batches(batch, n)
        params0 = steps.initial_params(cfg, seed)
        system = sut.System(cfg, hot, seed, dense_params=params0)
        keys = np.unique(np.concatenate(
            [b["ids"][b["mask"]] for b in batches]))
        probe = sut.StepProbe(keys, (1, n))
        probe.attach(system.trainer, system.box)
        stats0 = monitor.STATS.snapshot()
        recs = [system.run_pass(files[0], keep_batches=n),
                system.run_pass(files[1])]
        stats1 = monitor.STATS.snapshot()
        got = {"losses": recs[0]["losses"][:n], "after": probe.after}
        ref = steps.follow(cfg, params0, batches, hot, seed)
        numbers, notes = correct.compare(got, ref, cfg["embedding"]["dim"])
        numbers["ingest_mismatch"] = correct.ingest_mismatch(
            recs[0]["first_batches"], batches, [recs[0]["examples"]],
            [passes[0].num])
        return {"cfg": cfg, "numbers": numbers, "notes": notes, "recs": recs,
                "engines": system.engines(), "trainer": system.trainer,
                "stats": {k: stats1.get(k, 0) - stats0.get(k, 0)
                          for k in stats1},
                "snapshot": stats1}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def tower(cell: str, seed: int = 0, **model_args):
    """(cfg, reference module, model, params, pulled rows, ids) of a
    cell's tower at its rehearsal sizes — `model_args` over the cell's
    own — on seeded random weights and inputs, two examples."""
    import importlib

    import jax

    from paddlebox_tpu.models import MODEL_REGISTRY
    cfg, _ = rehearsal_cell(cell)
    a = {**cfg["model_args"], **model_args}
    cfg = {**cfg, "model_args": a}
    ref = importlib.import_module("benchmark.reference." + cfg["model"])
    model = MODEL_REGISTRY[cfg["model"]](**{
        k: tuple(v) if isinstance(v, list) else v for k, v in a.items()})
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = ref.init_params(k1, cfg)
    B, T = 2, a["seq_len"]
    pulled = jax.random.normal(k2, (B, T, 3 + a["hidden_size"])) * 0.3
    ids = jax.random.randint(k3, (B, T), 0, a["vocab_size"])
    return cfg, ref, model, params, pulled, ids
