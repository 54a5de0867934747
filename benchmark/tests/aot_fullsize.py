"""By hand, in the sandbox: compile one cell's step and apply programs at
full size for a described ``v5e:2x2`` chip and print what the TPU compiler
says of their memory — what it refuses here costs no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_fullsize.py <workload>

(``WAITING=benchmark/waiting_cells.json`` in the environment for a cell that
is not in ``BENCHMARK.json`` yet; the same for ``control_readings.py``.)

Nothing runs on a TPU and nothing here is a time. The trainer is built on
the CPU with ``jax.default_backend`` answering "tpu" (so its resolvers
take the chip's branches), its first working set is built at full size
from the cell's own traffic, one batch is packed by the host plan, and
the programs are lowered from those shapes for the described device.
"""

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(workload: str) -> None:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from benchmark import datagen, run, sut
    from paddlebox_tpu.parallel import make_mesh, mesh as mesh_lib
    from paddlebox_tpu.train.trainer import PLAN_ARITY

    jax.default_backend = lambda: "tpu"
    _, cell, cfg, mix = run.load_cell(workload, os.environ.get("WAITING"))
    batch = cfg["trainer"]["global_batch_size"]
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    hot = datagen.slot_hotness(mix, n_sparse)
    a, _ = datagen.make_passes(mix, n_sparse, dense_dim, batch, 1)
    tmp = tempfile.mkdtemp(prefix="pbtpu_aot_")
    files = datagen.write_pass(tmp, "A", a, mix["files_per_pass"])
    system = sut.System(cfg, hot, 1)
    tr = system.trainer
    ds = system._dataset(files)
    ds.load_into_memory(global_shuffle=False)
    ws = tr.feed_mgr.begin_pass(ds.unique_keys())
    pb = next(iter(ds.batches(batch)))
    host = tr._pack_host(ws, pb)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    tr.mesh = make_mesh(devices=topo.devices[:1])
    tr._rebuild_steps()
    bat = mesh_lib.batch_sharding(tr.mesh)
    tbl = mesh_lib.table_sharding(tr.mesh)
    rep = mesh_lib.replicated_sharding(tr.mesh)

    def like(x, sh):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sh)

    # the table as the device holds it: one array, or a plane table's two
    table = jax.tree.map(lambda plane: like(plane, tbl), ws.table)
    dstate = [like(x, rep) for x in tr.pack_dense()]
    args = [like(x, bat) for x in host]
    out = {"workload": workload, "table_shape": list(ws.table.shape),
           "plane_shapes": [list(p.shape) for p in jax.tree.leaves(ws.table)],
           "push_engine": tr.resolved_push_engine(ws),
           "pull_engine": tr.pull_engine, "push_overlap": tr.push_overlap,
           "host_plan": tr.engines()["host_plan"]}
    t = time.time()
    if tr.push_overlap:
        step = tr._defer_step_fn.lower(table, *dstate, *args).compile()
        ops = tr.split_defer_out(jax.eval_shape(
            tr._defer_step_fn, table, *dstate, *args))[1]
        apply = tr._apply_fn.lower(
            table, args[0], args[1], args[3], *args[4:4 + PLAN_ARITY],
            *[like(o, bat) for o in ops]).compile()
        programs = {"step": step, "apply": apply}
    else:
        programs = {"step": tr._step_fn.lower(table, *dstate,
                                              *args).compile()}
    out["compile_seconds"] = round(time.time() - t, 1)
    for name, prog in programs.items():
        m = prog.memory_analysis()
        text = prog.as_text()
        out[name] = {"argument_gb": m.argument_size_in_bytes / 1e9,
                     "output_gb": m.output_size_in_bytes / 1e9,
                     "temp_gb": m.temp_size_in_bytes / 1e9,
                     "alias_gb": m.alias_size_in_bytes / 1e9,
                     "custom_calls": text.count("tpu_custom_call"),
                     "kernels": sorted({k for k in (
                         "pbtpu_binned_merge_acc", "pbtpu_gather_pool",
                         "pbtpu_scatter_accumulate", "pbtpu_merge_update")
                         if k in text})}
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
