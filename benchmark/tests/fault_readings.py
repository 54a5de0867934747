"""By hand, on the chip: what ``window_counter_mismatch`` reads, at the
cell's own size, when the boundary's write-back is left out.

    python3 benchmark/tests/fault_readings.py <workload> <seed> [<seed> ...]

The program runs the cell's own set-up and one window pass; from the end
of the followed steps on, ``HostEmbeddingStore.write_back`` does nothing —
rows that retire to the host store, and rows flushed at the end, keep the
store's old bytes — and the rest of a run is driven over it, as
``test_correct.py`` does at a CPU size. One JSON line a seed; the script
fails if a run comes out correct.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def drop_write_back_after_probe(setattr_) -> None:
    """Plant the fault: `setattr_` (``setattr``, or a test's
    ``monkeypatch.setattr``) replaces the store's write-back once the
    probe of the first steps has switched itself off."""
    from benchmark import sut
    from paddlebox_tpu.embedding import HostEmbeddingStore
    real_save, real_write_back = (sut.StepProbe.save,
                                  HostEmbeddingStore.write_back)

    def save(self, trainer, *, mid_steps, **kw):
        out = real_save(self, trainer, mid_steps=mid_steps, **kw)
        if mid_steps >= max(self.steps):
            setattr_(HostEmbeddingStore, "write_back",
                     lambda store, keys, rows: None)
        return out

    setattr_(sut.StepProbe, "save", save)
    setattr_(HostEmbeddingStore, "write_back", real_write_back)


def main(workload: str, seeds: list[int]) -> None:
    from benchmark import run
    passed = []
    for seed in seeds:
        drop_write_back_after_probe(setattr)
        code, result = run.run(argparse.Namespace(
            workload=workload, seed=seed, seconds=1.0, trace=0,
            rehearse=False, keep_trace=None,
            waiting=os.environ.get("WAITING")))
        print(json.dumps({"workload": workload, "seed": seed, "code": code,
                          "fault": "write_back_dropped",
                          "correct": result and result["correct"],
                          "compared": result and result["compared"]}),
              flush=True)
        if result and result["correct"]:
            passed.append(seed)
    if passed:
        sys.exit(f"came out correct: {passed}")


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
