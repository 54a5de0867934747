"""Crash-safe pass lifecycle: atomic snapshots, manifest verification,
resume-from-pass, and the fault-injection kill→resume matrix.

The acceptance bar (ISSUE 3): for every registered fault point, killing a
training subprocess at that instruction and resuming must reproduce
bit-identical dense params and sparse table rows versus the uninterrupted
run; a deliberately truncated newest snapshot must be detected by checksum
and resume must fall back to the previous good one.

The subprocess matrix mirrors the reference's preemption model (SIGKILL via
``os._exit`` — no atexit, no finally, buffers lost; SURVEY.md §5 pass-
granularity restart). One point runs as a fast tier-1 smoke; the full
matrix is ``slow``.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
from paddlebox_tpu.utils import checkpoint as ckpt_lib
from paddlebox_tpu.utils import faultpoint
from paddlebox_tpu.utils.checkpoint import CheckpointCorruptError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "crash_worker.py")

# AFTER (skip count) per point, tuned so the kill lands in/after pass 2 —
# proving fallback to a real snapshot, not just a fresh start.
POINT_AFTER = {
    "ckpt.dense.pre_replace": 1,        # pass-2 snapshot's dense write
    "store.save_base.pre_replace": 1,   # pass-3 chain rotation base
    "store.save_delta.pre_replace": 0,  # pass-2 delta (pass 1 is a base)
    "store.save_delta.pre_manifest": 0,
    "feed_pass.flush.pre": 1,           # pass-2 save's D2H flush
    # ISSUE 14: the incremental delta feed's fetch window fires at every
    # reuse boundary (pass >= 2's begin_pass) — AFTER=1 kills the
    # pass-3 boundary, after the pass-2 snapshot committed
    "feed_pass.delta_stage.pre": 1,
    "trainer.push_apply.pre": 6,        # mid pass-2 deferred apply
    "pass_ckpt.pre_manifest": 1,        # pass-2 snapshot uncommitted
    "pass_ckpt.post_manifest": 1,       # pass-2 snapshot committed
    # ISSUE 5 points (the mid-pass/remote env of MIDPASS_REMOTE_ENV):
    "trainer.midpass.post_save": 2,     # mid pass-2 snapshot committed —
                                        # resume must skip from the cursor
    "remote_ckpt.upload.pre": 3,        # pass-2's first mirror upload
    # fires on the RESUME path (download with a wiped staging root) — the
    # dedicated test_kill_during_remote_download_resume flow, not the
    # generic kill→resume roundtrip
    "remote_ckpt.download.pre": 0,
    # ISSUE 6 step-loop windows (3 passes x 4 steps: AFTER=5 fires mid
    # pass 2 — the pack one on the producer thread, the step one right
    # before the dispatch)
    "trainer.pack.pre": 5,
    "trainer.step.pre": 5,
    # ISSUE 11 tiered-table windows (2 spill shards → 2 hits per save /
    # per boundary rebalance; AFTER=2 lands both in pass 2): the
    # streaming memmap save's pre-flush, and the pass-boundary RAM-tier
    # demotion — the cache is never authoritative, so both must resume
    # bit-exact
    "tiering.save.pre_flush": 2,
    "tiering.evict.pre": 2,
}

# points that only sit on the mid-pass / remote-mirror code paths run the
# worker with that configuration — which provably does not change the
# final planes (test_midpass_remote_run_matches_plain_golden)
MIDPASS_REMOTE_POINTS = {"trainer.midpass.post_save",
                         "remote_ckpt.upload.pre",
                         "remote_ckpt.download.pre"}

# points that only sit on the spill-tier code paths run the worker with
# a 2-shard ShardedEmbeddingStore over spill sub-stores (PBTPU_TABLE_
# TIERING=spill) — which provably does not change the final planes
# (test_spill_sharded_run_matches_plain_golden)
SPILL_POINTS = {"tiering.save.pre_flush", "tiering.evict.pre"}


def _midpass_remote_env(tmp_path):
    return {"PBTPU_MOCKFS_ROOT": str(tmp_path / "mock_root"),
            "PBTPU_MOCKFS_SCHEME": "hdfs",
            "PBTPU_CRASH_MIDPASS": "2",
            "PBTPU_CRASH_REMOTE": "hdfs://ck"}


def _spill_env(tmp_path):
    # RAM cache far below the ~120-key table: every pass faults through
    # the disk tier, so the kill windows sit on exercised code
    return {"PBTPU_TABLE_TIERING": "spill",
            "PBTPU_SPILL_CACHE_ROWS": "16",
            "PBTPU_SPILL_DIR": str(tmp_path / "spill"),
            "PBTPU_CRASH_SHARDS": "2"}


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faultpoint.disarm()


def _run_worker(root, out, env_extra=None, check=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PBTPU_FAULTPOINT", None)
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, WORKER, str(root), str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    if check and proc.returncode != 0:
        raise AssertionError(
            f"worker failed ({proc.returncode}):\n{proc.stdout}\n"
            f"{proc.stderr}")
    return proc


@pytest.fixture(scope="module")
def golden(tmp_path_factory):
    """Uninterrupted reference run → final-state npz."""
    d = tmp_path_factory.mktemp("golden")
    out = d / "out.npz"
    _run_worker(d / "root", out)
    with np.load(out) as z:
        return {k: z[k] for k in z.files}


def _assert_bitwise_equal(golden, out):
    with np.load(out) as z:
        assert sorted(z.files) == sorted(golden)
        for k in golden:
            np.testing.assert_array_equal(
                golden[k], z[k], err_msg=f"plane {k!r} diverged after "
                                         f"kill -> resume")


def _kill_resume_roundtrip(point, tmp_path, golden):
    root, out = tmp_path / "root", tmp_path / "out.npz"
    if point in MIDPASS_REMOTE_POINTS:
        env = _midpass_remote_env(tmp_path)
    elif point in SPILL_POINTS:
        env = _spill_env(tmp_path)
    else:
        env = {}
    killed = _run_worker(
        root, out, check=False,
        env_extra=dict(env, PBTPU_FAULTPOINT=point,
                       PBTPU_FAULTPOINT_AFTER=str(POINT_AFTER[point])))
    assert killed.returncode == 137, (
        f"expected the armed kill, got rc={killed.returncode}:\n"
        f"{killed.stdout}\n{killed.stderr}")
    assert f"FAULTPOINT KILL {point}" in killed.stderr
    assert not out.exists()
    resumed = _run_worker(root, out, env_extra=env)
    assert "resume cursor=" in resumed.stdout
    _assert_bitwise_equal(golden, out)
    return resumed


def test_kill_resume_smoke(tmp_path, golden):
    """Tier-1 fast path: one kill point end-to-end (the delta-file/manifest
    commit window)."""
    _kill_resume_roundtrip("store.save_delta.pre_manifest", tmp_path, golden)


@pytest.mark.slow
@pytest.mark.parametrize("point",
                         [p for p in faultpoint.POINTS
                          if p not in ("store.save_delta.pre_manifest",
                                       "remote_ckpt.download.pre")
                          and p not in faultpoint.ELASTIC_POINTS
                          and p not in faultpoint.ADMIT_POINTS
                          and p not in faultpoint.SERVING_POINTS
                          and p not in faultpoint.EXCHANGE_POINTS
                          and p not in faultpoint.MONITOR_POINTS
                          and p not in faultpoint.FLEET_POINTS])
def test_kill_resume_matrix(point, tmp_path, golden):
    """Every registered fault point: kill there, resume, prove bit-identical
    dense params + table rows + metric state vs the uninterrupted run. The
    mid-pass point's resume must come back through the shuffle cursor
    (skip_steps), not a pass replay."""
    resumed = _kill_resume_roundtrip(point, tmp_path, golden)
    if point == "trainer.midpass.post_save":
        assert "(skip 2)" in resumed.stdout, resumed.stdout


@pytest.mark.slow
def test_kill_during_remote_download_resume(tmp_path, golden):
    """remote_ckpt.download.pre fires on the RESUME path: train + mirror,
    wipe the local staging root (replacement host), kill the resume mid
    download, then a THIRD run re-downloads from the donefile and lands
    bit-identical."""
    env = _midpass_remote_env(tmp_path)
    root, out = tmp_path / "root", tmp_path / "out.npz"
    _run_worker(root, tmp_path / "full.npz", env_extra=env)  # mirror built
    killed = _run_worker(
        root, out, check=False,
        env_extra=dict(env, PBTPU_CRASH_WIPE_LOCAL="1",
                       PBTPU_FAULTPOINT="remote_ckpt.download.pre",
                       PBTPU_FAULTPOINT_AFTER="0"))
    assert killed.returncode == 137, (killed.stdout, killed.stderr)
    assert "FAULTPOINT KILL remote_ckpt.download.pre" in killed.stderr
    resumed = _run_worker(root, out,
                          env_extra=dict(env, PBTPU_CRASH_WIPE_LOCAL="1"))
    assert "resume cursor=" in resumed.stdout
    _assert_bitwise_equal(golden, out)


def test_midpass_remote_run_matches_plain_golden(tmp_path, golden):
    """Mid-pass snapshots + the remote mirror are read-only side effects:
    a full run with both on lands the SAME final planes as the plain
    golden (the matrix's license to flip them per point), and the remote
    root ends up holding a donefile + uploaded snapshots."""
    env = _midpass_remote_env(tmp_path)
    out = tmp_path / "out.npz"
    _run_worker(tmp_path / "root", out, env_extra=env)
    _assert_bitwise_equal(golden, out)
    mock_root = tmp_path / "mock_root" / "ck"
    assert (mock_root / "snapshots.donefile").exists()
    assert any(n.startswith("pass-") for n in os.listdir(mock_root))
    assert any(".mid" in n for n in os.listdir(mock_root))


def test_spill_sharded_run_matches_plain_golden(tmp_path, golden):
    """The tier is a storage choice, not a math change: a full run on a
    2-shard ShardedEmbeddingStore with SPILL sub-stores (memmap row
    files, 16-row RAM caches) lands the SAME final planes as the plain
    in-RAM golden — the license for the kill matrix to flip the tiering
    points on that configuration. Also proves the spill-backed shards
    actually ran disk-backed (per-shard row files exist)."""
    env = _spill_env(tmp_path)
    out = tmp_path / "out.npz"
    _run_worker(tmp_path / "root", out, env_extra=env)
    _assert_bitwise_equal(golden, out)
    spill_root = tmp_path / "spill"
    for s in ("shard-00", "shard-01"):
        assert (spill_root / s / "rows.dat").exists()
        assert (spill_root / s / "rows.dat").stat().st_size > 0


def test_tiering_save_ioerror_rolls_back(tmp_path):
    """tiering.save.pre_flush: an IO fault before the spill store's
    memmap flush + streamed payload leaves the chain at the previous
    committed save (the save_delta seq-commit discipline holds for the
    streaming writer too), and the store keeps training afterwards."""
    from paddlebox_tpu.embedding import SpillEmbeddingStore
    cfg = EmbeddingConfig(dim=2)
    st = SpillEmbeddingStore(cfg, spill_dir=str(tmp_path / "sp"),
                             cache_rows=8)
    keys = np.arange(1, 41, dtype=np.uint64)
    rows = st.lookup_or_init(keys)
    rows[:, 0] = 5.0
    st.write_back(keys, rows)
    path = str(tmp_path / "chain")
    st.save_base(path)
    rows = st.get_rows(keys)
    rows[:, 2] = 1.0
    st.write_back(keys, rows)
    st.save_delta(path)                     # committed: seq 1, col2 = 1.0
    rows[:, 2] = 2.0
    st.write_back(keys, rows)
    faultpoint.arm("tiering.save.pre_flush", action="ioerror")
    with pytest.raises(faultpoint.FaultInjected):
        st.save_delta(path)                 # dies before flush + stream
    faultpoint.disarm()
    loaded = HostEmbeddingStore.load(path)
    assert loaded.save_seq == 1
    np.testing.assert_allclose(loaded.get_rows(keys)[:, 2], 1.0)
    # the interrupted save burned no seq: the re-run commits seq 2
    st.save_delta(path)
    loaded2 = HostEmbeddingStore.load(path)
    assert loaded2.save_seq == 2
    np.testing.assert_allclose(loaded2.get_rows(keys)[:, 2], 2.0)


def test_every_point_has_a_matrix_entry():
    """A new crash window cannot be registered without extending the
    kill→resume matrix. The elastic re-formation points fire only inside
    a world shrink — no reform happens in this single-host worker — so
    they are covered by the elastic kill matrix (tests/test_elastic.py)
    instead; the serving publish points fire only in the publish path
    and are covered by the publish/swap kill matrix
    (tests/test_serving.py); the sharded-exchange points fire only in
    the ShardedEmbeddingStore save / eval-overflow-retry paths and are
    covered by tests/test_exchange.py; the telemetry-plane points fire
    only on the JSONL writer thread — telemetry must never perturb
    training state — and are covered by tests/test_doctor.py; the elastic
    ADMIT (world-grow) points fire only in ElasticWorld.admit / the
    post-grow ownership rebind and are covered by the grow kill matrix
    (tests/test_elastic.py + tests/grow_worker.py); the serving-fleet
    points fire only inside the replica-fleet lease/build/dispatch paths
    and are covered by the fleet kill matrix (tests/test_fleet.py). All
    carry the same closed-registry guard."""
    assert (set(POINT_AFTER) | set(faultpoint.ELASTIC_POINTS)
            | set(faultpoint.ADMIT_POINTS)
            | set(faultpoint.SERVING_POINTS)
            | set(faultpoint.EXCHANGE_POINTS)
            | set(faultpoint.MONITOR_POINTS)
            | set(faultpoint.FLEET_POINTS) == set(faultpoint.POINTS))
    assert not set(POINT_AFTER) & (set(faultpoint.ELASTIC_POINTS)
                                   | set(faultpoint.ADMIT_POINTS)
                                   | set(faultpoint.SERVING_POINTS)
                                   | set(faultpoint.EXCHANGE_POINTS)
                                   | set(faultpoint.MONITOR_POINTS)
                                   | set(faultpoint.FLEET_POINTS))


# ---------------------------------------------------------------------------
# in-process: atomic writes + corrupt-chain diagnosis
# ---------------------------------------------------------------------------

def test_atomic_save_pytree_never_tears(tmp_path):
    """An IO fault between the durable tmp write and the rename leaves the
    previous complete file under the final name."""
    f = str(tmp_path / "dense.npz")
    ckpt_lib.save_pytree({"w": np.arange(4.0, dtype=np.float32)}, f)
    faultpoint.arm("ckpt.dense.pre_replace", action="ioerror")
    with pytest.raises(faultpoint.FaultInjected):
        ckpt_lib.save_pytree({"w": np.zeros(4, np.float32)}, f)
    faultpoint.disarm()
    got = ckpt_lib.load_pytree({"w": np.zeros(4, np.float32)}, f)
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.arange(4.0, dtype=np.float32))
    # the failed writer cleaned its temp file up
    assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]


def test_load_pytree_corrupt_names_file(tmp_path):
    f = str(tmp_path / "dense.npz")
    ckpt_lib.save_pytree({"w": np.arange(64.0, dtype=np.float32)}, f)
    raw = open(f, "rb").read()
    with open(f, "wb") as fh:
        fh.write(raw[:len(raw) // 2])      # truncate
    with pytest.raises(CheckpointCorruptError, match="dense.npz"):
        ckpt_lib.load_pytree({"w": np.zeros(64, np.float32)}, f)
    with open(f, "wb") as fh:              # not a zip at all
        fh.write(b"garbage" * 10)
    with pytest.raises(CheckpointCorruptError, match="dense.npz"):
        ckpt_lib.load_pytree({"w": np.zeros(64, np.float32)}, f)


def _trained_store(tmp_path, n=40):
    cfg = EmbeddingConfig(dim=2)
    store = HostEmbeddingStore(cfg)
    keys = np.arange(1, n + 1, dtype=np.uint64)
    rows = store.lookup_or_init(keys)
    rows[:, 0] = 5.0
    store.write_back(keys, rows)
    return store, keys


def test_corrupt_mid_chain_delta_fails_loudly(tmp_path):
    """A truncated mid-chain delta must raise with the manifest diagnosis
    (file name + chain position), never half-replay."""
    store, keys = _trained_store(tmp_path)
    path = str(tmp_path / "sp")
    store.save_base(path)
    for v in (1.0, 2.0):
        rows = store.get_rows(keys)
        rows[:, 2] = v
        store.write_back(keys, rows)
        store.save_delta(path)
    d1 = os.path.join(path, "delta-00001.npz")
    raw = open(d1, "rb").read()
    with open(d1, "wb") as f:
        f.write(raw[:-20])
    with pytest.raises(CheckpointCorruptError) as ei:
        HostEmbeddingStore.load(path)
    msg = str(ei.value)
    assert "delta-00001.npz" in msg and "position" in msg
    # same-size bit-rot must be caught by the CRC, not just the size check
    flipped = bytearray(raw)
    flipped[len(raw) // 2] ^= 0xFF
    with open(d1, "wb") as f:
        f.write(bytes(flipped))
    with pytest.raises(CheckpointCorruptError, match="crc32"):
        HostEmbeddingStore.load(path)
    # a missing mid-chain member is equally loud
    with open(d1, "wb") as f:
        f.write(raw)                       # restore bytes…
    os.remove(os.path.join(path, "delta-00002.npz"))
    with pytest.raises(CheckpointCorruptError, match="delta-00002"):
        HostEmbeddingStore.load(path)


def test_tombstones_survive_chain_fallback(tmp_path):
    """Falling back to an earlier save_seq must not resurrect keys whose
    tombstone rode a delta inside the replayed prefix."""
    store, keys = _trained_store(tmp_path)
    path = str(tmp_path / "sp")
    store.save_base(path)
    store.shrink(min_show=10.0)            # evicts everything (show=5)
    assert len(store) == 0
    live = store.lookup_or_init(keys[:3])  # re-create 3 keys
    live[:, 2] = 7.0
    store.write_back(keys[:3], live)
    store.save_delta(path)                 # delta-1: tombstones + 3 rows
    rows = store.get_rows(keys[:3])
    rows[:, 2] = 9.0
    store.write_back(keys[:3], rows)
    store.save_delta(path)                 # delta-2
    # fallback horizon = seq 1 (as a snapshot committed at seq 1 records)
    loaded = HostEmbeddingStore.load(path, upto_seq=1)
    assert len(loaded) == 3                # evicted keys stayed dead
    np.testing.assert_allclose(loaded.get_rows(keys[:3])[:, 2], 7.0)
    # full replay sees delta-2's values
    loaded2 = HostEmbeddingStore.load(path)
    np.testing.assert_allclose(loaded2.get_rows(keys[:3])[:, 2], 9.0)


def test_chain_manifest_records_parents(tmp_path):
    store, keys = _trained_store(tmp_path)
    path = str(tmp_path / "sp")
    store.save_base(path, pass_id=1)
    rows = store.get_rows(keys)
    rows[:, 2] = 1.0
    store.write_back(keys, rows)
    store.save_delta(path, pass_id=2)
    m = ckpt_lib.read_manifest(path)
    assert m["chain"] == ["base.npz", "delta-00001.npz"]
    assert m["files"]["base.npz"]["parent"] is None
    assert m["files"]["delta-00001.npz"]["parent"] == "base.npz"
    assert m["pass_id"] == 2 and m["save_seq"] == 1
    for name in ("base.npz", "delta-00001.npz", "meta.json"):
        assert m["files"][name]["bytes"] == os.path.getsize(
            os.path.join(path, name))


# ---------------------------------------------------------------------------
# in-process: PassCheckpointer snapshot fallback + retention
# ---------------------------------------------------------------------------

def _tiny_trainer(seed=7):
    from paddlebox_tpu.models import DNNCTRModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig
    from tests.crash_worker import NUM_SLOTS, synth
    ds, schema = synth(n=128)
    store = HostEmbeddingStore(EmbeddingConfig(dim=4, learning_rate=0.05))
    tr = Trainer(DNNCTRModel(num_slots=NUM_SLOTS, emb_dim=4, dense_dim=1,
                             hidden=(8,)),
                 store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=64, auc_buckets=1 << 8),
                 seed=seed)
    return ds, tr, store


def test_truncated_newest_snapshot_falls_back(tmp_path):
    """Acceptance: a deliberately truncated newest snapshot is detected by
    checksum and resume restores the previous good one."""
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    ds, tr, store = _tiny_trainer()
    box = BoxPS(store)
    ckpt = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=2,
                            base_every=4)
    import jax
    state_after = {}
    for p in (1, 2):
        box.begin_pass()
        tr.train_pass(ds)
        box.end_pass(checkpointer=ckpt, trainer=tr)
        tr.flush_sparse()
        keys = np.sort(np.asarray(ds.unique_keys(), np.uint64))
        state_after[p] = (keys, store.get_rows(keys),
                          jax.tree.map(np.asarray, tr.params),
                          tr.global_step)
    # truncate pass-2's dense plane, keeping its manifest intact: only the
    # recorded size/CRC can catch this
    dense2 = os.path.join(ckpt.snap_dir(2), "dense.npz")
    raw = open(dense2, "rb").read()
    with open(dense2, "wb") as f:
        f.write(raw[:-32])
    with pytest.warns(UserWarning, match="failed verification"):
        found = ckpt.latest_valid()
    assert found is not None and found[0] == 1

    ds2, tr2, store2 = _tiny_trainer(seed=99)  # different init: must be
    box2 = BoxPS(store2)                       # overwritten by the restore
    ck2 = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=2,
                           base_every=4)
    with pytest.warns(UserWarning, match="failed verification"):
        cursor = tr2.resume(ck2, box=box2)
    assert cursor["pass_id"] == 1 and box2.pass_id == 1
    assert tr2.global_step == state_after[1][3]
    keys, rows, params, _ = state_after[1]
    np.testing.assert_array_equal(store2.get_rows(keys), rows)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        tr2.params, params)


def test_retention_keeps_last_n_and_referenced_chains(tmp_path):
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    ds, tr, store = _tiny_trainer()
    box = BoxPS(store)
    root = str(tmp_path / "ck")
    ckpt = PassCheckpointer(root, keep_last_n=2, base_every=2)
    for _ in range(5):
        box.begin_pass()
        tr.train_pass(ds)
        box.end_pass(checkpointer=ckpt, trainer=tr)
    snaps = sorted(n for n in os.listdir(root) if n.startswith("pass-"))
    assert snaps == ["pass-00004", "pass-00005"]
    chains = sorted(n for n in os.listdir(root) if n.startswith("chain-"))
    referenced = {ckpt_lib.read_manifest(os.path.join(root, s))["chain_dir"]
                  for s in snaps}
    assert set(chains) == referenced
    # every survivor still verifies end-to-end
    assert ckpt.latest_valid()[0] == 5


def test_resume_with_no_snapshots_returns_none(tmp_path):
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    ds, tr, store = _tiny_trainer()
    ck = PassCheckpointer(str(tmp_path / "empty"))
    assert tr.resume(ck, box=BoxPS(store)) is None


def test_faultpoint_registry_guards():
    with pytest.raises(KeyError):
        faultpoint.arm("not.a.point")
    with pytest.raises(ValueError):
        faultpoint.arm("ckpt.dense.pre_replace", action="explode")
    faultpoint.arm("ckpt.dense.pre_replace", action="ioerror", after=1)
    faultpoint.hit("ckpt.dense.pre_replace")   # skipped (after=1)
    with pytest.raises(faultpoint.FaultInjected):
        faultpoint.hit("ckpt.dense.pre_replace")


def test_delta_crash_before_manifest_resumes_previous_save(tmp_path):
    """The chain MANIFEST is the commit record: a save_delta that dies
    after writing the delta file + meta but BEFORE the manifest commit
    must leave a directory that load() resumes at the PREVIOUS save —
    not one that fails verification (the no-PassCheckpointer
    end_pass(need_save_delta) flow has nothing else to fall back to)."""
    store, keys = _trained_store(tmp_path)
    path = str(tmp_path / "sp")
    store.save_base(path)
    rows = store.get_rows(keys)
    rows[:, 2] = 1.0
    store.write_back(keys, rows)
    store.save_delta(path)                 # committed: seq 1, rows at 1.0
    rows[:, 2] = 2.0
    store.write_back(keys, rows)
    faultpoint.arm("store.save_delta.pre_manifest", action="ioerror")
    with pytest.raises(faultpoint.FaultInjected):
        store.save_delta(path)             # delta-2 + meta land, no commit
    faultpoint.disarm()
    loaded = HostEmbeddingStore.load(path)
    assert loaded.save_seq == 1            # manifest horizon, not meta's 2
    np.testing.assert_allclose(loaded.get_rows(keys)[:, 2], 1.0)


def test_foreign_save_between_snapshots_forces_base_rotation(tmp_path):
    """A FleetUtil-style save_delta on the shared store between two
    checkpointer saves consumes the dirty mask — the next snapshot must
    rotate to a full base (a delta into the open chain would silently
    miss those rows) and resume must still restore the exact state."""
    import jax
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    ds, tr, store = _tiny_trainer()
    box = BoxPS(store)
    ckpt = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=3,
                            base_every=8)
    box.begin_pass(); tr.train_pass(ds)
    box.end_pass(checkpointer=ckpt, trainer=tr)       # base (chain-0001)
    box.begin_pass(); tr.train_pass(ds)
    # foreign writer: a fleet-style delta into its own dir, mid-lifecycle
    store.save_delta(str(tmp_path / "fleet_delta"))
    box.end_pass(checkpointer=ckpt, trainer=tr)       # must rotate
    m = ckpt_lib.read_manifest(ckpt.snap_dir(2))
    assert m["chain_dir"] == "chain-0002"             # fresh base, seq 0
    assert m["save_seq"] == 0
    tr.flush_sparse()
    keys = np.sort(np.asarray(ds.unique_keys(), np.uint64))
    want = store.get_rows(keys)
    want_params = jax.tree.map(np.asarray, tr.params)

    ds2, tr2, store2 = _tiny_trainer(seed=42)
    cursor = tr2.resume(PassCheckpointer(str(tmp_path / "ck")),
                        box=BoxPS(store2))
    assert cursor["pass_id"] == 2
    np.testing.assert_array_equal(store2.get_rows(keys), want)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        tr2.params, want_params)


def test_foreign_save_base_with_eviction_forces_rotation(tmp_path):
    """A foreign save_base resets store.save_seq to 0 — aliasing with
    'nothing happened' right after our own base. The monotonic save_count
    guard must still rotate, or the next snapshot's delta silently drops
    the eviction the foreign base consumed (confirmed divergence repro
    from review)."""
    import jax
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    ds, tr, store = _tiny_trainer()
    box = BoxPS(store)
    ckpt = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=3,
                            base_every=8)
    box.begin_pass(); tr.train_pass(ds)
    box.end_pass(checkpointer=ckpt, trainer=tr)       # base, seq 0
    box.begin_pass(); tr.train_pass(ds)
    store.shrink(min_show=1e9)                        # evict everything
    store.save_base(str(tmp_path / "fleet_base"))     # foreign: seq -> 0
    box.end_pass(checkpointer=ckpt, trainer=tr)
    m = ckpt_lib.read_manifest(ckpt.snap_dir(2))
    assert m["chain_dir"] == "chain-0002"             # rotated, not delta
    keys = np.sort(np.asarray(ds.unique_keys(), np.uint64))

    ds2, tr2, store2 = _tiny_trainer(seed=42)
    cursor = tr2.resume(PassCheckpointer(str(tmp_path / "ck")),
                        box=BoxPS(store2))
    assert cursor["pass_id"] == 2
    assert len(store2) == len(store)                  # evictions honored
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        tr2.params, jax.tree.map(np.asarray, tr.params))


def test_failed_save_leaves_checkpointer_consistent(tmp_path):
    """A transient IO failure inside a snapshot save must not corrupt the
    checkpointer's chain state (a half-open baseless chain) or burn a
    delta sequence number (a permanent mid-chain gap): the NEXT save must
    succeed and produce a fully restorable snapshot."""
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    ds, tr, store = _tiny_trainer()
    box = BoxPS(store)
    ckpt = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=2,
                            base_every=8)
    box.begin_pass(); tr.train_pass(ds)
    box.end_pass(checkpointer=ckpt, trainer=tr)           # base ok
    box.begin_pass(); tr.train_pass(ds)
    faultpoint.arm("store.save_delta.pre_replace", action="ioerror")
    with pytest.raises(faultpoint.FaultInjected):
        box.end_pass(checkpointer=ckpt, trainer=tr)       # delta fails
    faultpoint.disarm()
    # failed rotation case too: force a rotation failure on a fresh chain
    ck2 = PassCheckpointer(str(tmp_path / "ck2"), keep_last_n=2)
    faultpoint.arm("store.save_base.pre_replace", action="ioerror")
    with pytest.raises(faultpoint.FaultInjected):
        ck2.save(tr, pass_id=1)
    faultpoint.disarm()
    # both checkpointers recover on the next save, end to end
    snap = ckpt.save(tr, box=box, metrics=box.metrics, pass_id=2)
    assert ckpt_lib.read_manifest(snap) is not None
    snap2 = ck2.save(tr, pass_id=1)
    assert ckpt_lib.read_manifest(snap2) is not None
    ds2, tr2, store2 = _tiny_trainer(seed=42)
    cursor = tr2.resume(PassCheckpointer(str(tmp_path / "ck")),
                        box=BoxPS(store2))
    assert cursor["pass_id"] == 2
    keys = np.sort(np.asarray(ds.unique_keys(), np.uint64))
    tr.flush_sparse()
    np.testing.assert_array_equal(store2.get_rows(keys),
                                  store.get_rows(keys))


def test_prune_tolerates_corrupt_old_snapshot_manifest(tmp_path):
    """Bit rot in a RETAINED (non-newest) snapshot's manifest must not
    make later saves raise — resume already skips it; prune must too."""
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    ds, tr, store = _tiny_trainer()
    box = BoxPS(store)
    ckpt = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=3,
                            base_every=8)
    for _ in range(2):
        box.begin_pass(); tr.train_pass(ds)
        box.end_pass(checkpointer=ckpt, trainer=tr)
    with open(os.path.join(ckpt.snap_dir(1), "MANIFEST.json"), "w") as f:
        f.write("{ not json")
    box.begin_pass(); tr.train_pass(ds)
    out = box.end_pass(checkpointer=ckpt, trainer=tr)     # must not raise
    assert ckpt_lib.read_manifest(out["snapshot"]) is not None
    assert ckpt.latest_valid()[0] == 3
