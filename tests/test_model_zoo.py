"""Model zoo: every family trains on the synthetic CTR task and lifts AUC."""

import jax
import numpy as np
import pytest

from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
from paddlebox_tpu.models import (MODEL_REGISTRY, DCNv2Model, DLRMModel,
                                  MMoEModel, WideDeepModel)
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.train import Trainer, TrainerConfig
from tests.test_train_e2e import NUM_SLOTS, synth_dataset


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


def test_registry_complete():
    assert set(MODEL_REGISTRY) == {"dnn_ctr", "deepfm", "wide_deep",
                                   "dcn_v2", "dlrm", "mmoe", "pv_rank",
                                   "smallthinker", "nemotron_h",
                                   "lfm2_moe", "deepseek_v3",
                                   "kimi_linear"}


@pytest.mark.parametrize("model_cls,kw", [
    (WideDeepModel, dict(hidden=(32, 16))),
    (DCNv2Model, dict(hidden=(32, 16), num_cross_layers=2)),
    (DLRMModel, dict(bottom_hidden=(16,), top_hidden=(32,))),
    (MMoEModel, dict(num_experts=3, num_tasks=2, expert_hidden=(32,),
                     expert_out=16, tower_hidden=(16,))),
])
def test_model_trains(mesh8, model_cls, kw):
    ds, schema = synth_dataset(2048)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, learning_rate=0.15))
    model = model_cls(num_slots=NUM_SLOTS, emb_dim=8, dense_dim=1, **kw)
    tr = Trainer(model, store, schema, mesh8,
                 TrainerConfig(global_batch_size=128, dense_lr=3e-3,
                               auc_buckets=1 << 12))
    results = [tr.train_pass(ds) for _ in range(3)]
    assert results[-1]["auc"] > 0.60, (model_cls.name, results)
    assert np.isfinite(results[-1]["loss_mean"])


def test_mmoe_multitask_heads(mesh8):
    ds, schema = synth_dataset(256, seed=4)
    store = HostEmbeddingStore(EmbeddingConfig(dim=4))
    model = MMoEModel(num_slots=NUM_SLOTS, emb_dim=4, dense_dim=1,
                      num_experts=2, num_tasks=3, expert_hidden=(8,),
                      expert_out=8, tower_hidden=(8,))
    tr = Trainer(model, store, schema, mesh8,
                 TrainerConfig(global_batch_size=64, auc_buckets=1 << 10))
    from paddlebox_tpu.embedding import PassWorkingSet
    ws = PassWorkingSet.begin_pass(store, ds.unique_keys(), mesh8)
    pb = next(ds.batches(64))
    idx = ws.translate(pb.ids, pb.mask)
    labels, dense = tr.split_floats(pb.floats)
    params = model.init(jax.random.PRNGKey(0))
    from paddlebox_tpu.embedding import sharded
    pulled = sharded.lookup(ws.table, np.asarray(idx).reshape(-1), store.cfg)
    pulled = pulled.reshape(64, tr.layout.total_len, store.cfg.pull_width)
    out = model.apply_tasks(params, pulled, pb.mask,
                            dense.astype(np.float32),
                            tr.layout.segment_ids)
    assert out.shape == (64, 3)
    assert np.isfinite(np.asarray(out)).all()
