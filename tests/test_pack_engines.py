"""Per-width-class _bp_pack engines (flags.pack_engine).

The pack's expensive op is the token reorder, and the v5e row-gather
sweep is sharply non-monotone in source width — so the pack dispatches
per payload width class (narrow <14 / gather_zone 14..63 / wide >=64).
The contract: all three engines produce the IDENTICAL packed operand
(only the gather's source width differs), the auto selection follows the
sweep's zone boundaries, and the choice can be read off pack_engine()
— the discipline whose absence let the round-5 _bp_pack
rewrite halve headline throughput unnoticed.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import set_flags
from paddlebox_tpu.embedding import EmbeddingConfig
from paddlebox_tpu.ops import pallas_kernels as pk


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags(pack_engine="auto", push_engine="auto")


def test_width_class_boundaries():
    assert pk.pack_width_class(8) == "narrow"
    assert pk.pack_width_class(13) == "narrow"
    assert pk.pack_width_class(14) == "gather_zone"
    assert pk.pack_width_class(40) == "gather_zone"
    assert pk.pack_width_class(63) == "gather_zone"
    assert pk.pack_width_class(64) == "wide"
    assert pk.pack_width_class(290) == "wide"


def _operands(cfg, n_rows, tok, seed=0):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, n_rows, size=tok).astype(np.int32))
    grads = jnp.asarray(
        rng.normal(size=(tok, cfg.grad_width)).astype(np.float32))
    shows = jnp.asarray(np.ones(tok, np.float32))
    clks = jnp.asarray((rng.random(tok) < 0.3).astype(np.float32))
    return idx, grads, shows, clks


@pytest.mark.parametrize("dim", [4, 16, 64])
def test_engines_produce_identical_packed_operand(dim):
    """Forcing any engine is always legal and bit-identical: the packed
    array, rstart, and end must not depend on the gather layout."""
    cfg = EmbeddingConfig(dim=dim, optimizer="adagrad")
    n_rows = 4096
    geom = pk._bp_geometry(cfg, n_rows)
    assert geom is not None
    TILE = pk._bp_tile(geom[3], geom[2])
    idx, grads, shows, clks = _operands(cfg, n_rows, 1000)

    outs = {}
    for eng in pk.PACK_ENGINES:
        set_flags(pack_engine=eng)
        packed, rstart, end = jax.jit(
            lambda i, g, s, c: pk._bp_pack(i, g, s, c, geom, TILE,
                                           n_rows))(idx, grads, shows,
                                                    clks)
        outs[eng] = (np.asarray(packed), np.asarray(rstart),
                     np.asarray(end))
    ref = outs["narrow"]
    for eng in ("gather_zone", "wide"):
        for a, b in zip(ref, outs[eng]):
            assert np.array_equal(a, b), f"{eng} diverges from narrow"


def test_engines_identical_with_host_plan():
    """Same invariant when the grouping arrives as a host plan (the
    production pack-pipeline path)."""
    from paddlebox_tpu.native.key_index import block_plan
    cfg = EmbeddingConfig(dim=8, optimizer="adagrad")
    n_rows = 4096
    geom = pk._bp_geometry(cfg, n_rows)
    SB = geom[3]
    TILE = pk._bp_tile(SB, geom[2])
    idx, grads, shows, clks = _operands(cfg, n_rows, 512)
    o, r, e = block_plan(np.asarray(idx), SB, n_rows // SB)
    plan = (jnp.asarray(o), jnp.asarray(r), jnp.asarray(e))
    outs = {}
    for eng in pk.PACK_ENGINES:
        set_flags(pack_engine=eng)
        packed, rstart, end = jax.jit(
            lambda i, g, s, c, p: pk._bp_pack(i, g, s, c, geom, TILE,
                                              n_rows, plan=p))(
            idx, grads, shows, clks, plan)
        outs[eng] = np.asarray(packed)
    assert np.array_equal(outs["narrow"], outs["gather_zone"])
    assert np.array_equal(outs["narrow"], outs["wide"])


def test_auto_selection_per_width():
    """pack_engine(cfg, rows) follows the width class where the kernel
    engages, honors the override, and is None on scatter-engine widths
    (no pack to choose)."""
    rows = 1 << 16
    # dim 8 -> P = 12 -> narrow
    assert pk.pack_engine(EmbeddingConfig(dim=8), rows) == "narrow"
    # dim 16 -> P = 20 -> gather_zone
    assert pk.pack_engine(EmbeddingConfig(dim=16), rows) == "gather_zone"
    # dim 64 -> G == 1 -> scatter engine keeps the push: no pack engine
    assert pk.pack_engine(EmbeddingConfig(dim=64), rows) is None
    # ...unless the kernel is forced, where the wide pack serves it
    set_flags(push_engine="binned_kernel")
    assert pk.pack_engine(EmbeddingConfig(dim=64), rows) == "wide"
    set_flags(push_engine="auto")
    # override is reported verbatim where a pack exists
    set_flags(pack_engine="wide")
    assert pk.pack_engine(EmbeddingConfig(dim=8), rows) == "wide"
    set_flags(pack_engine="auto")
    # premerged lanes arrive sorted — no reorder compiles, and the
    # record must say so instead of naming the width class
    assert pk.pack_engine(EmbeddingConfig(dim=16), rows,
                          premerged=True) == "premerged_no_reorder"


def test_forced_engine_typo_raises():
    """A misspelled forced engine must fail loudly at trace time, not
    silently measure auto (the A/B-trust property)."""
    cfg = EmbeddingConfig(dim=8, optimizer="adagrad")
    n_rows = 4096
    geom = pk._bp_geometry(cfg, n_rows)
    TILE = pk._bp_tile(geom[3], geom[2])
    idx, grads, shows, clks = _operands(cfg, n_rows, 64)
    set_flags(pack_engine="gatherzone")       # typo for gather_zone
    with pytest.raises(ValueError, match="pack_engine"):
        pk._bp_pack(idx, grads, shows, clks, geom, TILE, n_rows)
    with pytest.raises(ValueError, match="pack_engine"):
        pk.pack_engine(cfg, n_rows)


def test_binned_push_parity_across_engines():
    """End to end through the merge accumulator (interpret-mode kernel):
    the engine choice must not change the accumulated rows."""
    cfg = EmbeddingConfig(dim=8, optimizer="adagrad")
    n_rows = 4096
    idx, grads, shows, clks = _operands(cfg, n_rows, 600)
    accs = {}
    for eng in pk.PACK_ENGINES:
        set_flags(pack_engine=eng)
        accs[eng] = np.asarray(pk.binned_merge_acc(
            idx, grads, shows, clks, cfg, n_rows, n_split=3,
            interpret=True))
    assert np.array_equal(accs["narrow"], accs["gather_zone"])
    assert np.array_equal(accs["narrow"], accs["wide"])
