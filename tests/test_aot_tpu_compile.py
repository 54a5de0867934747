"""The chip's compiler, asked without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (on-chip-measurement guide, section 2). Interpret
mode — how every other kernel test in this suite runs — accepts what
Mosaic refuses: row DMAs that break the HBM tiling, SMEM blocks off the
1024-word tiling, scratch past the VMEM limit. These tests hand every
Pallas kernel a resolver can select on a TPU to the real compiler at the
widths the first rounds measured (dims 8 to 128), and hold the geometry functions to the
compiler's verdict: nothing they accept may be refused.

All compiles run in this process (the worker that describes the topology
holds libtpu's lock until it exits), in this one file, with the persistent
compilation cache off — a described-device executable can be written to
the cache but not read back.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from paddlebox_tpu.config import flags
from paddlebox_tpu.embedding.config import EmbeddingConfig
from paddlebox_tpu.ops import pallas_kernels as pk

ROWS = 1 << 19          # rows of a per-chip table
BATCH, SLOTS = 8192, 26


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture()
def on_tpu(monkeypatch):
    """Code that asks jax.default_backend() takes its TPU branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _cfg(dim, **kw):
    return EmbeddingConfig(dim=dim, optimizer="adagrad", learning_rate=0.05,
                           **kw)


def _lane_tiles(cfg):
    return -(-cfg.row_width // 128) * 128


def _compiled_text(fn, one_chip, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# every kernel a resolver can select, at dims 8 to 128
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,hot,storage", [
    (8, 1, "f32"), (16, 1, "f32"), (32, 4, "f32"), (8, 1, "int8")])
def test_binned_merge_acc_compiles(one_chip, dim, hot, storage):
    """The storage-agnostic merge half of binned_push (quantized planes
    dequant -> update -> requant around the same accumulator), fed the
    host plan's token grouping as the pack pipeline stages it."""
    cfg = _cfg(dim, storage=storage)
    geom = pk.binned_push_geometry(cfg, ROWS)
    assert geom is not None and pk.lane_groups(cfg, ROWS) >= 2
    n_tok, n_blocks = BATCH * SLOTS * hot, geom[1]
    i32, f32 = jnp.int32, jnp.float32

    def merge(idx, grads, shows, clks, order, rstart, end):
        return pk.binned_merge_acc(idx, grads, shows, clks, cfg, ROWS,
                                   n_split=flags.binned_push_splits,
                                   plan=(order, rstart, end))

    text = _compiled_text(
        merge, one_chip, ((n_tok,), i32), ((n_tok, cfg.grad_width), f32),
        ((n_tok,), f32), ((n_tok,), f32), ((n_tok,), i32),
        ((n_blocks,), i32), ((n_blocks,), i32))
    assert "tpu_custom_call" in text and "pbtpu_binned_merge_acc" in text


@pytest.mark.parametrize("dim,hot", [(32, 4), (64, 1), (128, 1)])
def test_gather_pool_compiles(one_chip, dim, hot):
    """The fused pull on a table of whole lane tiles: one (dim 32, 64)
    and two (dim 128) tiles a row."""
    cfg = _cfg(dim)
    W = _lane_tiles(cfg)
    assert pk.gather_pool_geometry(BATCH, SLOTS, hot, W) is not None
    text = _compiled_text(
        lambda t, i: pk.gather_pool(t, i, cfg, SLOTS, hot, interpret=False),
        one_chip, ((ROWS, W), jnp.float32),
        ((BATCH, SLOTS * hot), jnp.int32))
    assert "tpu_custom_call" in text and "pbtpu_gather_pool" in text


@pytest.mark.parametrize("dim,hot", [(32, 4), (64, 1), (128, 1)])
def test_scatter_accumulate_compiles(one_chip, dim, hot):
    """The fused push over premerged lanes, aliased in place."""
    cfg = _cfg(dim)
    W = _lane_tiles(cfg)
    assert pk.scatter_accumulate_geometry(ROWS, W) is not None
    n = BATCH * SLOTS * hot
    f32 = jnp.float32
    text = _compiled_text(
        lambda t, i, g, s, c: pk.scatter_accumulate(t, i, g, s, c, cfg,
                                                    interpret=False),
        one_chip, ((ROWS, W), f32), ((n,), jnp.int32),
        ((n, cfg.grad_width), f32), ((n,), f32), ((n,), f32), donate=(0,))
    assert "tpu_custom_call" in text and "pbtpu_scatter_accumulate" in text


@pytest.mark.parametrize("dim", [8, 64])
def test_merge_update_compiles(one_chip, dim):
    """The table-update scan: PBTPU_PALLAS=1 at narrow widths, and what
    sharded.push picks on a TPU for accumulators of 64 lanes and more."""
    cfg = _cfg(dim)
    text = _compiled_text(
        lambda t, a: pk.merge_update(t, a, cfg, interpret=False),
        one_chip, ((ROWS, cfg.row_width), jnp.float32),
        ((ROWS, cfg.grad_width + 3), jnp.float32))
    assert "tpu_custom_call" in text and "pbtpu_merge_update" in text


# ---------------------------------------------------------------------------
# what the compiler refuses, the geometry refuses
# ---------------------------------------------------------------------------

def _one_row_dma(table, idx):
    """The fused kernels' access pattern on a plain 2-D table: one
    (1, W) row per DMA — what they did before the (n, 1, W) row view."""
    n_rows, W = table.shape

    def kernel(idx_ref, table_ref, out_ref, sem):
        cp = pltpu.make_async_copy(table_ref.at[pl.ds(idx_ref[0], 1), :],
                                   out_ref, sem)
        cp.start()
        cp.wait()

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((1, W), table.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, W), lambda i, *_: (0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
    )(idx, table)


@pytest.mark.parametrize("dim", [8, 32, 128])
def test_logical_row_widths_are_refused(one_chip, dim):
    """At the logical row widths (13, 37, 133 columns) the compiler
    refuses the per-row DMA, so the geometry functions return None and
    the resolvers name other engines openly."""
    W = _cfg(dim).row_width
    assert W % 128
    assert pk.gather_pool_geometry(BATCH, SLOTS, 1, W) is None
    assert pk.scatter_accumulate_geometry(ROWS, W) is None
    with pytest.raises(Exception, match="aligned to tiling"):
        _compiled_text(_one_row_dma, one_chip, ((ROWS, W), jnp.float32),
                       ((1,), jnp.int32))


@pytest.mark.parametrize("table_width,push,pull_kernel", [
    (37, "binned_kernel", False),       # flags.table_pad_width = 0
    (128, "scatter_accumulate", True),  # flags.table_pad_width = 128
])
def test_resolvers_follow_the_geometry(on_tpu, table_width, push,
                                       pull_kernel):
    """On a TPU the engine record names what compiles: the 4-hot dim-32
    layout keeps the binned push and the unfused pull at its logical
    width, and takes both fused kernels on a lane-tile table."""
    cfg = _cfg(32)
    assert pk.resolve_push_engine(cfg, ROWS, premerged=True,
                                  table_width=table_width) == push
    assert pk.gather_pool_supported(cfg, BATCH, SLOTS, 4,
                                    table_width) is pull_kernel


# ---------------------------------------------------------------------------
# a wide-row trainer's two programs: no table-sized temporary
# ---------------------------------------------------------------------------

PLANE_ROWS = 2_621_440   # the benchmark's DLRM cell: 1.43 GB of table


def _dlrm_programs(topo, rows):
    """The deferred step and the apply of a DLRM-shaped trainer (dim 128,
    adagrad in the table, MLPerf's tower, 8192 x 26 one-hot tokens) over a
    table of `rows` rows, compiled for one described v5e chip."""
    import types

    import numpy as np

    from paddlebox_tpu.data import DataFeedSchema
    from paddlebox_tpu.embedding import (HostEmbeddingStore, quant,
                                         working_set)
    from paddlebox_tpu.models import DLRMModel
    from paddlebox_tpu.parallel import make_mesh, mesh as mesh_lib
    from paddlebox_tpu.train import Trainer, TrainerConfig
    from paddlebox_tpu.train.trainer import PLAN_ARITY

    cfg = _cfg(128)
    assert working_set.plane_layout(cfg)
    tr = Trainer(DLRMModel(SLOTS, 128, 13, (512, 256),
                           (1024, 1024, 512, 256), use_cvm=False),
                 HostEmbeddingStore(cfg),
                 DataFeedSchema.ctr(SLOTS, 13, batch_size=BATCH),
                 make_mesh(1), TrainerConfig(global_batch_size=BATCH))
    tr.mesh = make_mesh(devices=topo.devices[:1])
    tr._rebuild_steps()
    bat, tbl, rep = (f(tr.mesh) for f in (
        mesh_lib.batch_sharding, mesh_lib.table_sharding,
        mesh_lib.replicated_sharding))

    def like(x, sh):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sh)

    table = quant.PlaneTable(
        fp=jax.ShapeDtypeStruct((rows, quant.fp_width(cfg)), jnp.float32,
                                sharding=tbl),
        qx=jax.ShapeDtypeStruct((rows, 128), jnp.float32, sharding=tbl))
    ws = types.SimpleNamespace(table=table, rows_per_shard=rows,
                               padded_rows=rows)
    idx = np.random.default_rng(0).integers(
        1, rows, (BATCH, SLOTS)).astype(np.int32)
    host = (idx, np.ones(idx.shape, bool), np.zeros((BATCH, 13), np.float32),
            np.zeros(BATCH, np.float32), *tr._host_plan(ws, idx))
    args = [like(x, bat) for x in host]
    dstate = [like(x, rep) for x in tr.pack_dense()]
    assert tr.push_overlap and tr._use_plan and tr.push_premerged(ws)
    step = tr._defer_step_fn.lower(table, *dstate, *args).compile()
    ops = tr.split_defer_out(jax.eval_shape(
        tr._defer_step_fn, table, *dstate, *args))[1]
    apply = tr._apply_fn.lower(
        table, args[0], args[1], args[3], *args[4:4 + PLAN_ARITY],
        *[like(o, bat) for o in ops]).compile()
    return tr.resolved_push_engine(ws), step, apply


def _table_sized_results(text, rows):
    """Instructions of the optimized HLO whose result holds rows x 128
    elements or more, by opcode."""
    import re
    found = []
    for m in re.finditer(
            r"= \(?(?:f32|bf16|s32|u32)\[(\d+),(\d+)\]\S* ([a-z\-]+)\(",
            text):
        if int(m.group(1)) * int(m.group(2)) >= rows * 128:
            found.append(m.group(3))
    return found


def test_wide_row_programs_hold_no_table_sized_temporary(topo, on_tpu):
    """Lane-tile planes (working_set.plane_layout): neither program of a
    dim-128 trainer copies the table or builds a table-sized accumulator
    — the embedx plane is gathered and scattered in place, the apply is
    the touched-rows engine the resolver names, and what the apply holds
    beside its operands barely grows with the table."""
    engine, step, apply = _dlrm_programs(topo, PLANE_ROWS)
    assert engine == "scatter_accumulate"
    plane_bytes = PLANE_ROWS * 128 * 4
    for prog in (step, apply):
        text = prog.as_text()
        assert prog.memory_analysis().temp_size_in_bytes < plane_bytes
        assert "tpu_custom_call" not in text and "pbtpu_" not in text
        # the plane itself, the in-place scatter and what carries them
        assert set(_table_sized_results(text, PLANE_ROWS)) <= {
            "parameter", "fusion", "scatter", "tuple", "bitcast",
            "get-tuple-element"}
    # the step only reads the table; the apply scatters the embedx
    # plane's rows once and the narrow plane column by column
    assert "scatter" not in _table_sized_results(step.as_text(), PLANE_ROWS)
    assert apply.as_text().count(" scatter(") == 1 + 5
    m = apply.memory_analysis()
    assert m.alias_size_in_bytes >= plane_bytes         # updated in place
    # ... and follows the lanes, not the table: over twice the rows it
    # grows by the narrow plane's columns, a thirtieth of the table's
    _, _, apply_half = _dlrm_programs(topo, PLANE_ROWS // 2)
    half = apply_half.memory_analysis().temp_size_in_bytes
    table_growth = PLANE_ROWS // 2 * 133 * 4
    assert 0 <= m.temp_size_in_bytes - half <= table_growth // 10


# ---------------------------------------------------------------------------
# the state-space scan at the published Mamba-2 widths (64 heads of 64 in 8
# groups, state 128, chunks of 128), bfloat16 operands as the chip runs it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ssm_scan_compiles_at_the_published_widths(one_chip, on_tpu,
                                                   direction):
    from paddlebox_tpu.ops import ssm_scan as ss
    B, T, H, P, G, N = 1, 512, 64, 64, 8, 128
    assert ss.scan_geometry(128, H // G, P, N) == (2, 128)
    bf16, f32 = jnp.bfloat16, jnp.float32

    def scan(*args):
        return ss.ssm_scan(*args, chunk=128, interpret=False)

    def grads(*args):
        return jax.grad(lambda *a: jnp.sum(scan(*a).astype(f32)),
                        argnums=tuple(range(6)))(*args)

    text = _compiled_text(
        scan if direction == "forward" else grads, one_chip,
        ((B, T, H, P), bf16), ((B, T, H), f32), ((H,), f32),
        ((B, T, G, N), bf16), ((B, T, G, N), bf16), ((H,), f32))
    assert "tpu_custom_call" in text and "pbtpu_ssm_fwd" in text
    assert ("pbtpu_ssm_bwd" in text) == (direction == "backward")


# ---------------------------------------------------------------------------
# LFM2's mixers at the published widths: attention at a head size of 64 (32
# query heads over 8 key-value heads, 8192 positions, bfloat16 operands as
# the chip runs it: the blocks' last dimension is the whole head) and the
# gated short convolution (float32, 2048 channels, three taps)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_head_64_attention_compiles_at_the_published_widths(one_chip, on_tpu,
                                                            direction):
    from paddlebox_tpu.ops import flash_attention as fa
    B, H, KV, T, D = 2, 32, 8, 8192, 64
    assert fa.block_geometry(T, D) == (512, 512)
    assert fa.block_geometry(T, 128) == (512, 512)
    assert fa.block_geometry(T, 96) is None         # no tile, no half tile
    bf16, f32 = jnp.bfloat16, jnp.float32

    def attend(q, k, v):
        return fa.attention(q, k, v, interpret=False)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(attend(*a).astype(f32)),
                        argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(
        attend if direction == "forward" else grads, one_chip,
        ((B, H, T, D), bf16), ((B, KV, T, D), bf16), ((B, KV, T, D), bf16))
    assert "tpu_custom_call" in text and "pbtpu_attention_fwd" in text
    for name in ("pbtpu_attention_dq", "pbtpu_attention_dkv"):
        assert (name in text) == (direction == "backward")
    # the scores never exist whole
    assert f"{T},{T}]" not in text


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_latent_attention_compiles_at_the_published_widths(one_chip, on_tpu,
                                                           direction):
    """Kanana-2's multi-head latent attention as the kernels take it: 32
    heads whose queries and keys are 192 channels (the blocks' last
    dimension the whole head: a lane tile and a half) beside values of
    128, one sequence of 16384 positions, bfloat16 operands."""
    from paddlebox_tpu.ops import flash_attention as fa
    B, H, T, D, Dv = 1, 32, 16384, 192, 128
    assert fa.block_geometry(T, D) == fa.block_geometry(T, Dv) == (512, 512)
    assert fa.block_geometry(T, 160) is None
    bf16, f32 = jnp.bfloat16, jnp.float32

    def attend(q, k, v):
        return fa.attention(q, k, v, interpret=False)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(attend(*a).astype(f32)),
                        argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(
        attend if direction == "forward" else grads, one_chip,
        ((B, H, T, D), bf16), ((B, H, T, D), bf16), ((B, H, T, Dv), bf16))
    assert "tpu_custom_call" in text and "pbtpu_attention_fwd" in text
    for name in ("pbtpu_attention_dq", "pbtpu_attention_dkv"):
        assert (name in text) == (direction == "backward")
    assert f"{T},{T}]" not in text
    # the output is the values' width, each gradient its operand's
    assert f"bf16[{B},{H},{T},{Dv}]" in text
    if direction == "backward":
        assert f"bf16[{B},{H},{T},{D}]" in text


_INSTRUCTION = re.compile(r"\s+(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                          r"([\w\-]+)\((.*)$")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_ARRAY = re.compile(r"(f32|bf16|s32|u32|pred)\[([\d,]*)\](\{[^}]*\})?")
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}
# what the MLA attention half's latent path wrote to HBM a layer, by
# _hbm_writes, while it built q and k in float32, token-major, at 32 heads
# x 192 channels and left their transposes and casts to nn.causal_attention
# (the same compile of the program as it stood before its operands were
# made in the kernels' dtype and layout)
LATENT_WRITES_BEFORE = 3_129_673_792


def _hbm_writes(text):
    """{instruction: (scope, result, bytes it writes to HBM, whether it is a
    product, whether it replicates rows over the lanes)} over the
    instructions an ``XLA Ops`` event is made of (``device_scopes.
    scopes_of_hlo``). A bitcast and the start of an asynchronous copy or
    slice write nothing of their own; a result laid out in memory space 1
    (``S(1)``: the core's own memory, where the compiler prefetches) is not
    HBM. A product is a convolution, or a fusion around one; a broadcast
    that maps no operand dimension to the result's last replicates rows
    over the lanes."""
    from paddlebox_tpu.monitor import device_scopes
    rows = device_scopes.scopes_of_hlo(text)
    products, comp = set(), None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
        elif comp and " convolution(" in line:
            products.add(comp)
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(1) not in rows:
            continue
        name, result, opcode, rest = m.groups()
        written = 0
        if opcode not in ("bitcast", "copy-start", "slice-start"):
            for dtype, dims, layout in _ARRAY.findall(result):
                if "S(1)" not in layout:
                    written += _BYTES[dtype] * int(np.prod(
                        [int(d) for d in dims.split(",") if d]))
        product = opcode == "convolution" or any(
            c in products for c in re.findall(r"calls=%?([\w.\-]+)", rest))
        mapped = re.search(r"dimensions=\{([\d,]*)\}", rest)
        last = str(result.partition("]")[0].count(","))
        replicas = opcode == "broadcast" and mapped is not None \
            and last not in mapped.group(1).split(",")
        out[name] = (rows[name]["scope"], result, written, product, replicas)
    return out


def latent_attention_half_text(one_chip):
    """The optimized text of Kanana-2's attention half at the cell's widths
    (32 heads of 192 / 128, a latent of 512, 16384 positions): forward,
    recomputation and backward as a layer runs them
    (``recomputed(keep=KEPT)``), for a described v5e."""
    from paddlebox_tpu.models.deepseek_v3 import KEPT, DeepseekV3Model
    from paddlebox_tpu.models.nn import recomputed
    B, T, d = 1, 16384, 2048
    model = DeepseekV3Model(
        hidden_size=d, num_layers=1, dense_layers=1, num_attention_heads=32,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, intermediate_size=8, moe_intermediate_size=8,
        n_shared_experts=1, router_experts=8, experts_per_token=2,
        experts_held=8, routed_scaling_factor=2.448, rope_theta=1e6,
        rope_interleave=True, rms_norm_eps=1e-6, vocab_size=8, seq_len=T)
    names = ("wq", "wkv_a", "wkv_b", "wo", "kv_norm")
    shapes = {**model._shapes(True), "kv_norm": (512,)}

    def layer_half(*args):
        p, (u, g) = dict(zip(names, args[:5])), args[5:]
        out, back = jax.vjp(recomputed(model._attention, keep=KEPT), p, u)
        return out, back(g)

    f32 = jnp.float32
    return _compiled_text(layer_half, one_chip,
                          *[(shapes[k], f32) for k in names],
                          ((B, T, d), f32), ((B, T, d), f32))


def test_latent_attention_operands_are_made_once_in_the_kernels_dtype(
        one_chip, on_tpu):
    """Kanana-2's attention half (``latent_attention_half_text``): q, k and
    v reach the kernels in bfloat16, head-major, made once. Outside the
    kernels' custom calls and the products no instruction writes a float32
    array of the heads' channels at every position, in either layout (the
    op's row statistics, one value a row replicated over the kernels' 128
    lanes, are the attention op's own), and the latent path writes about
    half the HBM it wrote building q and k in float32, token-major: 1.61
    GB a layer against 3.13."""
    writes = _hbm_writes(latent_attention_half_text(one_chip))
    heads_f32 = [
        (name, scope, result)
        for name, (scope, result, _, product, replicas) in writes.items()
        for dtype, dims, _ in _ARRAY.findall(result)
        if dtype == "f32" and not (product or replicas
                                   or name.startswith("pbtpu_attention"))
        and len(dims.split(",")) >= 3 and int(dims.split(",")[-1]) >= 64
        and {32, 16384} <= {int(x) for x in dims.split(",")}]
    assert not heads_f32, heads_f32
    latent = sum(w for scope, _, w, _, _ in writes.values()
                 if scope == "latent")
    assert latent <= 0.52 * LATENT_WRITES_BEFORE, latent


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_short_conv_compiles_at_the_published_widths(one_chip, on_tpu,
                                                     direction):
    from paddlebox_tpu.ops import short_conv as sc
    n, T, d, K = 2, 8192, 2048, 3
    assert sc.conv_geometry(T, d, K) == (256, 512)
    f32 = jnp.float32

    def conv(*args):
        return sc.short_conv(*args, interpret=False)

    def grads(*args):
        return jax.grad(lambda *a: jnp.sum(conv(*a)),
                        argnums=(0, 1, 2, 3))(*args)

    text = _compiled_text(
        conv if direction == "forward" else grads, one_chip,
        ((n, T, d), f32), ((n, T, d), f32), ((n, T, d), f32), ((K, d), f32))
    assert "tpu_custom_call" in text
    assert ("pbtpu_short_conv_fwd" in text) == (direction == "forward")
    assert ("pbtpu_short_conv_bwd" in text) == (direction == "backward")


# ---------------------------------------------------------------------------
# Kimi Delta Attention at the published widths and the cell's length: 32
# heads of 128, one sequence of 16,384 positions in chunks of 128, q, k, v
# in bfloat16 as the chip runs them, log a and beta in float32; at the
# block of heads the rule picks (8), whose lowered module is no larger
# than one head's (every product batched over the block: the tracing and
# lowering a warm process repeats before it asks the compile cache)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_kda_compiles_at_the_published_widths(one_chip, on_tpu, direction,
                                              monkeypatch):
    from paddlebox_tpu.ops import kda as kd
    B, H, T, K = 1, 32, 16384, 128
    assert kd.kda_geometry(kd.CHUNK, kd.SUB, K, K)
    assert kd.kda_head_block(H, kd.CHUNK, K, K) == 8
    bf16, f32 = jnp.bfloat16, jnp.float32

    def op(*args):
        return kd.kda(*args, interpret=False)[0]

    def grads(*args):
        return jax.grad(lambda *a: jnp.sum(op(*a).astype(f32)),
                        argnums=tuple(range(5)))(*args)

    fn = op if direction == "forward" else grads
    shapes = (((B, H, T, K), bf16), ((B, H, T, K), bf16),
              ((B, H, T, K), bf16), ((B, H, T, K), f32), ((B, H, T), f32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and "pbtpu_kda_fwd" in text
    assert ("pbtpu_kda_bwd" in text) == (direction == "backward")
    with monkeypatch.context() as m:
        m.setattr(kd, "kda_head_block", lambda *shape: 1)
        one_head = jax.jit(fn).lower(*args).as_text()
    assert len(lowered.as_text()) <= 1.2 * len(one_head)


# ---------------------------------------------------------------------------
# the held experts' grouped products (ISSUE 39): the pair at the three token
# cells' operands, in the tiles the rule gives, at every rung of their
# ladders — Mosaic takes the tiles and the VMEM they ask for
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["smallthinker_21b_ep4",
                                  "nemotron3_nano_ep16", "lfm2_24b_a2b_ep8",
                                  "kanana2_30b_a3b_ep8",
                                  "kimi_linear_48b_a3b_ep32"])
def test_grouped_products_compile_at_the_cells_operands(one_chip, cell):
    from paddlebox_tpu.ops import grouped_matmul as gm
    from paddlebox_tpu.parallel.expert import route_rungs
    from test_grouped_matmul import CELLS
    tokens, choices, experts, held, d, h = CELLS[cell]
    rows = tokens * choices
    tm = gm.row_tile(rows * held // experts, held)
    bf16 = jnp.bfloat16

    def chunk(x, w_up, w_down, sizes, dy):
        """Both products and all their cotangents, as a rung runs them."""
        meta = gm.group_tiles(sizes, rows, tm)
        dot = functools.partial(gm.grouped_matmul, meta=meta,
                                interpret=False)
        y, back = jax.vjp(
            lambda x, w_up, w_down: dot(dot(x, w_up).astype(bf16), w_down),
            x, w_up, w_down)
        return y, back(dy)

    for rung in route_rungs(rows, held, experts):
        text = _compiled_text(
            chunk, one_chip, ((rung, d), bf16), ((held, d, h), bf16),
            ((held, h, d), bf16), ((held,), jnp.int32),
            ((rung, d), jnp.float32))
        # forward twice, the rows' cotangents twice; the weights' twice
        assert text.count('custom_call_target="tpu_custom_call"') == 6
        assert "pbtpu_gmm" in text and "pbtpu_tgmm" in text
        assert "ragged-dot" not in text


# ---------------------------------------------------------------------------
# the device scopes in a program the chip's compiler optimized (ISSUE 38):
# a small attention tower's differentiated step — the kernels' custom calls
# under ``attention``, and little of the program under no scope at all
# ---------------------------------------------------------------------------

def test_the_optimized_step_holds_its_instructions_under_scopes(one_chip,
                                                                on_tpu):
    from paddlebox_tpu import monitor
    from paddlebox_tpu.monitor import device_scopes
    from token_tower_common import tower
    _, _, model, params, pulled, ids = tower(
        "smallthinker_21b_ep4.seq8k", hidden_size=256,
        num_attention_heads=2, num_key_value_heads=1, head_dim=128,
        moe_ffn_hidden_size=128, sliding_window_size=256, vocab_size=1024,
        seq_len=512, head_chunk=256, expert_chunk_tokens=512)

    def step(params, pulled, ids):
        mask = jnp.ones(ids.shape, bool)
        with monitor.device_scope("tower"):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss(p, pulled, mask, None, None, ids)[0])(
                    params)
        with monitor.device_scope("dense_update"):
            return loss, jax.tree.map(lambda w, g: w - 1e-3 * g, params,
                                      grads)

    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=one_chip)
    text = jax.jit(step).lower(
        *jax.tree.map(like, (params, pulled, ids))).compile().as_text()
    rows = device_scopes.scopes_of_hlo(text)
    kernels = {name: row["scope"] for name, row in rows.items()
               if "pbtpu_attention" in name}
    for kernel in ("pbtpu_attention_fwd", "pbtpu_attention_dq",
                   "pbtpu_attention_dkv"):
        assert any(kernel in name for name in kernels), sorted(kernels)
    assert set(kernels.values()) == {"attention"}, kernels
    held = {row["scope"] for row in rows.values()}
    assert {"attention", "route", "experts", "head_loss", "tower",
            "dense_update"} <= held
    bare = [name for name, row in rows.items() if row["scope"] is None]
    assert len(bare) < 0.05 * len(rows), (len(bare), len(rows), bare[:40])
