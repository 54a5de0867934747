"""Kimi Delta Attention's chunked kernels (``ops/kda.py``) against the
literal recurrence (``kda_reference``), in the Pallas interpreter at small
sizes, jitted: the output and the cotangents of q, k, v, log a and beta
over several chunks of several sub-chunks; two sequences in one batch
whose states do not leak; a channel whose log decay sums to -200 inside
one chunk (a single position's -150 among them: its sub-chunk is taken
pair by pair), one that falls by 75 over half a sub-chunk (one reference
still serves it), and keys that repeat, the cases a chunked form can
lose; and the shapes the op refuses. The kernels take a block of heads
(``kda_head_block``: both heads here) and are held to the recurrence at
that block and at one head a block; the count of sub-chunks the block
took pair by pair (``pair_subchunks``) against one made in numpy."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.ops import kda as kd

B, H, T, K, V = 2, 2, 64, 16, 24
CHUNK, SUB = 32, 8          # two chunks of four sub-chunks


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _inputs(seed, case="plain"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = _l2(jax.random.normal(ks[0], (B, H, T, K))) * K ** -0.5
    k = _l2(jax.random.normal(ks[1], (B, H, T, K)))
    v = jax.random.normal(ks[2], (B, H, T, V))
    log_a = -jnp.exp(jax.random.uniform(ks[3], (B, H, T, K),
                                        minval=-5.0, maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, T)))
    if case == "strong_decay":
        # channel 0 of head 0 sums to -200 within the first chunk: -50 a
        # sub-chunk, one position alone -150
        log_a = log_a.at[:, 0, :CHUNK, 0].set(-50.0 / 31)
        log_a = log_a.at[:, 0, 12, 0].set(-150.0)
    elif case == "both_heads":
        # head 0 as above; head 1's channel 5 falls by 150 in the same
        # sub-chunk of the first chunk and in one of the second: a block
        # of both heads takes the first sub-chunk once
        log_a = log_a.at[:, 0, :CHUNK, 0].set(-50.0 / 31)
        log_a = log_a.at[:, 0, 12, 0].set(-150.0)
        log_a = log_a.at[:, 1, 11, 5].set(-150.0)
        log_a = log_a.at[:, 1, CHUNK + 10, 5].set(-150.0)
    elif case == "near_the_limit":
        # channel 3 of head 1 falls by 75 over half a sub-chunk: one
        # reference a sub-chunk still serves it, with factors near e^+-75
        log_a = log_a.at[:, 1, :, 3].set(-75.0 / (SUB // 2))
    elif case == "repeated_keys":
        # one key for every position of head 1, beta near one: the
        # chunk's M is near 1 below its diagonal, its powers large
        k = k.at[:, 1].set(jnp.broadcast_to(k[:, 1, :1], (B, T, K)))
        beta = beta.at[:, 1].set(0.95)
        log_a = log_a.at[:, 1].set(-1e-3)
    do = jax.random.normal(ks[5], (B, H, T, V))
    return (q, k, v, log_a, beta), do


def _both(args, do):
    """(output, the five cotangents) of the kernels and of the
    recurrence."""
    def run(op):
        o, back = jax.vjp(op, *args)
        return o, back(do)

    kernels = jax.jit(lambda *a: run(
        lambda *x: kd.kda(*x, chunk=CHUNK, sub=SUB, interpret=True)[0]))
    return kernels(*args, do), jax.jit(lambda *a: run(kd.kda_reference))(
        *args, do)


CASES = ["plain", "strong_decay", "near_the_limit", "repeated_keys",
         "both_heads"]


def _numpy_pairs(log_a, chunk, sub, hb):
    """The (sequence, head block, sub-chunk) visits where some channel of
    some head of the block falls by more than LIMIT across either half of
    the sub-chunk, counted in numpy from log a."""
    la = np.asarray(log_a, np.float32)
    B, H, T, K = la.shape
    g = np.cumsum(la.reshape(B, H, T // chunk, chunk, K), axis=3,
                  dtype=np.float32).reshape(B, H, T // sub, sub, K)
    mid = g[..., sub // 2, :]
    fall = np.maximum(g[..., 0, :] - mid, mid - g[..., sub - 1, :])
    fall = fall.reshape(B, H // hb, hb, T // sub, K).max(axis=(2, 4))
    return int(np.sum(~(fall <= kd.LIMIT)))


@pytest.mark.parametrize("case", CASES)
def test_kernels_match_the_literal_recurrence(case):
    assert kd.kda_head_block(H, CHUNK, K, V) == 2
    _check(case)


@pytest.mark.parametrize("case", CASES)
def test_kernels_at_one_head_a_block_match_the_literal_recurrence(
        case, monkeypatch):
    monkeypatch.setattr(kd, "kda_head_block", lambda *shape: 1)
    _check(case)


def _check(case):
    args, do = _inputs(3, case)
    if case == "strong_decay":
        cum = jnp.cumsum(args[3][0, 0, :CHUNK, 0])
        assert float(cum[-1]) <= -199.0
    (o, grads), (o_ref, grads_ref) = _both(args, do)
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o, o_ref, atol=2e-5 * float(
        jnp.abs(o_ref).max()))
    for name, g, want in zip(("q", "k", "v", "log_a", "beta"), grads,
                             grads_ref):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(
            g, want, atol=5e-5 * max(float(jnp.abs(want).max()), 1.0),
            err_msg=name)


def test_two_sequences_in_one_batch_keep_their_own_states():
    """Sequence 1's inputs changed: sequence 0's output and cotangents are
    the same bits, and sequence 1's are the recurrence's on it alone."""
    (q, k, v, log_a, beta), do = _inputs(5)
    (o, g), _ = _both((q, k, v, log_a, beta), do)
    other = (q.at[1].multiply(-1.0), k.at[1].set(k[1, ::-1]),
             v.at[1].multiply(2.0), log_a.at[1].multiply(3.0),
             beta.at[1].set(1.0 - beta[1]))
    (o2, g2), (o2_ref, _) = _both(other, do)
    np.testing.assert_array_equal(o[0], o2[0])
    for a, b in zip(g, g2):
        np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_allclose(o2[1], o2_ref[1],
                               atol=2e-5 * float(jnp.abs(o2_ref).max()))
    assert float(jnp.abs(o2[1] - o[1]).max()) > 1e-2


def test_the_chunk_sums_start_at_every_chunk():
    x = jnp.arange(2 * 3 * 8 * 2, dtype=jnp.float32).reshape(2, 3, 8, 2)
    got = kd.chunk_cumsum(x, 4)
    want = jnp.concatenate([jnp.cumsum(x[:, :, :4], axis=2),
                            jnp.cumsum(x[:, :, 4:], axis=2)], axis=2)
    np.testing.assert_array_equal(got, want)


def test_shapes_the_op_refuses(monkeypatch):
    (q, k, v, log_a, beta), _ = _inputs(1)
    with pytest.raises(ValueError, match="does not divide"):
        kd.kda(q[:, :, :48], k[:, :, :48], v[:, :, :48], log_a[:, :, :48],
               beta[:, :, :48], chunk=32)
    with pytest.raises(ValueError, match="do not agree"):
        kd.kda(q, k, v, log_a, beta[:, :1])
    with pytest.raises(ValueError, match="sub-chunk"):
        kd.kda(q, k, v, log_a, beta, chunk=32, sub=12)
    # on the chip the chunk and both head widths are whole lane tiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not kd.kda_geometry(128, 16, 64, 128)
    assert not kd.kda_geometry(64, 16, 128, 128)
    assert kd.kda_geometry(128, 16, 128, 128)
    with pytest.raises(ValueError, match="128-lane"):
        kd.kda(q, k, v, log_a, beta, chunk=32, sub=8)


def test_the_head_block_follows_the_shapes():
    """The largest divisor of H whose estimate the budget holds: both of
    the tests' heads; at the published widths a block of 8 of 32 (16
    would pass the budget); one head where none fits."""
    assert kd.kda_head_block(H, CHUNK, K, V) == 2
    assert kd.kda_head_block(32, 128, 128, 128) == 8
    assert kd._vmem_bytes(8, 128, 128, 128) <= kd.VMEM_BUDGET \
        < kd._vmem_bytes(16, 128, 128, 128)
    assert kd.kda_head_block(24, 128, 128, 128) == 8
    assert kd.kda_head_block(7, 128, 128, 128) == 7
    assert kd.kda_head_block(4, 128, 4096, 4096) == 1


@pytest.mark.parametrize("block", ["one", "rule"])
@pytest.mark.parametrize("case", ["plain", "strong_decay", "both_heads"])
def test_pair_subchunks_counts_what_numpy_counts(case, block, monkeypatch):
    """The op's count at the kernels' block against numpy's from log a:
    none where every fall is small; head 0's one sub-chunk a sequence;
    with head 1's two more, three a sequence at one head a block and two
    at a block of both (one sub-chunk is both heads')."""
    hb = 1 if block == "one" else 2
    if block == "one":
        monkeypatch.setattr(kd, "kda_head_block", lambda *shape: 1)
    (q, k, v, log_a, beta), _ = _inputs(3, case)
    _, pairs = jax.jit(lambda *a: kd.kda(*a, chunk=CHUNK, sub=SUB,
                                         interpret=True))(
        q, k, v, log_a, beta)
    want = _numpy_pairs(log_a, CHUNK, SUB, hb)
    assert float(pairs) == want
    assert want == B * {"plain": 0, "strong_decay": 1,
                        "both_heads": 3 if hb == 1 else 2}[case]
