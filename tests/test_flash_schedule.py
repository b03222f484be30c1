"""Flash attention's schedule: the tiles and grids `schedule()` chooses
from a call's shapes, and parity of forward and all three gradients with
`_reference` (interpret mode) over tile choices forced through `block_q=` /
`block_k=` (and `schedule(span=)`) and the rule's own, for every mask form, in float32
and bfloat16. The sequences are several tiles long, so the walks cross the
diagonal bound, a span wholly above it (a grid step whose index map clamps
and which folds nothing), a tile the block table skips, and rows with no
visible key."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

S = 512
# (block_q, block_k, span): 128-wide tiles walked inside ONE grid step a
# q tile; the same tiles in spans of two (the third grid axis has two
# steps, one of them dead above the diagonal); uneven tiles; the rule's
TILES = {"t128": (128, 128, None), "t128-span256": (128, 128, 256),
         "t256x128": (256, 128, None), "rule": (None, None, None)}
MODES = ("causal", "causal-sq<sk", "kbias", "mask", "segments", "blocks")


def _case(mode, dtype, seed=0):
    """(q, k, v, causal, wrapper keywords, reference keywords)."""
    rng = np.random.default_rng(seed)
    b, h, d = 1, 2, 32
    sq = S // 2 if mode == "causal-sq<sk" else S
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((b, S, h, d)), dtype)
            for _ in range(2))
    causal, kw, ref = mode.startswith("causal"), {}, {}
    if mode == "kbias":                     # the last keys are padding
        bias = jnp.where(jnp.arange(S) < S - 72, 0.0, fa.NEG_INF)
        kw["mask"] = bias[None, None, None, :].astype(jnp.float32)
        ref["kbias"] = jnp.broadcast_to(bias, (b, S)).astype(jnp.float32)
    if mode == "mask":                      # random, forty rows see nothing
        keep = rng.random((1, 1, sq, S)) > 0.3
        keep[:, :, 100:140, :] = False
        ref["mask"] = kw["mask"] = jnp.asarray(
            np.where(keep, 0.0, fa.NEG_INF), jnp.float32)
    if mode == "segments":                  # three packed documents
        causal = True
        segs = jnp.asarray((np.arange(S) * 3) // S, jnp.int32)[None]
        kw["segment_ids"] = segs
        ref["qseg"] = ref["kseg"] = segs
    if mode == "blocks":                    # a 128-wide band + a global tile
        i = np.arange(S // 128)
        table = (np.abs(i[:, None] - i[None, :]) <= 1) | (i[None, :] == 0)
        table[2, :] = False                 # a whole q tile sees nothing
        keep = np.kron(table, np.ones((128, 128), bool))
        kw["block_mask"] = jnp.asarray(table, jnp.int32)
        ref["mask"] = kw["mask"] = jnp.asarray(
            np.where(keep, 0.0, fa.NEG_INF), jnp.float32)[None, None]
    return q, k, v, causal, kw, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("tiles", sorted(TILES))
def test_forward_and_gradients_match_reference(tiles, mode, dtype,
                                               monkeypatch):
    q, k, v, causal, kw, ref = _case(mode, dtype)
    block_q, block_k, span = TILES[tiles]
    if span:        # what the rule does to a sequence that outgrows VMEM
        monkeypatch.setattr(fa, "schedule",
                            functools.partial(fa.schedule, span=span))
    scale = 1.0 / math.sqrt(q.shape[-1])
    w = jnp.asarray(np.random.default_rng(1).standard_normal(q.shape),
                    jnp.float32)

    def run(f):
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(w.astype(out.dtype))

    got = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=block_q,
        block_k=block_k, **kw))
    want = run(lambda q, k, v: fa._reference(q, k, v, causal, scale, **ref))
    # float32 operands: products exact on the CPU; bfloat16 operands: P and
    # dS are rounded to bfloat16 for their second matmul (2^-9 each)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = (np.asarray(x, np.float32) for x in (a, r))
        assert np.isfinite(a).all(), name
        bound = tol * max(1.0, float(np.abs(r).max()))
        assert float(np.abs(a - r).max()) <= bound, (name, tiles, mode)
    if mode == "mask":      # rows with no visible key: exactly zero
        assert not np.asarray(got[0], np.float32)[0, 100:140].any()
        assert not np.asarray(got[1], np.float32)[0, 100:140].any()


CELLS = {"gpt2-124m.train": ((28, 1024, 12, 64), 336),
         "gpt3-1.3b.train-4chip, a shard": ((4, 1024, 8, 128), 32)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_schedule_at_the_training_cells(cell):
    """512-wide tiles, the whole sequence in one span, two grid steps a
    head and kernel where the 128 x 128 grid took 64, none of them dead."""
    shape, heads = CELLS[cell]
    sch = fa.schedule(shape, shape, jnp.bfloat16, True)
    assert (sch.block_q, sch.block_k) == (512, 512)
    assert (sch.span_q, sch.span_k) == (1024, 1024)
    before = heads * (1024 // 128) ** 2        # 21,504 at the one-chip cell
    assert sch.steps == (heads * 2,) * 3
    assert all(10 * s <= before for s in sch.steps)
    assert sch.dead_steps == (0, 0, 0)
    assert sch.tiles == (heads * 3,) * 3       # the diagonal leaves 3 of 4
    assert fa._vmem_bytes(512, 1024, 512, 512, shape[-1], 2,
                          False) <= fa.VMEM_BUDGET


@pytest.mark.parametrize("keys", [64, 100, 128, 256])
def test_short_sequence_is_its_own_tile(keys):
    """min(tile, s): one grid step a head and kernel, as the 128-wide grid
    gave a sequence of up to 128 keys; and the kernels agree with the
    reference there."""
    shape = (2, keys, 4, 64)
    sch = fa.schedule(shape, shape, jnp.float32, True)
    assert (sch.block_q, sch.block_k, sch.span_q, sch.span_k) == (keys,) * 4
    assert sch.steps == (8, 8, 8) and sch.dead_steps == (0, 0, 0)
    rng = np.random.default_rng(keys)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(3))

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(
            q, k, v)

    got = grads(lambda q, k, v: fa.flash_attention(q, k, v, interpret=True))
    want = grads(lambda q, k, v: fa._reference(q, k, v, True, 0.125))
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, atol=2e-5, rtol=2e-5)


def test_schedule_follows_masks_tables_and_length():
    shape = (8, 1024, 12, 64)
    # a dense mask streams a (block_q, span) float32 slab, which the rule
    # counts: with float32 operands the span shrinks to a tile, and the
    # step above the diagonal is stepped but copies nothing
    sch = fa.schedule(shape, shape, jnp.float32, True, mask=True)
    assert (sch.block_q, sch.span_k) == (512, 512)
    assert fa._vmem_bytes(512, 512, 512, 512, 64, 4, True) <= fa.VMEM_BUDGET
    assert fa._vmem_bytes(512, 1024, 512, 512, 64, 4, True) > fa.VMEM_BUDGET
    assert sch.steps == (96 * 4,) * 3 and sch.dead_steps == (96,) * 3
    assert sch.tiles == (96 * 3,) * 3
    # a block table's granularity is the caller's
    sch = fa.schedule(shape, shape, jnp.bfloat16, False,
                      block_mask_shape=(8, 8), block_q=512)
    assert (sch.block_q, sch.block_k, sch.span_k) == (128, 128, 1024)
    assert sch.steps == (96 * 8,) * 3
    # a sequence too long to sit in VMEM whole keeps a third grid axis
    long = (1, 16384, 8, 128)
    sch = fa.schedule(long, long, jnp.bfloat16, True)
    assert sch.block_q == sch.block_k == 512 and sch.span_k < 16384
    assert fa._vmem_bytes(512, sch.span_k, 512, 512, 128, 2,
                          False) <= fa.VMEM_BUDGET
    n = 16384 // 512
    assert sch.tiles == (8 * n * (n + 1) // 2,) * 3
    assert sch.steps[0] == 8 * n * (16384 // sch.span_k)
    # cross-length causal: bottom-right aligned, every key tile is seen
    sch = fa.schedule((1, 512, 2, 64), (1, 1024, 2, 64), jnp.float32, True)
    assert sch.tiles == (2 * 2,) * 3 and sch.dead_steps == (0, 0, 0)
    # lengths with no tile
    assert fa.schedule((1, 192, 2, 64), (1, 192, 2, 64), jnp.float32,
                       True) is None
    assert fa.schedule((1, 640, 2, 64), (1, 640, 2, 64), jnp.float32,
                       True).block_q == 128
