"""Flash attention's schedule: the tiles and grids `schedule()` chooses
from a call's shapes, and parity of forward and all three gradients with
`_reference` (interpret mode) over tile choices forced through `block_q=` /
`block_k=` (and `schedule(span=)`) and the rule's own, for every mask form, in float32
and bfloat16. The sequences are several tiles long, so the walks cross the
diagonal bound, a span wholly above it (a grid step whose index map clamps
and which folds nothing), a tile the block table skips, and rows with no
visible key. The same over the LAYOUTS: heads read where the caller left
them, [b, s, h*d], one to a 128-lane block, two or four sharing one, a
key/value head shared by a group of query heads; and the head sizes and
counts that stay on flat [b*h, s, d] copies, which warn once a shape. And
over the two BACKWARDS: the fused one (a delta pre-pass, then dQ, dK and dV
from one pass over the score tiles: all of a toy's sq and sk in ONE grid
step of straight-line code where no span is forced, its diagonal tiles in
strips where they are wider than 128; a k tile a step with dQ's accumulator
riding across the steps and its blocks written on the last where one is),
which every toy shape takes by the rule, and the two-kernel one, forced
(`schedule(fused=False)`). The jaxpr of a step says what stands around the
kernels, and `profiler.traced_counts()` which backward a trace took."""

import functools
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import equations
from paddle_tpu import profiler
from paddle_tpu.ops.pallas import flash_attention as fa

S = 512
# (block_q, block_k, span): 128-wide tiles walked inside ONE grid step a
# q tile; the same tiles in spans of two (the third grid axis has two
# steps, one of them dead above the diagonal); uneven tiles (the fused
# backward folds a 256-wide diagonal tile in two strips); the rule's
TILES = {"t128": (128, 128, None), "t128-span256": (128, 128, 256),
         "t256x128": (256, 128, None), "rule": (None, None, None)}
MODES = ("causal", "causal-sq<sk", "kbias", "mask", "segments", "blocks")
# (query heads, key/value heads, head size) -> heads a block in place, 0
# for the flat copies: two heads of 32 do not fill a 128-lane block, three
# of 64 do not pair, 96 divides nothing
LAYOUTS = {"2x32": ((2, 2, 32), 0), "4x64": ((4, 4, 64), 2),
           "2x128": ((2, 2, 128), 1), "4x32": ((4, 4, 32), 4),
           "8over2x128": ((8, 2, 128), 1), "3x64": ((3, 3, 64), 0),
           "2x96": ((2, 2, 96), 0)}


def _case(mode, dtype, seed=0, heads=LAYOUTS["2x32"][0]):
    """(q, k, v, causal, wrapper keywords, reference keywords)."""
    rng = np.random.default_rng(seed)
    b, (h, hk, d) = 1, heads
    sq = S // 2 if mode == "causal-sq<sk" else S
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((b, S, hk, d)), dtype)
            for _ in range(2))
    causal, kw, ref = mode.startswith("causal"), {}, {}
    if mode == "kbias":                     # the last keys are padding
        bias = jnp.where(jnp.arange(S) < S - 72, 0.0, fa.NEG_INF)
        kw["mask"] = bias[None, None, None, :].astype(jnp.float32)
        ref["kbias"] = jnp.broadcast_to(bias, (b, S)).astype(jnp.float32)
    if mode in ("mask", "headmask"):        # random, forty rows see nothing
        keep = rng.random((1, h if mode == "headmask" else 1, sq, S)) > 0.3
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


DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# every tile choice on the toy heads; forced tiles and the rule's on each
# other layout, with a mask a head where heads share a block or a key
PARITY = [(t, m, dt, "2x32") for t in sorted(TILES) for m in MODES
          for dt in DTYPES]
PARITY += [(t, m, dt, lay) for lay in sorted(LAYOUTS) if lay != "2x32"
           for t in ("t128", "rule") for m in MODES for dt in DTYPES
           if LAYOUTS[lay][1] or (m, t) in (("causal", "rule"),
                                            ("mask", "t128"))]
PARITY += [("t128", "headmask", dt, lay) for lay in ("4x64", "8over2x128")
           for dt in DTYPES]
# every case above takes the fused backward; the two-kernel one, which the
# rule keeps for sequences whose dQ accumulator no VMEM holds, over every
# tile choice and mask form on the toy heads and the rule's on each layout
# that is read in place
PARITY = [case + (True,) for case in PARITY]
PARITY += [(t, m, "f32", "2x32", False) for t in sorted(TILES)
           for m in MODES]
PARITY += [("rule", m, "bf16", lay, False)
           for lay in ("4x64", "2x128", "8over2x128", "2x96")
           for m in ("causal", "causal-sq<sk", "mask", "segments")]


@pytest.mark.parametrize(
    "tiles,mode,dtype,layout,fused",
    [pytest.param(t, m, DTYPES[dt], lay, fused, id="-".join(
        (t, m, dt) + ((lay,) if lay != "2x32" else ())
        + (() if fused else ("two-kernels",))))
     for t, m, dt, lay, fused in PARITY])
def test_forward_and_gradients_match_reference(tiles, mode, dtype, layout,
                                               fused, monkeypatch):
    heads, in_place = LAYOUTS[layout]
    q, k, v, causal, kw, ref = _case(mode, dtype, heads=heads)
    sch = fa.schedule(q.shape, k.shape, dtype, causal)
    assert sch.heads_per_block == in_place and sch.fused_backward
    block_q, block_k, span = TILES[tiles]
    forced = {"span": span} if span else {}     # what the rule does to a
    if not fused:                               # sequence that outgrows VMEM
        forced["fused"] = False
    if forced:
        monkeypatch.setattr(fa, "schedule",
                            functools.partial(fa.schedule, **forced))
    taken = profiler.traced_counts()
    scale = 1.0 / math.sqrt(q.shape[-1])
    w = jnp.asarray(np.random.default_rng(1).standard_normal(q.shape),
                    jnp.float32)

    def run(f):
        def out_and_gradients(q, k, v):
            out, vjp = jax.vjp(f, q, k, v)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(out_and_gradients)(q, k, v)  # one compile, not three

    with warnings.catch_warnings(record=True) as said:
        warnings.simplefilter("always")
        got = run(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, interpret=True, block_q=block_q,
            block_k=block_k, **kw))
    # a flat shape says so (once: test_a_flat_shape_warns_once), and no
    # shape falls back to the reference
    assert not any("XLA reference" in str(w.message) for w in said)
    assert in_place == 0 or not any("flat" in str(w.message) for w in said)
    # the trace of the gradient took the backward it was told to, once
    took = {n: c - taken.get(n, 0)
            for n, c in profiler.traced_counts().items() if c != taken.get(n)}
    assert took == {"flash_bwd_fused" if fused
                    else "flash_bwd_two_kernels": 1}
    want = run(lambda q, k, v: fa._reference(q, k, v, causal, scale, **ref))
    # float32 operands: products exact on the CPU; bfloat16 operands: P and
    # dS are rounded to bfloat16 for their second matmul (2^-9 each)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = (np.asarray(x, np.float32) for x in (a, r))
        assert np.isfinite(a).all(), name
        bound = tol * max(1.0, float(np.abs(r).max()))
        assert float(np.abs(a - r).max()) <= bound, (name, tiles, mode)
    if mode in ("mask", "headmask"):  # rows with no visible key: zero
        assert not np.asarray(got[0], np.float32)[0, 100:140].any()
        assert not np.asarray(got[1], np.float32)[0, 100:140].any()


# q's shape, key/value heads, heads a block in place, spans a sequence
CELLS = {"gpt2-124m.train": ((28, 1024, 12, 64), 12, 2, 1),
         "gpt3-1.3b.train-4chip, a shard": ((4, 1024, 8, 128), 8, 1, 1),
         "zaya1-8b.train-8k": ((1, 8192, 8, 128), 2, 1, 2)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_schedule_at_the_training_cells(cell):
    """512-wide tiles and 1024 keys in one span: two grid steps a block of
    heads for the forward where the 128 x 128 grid took 64 a head, none of
    them dead (ZAYA's 8192 keys sit in VMEM as two spans, and a tile of the
    first half steps once over the span above its diagonal). Every cell's
    heads are read in place, two of 64 to a block, one of 128 (ZAYA's two
    key/value heads by four query heads each). Every cell's backward is the
    fused one: at 1024 keys all of a head block in ONE grid step, under the
    chip's default VMEM; ZAYA's a k tile a step beside all of sq and dQ's
    4 MiB accumulator, for which it asks the compiler for more. The
    two-kernel backward's schedule is what it was."""
    shape, kv_heads, in_place, spans = CELLS[cell]
    b, s, h, d = shape
    sch = fa.schedule(shape, (b, s, kv_heads, d), jnp.bfloat16, True)
    two = fa.schedule(shape, (b, s, kv_heads, d), jnp.bfloat16, True,
                      fused=False)
    assert sch[:4] == two[:4] and not two.fused_backward
    assert sch.heads_per_block == two.heads_per_block == in_place
    assert (sch.block_q, sch.block_k) == (512, 512)
    assert (sch.span_q, sch.span_k) == (s // spans,) * 2
    blocks, n = b * h // in_place, s // 512
    assert two.steps == (blocks * n * spans,) * 3
    assert all(10 * steps <= b * h * (s // 128) ** 2 for steps in two.steps)
    assert two.dead_steps == (blocks * n * (spans - 1) // 2,) * 3
    # the diagonal leaves 3 tiles of 4 at 1024 keys, 136 of 256 at 8192
    live = b * h * n * (n + 1) // 2
    assert two.tiles == (live,) * 3 and sch.tiles == (live, 0, live)
    assert fa._vmem_bytes(512, s // spans, 512, 512, d, 2, 0,
                          in_place) <= fa.VMEM_BUDGET
    assert spans == 1 or fa._vmem_bytes(512, s, 512, 512, d, 2, 0,
                                        in_place) > fa.VMEM_BUDGET
    # the fused backward: the forward's steps, a delta step a 2048 rows
    assert sch.fused_backward
    assert sch.steps[:2] == (two.steps[0], blocks * max(s // 2048, 1))
    count = fa._fused_vmem_bytes(sch.bwd_span_q, sch.bwd_span_k, s, 512,
                                 512, d, 2, 0, max(in_place, 1))
    assert sch.dead_steps[1:] == (0, 0)
    if spans == 1:      # one step a head block
        assert (sch.bwd_span_q, sch.bwd_span_k) == (s, s)
        assert sch.steps[2] == blocks
        assert count <= fa.VMEM_BUDGET and sch.bwd_vmem_limit is None
    else:   # 16 k tiles a head, each beside all of sq: q and dO come once
        assert (sch.bwd_span_q, sch.bwd_span_k) == (s, 512)
        assert sch.steps[2] == blocks * n
        assert fa.VMEM_BUDGET < count <= fa.FUSED_VMEM_BUDGET
        assert sch.bwd_vmem_limit == fa.FUSED_VMEM_LIMIT


def test_which_backward_is_a_pure_function_of_the_shapes():
    """`Schedule.fused_backward` follows from shapes, dtype and masks
    alone: fused at the three cells' shapes (above) and wherever dQ's
    float32 accumulator for all of sq fits beside a step's blocks; two
    kernels where that accumulator would take more than half of the budget
    (32768 queries of 128 lanes are 16 MiB); equal for equal inputs; and a shape that does not tile
    has no schedule at all."""
    def sch(s, h=8, d=128, dtype=jnp.bfloat16, **kw):
        return fa.schedule((1, s, h, d), (1, s, h, d), dtype, True, **kw)

    assert sch(16384).fused_backward and sch(16384).bwd_span_k == 512
    assert sch(16384).bwd_vmem_limit == fa.FUSED_VMEM_LIMIT
    long = sch(32768)
    assert not long.fused_backward and long.bwd_vmem_limit is None
    assert (long.bwd_span_q, long.bwd_span_k) == (0, 0)
    assert long.steps[1] == long.steps[0] and long.tiles[1] == long.tiles[0]
    # (a k tile beside ONE q tile would fit; more than half the budget for
    # the accumulator is what the rule refuses)
    assert 2 * 32768 * 128 * 4 > fa.FUSED_VMEM_BUDGET >= 2 * 16384 * 128 * 4
    assert long == sch(32768) and sch(16384) == sch(16384)
    assert sch(1024) != sch(1024, dtype=jnp.float32)    # the dtype counts
    # few tiles: one step of straight-line code; many: a k tile a step
    assert sch(1024).bwd_span_k == 1024
    assert sch(1024, block_q=128, block_k=128).bwd_span_k == 128
    assert 2 * 2 <= fa.ONE_STEP_TILES < 8 * 8
    # forcing the two-kernel backward changes nothing else
    two = sch(1024, fused=False)
    assert not two.fused_backward and two[:4] == sch(1024)[:4]
    assert sch(192) is None and sch(192, fused=False) is None


@pytest.mark.parametrize("layout", ["3x64", "2x96"])
def test_a_flat_shape_warns_once(layout):
    """The layout is chosen per compiled program, so its counter is the
    schedule's field and ONE warning a shape."""
    h, hk, d = LAYOUTS[layout][0]
    q = jnp.zeros((2, 128, h, d), jnp.float32)      # a shape of its own
    assert fa.schedule(q.shape, q.shape, q.dtype, True).heads_per_block == 0
    with warnings.catch_warnings(record=True) as said:
        warnings.simplefilter("always")
        for _ in range(2):
            fa.flash_attention(q, q, q, interpret=True)
    flat = [w for w in said if "flat [b*h, s, d] copies" in str(w.message)]
    assert len(flat) == 1 and str(q.shape) in str(flat[0].message)


def test_no_transposed_copy_stands_around_the_kernels():
    """At the one-chip cell's shape a step's three kernels read q, k, v, o
    and dO as [b, s, h*d], a free reshape of what the projections wrote,
    and write o, dq, dk, dv the same way: no rank-4 transpose is left."""
    shape = (28, 1024, 12, 64)
    args = [jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(*args).jaxpr
    eqns = list(equations(jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 3
    for e in kernels:
        big = [v.aval.shape for v in list(e.invars) + list(e.outvars)
               if v.aval.dtype == jnp.bfloat16]
        assert big and set(big) == {(28, 1024, 12 * 64)}
    assert not [e for e in eqns if e.primitive.name == "transpose"
                and len(e.invars[0].aval.shape) >= 4]


def test_zayas_attention_repeats_no_key_or_value_head(monkeypatch):
    """`cca_attention` hands SDPA its 2 key/value heads as they are: the
    kernels' k and v operands are [b, s, 2 * 128] beside q's [b, s, 8 *
    128]: no copy of a head a query exists for them to read."""
    from paddle_tpu.models import zaya
    from paddle_tpu.ops import impl

    monkeypatch.setattr(impl, "_flash_enabled", lambda: True)
    cfg = zaya.ZayaConfig(
        vocab_size=64, hidden_size=256, num_hidden_layers=1,
        num_attention_heads=8, num_key_value_heads=2, head_dim=128,
        cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
        rope_theta=5e6, num_experts=2, num_experts_per_tok=1,
        moe_intermediate_size=32, router_hidden_size=16, rms_norm_eps=1e-5,
        experts_held=2, first_expert=0)
    model = zaya.ZayaForCausalLM(cfg)
    params = {k: jax.ShapeDtypeStruct(t.shape, t._value.dtype)
              for k, t in model.named_parameters()}
    pre = "layers.0.attn."
    u = jax.ShapeDtypeStruct((1, 256, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, u: jnp.sum(zaya.cca_attention(cfg, p, pre, u)),
        argnums=(0, 1)))(params, u).jaxpr
    eqns = list(equations(jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in kernels] == [
        "flash_fwd", "flash_bwd_delta", "flash_bwd"]
    for e in kernels[::2]:  # q, k, v lead the walking kernels' operands
        assert [v.aval.shape for v in e.invars[:3]] == [
            (1, 256, 8 * 128), (1, 256, 2 * 128), (1, 256, 2 * 128)]
    # (the second convolution transposes its 8 + 2 heads; nothing does q's
    # 8 or k's and v's 2)
    assert not [e for e in eqns if e.primitive.name == "transpose"
                and e.invars[0].aval.shape in ((1, 256, 8, 128),
                                               (1, 256, 2, 128))]


@pytest.mark.parametrize("keys", [64, 100, 128, 256])
def test_short_sequence_is_its_own_tile(keys):
    """min(tile, s): one grid step a head and kernel, as the 128-wide grid
    gave a sequence of up to 128 keys; and the kernels agree with the
    reference there."""
    shape = (2, keys, 4, 64)
    sch = fa.schedule(shape, shape, jnp.float32, True)
    assert (sch.block_q, sch.block_k, sch.span_q, sch.span_k) == (keys,) * 4
    # two heads of 64 to a block: a grid step a pair
    assert sch.heads_per_block == 2
    assert sch.steps == (4, 4, 4) and sch.dead_steps == (0, 0, 0)
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


@pytest.mark.parametrize("heads", ["4x64", "2x128"])
def test_a_block_table_of_few_tiles_in_one_straight_line_step(heads):
    """Four tiles are ONE grid step of the fused backward, every index known
    at trace time, the table's too: a dead tile's code is there and a
    `pl.when` on the table's entry skips it."""
    (h, hk, d), _ = LAYOUTS[heads]
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, n, d)), jnp.float32)
               for n in (h, hk, hk))
    table = np.array([[1, 0], [1, 1]])      # the diagonal's own: causal
    sch = fa.schedule(q.shape, k.shape, q.dtype, True,
                      block_mask_shape=table.shape)
    assert (sch.block_q, sch.bwd_span_q, sch.bwd_span_k) == (128, 256, 256)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(
            q, k, v)

    got = grads(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, block_mask=table, interpret=True))
    want = grads(lambda q, k, v: fa._reference(q, k, v, True, d ** -0.5))
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, atol=2e-5, rtol=2e-5)


def test_schedule_follows_masks_tables_and_length():
    wide, shape = (8, 1024, 12, 128), (8, 1024, 12, 64)
    # a dense mask streams a (block_q, span) float32 slab, which the rule
    # counts: with float32 operands the span shrinks to a tile, and the
    # step above the diagonal is stepped but copies nothing
    sch = fa.schedule(wide, wide, jnp.float32, True, mask=True)
    assert (sch.block_q, sch.span_k) == (512, 512)
    assert fa._vmem_bytes(512, 512, 512, 512, 128, 4, 1) <= fa.VMEM_BUDGET
    assert fa._vmem_bytes(512, 1024, 512, 512, 128, 4, 1) > fa.VMEM_BUDGET
    two = fa.schedule(wide, wide, jnp.float32, True, mask=True, fused=False)
    assert two.steps == (96 * 4,) * 3 and two.dead_steps == (96,) * 3
    assert two.tiles == (96 * 3,) * 3
    # the fused backward streams a (q span, k tile) slab: all of sq beside
    # a k tile, two steps a head, with more VMEM asked for
    assert (sch.bwd_span_q, sch.bwd_span_k) == (1024, 512)
    assert sch.steps == (96 * 4, 96, 96 * 2)
    assert sch.dead_steps == (96, 0, 0) and sch.tiles == (288, 0, 288)
    assert sch.bwd_vmem_limit == fa.FUSED_VMEM_LIMIT
    # two heads of 64 in a block keep each head's own lanes of the
    # grid-side operands besides: one 512-wide tile no longer fits with
    # the slab, a mask a head doubles the slab, and the tiles halve
    assert fa._vmem_bytes(512, 512, 512, 512, 64, 4, 1, 2) > fa.VMEM_BUDGET
    for heads_of_mask in (1, 12):
        sch = fa.schedule(shape, shape, jnp.float32, True,
                          mask=heads_of_mask)
        assert sch.heads_per_block == 2
        assert (sch.block_q, sch.block_k, sch.span_k) == (256, 256, 1024)
        assert sch.steps[0] == 48 * 4 and sch.dead_steps == (0, 0, 0)
        # sixteen tiles: a k tile a step, all of sq beside it
        assert (sch.bwd_span_q, sch.bwd_span_k) == (1024, 256)
        assert sch.steps[1:] == (48, 48 * 4)
    # a block table's granularity is the caller's
    sch = fa.schedule(shape, shape, jnp.bfloat16, False,
                      block_mask_shape=(8, 8), block_q=512)
    assert (sch.block_q, sch.block_k, sch.span_k) == (128, 128, 1024)
    assert sch.steps == (48 * 8, 48, 48 * 8)    # a step a pair of heads
    assert (sch.bwd_span_q, sch.bwd_span_k) == (1024, 128)
    # a sequence too long to sit in VMEM whole keeps a third grid axis
    long = (1, 16384, 8, 128)
    sch = fa.schedule(long, long, jnp.bfloat16, True)
    assert sch.block_q == sch.block_k == 512 and sch.span_k < 16384
    assert fa._vmem_bytes(512, sch.span_k, 512, 512, 128, 2,
                          False) <= fa.VMEM_BUDGET
    n = 16384 // 512
    assert sch.tiles == (8 * n * (n + 1) // 2, 0, 8 * n * (n + 1) // 2)
    assert sch.steps[0] == 8 * n * (16384 // sch.span_k)
    # and the fused backward's q span is the longest that leaves room for
    # dQ's 8 MiB: a k tile's steps above the diagonal are dead
    assert 0 < sch.bwd_span_q < 16384 and sch.bwd_span_k == 512
    spans = 16384 // sch.bwd_span_q
    assert sch.steps[2] == 8 * n * spans
    assert sch.dead_steps[2] == 8 * sum(
        (m + 1) * sch.bwd_span_q <= j * 512 for j in range(n)
        for m in range(spans))
    # cross-length causal: bottom-right aligned, every key tile is seen
    sch = fa.schedule((1, 512, 2, 64), (1, 1024, 2, 64), jnp.float32, True)
    assert sch.tiles == (2 * 2, 0, 2 * 2) and sch.dead_steps == (0, 0, 0)
    assert (sch.bwd_span_q, sch.bwd_span_k) == (512, 1024)
    # lengths with no tile
    assert fa.schedule((1, 192, 2, 64), (1, 192, 2, 64), jnp.float32,
                       True) is None
    assert fa.schedule((1, 640, 2, 64), (1, 640, 2, 64), jnp.float32,
                       True).block_q == 128
