"""The kernels of the main path, compiled for a TPU v5e that is described
and not attached (on-chip-measurement guide, section 2), at GPT-2 124M
widths (12 heads x 64, sequence 1024, pages of 16) and, for the ragged
kernel, at the decode cell's: GPT-3 1.3B's 16 x 128 in bf16 over 3136
pages with a table 128 wide, and a grouped 32/8 x 128; the latent decode
kernel at `kimi-k2.7-code.decode-16k`'s (48 sequences, 1280-page tables and
their 80 run flags a row, 16-token pages of 640 lanes); the sparse cell's
scan over index pages and its attention over a selection, in both forms
(`deepseek-v3.2.decode-sparse-16k`: 36 sequences, 128 heads, 40384 pages).

Nothing runs, so this says nothing about results or times; it raises what
the chip's compiler would raise (an unparsable contraction, a block the
tiling refuses, more scoped VMEM than the chip has), which interpret mode
cannot show. About two seconds a case."""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

N_HEADS, HEAD_DIM, SEQ, BATCH, PAGE = 12, 64, 1024, 8, 16
NUM_PAGES = 1024
# chip_smoke.py's serve phase draws prompts of 128..512 tokens, prefilled
# whole: 512 is its widest prefill bucket
PREFILL_BUCKET = 512


@pytest.fixture(scope="module")
def v5e():
    """The devices of a described v5e 2x2, persistent compile cache off (a
    compile for a described device is written to it but cannot be read
    back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _ragged(dtype, B, T, n_q=N_HEADS, n_kv=N_HEADS, d=HEAD_DIM,
            pages=NUM_PAGES, table=SEQ // PAGE):
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention

    def fn(q, kp, vp, table, start, qlen):
        return ragged_paged_attention(q, kp, vp, table, start, qlen,
                                      interpret=False)

    pool = ((pages, PAGE, n_kv, d), dtype)
    return fn, [((B, T, n_q, d), dtype), pool, pool,
                ((B, table), jnp.int32), ((B,), jnp.int32),
                ((B,), jnp.int32)]


def _ragged_1p3b(B, T, n_q=16, n_kv=16):
    """gpt3-1.3b.decode's kernel: bf16 pages of 16 x 128, the pool and
    the table the bench builds (3136 pages, 2048 / 16 entries a row)."""
    return _ragged(jnp.bfloat16, B, T, n_q=n_q, n_kv=n_kv, d=128,
                   pages=3136, table=128)


def _latent(B, pages=50752, table=1280, n_q=64, lanes=640, v_lanes=512,
            flags_in=True):
    """kimi-k2.7-code.decode-16k's decode kernel: 48 sequences, 64 query
    heads over ONE shared key a token, bf16 latent pages of 16 tokens x 640
    lanes (576 values padded to whole lane tiles), the pool and the table
    the bench builds (50752 pages; 20480 / 16 = 1280 entries a row, 245 KB
    of int32 in scalar memory) and, beside it, the flags of which groups
    of 16 entries are runs of consecutive pages (48 x 80 int32). The flags
    are DATA: an all-run and a no-run table are one program. `flags_in`:
    an operand, as the runner's decode step passes them (computed once
    for its layers); else computed from the table inside the call."""
    from paddle_tpu.ops.pallas import latent_paged_attention as lpa

    pool = jax.ShapeDtypeStruct((pages, PAGE, lanes), jnp.bfloat16)
    ppb, group = lpa.walk_shape(n_q, pool, v_lanes)
    assert (ppb, group) == (64, 16)

    def fn(q, pool, table, pos, *runs):
        return lpa.latent_paged_attention(
            q, pool, table, pos, v_lanes=v_lanes, scale=0.1,
            interpret=False, runs=runs[0] if runs else None)

    return fn, [((B, n_q, lanes), jnp.bfloat16), (pool.shape, pool.dtype),
                ((B, table), jnp.int32), ((B,), jnp.int32)] + (
        [((B, table // group), jnp.int32)] if flags_in else [])


# deepseek-v3.2.decode-sparse-16k: 36 sequences, the pool and the table the
# bench builds (40384 pages of 16 tokens, 1280 entries a row)
DSA_B, DSA_PAGES, DSA_TABLE = 36, 40384, 1280


def _dsa_scan():
    """The indexer's scan: 64 index heads of 128 over bf16 index pages of
    16 x 128, blocks of 128 pages copied 64 at a time (the run flags an
    operand, as the runner's step passes them)."""
    from paddle_tpu.ops.pallas import sparse_latent_attention as sla

    ipool = jax.ShapeDtypeStruct((DSA_PAGES, PAGE, 128), jnp.bfloat16)
    assert sla.scan_shape(ipool) == (128, 64)

    def fn(q_i, w_i, ipool, table, pos, runs):
        return sla.paged_index_scores(q_i, w_i, ipool, table, pos,
                                      interpret=False, runs=runs)

    return fn, [((DSA_B, 64, 128), jnp.bfloat16), ((DSA_B, 64), jnp.float32),
                (ipool.shape, ipool.dtype), ((DSA_B, DSA_TABLE), jnp.int32),
                ((DSA_B,), jnp.int32), ((DSA_B, DSA_TABLE // 64), jnp.int32)]


def _dsa_attend():
    """Attention over the selection at 128 heads: the latent kernel's walk
    with every block folded under the sequence's row of 20480 scores."""
    from paddle_tpu.ops.pallas import latent_paged_attention as lpa

    def walk(q, pool, table, pos, runs, scores, value, last):
        return lpa.latent_paged_attention(
            q, pool, table, pos, v_lanes=512, scale=0.1, interpret=False,
            runs=runs, select=(scores, value, last))

    return walk, [((DSA_B, 128, 640), jnp.bfloat16),
                  ((DSA_PAGES, PAGE, 640), jnp.bfloat16),
                  ((DSA_B, DSA_TABLE), jnp.int32), ((DSA_B,), jnp.int32),
                  ((DSA_B, DSA_TABLE // 16), jnp.int32),
                  ((DSA_B, DSA_TABLE * PAGE), jnp.float32),
                  ((DSA_B,), jnp.float32), ((DSA_B,), jnp.int32)]


def _dsa_layer(monkeypatch):
    """A sparse layer's whole decode attention as the runner's step calls
    it on a TPU: both page writes, the scan, the selection's two searches
    (no sort), the walk under the selection."""
    from paddle_tpu.serving.runners.deepseek_v3 import _sparse_latent_attend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fn(q, latent, q_i, k_i, w_i, pool, ipool, table, page, off, pos,
           scan_runs, walk_runs):
        out, _ = _sparse_latent_attend(
            q, latent, (q_i, k_i, w_i), (pool, ipool), table, page, off, pos,
            jnp.ones_like(pos), "ragged", scale=0.1, v_lanes=512, topk=2048,
            runs=(scan_runs, walk_runs))
        return out

    bf16, i32 = jnp.bfloat16, jnp.int32
    return fn, [((DSA_B, 1, 128, 640), bf16), ((DSA_B, 1, 640), bf16),
                ((DSA_B, 1, 64, 128), bf16), ((DSA_B, 1, 128), bf16),
                ((DSA_B, 1, 64), jnp.float32),
                ((DSA_PAGES, PAGE, 640), bf16), ((DSA_PAGES, PAGE, 128), bf16),
                ((DSA_B, DSA_TABLE), i32), ((DSA_B, 1), i32),
                ((DSA_B, 1), i32), ((DSA_B,), i32),
                ((DSA_B, DSA_TABLE // 64), i32),
                ((DSA_B, DSA_TABLE // 16), i32)]


def _delta_decode(B, H=30, d_k=96, d_v=192):
    """olmo-hybrid-7b.decode-wide's single-token update of the gated delta
    rule: 64 sequences against a layer's pool of 65 float32 states of 96 x
    5760 (ten heads a grid step), aliased in place; and the batch-1 step
    of the naive_generate oracle."""
    from paddle_tpu.ops.pallas.gated_delta_decode import gated_delta_decode

    def fn(state, q, k, v, g, beta, live):
        return gated_delta_decode(state, q, k, v, g, beta, live,
                                  interpret=False)

    f32 = jnp.float32
    return fn, [((B + 1, d_k, H * d_v), f32), ((B, H, d_k), f32),
                ((B, H, d_k), f32), ((B, H, d_v), f32), ((B, H), f32),
                ((B, H), f32), ((B,), jnp.bool_)]


def _ragged_hybrid(B, T, dtype=jnp.bfloat16):
    """olmo-hybrid-7b.decode-wide's four paged layers: 30 heads of 128
    ALLOCATED as 32 (what the chip copies as whole tiles below 32 bits),
    4160 pages, a table 128 wide; a span attends 128 rows at a time."""
    return _ragged(dtype, B, T, n_q=32, n_kv=32, d=128, pages=4160,
                   table=128)


def _ragged_phi4(B, table, pages, bounded, dtype=jnp.bfloat16):
    """phi-4-mini-flash.reason-12k's attention: 40 query rows of 128 lanes
    (a head padded to its pair's width) over ROW pools of 10 key/value
    pairs, 16 x 10 = 160 rows a page; the whole-context layer's table is
    1024 wide, a window layer's 33 with a lower bound a sequence."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention

    def fn(q, kp, vp, table, start, qlen, *lower):
        return ragged_paged_attention(q, kp, vp, table, start, qlen,
                                      scale=0.125, interpret=False,
                                      lower=lower[0] if lower else None,
                                      kv_heads=10)

    pool = ((pages, PAGE * 10, 128), dtype)
    vec = ((B,), jnp.int32)
    return fn, [((B, 1, 40, 128), jnp.bfloat16), pool, pool,
                ((B, table), jnp.int32), vec, vec] + [vec] * bounded


def _ragged_laguna(B, T, n_q, table, pages, bounded, dtype=jnp.bfloat16):
    """laguna-xs.2.agent-8k's attention: 48 (a full layer) or 64 (a sliding
    layer) query heads of 128 over (k, v) pages of 8 heads; a full layer's
    table is 576 wide over 36928 pages, a sliding layer's ring 33 wide over
    2177 pages with a lower bound a sequence; a decode step (64 rows, T 1)
    and a prefill piece (one sequence, 2048 rows, groups of 6 or 8)."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention

    def fn(q, kp, vp, table, start, qlen, *lower):
        return ragged_paged_attention(q, kp, vp, table, start, qlen,
                                      interpret=False,
                                      lower=lower[0] if lower else None)

    pool = ((pages, PAGE, 8, 128), dtype)
    vec = ((B,), jnp.int32)
    return fn, [((B, T, n_q, 128), jnp.bfloat16), pool, pool,
                ((B, table), jnp.int32), vec, vec] + [vec] * bounded


def _grouped_laguna(rows, block, width_in, width_out, out_dtype=None):
    """laguna-xs.2.agent-8k's expert products as the serving form's walk
    makes them: 256 experts of 2048 x 512 (gate, up) and 512 x 2048 (down,
    float32 out), the layout of a decode step (64 rows: 512 pairs in blocks
    of 16) and of a prefill piece (2048 rows: 16384 pairs in blocks of
    64)."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def fwd(x, w, block_group, n_live):
        return grouped_matmul(x, w, block_group, n_live, block_rows=block,
                              out_dtype=out_dtype, interpret=False)

    bf16 = jnp.bfloat16
    return fwd, [((rows, width_in), bf16), ((256, width_in, width_out), bf16),
                 ((rows // block,), jnp.int32), ((), jnp.int32)]


def _scan_decode(B, n=16, c=5120):
    """The selective scan's decode update at the published widths: B
    sequences against a layer's pool of B + 1 float32 states of 16 x 5120,
    aliased in place."""
    from paddle_tpu.ops.pallas.selective_scan_decode import \
        selective_scan_decode

    def fn(state, x, dt, A, Bm, C, live):
        return selective_scan_decode(state, x, dt, A, Bm, C, live,
                                     interpret=False)

    f32 = jnp.float32
    return fn, [((B + 1, n, c), f32), ((B, c), f32), ((B, c), f32),
                ((n, c), f32), ((B, n), f32), ((B, n), f32),
                ((B,), jnp.bool_)]


# the training cells' attention: gpt2-124m.train's batch of 28, and
# gpt3-1.3b.train-4chip's 8 x 16 heads of 128 (4 x 8 a shard of dp 2 x tp 2)
GPT2_CELL = (28, SEQ, N_HEADS, HEAD_DIM)
GPT3_CELL = (8, SEQ, 16, 128)


def _flash(dtype, backward, mode="dense", shape=None, kv_heads=None):
    """mode: the mask forms chip_smoke's kernel phase validates at batch
    8 — "padbias" (a [b, 1, 1, sk] key-padding mask, streamed as a per-key
    bias) and "segments" (packed-sequence ids) ride per-batch-row vectors
    whose blocks broke the TPU block rule at every batch but 1; "mask" is
    flashmask_attention's dense [1, 1, sq, sk] float32 bias, streamed a
    slab a grid step (the tile rule has to count it), "headmask" one of
    [b, h, sq, sk], a slab a head of the block. `shape`: a training
    cell's real [b, s, h, d]: a tile rule that overflows scoped VMEM has
    to fail here, not on the chip. `kv_heads`: k and v with fewer heads,
    each read in place by its group of query heads."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    b, s, h, d = shape = shape or (BATCH, SEQ, N_HEADS, HEAD_DIM)

    def fwd(q, k, v):
        kw = {}
        if mode == "padbias":
            kw["mask"] = jnp.zeros((b, 1, 1, s), jnp.float32)
        if mode == "segments":
            kw["segment_ids"] = jnp.zeros((b, s), jnp.int32)
        if mode == "mask":
            kw["mask"] = jnp.zeros((1, 1, s, s), jnp.float32)
        if mode == "headmask":
            kw["mask"] = jnp.zeros((b, h, s, s), jnp.float32)
        return flash_attention(q, k, v, causal=mode != "padbias",
                               interpret=False, **kw)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    kv = (b, s, kv_heads or h, d)
    return (fwd_bwd if backward else fwd), [(shape, dtype), (kv, dtype),
                                            (kv, dtype)]


def _grouped(dtype, backward, rows=10240, width=2048, groups=8, block=256):
    """zaya1-8b.train-8k's expert products: the pairs of 8192 tokens in
    blocks of 256 rows (8192 + 8 x 256 of layout), 8 experts of 2048 x
    2048; forward, and with it dx and dw."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def fwd(x, w, block_group, n_live):
        return grouped_matmul(x, w, block_group, n_live, block_rows=block,
                              interpret=False)

    def fwd_bwd(x, w, block_group, n_live):
        return jax.grad(lambda x, w: jnp.sum(fwd(
            x, w, block_group, n_live).astype(jnp.float32) ** 2),
            argnums=(0, 1))(x, w)

    return (fwd_bwd if backward else fwd), [
        ((rows, width), dtype), ((groups, width, width), dtype),
        ((rows // block,), jnp.int32), ((), jnp.int32)]


def _auto_decode_kernel(monkeypatch):
    """Whatever attn_impl="auto" resolves to on a TPU for a GPT-2 decode
    step (q_len bucket 1): the test steers the backend question, the
    runner's dispatch answers it (on a stand-in with GPT-2's head layout —
    building a runner costs seconds and the dispatch reads nothing else)."""
    import types

    from paddle_tpu.serving.model_runner import PagedModelRunner

    gpt2 = types.SimpleNamespace(
        attn_impl="auto", n_heads=N_HEADS, n_kv_heads=N_HEADS,
        head_dim=HEAD_DIM, _impl_logged=set())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    impl = PagedModelRunner._attn_impl_for(gpt2, 1)
    assert impl == "ragged", f"auto on a TPU resolved to {impl!r}"
    return _ragged(jnp.float32, BATCH, 1)


CASES = {
    "ragged-fp32-decode": lambda mp: _ragged(jnp.float32, BATCH, 1),
    "ragged-bf16-decode": lambda mp: _ragged(jnp.bfloat16, BATCH, 1),
    "ragged-fp32-prefill": lambda mp: _ragged(jnp.float32, 1,
                                              PREFILL_BUCKET),
    "ragged-bf16-prefill": lambda mp: _ragged(jnp.bfloat16, 1,
                                              PREFILL_BUCKET),
    "ragged-fp32-longest-prompt": lambda mp: _ragged(jnp.float32, 1, SEQ),
    # the smoke's other shapes: its second prefill bucket, and the
    # batch-1 decode step of the naive_generate oracle
    "ragged-fp32-prefill-256": lambda mp: _ragged(jnp.float32, 1, 256),
    "ragged-bf16-prefill-256": lambda mp: _ragged(jnp.bfloat16, 1, 256),
    "ragged-fp32-decode-b1": lambda mp: _ragged(jnp.float32, 1, 1),
    # the decode cell: 32 slots a step, its three prefill buckets' ends
    "ragged-1p3b-decode-b32": lambda mp: _ragged_1p3b(32, 1),
    "ragged-1p3b-prefill-1024": lambda mp: _ragged_1p3b(1, 1024),
    # few-row spans (speculative verify): 32 rows stack the three terms of
    # P V into one product of 96, 128 rows keep three products
    "ragged-1p3b-verify-span-2-b32": lambda mp: _ragged_1p3b(32, 2),
    "ragged-1p3b-verify-span-8-b32": lambda mp: _ragged_1p3b(32, 8),
    "ragged-1p3b-prefill-256": lambda mp: _ragged_1p3b(1, 256),
    # grouped heads at 32/8 x 128 (ROADMAP R3/R4's shape)
    "ragged-gqa-32-8x128-decode-b32": lambda mp: _ragged_1p3b(32, 1, 32, 8),
    "ragged-gqa-32-8x128-prefill-256": lambda mp: _ragged_1p3b(1, 256, 32,
                                                               8),
    # the latent decode kernel at the Kimi cell's shapes, and the batch-1
    # step of the naive_generate oracle
    "latent-kimi-decode-b48": lambda mp: _latent(48),
    "latent-kimi-decode-b1": lambda mp: _latent(1),
    "latent-kimi-decode-b48-flags-inside": lambda mp: _latent(
        48, flags_in=False),
    # the sparse cell: the indexer's scan, the walk over every live page
    # under the selection, and a layer's attention whole
    "dsa-scan-b36": lambda mp: _dsa_scan(),
    "dsa-masked-walk-b36": lambda mp: _dsa_attend(),
    "dsa-layer-b36": _dsa_layer,
    # the hybrid cell: the delta rule's decode update, and the ragged
    # kernel at 32 allocated heads (a decode step, a span's piece)
    "delta-decode-b64": lambda mp: _delta_decode(64),
    "delta-decode-b1": lambda mp: _delta_decode(1),
    "ragged-hybrid-32x128-decode-b64": lambda mp: _ragged_hybrid(64, 1),
    "ragged-hybrid-32x128-span-128": lambda mp: _ragged_hybrid(1, 128),
    "ragged-hybrid-32x128-fp8-decode-b64": lambda mp: _ragged_hybrid(
        64, 1, jnp.float8_e4m3fn),
    # the Phi-4-mini-flash cell: row pools of 10 pairs, the shared cache's
    # wide table, a window's ring with its lower bound, fp8 pages (the
    # control), the oracle's batch of 1; the scan's decode update
    "ragged-phi4-shared-b48": lambda mp: _ragged_phi4(48, 1024, 49216,
                                                      False),
    "ragged-phi4-window-b48": lambda mp: _ragged_phi4(48, 33, 1585, True),
    "ragged-phi4-window-b1": lambda mp: _ragged_phi4(1, 33, 1585, True),
    "ragged-phi4-shared-fp8-b48": lambda mp: _ragged_phi4(
        48, 1024, 49216, False, jnp.float8_e4m3fn),
    "ragged-phi4-window-fp8-b48": lambda mp: _ragged_phi4(
        48, 33, 1585, True, jnp.float8_e4m3fn),
    # the Laguna cell: one kernel at two head counts over 8 K/V heads, with
    # and without a lower bound, few-rows and prefill layouts (groups of 6
    # and of 8), fp8 pages (the control), the oracle's batch of 1; the
    # expert layer's grouped products at 256 held experts
    "ragged-laguna-full-48-b64": lambda mp: _ragged_laguna(
        64, 1, 48, 576, 36928, False),
    "ragged-laguna-window-64-b64": lambda mp: _ragged_laguna(
        64, 1, 64, 33, 2177, True),
    "ragged-laguna-full-48-b1": lambda mp: _ragged_laguna(
        1, 1, 48, 576, 36928, False),
    "ragged-laguna-window-64-b1": lambda mp: _ragged_laguna(
        1, 1, 64, 33, 2177, True),
    "ragged-laguna-full-48-piece-2048": lambda mp: _ragged_laguna(
        1, 2048, 48, 576, 36928, False),
    "ragged-laguna-group-8-piece-2048": lambda mp: _ragged_laguna(
        1, 2048, 64, 576, 36928, False),
    "ragged-laguna-full-48-fp8-b64": lambda mp: _ragged_laguna(
        64, 1, 48, 576, 36928, False, jnp.float8_e4m3fn),
    "ragged-laguna-window-64-fp8-b64": lambda mp: _ragged_laguna(
        64, 1, 64, 33, 2177, True, jnp.float8_e4m3fn),
    "grouped-laguna-decode-gate": lambda mp: _grouped_laguna(
        4608, 16, 2048, 512),
    "grouped-laguna-decode-down": lambda mp: _grouped_laguna(
        4608, 16, 512, 2048, jnp.float32),
    "grouped-laguna-piece-gate": lambda mp: _grouped_laguna(
        32768, 64, 2048, 512),
    "grouped-laguna-piece-down": lambda mp: _grouped_laguna(
        32768, 64, 512, 2048, jnp.float32),
    "scan-decode-b48": lambda mp: _scan_decode(48),
    "scan-decode-b1": lambda mp: _scan_decode(1),
    "flash-fp32-fwd": lambda mp: _flash(jnp.float32, False),
    "flash-bf16-fwd": lambda mp: _flash(jnp.bfloat16, False),
    "flash-fp32-fwd-bwd": lambda mp: _flash(jnp.float32, True),
    "flash-bf16-fwd-bwd": lambda mp: _flash(jnp.bfloat16, True),
    "flash-fp32-padbias-fwd-bwd": lambda mp: _flash(jnp.float32, True,
                                                    "padbias"),
    "flash-fp32-segments-fwd-bwd": lambda mp: _flash(jnp.float32, True,
                                                     "segments"),
    # the training cells' real shapes, and the two streamed mask forms at
    # 1024 keys
    "flash-bf16-gpt2-cell-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, shape=GPT2_CELL),
    "flash-bf16-gpt3-shard-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, shape=(4, SEQ, 8, 128)),
    "flash-bf16-mask-1024-fwd-bwd": lambda mp: _flash(jnp.bfloat16, True,
                                                      "mask"),
    "flash-bf16-segments-gpt2-cell-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, "segments", shape=GPT2_CELL),
    # a sequence too long for one span: the third grid axis, its clamped
    # index maps and the accumulators carried across its steps
    "flash-bf16-16k-keys-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, shape=(1, 16 * SEQ, 8, 128)),
    # dQ's float32 accumulator for 32768 queries is 16 MiB: the backward
    # stays two kernels, a q walk and a k walk
    "flash-bf16-32k-keys-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, shape=(1, 32 * SEQ, 8, 128)),
    # a sequence under 128 is one tile of its own length, read whole
    "flash-bf16-segments-100-keys-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, "segments", shape=(BATCH, 100, N_HEADS,
                                               HEAD_DIM)),
    "auto-decode-12x64": _auto_decode_kernel,
    # the sparse training cell: its attention in the latent (one sequence
    # of 8192 keys, 8 query heads of 128 on 2 key/value heads, each read
    # where it lies by its four query heads) and its grouped expert products
    "flash-bf16-zaya-cell-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, shape=(1, 8 * SEQ, 8, 128), kv_heads=2),
    # heads that share a 128-lane block: four of 32; two of 64 under a mask
    # a head (a slab each) in float32, where the rule halves the tiles; and
    # a head size that fills no block, on the flat copies
    "flash-bf16-4x32-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, shape=(BATCH, SEQ, 4, 32)),
    "flash-fp32-headmask-fwd-bwd": lambda mp: _flash(
        jnp.float32, True, "headmask", shape=(2, SEQ, 4, HEAD_DIM)),
    "flash-bf16-flat-2x96-fwd-bwd": lambda mp: _flash(
        jnp.bfloat16, True, shape=(BATCH, SEQ, 2, 96)),
    "grouped-bf16-zaya-cell-fwd": lambda mp: _grouped(jnp.bfloat16, False),
    "grouped-bf16-zaya-cell-fwd-bwd": lambda mp: _grouped(jnp.bfloat16,
                                                          True),
    "grouped-fp32-fwd-bwd": lambda mp: _grouped(jnp.float32, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, v5e, monkeypatch):
    fn, shapes = CASES[case](monkeypatch)
    one_chip = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_one_flash_call_is_one_kernel_forward_and_two_backward():
    """What `bench/zaya_trace.py` pins and a `perf_opt` PR may not edit:
    it tells `zaya1-8b.train-8k`'s Mosaic calls apart by count and order,
    `FORWARD = "FGGG"` and `BACKWARD = "GGGGGGFF"`, twelve a layer, and
    `cca_attn_roofline`, `expert_ffn_roofline` and `moe_train_mfu` read
    nothing where a step has another count. So the gradient of ONE flash
    call is exactly two Mosaic calls after the forward's one, both
    attention's: the delta pre-pass as a kernel of its own (not an XLA
    fusion, not folded into the fused kernel), then the fused backward; at
    the cell's own shape, and at the other two cells'."""
    from _helpers import kernel_names
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    for shape, kv_heads in (((1, 8 * SEQ, 8, 128), 2), (GPT2_CELL, N_HEADS),
                            ((4, SEQ, 8, 128), 8)):
        b, s, h, d = shape
        q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((b, s, kv_heads, d), jnp.bfloat16)
        forward = jax.make_jaxpr(lambda *a: flash_attention(
            *a, interpret=False))(q, kv, kv)
        both = jax.make_jaxpr(jax.value_and_grad(
            lambda *a: jnp.sum(flash_attention(*a, interpret=False).astype(
                jnp.float32)), argnums=(0, 1, 2)))(q, kv, kv)
        assert kernel_names(forward.jaxpr) == ["flash_fwd"]
        assert kernel_names(both.jaxpr) == [
            "flash_fwd", "flash_bwd_delta", "flash_bwd"]


# GPT-2's toy batch in float32, and gpt3-1.3b.train-4chip's own attention
# in bfloat16; each device's kernels see its own batch rows and heads where
# they lie, [b, s, h*d]: 4 x 6 of 64, 4 x 8 of 128
MESH_CASES = {
    "toy-fp32": (jnp.float32, None,
                 f"f32[{BATCH // 2},{SEQ},{N_HEADS // 2 * HEAD_DIM}]"),
    "gpt3-cell-bf16": (jnp.bfloat16, GPT3_CELL, f"bf16[4,{SEQ},{8 * 128}]"),
}


@pytest.mark.parametrize("case", sorted(MESH_CASES))
def test_flash_compiles_per_shard_under_a_dp2_tp2_mesh(case, v5e):
    """GSPMD cannot partition a Mosaic kernel: in a program compiled for
    a mesh (jit.TrainStep under parallel.init_mesh, the README's
    hybrid-parallel step) flash attention must reach the compiler inside a
    shard_map, batch over dp, heads over tp — the four-chip path of
    chip_smoke.py --chips 4."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.mesh import program_mesh_scope

    dtype, shape, per_shard = MESH_CASES[case]
    fn, shapes = _flash(dtype, True, shape=shape)
    mesh = Mesh(np.asarray(v5e).reshape(2, 2), ("dp", "tp"))
    spec = NamedSharding(mesh, P("dp", None, "tp", None))
    args = [jax.ShapeDtypeStruct(s, d, sharding=spec) for s, d in shapes]
    with program_mesh_scope(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert per_shard in text


def test_ragged_compiles_per_shard_under_a_model4_mesh(v5e):
    """The tensor-parallel engine's kernel wrapper on serving_mesh(data=1,
    model=4): three of GPT-2's twelve heads per chip. Every mesh axis has
    to be manual — the compiler refuses the kernel while the (size-1)
    data axis is left to GSPMD."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.serving.model_runner import _shard_mapped_kernel

    kernel, shapes = _ragged(jnp.float32, BATCH, 1)
    mesh = Mesh(np.asarray(v5e).reshape(1, 4), ("data", "model"))
    heads = P(None, None, "model", None)
    fn = _shard_mapped_kernel(kernel, (mesh, "model"), heads)
    specs = [heads] * 3 + [P()] * 3
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, sp))
            for (s, d), sp in zip(shapes, specs)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _toy_train_step(tensor_parallel, hidden=128):
    """A `jit.TrainStep` of a two-layer GPT `hidden` wide, built with no
    mesh installed (nothing can be put on a described device), and the
    shapes of what its step takes."""
    import paddle_tpu as paddle
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn

    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=256, hidden_size=hidden, num_layers=2,
                          num_heads=hidden // 128 or 4,
                          ffn_hidden=4 * hidden, max_seq_len=128,
                          tensor_parallel=tensor_parallel))
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-3)
    step = paddle.jit.TrainStep(model, gpt_loss_fn, opt, amp_level="O1")
    tokens = jax.ShapeDtypeStruct((BATCH, 128), jnp.int32)
    scalars = (default_generator.next_key(), jnp.float32(0), jnp.int32(0))
    return step, scalars, tokens


def _placed(tree, sharding_of):
    """`tree` as `ShapeDtypeStruct`s, each leaf placed by
    `sharding_of(path, leaf)`."""
    return jax.tree_util.tree_map_with_path(
        lambda path, v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=sharding_of(path, v)), tree)


def test_train_step_on_a_dp2_tp2_mesh_overlaps_its_all_reduces(v5e):
    """The step `TrainStep._build` makes for a two-layer tensor-parallel
    GPT, compiled for the described 2 x 2 with the options `TrainStep`
    passes for a mesh of TPU devices: what PR 45 reached, by family."""
    import collections

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import parallel as dist
    from paddle_tpu.jit import api
    from paddle_tpu.parallel.debug import collective_forms, collectives
    from paddle_tpu.parallel.mesh import program_mesh_scope

    step, scalars, tokens = _toy_train_step(tensor_parallel=True,
                                            hidden=1024)
    mesh = dist.init_mesh({"dp": 2, "tp": 2}, devices=v5e)
    try:
        assert api._mesh_compiler_options(mesh) == api._MESH_COMPILER_OPTIONS
        step._build()
        specs = step.func.param_shardings()

        def of_param(path, v):      # a moment lies where its parameter does
            spec = specs.get(path[0].key) if v.ndim else None
            return NamedSharding(mesh, spec or P())

        rep = lambda path, v: NamedSharding(mesh, P())
        batch = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                     sharding=NamedSharding(mesh, P("dp")))
        args = (_placed(step.params, of_param), _placed(step.buffers, rep),
                _placed(step.opt_state, of_param), *_placed(scalars, rep),
                (batch, batch))
        plain = jax.jit(step._compiled.__wrapped__, donate_argnums=(0, 1, 2))
        with program_mesh_scope(mesh):
            with_options, without = (
                f.lower(*args).compile().as_text()
                for f in (step._compiled, plain))
    finally:
        dist.set_mesh(None)

    def forms(text):
        return collections.Counter(
            (axes, phase, form)
            for op, axes, phase, form, nbytes in collective_forms(text, mesh)
            if op == "all-reduce" and nbytes >= 4096)

    before, after = forms(without), forms(with_options)
    # the same collectives over the same axes, in the same types and sizes:
    # only when they run differs
    assert collections.Counter(collectives(without, mesh)) == \
        collections.Counter(collectives(with_options, mesh))
    # left alone the compiler overlaps no all-reduce
    assert not [k for k in before if k[2] == "async"], before
    # tp's backward all-reduces (one a column-parallel product, two a
    # layer, and the head's) run under a `dw` product where one is left to
    # run under: 2 of these two layers' 5, 38 of the cell's 49
    assert after[("tp",), "backward", "async"] >= 2
    assert sum(after[("tp",), "backward", f] for f in ("sync", "async")) \
        == before[("tp",), "backward", "sync"]
    # dp's gradients are left as they were: merged into a few ops behind
    # the last layer's backward, which the chip waits at (PERF.md, PR 45:
    # what runs them beside the backward costs the compiler too much)
    assert after[("dp",), "backward", "sync"] >= 1
    assert after[("dp",), "backward", "async"] <= 1
    # the forward's still wait: the next operation needs their sums
    assert after[("tp",), "forward", "sync"] >= 4


def test_one_chip_train_step_is_text_for_text_the_plain_jit(v5e):
    """Without a mesh `TrainStep` passes the compiler nothing: its program
    is the text of a plain `jax.jit` of the same step."""
    from paddle_tpu.jit import api

    assert api._mesh_compiler_options(None) is None
    step, scalars, tokens = _toy_train_step(tensor_parallel=False)
    step._build()
    one_chip = SingleDeviceSharding(v5e[0])
    args = _placed((step.params, step.buffers, step.opt_state, *scalars,
                    (tokens, tokens)), lambda path, v: one_chip)
    plain = jax.jit(step._compiled.__wrapped__, donate_argnums=(0, 1, 2))
    texts = [f.lower(*args).compile().as_text()
             for f in (step._compiled, plain)]
    assert "all-reduce" not in texts[0]
    assert texts[0] == texts[1]
