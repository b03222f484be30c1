"""Flash attention as a Pallas TPU kernel — dense, masked, and varlen.

Reference: the reference wraps the CUDA flashattn library
(paddle/phi/kernels/gpu/flash_attn_kernel.cu over third_party/flashattn,
exposed via nn/functional/flash_attention.py:358, flash_attn_unpadded at
:756 and flashmask_attention at :1299). On TPU the kernel is written in
Pallas: grid (batch*head, q_blocks, k_blocks) with the K axis innermost,
VMEM scratch accumulators (running max / denom / output) carried across K
tiles, fp32 online softmax — only one (block_q, d) Q tile and one
(block_k, d) K/V tile are VMEM-resident per step, so memory is independent
of sequence length and the attention matrix never exists in HBM. MXU does
the two matmuls per tile; the VPU does the softmax algebra.

Masking (four independent mechanisms, composable with `causal`):
  * additive mask — an fp32 [b, 1|h, sq, sk] bias streamed tile-by-tile
    into VMEM and added to the scores (the reference's attn_mask semantic;
    the bias itself is O(s^2) HBM but the score matrix still never
    materializes and the read is fused into the attention loop);
  * kv bias — an fp32 [b, sk] per-KEY additive bias streamed as
    (1, block_k) tiles: the O(s) form of the ubiquitous key-padding mask
    ([b, 1, 1, sk] attn_mask shapes lower here, NOT to a dense O(s^2)
    broadcast), exact additive semantics at every query row;
  * segment ids — int32 [b, sq] / [b, sk] per-token ids; attention is
    allowed only where q_seg == k_seg. This is the varlen/packed form:
    flash_attn_unpadded's cu_seqlens lower onto it with O(s) memory, the
    same design as jax.experimental.pallas.ops.tpu flash attention;
  * bool masks are canonicalized to additive NEG_INF outside the kernel.

Fully-masked rows are well-defined: the online-softmax guard zeroes
probabilities where the score is hard-masked, so such rows produce 0
output and 0 gradient instead of NaN.

Forward and backward are Pallas kernels (FlashAttention-2 style backward:
a dQ kernel accumulating over K tiles and a dK/dV kernel accumulating over
Q tiles, both recomputing P from the saved per-row log-sum-exp).

Layout: [batch, seq, heads, head_dim] (paddle flash-attn convention).
Causal masking is bottom-right aligned (tril k=sk-sq), matching the XLA
reference path for cross-length (KV-decode) shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

try:  # TPU-specific memory spaces (absent on pure-CPU builds)
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# Hard-mask detection threshold for the fully-masked-row guard: scores at
# or below this are treated as "structurally masked" and contribute exactly
# zero probability in both fwd and bwd (real scores never get near -5e29).
MASKED_BELOW = NEG_INF * 0.5
# Per-row stats (lse, delta) ride a small trailing lane dim so their block
# shapes satisfy the Mosaic tiling rule (last dim == array dim); 8 keeps the
# HBM cost at 8 floats/row instead of a full 128-lane broadcast.
LSE_LANES = 8


def _tile_scores(q_ref, k_ref, qi, ki, block_q, block_k, causal, scale,
                 seq_k, seq_q, mask_ref=None, kbias_ref=None, qseg_ref=None,
                 kseg_ref=None):
    """Shared per-tile scaled+masked scores (ONE definition of the causal /
    additive / kv-bias / segment masks for fwd and both bwd kernels)."""
    q = q_ref[0].astype(jnp.float32)
    k_tile = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k_tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask_ref is not None:
        s = s + mask_ref[0].astype(jnp.float32)
    if kbias_ref is not None:
        s = s + kbias_ref[0, 0].astype(jnp.float32)[None, :]
    if qseg_ref is not None:
        qs = qseg_ref[0, 0]
        ks = kseg_ref[0, 0]
        s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
    if causal:
        q_start = (seq_k - seq_q) + qi * block_q
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return q, k_tile, s


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
              block_q, block_k, causal, scale, seq_k, seq_q,
              mask_ref=None, kbias_ref=None, qseg_ref=None, kseg_ref=None):
    """Shared backward tile math: recompute P from lse, form dS."""
    q, k_tile, s = _tile_scores(q_ref, k_ref, qi, ki, block_q, block_k,
                                causal, scale, seq_k, seq_q,
                                mask_ref, kbias_ref, qseg_ref, kseg_ref)
    v_tile = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    # lse/delta are stored value-broadcast over a trailing LSE_LANES dim
    # (Mosaic block rule: last block dim must divide 128 or equal the array
    # dim — a bare (1, block_q) spec is not lowerable); read one lane back.
    lse = lse_ref[0][:, :1].astype(jnp.float32)
    delta = delta_ref[0][:, :1].astype(jnp.float32)
    # hard-masked entries get exactly 0 even on fully-masked rows where the
    # saved lse is itself ~NEG_INF (exp(s - lse) would be exp(0) = 1 there)
    p = jnp.where(s <= MASKED_BELOW, 0.0, jnp.exp(s - lse))
    dp = jax.lax.dot_general(do, v_tile, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return q, k_tile, do, p, ds


def _split_refs(refs, n_lead, has_mask, has_kbias, has_seg,
                has_blockmask=False):
    """Peel (mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest) off a
    flat pallas ref list after the first `n_lead` fixed inputs."""
    i = n_lead
    mask_ref = kbias_ref = qseg_ref = kseg_ref = bm_ref = None
    if has_mask:
        mask_ref = refs[i]
        i += 1
    if has_kbias:
        kbias_ref = refs[i]
        i += 1
    if has_seg:
        qseg_ref, kseg_ref = refs[i], refs[i + 1]
        i += 2
    if has_blockmask:
        bm_ref = refs[i]
        i += 1
    return mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, refs[i:]


def _flash_fwd_kernel(*refs, block_q: int, block_k: int, causal: bool,
                      scale: float, seq_k: int, seq_q: int, has_mask: bool,
                      has_kbias: bool, has_seg: bool, has_blockmask: bool,
                      with_lse: bool):
    """One grid step: fold one K/V tile into this Q block's accumulators."""
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, 3, has_mask, has_kbias, has_seg, has_blockmask)
    if with_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (o_ref, m_ref, l_ref, acc_ref), lse_ref = rest, None
    d = q_ref.shape[-1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros((block_q, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)

    # bottom-right-aligned causal offset: query i sees keys <= i + (sk - sq)
    causal_offset = seq_k - seq_q
    q_start = causal_offset + qi * block_q
    live = (ki * block_k <= q_start + block_q - 1) if causal else True
    if bm_ref is not None:
        # block-sparse: whole (qi, ki) tiles named dead by the block mask
        # skip their matmuls entirely (pl.when guards real FLOPs)
        live = live & (bm_ref[qi, ki] > 0)

    @pl.when(live)
    def _tile():
        _, _, s = _tile_scores(q_ref, k_ref, qi, ki, block_q, block_k,
                               causal, scale, seq_k, seq_q,
                               mask_ref, kbias_ref, qseg_ref, kseg_ref)
        v_tile = v_ref[0].astype(jnp.float32)
        m = m_ref[:]
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        # guard: on a row where every key so far is hard-masked, new_m is
        # still NEG_INF and exp(s - new_m) would be exp(0) = 1 — force 0 so
        # the row's l stays 0 and its output is exactly zero
        p = jnp.where(s <= MASKED_BELOW, 0.0, jnp.exp(s - new_m))
        corr = jnp.exp(m - new_m)
        m_ref[:] = new_m
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp per row, saved for the backward kernels
            # (broadcast across the LSE_LANES lane dim)
            lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))
            lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], LSE_LANES))


def _extra_inputs_specs(mask, kbias, qseg, kseg, h, block_q, block_k,
                        order, block_mask=None):
    """Streamed mask/kv-bias/segment/block-mask inputs + BlockSpecs.

    order 'qk': grid (bh, qi, ki) — fwd and the dQ kernel.
    order 'kq': grid (bh, ki, qi) — the dK/dV kernel.
    mask: [b, 1|h, sq, sk] additive fp32; kbias: [b, sk] additive fp32;
    segs: int32 [b, sq] / [b, sk]; block_mask: int32 [nq, nk] tile
    liveness (0 tiles are skipped — their FLOPs never run)."""
    inputs, specs = [], []
    if mask is not None:
        b, mh, sq, sk = mask.shape
        mf = mask.reshape(b * mh, sq, sk)
        if order == "qk":
            idx = ((lambda bh, qi, ki: (bh, qi, ki)) if mh != 1 else
                   (lambda bh, qi, ki: (bh // h, qi, ki)))
        else:
            idx = ((lambda bh, ki, qi: (bh, qi, ki)) if mh != 1 else
                   (lambda bh, ki, qi: (bh // h, qi, ki)))
        inputs.append(mf)
        specs.append(pl.BlockSpec((1, block_q, block_k), idx))
    # per-key / per-row vectors ride as [b, 1, s] with (1, 1, block)
    # blocks: a bare (1, block) block over [b, s] breaks the TPU block
    # rule (second-to-last block dim 8-aligned or the whole dim) for
    # every batch but 1
    if kbias is not None:
        if order == "qk":
            kbidx = lambda bh, qi, ki: (bh // h, 0, ki)  # noqa: E731
        else:
            kbidx = lambda bh, ki, qi: (bh // h, 0, ki)  # noqa: E731
        inputs.append(kbias.astype(jnp.float32)[:, None])
        specs.append(pl.BlockSpec((1, 1, block_k), kbidx))
    if qseg is not None:
        if order == "qk":
            qidx = lambda bh, qi, ki: (bh // h, 0, qi)   # noqa: E731
            kidx = lambda bh, qi, ki: (bh // h, 0, ki)   # noqa: E731
        else:
            qidx = lambda bh, ki, qi: (bh // h, 0, qi)   # noqa: E731
            kidx = lambda bh, ki, qi: (bh // h, 0, ki)   # noqa: E731
        inputs += [qseg.astype(jnp.int32)[:, None],
                   kseg.astype(jnp.int32)[:, None]]
        specs += [pl.BlockSpec((1, 1, block_q), qidx),
                  pl.BlockSpec((1, 1, block_k), kidx)]
    if block_mask is not None:
        # the whole [n_qblocks, n_kblocks] table rides in VMEM (tiny);
        # every grid step indexes it by (qi, ki)
        nq, nk = block_mask.shape
        inputs.append(block_mask.astype(jnp.int32))
        specs.append(pl.BlockSpec((nq, nk), lambda *_: (0, 0)))
    return inputs, specs


def _flash_forward(q, k, v, mask, kbias, qseg, kseg, block_mask,
                   causal: bool, scale: float, block_q: int, block_k: int,
                   interpret: bool, with_lse: bool = False):
    """q/k/v: [b, s, h, d] -> out [b, s, h, d] (+ lse [b*h, sq, LSE_LANES]
    fp32, value-broadcast across the trailing lane dim)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]

    qf = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kf = jnp.swapaxes(k, 1, 2).reshape(b * h, sk, d)
    vf = jnp.swapaxes(v, 1, 2).reshape(b * h, sk, d)

    grid = (b * h, sq // block_q, sk // block_k)
    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  scale=scale, seq_k=sk, seq_q=sq,
                  has_mask=mask is not None, has_kbias=kbias is not None,
                  has_seg=qseg is not None,
                  has_blockmask=block_mask is not None, with_lse=with_lse)

    scratch = [
        _scratch((block_q, 1)),
        _scratch((block_q, 1)),
        _scratch((block_q, d)),
    ]
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, h, block_q, block_k, "qk",
        block_mask=block_mask)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
    ] + extra_specs
    o_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    if with_lse:
        out, lse = pl.pallas_call(
            functools.partial(_flash_fwd_kernel, **common),
            out_shape=(jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
                       jax.ShapeDtypeStruct((b * h, sq, LSE_LANES),
                                            jnp.float32)),
            grid=grid, in_specs=in_specs,
            out_specs=(o_spec,
                       pl.BlockSpec((1, block_q, LSE_LANES),
                                    lambda bh, qi, ki: (bh, qi, 0))),
            scratch_shapes=scratch, interpret=interpret, name="flash_fwd",
        )(qf, kf, vf, *extra_in)
        return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2), lse
    out = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=grid, in_specs=in_specs, out_specs=o_spec,
        scratch_shapes=scratch, interpret=interpret, name="flash_fwd",
    )(qf, kf, vf, *extra_in)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


def _scratch(shape):
    if pltpu is not None:
        return pltpu.VMEM(shape, jnp.float32)
    return pl.pallas_call  # unreachable on CPU (interpret handles VMEM spec)


def _flash_bwd_dq_kernel(*refs, block_q, block_k, causal, scale, seq_k,
                         seq_q, has_mask, has_kbias, has_seg,
                         has_blockmask):
    """dQ_i = scale * sum_j dS_ij K_j, dS = P * (dO V^T - delta).
    Grid (bh, qi, ki); accumulate over ki in VMEM scratch."""
    q_ref, k_ref, v_ref, do_ref = refs[:4]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, 4, has_mask, has_kbias, has_seg, has_blockmask)
    lse_ref, delta_ref, dq_ref, acc_ref = rest
    d = q_ref.shape[-1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)

    causal_offset = seq_k - seq_q
    q_start = causal_offset + qi * block_q
    live = (ki * block_k <= q_start + block_q - 1) if causal else True
    if bm_ref is not None:
        live = live & (bm_ref[qi, ki] > 0)

    @pl.when(live)
    def _tile():
        _, k_t, _, _, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                     delta_ref, qi, ki, block_q, block_k,
                                     causal, scale, seq_k, seq_q, mask_ref,
                                     kbias_ref, qseg_ref, kseg_ref)
        acc_ref[:] += scale * jax.lax.dot_general(
            ds, k_t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, block_q, block_k, causal, scale, seq_k,
                          seq_q, has_mask, has_kbias, has_seg,
                          has_blockmask):
    """dV_j = P^T dO; dK_j = scale * dS^T Q. Grid (bh, ki, qi); accumulate
    over qi in VMEM scratch."""
    q_ref, k_ref, v_ref, do_ref = refs[:4]
    mask_ref, kbias_ref, qseg_ref, kseg_ref, bm_ref, rest = _split_refs(
        refs, 4, has_mask, has_kbias, has_seg, has_blockmask)
    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    d = q_ref.shape[-1]
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_qb = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc[:] = jnp.zeros((block_k, d), jnp.float32)

    causal_offset = seq_k - seq_q
    q_start = causal_offset + qi * block_q
    # this q block contributes iff its LAST query can see this k tile
    live = (q_start + block_q - 1 >= ki * block_k) if causal else True
    if bm_ref is not None:
        live = live & (bm_ref[qi, ki] > 0)

    @pl.when(live)
    def _tile():
        q, _, do, p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, qi, ki, block_q, block_k,
                                    causal, scale, seq_k, seq_q, mask_ref,
                                    kbias_ref, qseg_ref, kseg_ref)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_qb - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, do, lse, mask, kbias, qseg, kseg,
                    block_mask, causal, scale, block_q, block_k,
                    interpret):
    """Returns (dq, dk, dv) in the [b, s, h, d] layout."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    flat = lambda t, s: jnp.swapaxes(t, 1, 2).reshape(b * h, s, d)
    qf, kf, vf = flat(q, sq), flat(k, sk), flat(v, sk)
    of, dof = flat(o, sq), flat(do, sq)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, XLA fuses it;
    # broadcast over LSE_LANES to match the kernels' per-row-stat layout
    delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (b * h, sq, LSE_LANES))

    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  scale=scale, seq_k=sk, seq_q=sq,
                  has_mask=mask is not None, has_kbias=kbias is not None,
                  has_seg=qseg is not None,
                  has_blockmask=block_mask is not None)

    # ---- dQ: grid (bh, qi, ki) -------------------------------------------
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, h, block_q, block_k, "qk",
        block_mask=block_mask)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        ] + extra_specs + [
            pl.BlockSpec((1, block_q, LSE_LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LSE_LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        scratch_shapes=[_scratch((block_q, d))],
        interpret=interpret, name="flash_bwd_dq",
    )(qf, kf, vf, dof, *extra_in, lse, delta)

    # ---- dK/dV: grid (bh, ki, qi) ----------------------------------------
    extra_in, extra_specs = _extra_inputs_specs(
        mask, kbias, qseg, kseg, h, block_q, block_k, "kq",
        block_mask=block_mask)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        out_shape=(jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk, d), v.dtype)),
        grid=(b * h, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
        ] + extra_specs + [
            pl.BlockSpec((1, block_q, LSE_LANES),
                         lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LSE_LANES),
                         lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ),
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        interpret=interpret, name="flash_bwd_dkv",
    )(qf, kf, vf, dof, *extra_in, lse, delta)

    unflat = lambda t, s: jnp.swapaxes(t.reshape(b, h, s, d), 1, 2)
    return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)


def _reference(q, k, v, causal, scale, mask=None, kbias=None, qseg=None,
               kseg=None):
    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kT = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vT = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) * scale
    if mask is not None:
        s = s + mask.astype(jnp.float32)        # [b, 1|h, sq, sk] additive
    if kbias is not None:
        s = s + kbias.astype(jnp.float32)[:, None, None, :]  # [b, sk]
    if qseg is not None:
        seg_ok = qseg[:, None, :, None] == kseg[:, None, None, :]
        s = jnp.where(seg_ok, s, NEG_INF)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(cm[None, None], s, NEG_INF)
    # match the kernel's fully-masked-row semantics: such rows output 0
    row_live = jnp.any(s > MASKED_BELOW, axis=-1, keepdims=True)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(row_live, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vT)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _zero_cot(x):
    """Zero cotangent matching a primal that the kernel treats as constant
    (mask / segment ids); None passes through, ints get float0."""
    if x is None:
        return None
    if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
        return np.zeros(x.shape, jax.dtypes.float0)
    return jnp.zeros_like(x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def _flash(q, k, v, mask, kbias, qseg, kseg, block_mask, causal, scale,
           block_q, block_k, interpret):
    return _flash_forward(q, k, v, mask, kbias, qseg, kseg, block_mask,
                          causal, scale, block_q, block_k, interpret)


def _flash_fwd(q, k, v, mask, kbias, qseg, kseg, block_mask, causal,
               scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, mask, kbias, qseg, kseg, block_mask,
                              causal, scale, block_q, block_k, interpret,
                              with_lse=True)
    return out, (q, k, v, mask, kbias, qseg, kseg, block_mask, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, mask, kbias, qseg, kseg, block_mask, o, lse = res
    dq, dk, dv = _flash_backward(q, k, v, o, g, lse, mask, kbias, qseg,
                                 kseg, block_mask, causal, scale, block_q,
                                 block_k, interpret)
    return (dq, dk, dv, _zero_cot(mask), _zero_cot(kbias),
            _zero_cot(qseg), _zero_cot(kseg), _zero_cot(block_mask))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _block_shapes_ok(q, k, block_q, block_k, v=None) -> bool:
    b, sq, h, d = q.shape
    sk = k.shape[1]
    # d % 8 == 0: Mosaic pads sub-128 lane dims, so head_dim 64 (the GPT
    # 512/8 flagship and most small/medium models) runs the flash kernel
    # instead of silently falling back to the O(seq^2) XLA path.
    return (sq % block_q == 0 and sk % block_k == 0 and d % 8 == 0
            and q.shape[:1] + q.shape[2:] == k.shape[:1] + k.shape[2:]
            and (v is None or tuple(v.shape) == tuple(k.shape)))


def _canon_mask(mask, b, h, sq, sk):
    """Canonicalize a paddle-style attn_mask. Accepts bool (True = attend,
    reference convention) or additive float, with broadcastable shapes.

    Returns (dense, kbias): key-padding forms [*, *, 1, sk] lower to a
    kbias [b, sk] (O(s) HBM, streamed as (1, block_k) tiles) with dense
    None; anything with a per-query axis becomes dense additive fp32
    [b, 1|h, sq, sk] with kbias None."""
    mask = jnp.asarray(mask)
    if mask.dtype == jnp.bool_:
        mask = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
    if mask.ndim == 2:          # [sq|1, sk]
        mask = mask[None, None]
    elif mask.ndim == 3:        # [b, sq|1, sk]
        mask = mask[:, None]
    if mask.ndim != 4:
        raise ValueError(f"attn mask rank {mask.ndim} not supported")
    if mask.shape[1] == 1 and mask.shape[2] == 1:
        # key-padding form: identical for every query row and head — do
        # NOT broadcast to O(s^2); stream as a per-key bias instead
        kbias = jnp.broadcast_to(mask[:, 0, 0, :].astype(jnp.float32),
                                 (b, sk))
        return None, kbias
    mh = 1 if mask.shape[1] == 1 else h
    return jnp.broadcast_to(mask.astype(jnp.float32),
                            (b, mh, sq, sk)), None


def _canon_segments(segment_ids, b, sq, sk):
    """segment_ids: int [b, s] (self-attention) or a (q_seg, kv_seg) pair;
    returns int32 ([b, sq], [b, sk])."""
    if isinstance(segment_ids, (tuple, list)):
        qseg, kseg = segment_ids
    else:
        qseg = kseg = segment_ids
    qseg = jnp.asarray(qseg, jnp.int32)
    kseg = jnp.asarray(kseg, jnp.int32)
    if qseg.shape != (b, sq) or kseg.shape != (b, sk):
        raise ValueError(
            f"segment_ids shapes {qseg.shape}/{kseg.shape} don't match "
            f"q/kv sequences ({b},{sq})/({b},{sk})")
    return qseg, kseg


DEFAULT_CHECK_SHAPES = ((1, 256, 4, 64), (2, 512, 8, 64), (1, 256, 4, 128))


def validate_against_reference(shapes=DEFAULT_CHECK_SHAPES, interpret=None,
                               tol_out=None, tol_grad=None, seed=0):
    """Run the Pallas kernels (fwd + bwd) against the XLA reference path and
    return {"max_abs_err", "shapes": [[b,s,h,d,mode,err_o,err_g],...],
    "pass"} — each shapes row carries 7 elements, with the attention mode
    string at index 4 (one of "dense", "densemask", "padbias", "segments",
    matching the case list built below).

    Covers the dense-causal, additive-padding-mask, and segment-id (varlen)
    paths. Single source of truth for the kernel-vs-reference criterion —
    used by both the bench ladder's on-hardware check and the TPU pytest
    tier, so the two can't drift apart."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Interpret mode computes dots in true fp32 — hold it to tight bounds.
    # On the MXU, fp32 dots run as bf16 multi-pass (default precision), so
    # both the kernel and the XLA reference carry ~2^-8 relative rounding;
    # the comparison bound must absorb it.
    if tol_out is None:
        tol_out = 2e-3 if interpret else 2e-2
    if tol_grad is None:
        tol_grad = 5e-2 if interpret else 1e-1
    rng = np.random.default_rng(seed)
    worst = 0.0
    checked = []
    ok = True
    # (shape, mode): dense causal for every shape, plus a dense-mask, a
    # kv-bias (padding) and a packed-segment case on the first shape
    cases = [(sh, "dense") for sh in shapes]
    cases += [(shapes[0], "densemask"), (shapes[0], "padbias"),
              (shapes[0], "segments")]
    for (b, s, h, d), mode in cases:
        q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)),
                               jnp.float32) for _ in range(3))
        scale = 1.0 / math.sqrt(d)
        mask = kbias = segs = None
        causal = mode not in ("densemask", "padbias")
        valid = jnp.arange(s) < (3 * s) // 4   # last quarter = padding
        if mode == "densemask":
            mask = jnp.broadcast_to(
                jnp.where(valid, 0.0, NEG_INF)[None, None, None, :],
                (b, 1, s, s)).astype(jnp.float32)
        elif mode == "padbias":
            # the O(s) key-padding form (ERNIE-style [b,1,1,sk] lowering)
            kbias = jnp.broadcast_to(
                jnp.where(valid, 0.0, NEG_INF)[None, :], (b, s)
            ).astype(jnp.float32)
        elif mode == "segments":
            segs = jnp.broadcast_to((jnp.arange(s) * 4) // s, (b, s)
                                    ).astype(jnp.int32)

        def f_f(q, k, v, mask=mask, kbias=kbias, segs=segs, causal=causal,
                scale=scale):
            qs, ks = (segs, segs) if segs is not None else (None, None)
            return _flash(q, k, v, mask, kbias, qs, ks, None, causal,
                          scale, 128, 128, interpret)

        def f_r(q, k, v, mask=mask, kbias=kbias, segs=segs, causal=causal,
                scale=scale):
            return _reference(q, k, v, causal, scale, mask=mask,
                              kbias=kbias, qseg=segs, kseg=segs)

        def out_and_grads(f, q, k, v):
            out, vjp = jax.vjp(f, q, k, v)
            return out, vjp(2.0 * out)      # d sum(o^2) / d (q, k, v)

        o_f, g_f = jax.jit(functools.partial(out_and_grads, f_f))(q, k, v)
        o_r, g_r = jax.jit(functools.partial(out_and_grads, f_r))(q, k, v)
        err_o = float(jnp.max(jnp.abs(o_f - o_r)))
        err_g = max(float(jnp.max(jnp.abs(x - y)))
                    for x, y in zip(g_f, g_r))
        worst = max(worst, err_o, err_g)
        ok = ok and err_o < tol_out and err_g < tol_grad
        checked.append([b, s, h, d, mode, err_o, err_g])
    return {"max_abs_err": worst, "shapes": checked, "pass": ok,
            "interpret": interpret}


_FALLBACK_WARNED: set = set()


def _log_fallback(q, k, block_q, block_k):
    """The silent-fallback condition is a dead-kernel bug magnet — warn once
    per shape so it is visible which configs miss the flash path."""
    key = (tuple(q.shape), tuple(k.shape), block_q, block_k)
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        import warnings

        warnings.warn(
            f"flash_attention: shapes q={tuple(q.shape)} k={tuple(k.shape)} "
            f"don't tile (block_q={block_q}, block_k={block_k}); using the "
            "O(seq^2) XLA reference path", stacklevel=3)


def flash_attention(q, k, v, causal: bool = True, scale=None,
                    mask=None, segment_ids=None, block_mask=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None):
    """Pallas flash attention with automatic fallback to the XLA reference
    when shapes don't tile (same dispatch pattern as the reference's
    sdp_kernel selection, nn/functional/flash_attention.py).

    mask: additive float or bool (True=attend) attn mask, broadcastable to
    [b, 1|h, sq, sk] — streamed tile-wise into the kernel; key-padding
    forms ([*, *, 1, sk]) are lowered to an O(s) per-key bias.
    segment_ids: int [b, s] or (q_seg [b, sq], kv_seg [b, sk]) — varlen /
    packed-sequence masking with O(s) memory (attend iff ids equal).
    block_mask: int/bool [sq//block_q, sk//block_k] tile liveness —
    dead tiles' FLOPs are skipped entirely (block-sparse attention). The
    block mask must be IMPLIED by the elementwise masks (a tile marked
    dead must already be fully masked by mask/segments/causal), otherwise
    results diverge from the dense computation; callers like
    sparse_attention derive both from the same pattern."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    kbias = None
    if mask is not None:
        mask, kbias = _canon_mask(mask, b, h, sq, sk)
    qseg = kseg = None
    if segment_ids is not None:
        qseg, kseg = _canon_segments(segment_ids, b, sq, sk)
    if block_mask is not None:
        block_mask = jnp.asarray(block_mask, jnp.int32)
        if block_mask.shape != (sq // block_q, sk // block_k):
            raise ValueError(
                f"block_mask {block_mask.shape} != tile grid "
                f"({sq // block_q}, {sk // block_k})")
    if causal and sq > sk:
        # bottom-right alignment gives early queries ZERO visible keys —
        # handled by the masked-row guard, but parity with the XLA path is
        # simplest via the reference for this rare decode shape
        _log_fallback(q, k, block_q, block_k)
        return _reference(q, k, v, causal, scale, mask, kbias, qseg, kseg)
    if not _block_shapes_ok(q, k, block_q, block_k, v=v):
        _log_fallback(q, k, block_q, block_k)
        return _reference(q, k, v, causal, scale, mask, kbias, qseg, kseg)
    statics = (causal, scale, block_q, block_k, interpret)
    placed = _program_mesh_axes(b, h)
    if placed is None:
        return _flash(q, k, v, mask, kbias, qseg, kseg, block_mask,
                      *statics)
    # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    # shard_map"): in a program compiled for a mesh each device runs the
    # kernel on its own batch rows ('dp') and heads ('tp'), every mesh
    # axis manual
    mesh, bax, hax = placed
    qkv, row = P(bax, None, hax, None), P(bax, None)
    extras = {n: x for n, x in (("mask", mask), ("kbias", kbias),
                                ("qseg", qseg), ("kseg", kseg),
                                ("block_mask", block_mask)) if x is not None}
    specs = {"kbias": row, "qseg": row, "kseg": row, "block_mask": P()}
    if mask is not None:
        specs["mask"] = P(bax, hax if mask.shape[1] == h else None,
                          None, None)

    def per_shard(q, k, v, ex):
        return _flash(q, k, v, ex.get("mask"), ex.get("kbias"),
                      ex.get("qseg"), ex.get("kseg"), ex.get("block_mask"),
                      *statics)

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(qkv, qkv, qkv, {n: specs[n] for n in extras}),
        out_specs=qkv, check_vma=False,
    )(q, k, v, extras)


def _program_mesh_axes(b: int, h: int):
    """(mesh, batch axis, head axis) when the program being traced computes
    on a multi-device mesh (parallel.mesh.program_mesh) and the call is
    not already inside a manual region; None otherwise. An axis is named
    only where it divides the dimension; unnamed axes compute replicated."""
    from paddle_tpu.parallel.mesh import program_mesh

    mesh = program_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None

    def pick(axis, n):
        return (axis if axis in mesh.axis_names
                and n % mesh.shape[axis] == 0 else None)

    return mesh, pick("dp", b), pick("tp", h)
