"""DeepseekV3Runner: models.DeepseekV3ForCausalLM (DeepSeek-V3, Kimi K2
and, with an indexer, DeepSeek-V3.2) served through the paged chassis, over
latent pages; the two attends over those pages are here with it."""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import deepseek_v3 as _dsv3
from paddle_tpu.serving.model_runner import SCALE_SUFFIX, PagedModelRunner

logger = logging.getLogger(__name__)


def _latent_attend(q, latent_new, layer_pools, tables, write_page,
                   write_off, pos_q, q_len, impl: str, scale: float,
                   v_lanes: int, runs=None):
    """The page write and attend of a LATENT layer: one array a page,
    `[num_blocks, page, lanes]`, each token's row its compressed key whose
    first `v_lanes` lanes are also its value, shared by every query head (the
    absorbed form of latent attention; models/deepseek_v3.py). q: [B, T,
    n_h, lanes]; latent_new: [B, T, lanes]. Returns ([B, T, n_h,
    v_lanes], (pool,)): the per-head sums of p . value, which the caller
    takes through its value projection. "ragged" is the kernel over
    latent pages (a decode step: T == 1; `runs` its flags of which
    groups of the table are consecutive pages, where the caller's layers
    share one table), "reference" the gather path for any span."""
    (pool,) = layer_pools
    pool = pool.at[write_page, write_off].set(latent_new.astype(pool.dtype))
    B, T = q.shape[0], q.shape[1]
    if impl == "ragged":
        from paddle_tpu.ops.pallas.latent_paged_attention import \
            latent_paged_attention

        if T != 1:
            raise ValueError(f"the latent kernel is a decode kernel; span "
                             f"of {T} rows")
        out = latent_paged_attention(q[:, 0], pool, tables, pos_q,
                                     v_lanes=v_lanes, scale=scale, runs=runs)
        return out[:, None], (pool,)
    lat = pool[tables].reshape(B, -1, pool.shape[-1])           # [B, L, lanes]
    s = jnp.einsum("bthc,blc->bhtl", q, lat,
                   preferred_element_type=jnp.float32) * scale
    t_idx = jnp.arange(T, dtype=jnp.int32)
    visible = ((jnp.arange(lat.shape[1], dtype=jnp.int32)[None, None, :]
                <= pos_q[:, None, None] + t_idx[None, :, None])
               & (t_idx[None, :, None] < q_len[:, None, None]))  # [B, T, L]
    p = jax.nn.softmax(jnp.where(visible[:, None], s, -1e30), axis=-1)
    out = jnp.einsum("bhtl,blc->bthc", p.astype(lat.dtype),
                     lat[..., :v_lanes])
    return out.astype(q.dtype), (pool,)


def _sparse_latent_attend(q, latent_new, index, layer_pools, tables,
                          write_page, write_off, pos_q, q_len, impl: str,
                          scale: float, v_lanes: int, topk: int, runs=None):
    """The page write and attend of a latent layer under a learned
    selection (DeepSeek Sparse Attention): TWO arrays a page behind one
    table, the latent rows and the indexer's keys `[num_blocks, page, index
    lanes]`. `index` is
    the indexer's view of the new tokens (models/deepseek_v3.index_project,
    padded to the page's lanes): queries [B, T, heads, lanes], the tokens'
    keys [B, T, lanes], the heads' weights [B, T, heads] float32. Each
    query row scores the index keys of its context, keeps the `topk` best
    (ties to the lower position) and attends over those rows alone.
    "ragged" is a decode step on the chip: the scan kernel over index
    pages, then the latent kernel's walk over every live page with each
    block folded under the selection (`topk_threshold`: exact, no list of
    rows is made; ops/pallas/sparse_latent_attention.py says why the walk
    and not a fetch by row). `runs`: (the scan's, the walk's) flags of
    consecutive pages. "reference" is the gather path for any span. Returns ([B, T,
    n_h, v_lanes], (pool, index pool))."""
    from paddle_tpu.ops.pallas import sparse_latent_attention as sla

    pool, ipool = layer_pools
    q_i, k_i, w_i = index
    pool = pool.at[write_page, write_off].set(latent_new.astype(pool.dtype))
    ipool = ipool.at[write_page, write_off].set(k_i.astype(ipool.dtype))
    B, T = q.shape[0], q.shape[1]
    if impl == "ragged":
        if T != 1:
            raise ValueError(f"the sparse latent kernels are decode "
                             f"kernels; span of {T} rows")
        scan_runs, walk_runs = runs if runs is not None else (None, None)
        with jax.named_scope("block/dsa/index"):
            scores = sla.paged_index_scores(q_i[:, 0], w_i[:, 0], ipool,
                                            tables, pos_q, runs=scan_runs)
        keys = scores.shape[1]
        if keys <= topk:                 # every visible key is chosen
            with jax.named_scope("block/dsa/attend"):
                out = sla.latent_paged_attention(
                    q[:, 0], pool, tables, pos_q, v_lanes=v_lanes,
                    scale=scale, runs=walk_runs)
        else:
            with jax.named_scope("block/dsa/select"):
                value, last = _dsv3.topk_threshold(scores, topk)
            with jax.named_scope("block/dsa/attend"):
                out = sla.latent_paged_attention(
                    q[:, 0], pool, tables, pos_q, v_lanes=v_lanes,
                    scale=scale, runs=walk_runs,
                    select=(scores, value, last))
        return out[:, None], (pool, ipool)
    L = tables.shape[1] * pool.shape[1]
    t_idx = jnp.arange(T, dtype=jnp.int32)
    visible = ((jnp.arange(L, dtype=jnp.int32)[None, None, :]
                <= pos_q[:, None, None] + t_idx[None, :, None])
               & (t_idx[None, :, None] < q_len[:, None, None]))  # [B, T, L]
    with jax.named_scope("block/dsa/index"):
        scores = jax.vmap(_dsv3.index_scores)(
            q_i, w_i, ipool[tables].reshape(B, L, ipool.shape[-1]))
    with jax.named_scope("block/dsa/select"):
        chosen = visible & _dsv3.topk_mask(
            jnp.where(visible, scores, -jnp.inf).reshape(B * T, L), topk
        ).reshape(B, T, L)
    with jax.named_scope("block/dsa/attend"):
        lat = pool[tables].reshape(B, L, pool.shape[-1])
        s = jnp.einsum("bthc,blc->bhtl", q, lat,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(chosen[:, None], s, -1e30), axis=-1)
        out = jnp.einsum("bhtl,blc->bthc", p.astype(lat.dtype),
                         lat[..., :v_lanes])
    return out.astype(q.dtype), (pool, ipool)


class DeepseekV3Runner(PagedModelRunner):
    """Paged-step adapter for models.DeepseekV3ForCausalLM: latent
    attention over LATENT pages and a routed + shared expert layer, one
    rank's share of it (models/deepseek_v3.py has the equations and the
    functions; this class is their paging).

    A layer's cache is ONE array a page, `[num_blocks, page, lanes]`:
    per token c_kv | k_r (`cfg.latent_dim` values), allocated with its
    lanes rounded up to whole 128-lane tiles because the chip copies a
    page only as whole tiles (576 -> 640; PERF.md). A configuration with
    an indexer (`cfg.index_topk`: DeepSeek-V3.2) names a SECOND array a
    page behind the same table, the indexer's key of each token, and
    every query row attends over the `index_topk` keys it scored best
    (`_sparse_latent_attend`; a prompt's span in the expanded form under
    `selection_mask`, its heads a group at a time); it counts the keys
    scored and kept beside the rest. Two attention paths
    from one set of weights, chosen from shapes: ONE sequence's span of
    several rows (a prefill bucket, a chunk) runs the EXPANDED form
    (per-head keys and values rebuilt from the table's latent rows,
    blocked over query and key rows), anything else the ABSORBED form
    through `_latent_attend`: the latent decode kernel where `attn_impl`
    resolves to "ragged" (a decode step on a TPU), the gather path
    elsewhere. `weight_dtype="int8"` / "fp8" convert the dense matrices
    (the experts and the router stay floating); latent pages come in the
    stated dtype only. The expert layers count (tokens routed, pairs
    computed here, held experts touched) and so does the latent kernel's
    walk (groups of pages copied, those copied as one run): an output of
    every single-pass step, handed to `on_step_counts`."""

    COUNTS = ("moe_tokens_routed", "moe_local_pairs", "moe_experts_touched",
              "latent_copy_groups", "latent_run_groups")
    # what a runner with an indexer counts besides
    SPARSE_COUNTS = ("dsa_keys_scored", "dsa_keys_selected")
    HEAD_ROWS = True

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        if quant.get("kv_dtype", "fp32") != "fp32":
            raise ValueError(
                f"kv_dtype={quant['kv_dtype']!r}: latent pages come in the "
                "model's stated dtype only (no quantized rung for them yet)")
        if quant.get("weight_dtype") == "int4":
            raise ValueError("weight_dtype='int4' is not wired for the "
                             "latent-attention runner (int8 and fp8 are)")
        params = functionalize(model).param_values()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.n_heads = cfg.num_attention_heads
        self.vocab_size = cfg.vocab_size
        # a page's lanes: the latent row in whole 128-lane tiles
        self.page_lanes = -(-cfg.latent_dim // 128) * 128
        self.sparse = cfg.index_topk is not None
        if self.sparse:
            self.index_lanes = -(-cfg.index_head_dim // 128) * 128
            self.COUNTS = self.COUNTS + self.SPARSE_COUNTS
        self._rope_cos, self._rope_sin = _dsv3.rope_tables(
            cfg, self.max_model_len)                   # [L, rope] fp32
        self._scale = _dsv3.softmax_scale(cfg)
        if self.weight_dtype != "fp32":
            names = ["lm_head.weight"]
            for i in range(self.num_layers):
                pre = f"layers.{i}."
                names += [pre + "self_attn." + n + ".weight" for n in (
                    "q_a_proj", "q_b_proj", "kv_a_proj_with_mqa",
                    "kv_b_proj", "o_proj") + (
                        ("indexer.wq_b", "indexer.wk",
                         "indexer.weights_proj") if self.sparse else ())]
                mlp = pre + ("mlp." if cfg.is_dense(i)
                             else "mlp.shared_experts.")
                names += [mlp + n + ".weight" for n in (
                    "gate_proj", "up_proj", "down_proj")]
            self._quantize_weights(names)

    def page_layout(self):
        layout = [((self.page_lanes,), self.dtype)]
        if self.sparse:
            layout.append(((self.index_lanes,), self.dtype))
        return layout

    def _param_specs(self, layout):
        raise NotImplementedError(
            "DeepseekV3Runner serves one chip's share; exchanging experts "
            "and splitting latent pages over a mesh is not built")

    def _attn_impl_for(self, q_len_bucket: int) -> str:
        """The ABSORBED paths: the latent kernel for a decode step where
        a kernel is wanted ("auto" on a TPU, or "ragged": interpret mode
        off it), else the gather reference. (One sequence's longer span
        takes the expanded form whatever this says: `_forward`.)"""
        want_kernel = (self.attn_impl == "ragged"
                       or (self.attn_impl == "auto"
                           and jax.default_backend() == "tpu"))
        impl = "ragged" if want_kernel and q_len_bucket == 1 else "reference"
        key = (q_len_bucket, impl)
        if key not in self._impl_logged:
            self._impl_logged.add(key)
            logger.info("serving attention impl: latent %s (q_len bucket "
                        "%d, %d heads over %d lanes, attn_impl=%s)", impl,
                        q_len_bucket, self.n_heads, self.page_lanes,
                        self.attn_impl)
        return impl

    def _kv_page_bytes(self) -> int:
        """A page's bytes in every layer; under a selection both its arrays
        (the scan reads every live page's index keys, the walk its latent
        rows: each block is folded under the selection, none is skipped)."""
        lanes = self.page_lanes + (self.index_lanes if self.sparse else 0)
        return (self.num_layers * self.block_size * lanes
                * np.dtype(self.dtype).itemsize)

    def _fold_block_pages(self, span: int) -> int:
        return 0        # the latent kernel's walk, not the ragged one's

    def _w(self, params, name):
        """A named matrix as its floating self (dequantized where
        `_quantize_weights` converted it): the absorbed form multiplies
        by slices of kv_b_proj, not by the whole of it."""
        w, s = params[name], params.get(name + SCALE_SUFFIX)
        dt = params["embed_tokens.weight"].dtype
        return w.astype(dt) if s is None else w.astype(dt) * s.astype(dt)

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools, head_rows=None):
        cfg, m = self.cfg, _dsv3
        B, T = tokens.shape
        lanes, nh = self.page_lanes, self.n_heads
        impl = self._attn_impl_for(T)
        expanded = B == 1 and T > 1
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        cos = jnp.take(self._rope_cos, positions, axis=0)      # [B, T, rope]
        sin = jnp.take(self._rope_sin, positions, axis=0)
        valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < q_lens[:, None]).reshape(B * T)
        experts = jnp.zeros((3,), jnp.int32)
        walked = jnp.zeros((2,), jnp.int32)
        runs = None
        if impl == "ragged" and not expanded:
            # which groups of the table are runs of consecutive pages: the
            # layers share one table, so once for the step's program
            from paddle_tpu.ops.pallas import latent_paged_attention as lpa

            pool = pools[0][0]
            _, group = lpa.walk_shape(nh, pool, cfg.kv_lora_rank)
            runs = lpa.page_runs(tables, group)
            walked = cfg.num_hidden_layers * lpa.walked_groups(
                runs, pos_q, self.block_size, group, tables.shape[1])
            if self.sparse:
                # the scan over index pages walks in groups of its own
                from paddle_tpu.ops.pallas.sparse_latent_attention import \
                    scan_shape

                runs = (lpa.page_runs(tables, scan_shape(pools[0][1])[1]),
                        runs)
        new_pools = []
        for i in range(cfg.num_hidden_layers):
            pre = f"layers.{i}."
            if self.sparse:
                x, layer = self._sparse_attention(
                    params, pre, x, cos, sin, pools[i], tables, write_page,
                    write_off, pos_q, q_lens, impl, expanded, runs)
            else:
                with jax.named_scope("block/mla"):
                    h = m.rms_norm(x, params[pre + "input_layernorm.weight"],
                                   cfg.rms_norm_eps)
                    qn, qr, lat, _ = m.mla_project(cfg, params, pre, h, cos,
                                                   sin, mm=self._mm)
                    lat = jnp.pad(lat, ((0, 0), (0, 0),
                                        (0, lanes - cfg.latent_dim)))
                    w_kvb = self._w(params,
                                    pre + "self_attn.kv_b_proj.weight")
                    if expanded:
                        (pool,) = pools[i]
                        pool = pool.at[write_page, write_off].set(
                            lat.astype(pool.dtype))
                        o = m.expanded_attention(
                            cfg, qn[0], qr[0],
                            pool[tables[0]].reshape(-1, lanes), w_kvb,
                            pos_q[0], q_lens[0])[None]
                        layer = (pool,)
                    else:
                        o, layer = _latent_attend(
                            m.absorb_queries(cfg, qn, qr, w_kvb, lanes), lat,
                            pools[i], tables, write_page, write_off, pos_q,
                            q_lens, impl, self._scale, cfg.kv_lora_rank,
                            runs)
                        o = m.absorb_outputs(cfg, o, w_kvb)
                    x = x + self._mm(params, pre + "self_attn.o_proj.weight",
                                     o)
            h = m.rms_norm(x, params[pre + "post_attention_layernorm.weight"],
                           cfg.rms_norm_eps).reshape(B * T, -1)
            if cfg.is_dense(i):
                with jax.named_scope("block/mlp"):
                    f = m.dense_ffn(params, pre + "mlp.", h, self._mm)
            else:
                f, c = m.moe_ffn(cfg, params, pre + "mlp.", h, valid,
                                 self._mm)
                experts = experts + c
            x = x + f.reshape(B, T, -1)
            new_pools.append(layer)
        with jax.named_scope("final_norm"):
            x = m.rms_norm(x, params["norm.weight"], cfg.rms_norm_eps)
            if head_rows is not None:
                x = jnp.take_along_axis(x, head_rows[:, None, None], axis=1)
        with jax.named_scope("lm_head"):
            logits = self._mm(params, "lm_head.weight", x)
        counts = [experts, walked]
        if self.sparse:
            # every live query row scored its context and kept the best
            t_idx = jnp.arange(T, dtype=jnp.int32)[None, :]
            context = jnp.where(t_idx < q_lens[:, None],
                                pos_q[:, None] + t_idx + 1, 0)
            counts.append(cfg.num_hidden_layers * jnp.stack(
                [jnp.sum(context),
                 jnp.sum(jnp.minimum(context, cfg.index_topk))]))
        return logits, new_pools, jnp.concatenate(counts)

    def _sparse_attention(self, params, pre, x, cos, sin, layer_pools,
                          tables, write_page, write_off, pos_q, q_lens, impl,
                          expanded, runs):
        """One layer's attention under the indexer's selection, residual
        added: (x, the layer's (latent pool, index pool))."""
        cfg, m = self.cfg, _dsv3
        lanes = self.page_lanes
        pad = lambda a, n: jnp.pad(
            a, ((0, 0),) * (a.ndim - 1) + ((0, n - a.shape[-1]),))
        with jax.named_scope("block/mla"):
            h = m.rms_norm(x, params[pre + "input_layernorm.weight"],
                           cfg.rms_norm_eps)
            # (a prompt's span makes its queries a group of heads at a
            # time and leaves these to the compiler's dead-code pass)
            qn, qr, lat, c_q = m.mla_project(cfg, params, pre, h, cos, sin,
                                             mm=self._mm)
            lat = pad(lat, lanes)
            w_kvb = self._w(params, pre + "self_attn.kv_b_proj.weight")
        with jax.named_scope("block/dsa/index"):
            q_i, k_i, w_i = m.index_project(cfg, params, pre, h, c_q, cos,
                                            sin, mm=self._mm)
            q_i, k_i = pad(q_i, self.index_lanes), pad(k_i, self.index_lanes)
        if expanded:
            pool, ipool = layer_pools
            pool = pool.at[write_page, write_off].set(lat.astype(pool.dtype))
            ipool = ipool.at[write_page, write_off].set(
                k_i.astype(ipool.dtype))
            with jax.named_scope("block/dsa/select"):
                chosen = m.selection_mask(
                    cfg, q_i[0], w_i[0],
                    ipool[tables[0]].reshape(-1, self.index_lanes),
                    pos_q[0], q_lens[0])
            with jax.named_scope("block/dsa/attend"):
                o = m.sparse_expanded_attention(
                    cfg, c_q[0], cos[0], sin[0],
                    pool[tables[0]].reshape(-1, lanes), chosen,
                    self._w(params, pre + "self_attn.q_b_proj.weight"),
                    w_kvb, self._w(params, pre + "self_attn.o_proj.weight"),
                    pos_q[0], q_lens[0])[None]
            return x + o, (pool, ipool)
        o, layer = _sparse_latent_attend(
            m.absorb_queries(cfg, qn, qr, w_kvb, lanes), lat,
            (q_i, k_i, w_i), layer_pools, tables, write_page, write_off,
            pos_q, q_lens, impl, self._scale, cfg.kv_lora_rank,
            cfg.index_topk, runs)
        with jax.named_scope("block/mla"):
            o = m.absorb_outputs(cfg, o, w_kvb)
            return x + self._mm(params, pre + "self_attn.o_proj.weight",
                                o), layer
