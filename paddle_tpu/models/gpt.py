"""Flagship model: GPT/ERNIE-style decoder-only transformer.

Reference model family: the fleet GPT-3 hybrid-parallel config
(BASELINE.json configs[3]) and PaddleNLP-style GPT built from paddle.nn
layers + fleet mpu layers (SURVEY.md §2.10).

TPU-native parallelism in ONE model definition:
  - dp  : batch dim sharded (input constraint; DataParallel wrapper)
  - tp  : Column/RowParallelLinear + VocabParallelEmbedding param shardings;
          GSPMD inserts the collectives
  - sp  : Megatron sequence parallelism — activations outside the matmul
          pairs sharded on seq over 'tp'
  - ep  : optional switch-MoE FFN blocks, experts sharded over 'ep'
  - pp  : via parallel.pipeline.pipeline_apply (stacked stage params +
          ppermute rotation); see gpt_pipeline_train_step below
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu import profiler as _prof
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer, LayerList
from paddle_tpu.nn.layers import Dropout, Embedding, LayerNorm, Linear
from paddle_tpu.parallel.api import sharding_constraint
from paddle_tpu.parallel.mesh import current_mesh
from paddle_tpu.parallel.moe import MoELayer
from paddle_tpu.parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: Optional[int] = None
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: str = "float32"
    tensor_parallel: bool = False      # use mpu layers + tp shardings
    sequence_parallel: bool = False    # Megatron SP activation sharding
    moe_every: int = 0                 # every k-th block uses MoE FFN (0=off)
    moe_experts: int = 8
    tie_embeddings: bool = True

    def __post_init__(self):
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        w_in = I.Normal(0.0, 0.02)
        w_out = I.Normal(0.0, 0.02 / math.sqrt(2 * cfg.num_layers))
        if cfg.tensor_parallel:
            self.qkv = ColumnParallelLinear(h, 3 * h, weight_attr=w_in,
                                            gather_output=False)
            self.out = RowParallelLinear(h, h, weight_attr=w_out,
                                         input_is_parallel=True)
        else:
            self.qkv = Linear(h, 3 * h, weight_attr=w_in)
            self.out = Linear(h, h, weight_attr=w_out)
        self.drop = Dropout(cfg.dropout)

    def forward(self, x):
        b, s, h = x.shape
        qkv = self.qkv(x)
        # columns first, heads after: a [.., heads, 64] array between the
        # projection and the kernels costs a transposed copy each way (the
        # chip tiles the last two dimensions), a slice of whole columns of
        # [b, s, 3h] is read as it lies
        q, k, v = (qkv[:, :, i * h:(i + 1) * h].reshape(
            [b, s, self.num_heads, self.head_dim]) for i in range(3))
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = out.reshape([b, s, h])
        return self.drop(self.out(out))


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.ffn_hidden
        w_in = I.Normal(0.0, 0.02)
        w_out = I.Normal(0.0, 0.02 / math.sqrt(2 * cfg.num_layers))
        if cfg.tensor_parallel:
            self.fc1 = ColumnParallelLinear(h, f, weight_attr=w_in,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(f, h, weight_attr=w_out,
                                         input_is_parallel=True)
        else:
            self.fc1 = Linear(h, f, weight_attr=w_in)
            self.fc2 = Linear(f, h, weight_attr=w_out)
        self.drop = Dropout(cfg.dropout)

    def forward(self, x):
        return self.drop(self.fc2(F.gelu(self.fc1(x), approximate=True)))


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig, use_moe: bool = False):
        super().__init__()
        self.cfg = cfg
        self.ln1 = LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size)
        if use_moe:
            self.mlp = MoELayer(cfg.hidden_size, cfg.ffn_hidden,
                                cfg.moe_experts)
        else:
            self.mlp = GPTMLP(cfg)

    def _sp(self, x):
        # Megatron SP: outside the matmul pair, activations shard on seq
        if self.cfg.sequence_parallel:
            return sharding_constraint(x, P("dp", "tp", None))
        return x

    def forward(self, x):
        # scope names reach the device trace as block/attn, block/mlp
        with jax.named_scope("block"):
            with jax.named_scope("attn"):
                x = x + self.attn(self.ln1(self._sp(x)))
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.ln2(self._sp(x)))
        return x


class GPT(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        # every parameter is initialised eagerly here: part of set-up
        with _prof.always_span("model.build", model="GPT"):
            if cfg.tensor_parallel:
                self.wte = VocabParallelEmbedding(cfg.vocab_size,
                                                  cfg.hidden_size)
            else:
                self.wte = Embedding(cfg.vocab_size, cfg.hidden_size,
                                     weight_attr=I.Normal(0.0, 0.02))
            self.wpe = Embedding(cfg.max_seq_len, cfg.hidden_size,
                                 weight_attr=I.Normal(0.0, 0.02))
            self.drop = Dropout(cfg.dropout)
            blocks = []
            for i in range(cfg.num_layers):
                use_moe = cfg.moe_every > 0 and (i + 1) % cfg.moe_every == 0
                blocks.append(GPTBlock(cfg, use_moe=use_moe))
            self.blocks = LayerList(blocks)
            self.ln_f = LayerNorm(cfg.hidden_size)
            if not cfg.tie_embeddings:
                self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                      bias_attr=False)

    def forward(self, input_ids):
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            pos = Tensor._wrap(jnp.arange(s))
            x = self.wte(input_ids) + self.wpe(pos)
            mesh = current_mesh()
            if mesh is not None and "dp" in mesh.axis_names:
                x = sharding_constraint(x, P("dp", None, None))
            x = self.drop(x)
        for blk in self.blocks:
            x = blk(x)
        with jax.named_scope("final_norm"):
            x = self.ln_f(x)
        with jax.named_scope("lm_head"):
            if self.cfg.tie_embeddings:
                from paddle_tpu.ops.registry import C_OPS

                logits = C_OPS.matmul(x, self.wte.weight, transpose_y=True)
            else:
                logits = self.lm_head(x)
        return logits

    def loss(self, logits, labels):
        """Next-token cross entropy (labels already shifted)."""
        v = logits.shape[-1]
        return F.cross_entropy(logits.reshape([-1, v]), labels.reshape([-1]))


def gpt_loss_fn(logits, labels):
    v = logits.shape[-1]
    return F.cross_entropy(logits.reshape([-1, v]), labels.reshape([-1]))


# ===========================================================================
# Pipeline-parallel training step (dp x pp x tp), fully compiled.
# ===========================================================================


def build_pipeline_train_step(cfg: GPTConfig, mesh: Mesh, num_micro: int = 4,
                              lr: float = 1e-3, schedule: str = "gpipe",
                              v: int | None = None):
    """Returns (step_fn, state) where step_fn(state, tokens, labels) ->
    (new_state, loss) is jitted over the mesh with dp/pp/tp shardings.

    Architecture: embedding + head replicated across pp (computed by all
    stages — cheap relative to blocks); transformer blocks stacked on a
    leading stage axis sharded over 'pp' and rotated with ppermute
    (parallel.pipeline). tp shardings on block params ride GSPMD-auto inside
    the shard_map body.

    schedule: 'gpipe' (fwd scan + autodiff), 'interleave' (VPP, v chunks per
    device, ~v-fold bubble cut), '1f1b' (fused fwd+bwd, O(pp) activation
    stash), 'zbh1' (zero-bubble H1: B/W backward split, 1/3 less bubble
    than 1F1B at the same stash), or 'zbvpp' (zero-bubble virtual pipeline:
    interleave topology x B/W split, memory-aware W placement) —
    parallel/pipeline_schedules.py;
    reference fleet/meta_parallel/pipeline_parallel.py:684,1308 and
    passes/pipeline_scheduler_pass/pipeline_zero_bubble.py.
    """
    from paddle_tpu.jit.functionalize import functionalize
    from paddle_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
    from paddle_tpu.parallel.pipeline_schedules import (
        interleave_permutation, pipeline_1f1b, pipeline_apply_interleave,
        pipeline_zbh1, pipeline_zbvpp,
    )

    if schedule not in ("gpipe", "1f1b", "interleave", "zbh1", "zbvpp"):
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}: "
            "expected 'gpipe', '1f1b', 'interleave', 'zbh1', or 'zbvpp'")
    npp = mesh.shape["pp"]
    assert cfg.num_layers % npp == 0
    group = 1
    if schedule in ("interleave", "zbvpp"):
        # v chunks per device; each virtual stage is a chain of `group`
        # consecutive blocks (group = num_layers / (v*pp))
        v = v or cfg.num_layers // npp
        if cfg.num_layers % (v * npp) != 0:
            raise ValueError(
                f"num_layers {cfg.num_layers} not divisible by v*pp = "
                f"{v}*{npp}")
        group = cfg.num_layers // (v * npp)

    model = GPT(cfg)
    func = functionalize(model)
    all_params = func.param_values()

    block_names = sorted(
        {k.split(".", 2)[2] for k in all_params if k.startswith("blocks.")})
    n_layers = cfg.num_layers
    if schedule in ("interleave", "zbvpp"):
        # [V, group, ...] in DEVICE-MAJOR virtual-stage order so the
        # P('pp')-sharded stack keeps each device's v chunks local (no
        # per-step resharding); virtual stage j = blocks j*group..+group
        perm = interleave_permutation(npp, v)
        stacked = {
            bn: jnp.stack([
                jnp.stack([all_params[f"blocks.{j * group + g}.{bn}"]
                           for g in range(group)])
                for j in perm])
            for bn in block_names
        }
    else:
        block_dicts = [
            {bn: all_params[f"blocks.{i}.{bn}"] for bn in block_names}
            for i in range(n_layers)
        ]
        stacked = stack_stage_params(block_dicts)
    outer = {k: v_ for k, v_ in all_params.items()
             if not k.startswith("blocks.")}

    block_func = functionalize(model.blocks[0])

    def stage_fn(block_params, h):
        out, _ = block_func.apply(block_params, {}, None, True, h)
        return out

    if schedule in ("interleave", "zbvpp"):
        from paddle_tpu.parallel.pipeline import chain_stages

        base_stage_fn = stage_fn

        def stage_fn(group_params, h):  # noqa: F811 — chain of `group` blocks
            return chain_stages(base_stage_fn, group_params, h)

    def stacked_spec(name, val):
        """Stage axis sharded on 'pp'; weight matrices additionally
        tensor-parallel on 'tp' (column for qkv/fc1, row for out/fc2).
        Interleave stacks carry an extra (unsharded) group axis."""
        extra = (None,) if schedule in ("interleave", "zbvpp") else ()
        if mesh.shape.get("tp", 1) > 1:
            if any(s in name for s in ("qkv.weight", "fc1.weight")):
                return P("pp", *extra, None, "tp")
            if any(s in name for s in ("out.weight", "fc2.weight")):
                return P("pp", *extra, "tp", None)
            if any(s in name for s in ("qkv.bias", "fc1.bias")):
                return P("pp", *extra, "tp")
        return P("pp")

    def embed(outer_p, tokens):
        s = tokens.shape[-1]
        x = (jnp.take(outer_p["wte.weight"], tokens, axis=0)
             + jnp.take(outer_p["wpe.weight"], jnp.arange(s), axis=0))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, "dp", None, None)))

    def head_loss(outer_p, y, labels):
        """Final norm + tied head + CE; y/labels may be all micro-batches
        ([m,b,s,...]) or one ([b,s,...])."""
        xf = y.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        xn = ((xf - mu) * jax.lax.rsqrt(var + 1e-5)).astype(y.dtype)
        xn = xn * outer_p["ln_f.weight"] + outer_p["ln_f.bias"]
        logits = jnp.einsum("...sh,vh->...sv", xn, outer_p["wte.weight"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return jnp.mean(nll)

    def fwd(outer_p, stacked_p, tokens, labels):
        x = embed(outer_p, tokens)
        if schedule == "interleave":
            y = pipeline_apply_interleave(stage_fn, stacked_p, x, mesh, v=v,
                                          num_micro=num_micro,
                                          layout="device")
        else:
            y = pipeline_apply(stage_fn, stacked_p, x, mesh,
                               num_micro=num_micro)
        return head_loss(outer_p, y, labels)

    def grads_fused(outer_p, stacked_p, tokens, labels):
        """Fused-schedule path (1f1b / zbh1 / zbvpp): the pipeline returns grads
        directly; the embedding closes the loop through an explicit vjp on
        dx, and the tied head/ln_f grads add to the embedding's."""
        x, emb_vjp = jax.vjp(lambda op: embed(op, tokens), outer_p)
        if schedule == "zbvpp":
            loss, g_stacked, g_head, dx = pipeline_zbvpp(
                stage_fn, stacked_p, x, labels, head_loss, outer_p, mesh,
                v=v, num_micro=num_micro, layout="device")
        else:
            pipe = pipeline_zbh1 if schedule == "zbh1" else pipeline_1f1b
            loss, g_stacked, g_head, dx = pipe(
                stage_fn, stacked_p, x, labels, head_loss, outer_p, mesh,
                num_micro=num_micro)
        g_emb = emb_vjp(dx)[0]
        g_outer = jax.tree_util.tree_map(jnp.add, g_head, g_emb)
        return loss, (g_outer, g_stacked)

    def step(state, tokens, labels):
        outer_p, stacked_p = state
        if schedule in ("1f1b", "zbh1", "zbvpp"):
            loss, grads = grads_fused(outer_p, stacked_p, tokens, labels)
        else:
            loss, grads = jax.value_and_grad(fwd, argnums=(0, 1))(
                outer_p, stacked_p, tokens, labels)
        g_outer, g_stacked = grads
        new_outer = jax.tree_util.tree_map(
            lambda p, g: (p - lr * g).astype(p.dtype), outer_p, g_outer)
        new_stacked = jax.tree_util.tree_map(
            lambda p, g: (p - lr * g).astype(p.dtype), stacked_p, g_stacked)
        return (new_outer, new_stacked), loss

    # shard initial state
    stacked_sharded = {
        k: jax.device_put(v, NamedSharding(mesh, stacked_spec(k, v)))
        for k, v in stacked.items()
    }
    outer_sharded = {
        k: jax.device_put(v, NamedSharding(mesh, P()))
        for k, v in outer.items()
    }
    step_jit = jax.jit(step, donate_argnums=(0,))
    return step_jit, (outer_sharded, stacked_sharded)
