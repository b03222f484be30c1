"""Tensor-parallel (model-parallel) layers.

Reference: python/paddle/distributed/fleet/layers/mpu/mp_layers.py —
VocabParallelEmbedding:49, ColumnParallelLinear:336, RowParallelLinear:543,
ParallelCrossEntropy:744 — built on explicit _c_identity/_mp_allreduce comm
ops (mpu/mp_ops.py).

TPU-native: the weights carry PartitionSpecs over the 'tp' mesh axis and the
activations carry sharding constraints; GSPMD inserts the identity/allreduce
collectives the reference writes by hand. Megatron sequence parallelism
(fleet/utils/sequence_parallel_utils.py) is the `sequence_parallel=True`
flag: activations outside the matmul pair are sharded on the sequence dim
over 'tp', turning the allreduce into reduce_scatter + allgather.

The rule of every constraint here: a layer says where 'tp' lies on ONE
dimension of its activation (`"tp"`: sharded; `None`: whole on every tp
rank, which is what makes GSPMD emit the all-reduce) and nothing about the
others (`_tp_spec`). A `None` on the batch dimension would mean "replicated
over every mesh axis", dp included: each data-parallel replica would be
handed the whole batch after every layer, and cut back to its half by the
next constraint that names dp.
"""

from __future__ import annotations

from jax.sharding import PartitionSpec as P

from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer
from paddle_tpu.parallel.api import sharding_constraint
from paddle_tpu.parallel.mesh import current_mesh


def _tp_size() -> int:
    m = current_mesh()
    return m.shape.get("tp", 1) if m is not None else 1


def _tp_spec(ndim: int, axis: int, placement) -> P:
    """`placement` ("tp" or None) on dimension `axis`; every other dimension
    is the partitioner's to place (the batch stays where the step put it)."""
    spec = [P.UNCONSTRAINED] * ndim
    spec[axis] = placement
    return P(*spec)


class ColumnParallelLinear(Layer):
    """Weight [in, out] sharded on out ('tp'); output stays tp-sharded when
    gather_output=False (feeds a RowParallelLinear)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            [in_features, out_features],
            default_initializer=weight_attr or I.XavierNormal(),
            attr={"sharding": P(None, "tp")})
        self.bias = None
        if has_bias:
            self.bias = self.create_parameter(
                [out_features], is_bias=True, attr={"sharding": P("tp")})

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        return sharding_constraint(out, _tp_spec(
            out.ndim, -1, None if self.gather_output else "tp"))


class RowParallelLinear(Layer):
    """Weight [in, out] sharded on in ('tp'); input arrives tp-sharded on its
    last dim; output needs the allreduce, which GSPMD emits from the
    replicated output constraint."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            [in_features, out_features],
            default_initializer=weight_attr or I.XavierNormal(),
            attr={"sharding": P("tp", None)})
        self.bias = None
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)

    def forward(self, x):
        if not self.input_is_parallel:
            x = sharding_constraint(x, _tp_spec(x.ndim, -1, "tp"))
        out = F.linear(x, self.weight, None)
        out = sharding_constraint(out, _tp_spec(out.ndim, -1, None))
        if self.bias is not None:
            out = out + self.bias
        return out


class VocabParallelEmbedding(Layer):
    """Embedding weight sharded on the vocab dim over 'tp'. GSPMD handles the
    masked-lookup + allreduce the reference implements manually
    (mp_layers.py:49 + c_embedding kernel)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim],
            default_initializer=weight_attr or I.Normal(0.0, 0.02),
            attr={"sharding": P("tp", None)})

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return sharding_constraint(out, _tp_spec(out.ndim, -1, None))


class ParallelCrossEntropy(Layer):
    """Cross entropy over tp-sharded logits (reference mp_layers.py:744 over
    c_softmax_with_cross_entropy). GSPMD: constrain logits sharded on the
    class dim; the log-softmax reduction generates the tp allreduce."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        input = sharding_constraint(input, _tp_spec(input.ndim, -1, "tp"))
        return F.cross_entropy(input, label, reduction="none",
                               ignore_index=self.ignore_index)


# --------------------------------------------------------------- Megatron SP


class ScatterOp:
    """Reference sequence_parallel_utils.py:85 — scatter activation along the
    sequence dim across tp. Here: a sharding constraint."""

    @staticmethod
    def apply(x, axis=1):
        return sharding_constraint(x, _tp_spec(x.ndim, axis, "tp"))


class GatherOp:
    """Reference :97 — gather sequence-sharded activation back."""

    @staticmethod
    def apply(x, axis=1):
        return sharding_constraint(x, _tp_spec(x.ndim, axis, None))


def mark_as_sequence_parallel_parameter(param):
    param.is_distributed = True
