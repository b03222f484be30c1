"""Layer base class + containers.

Reference: python/paddle/nn/layer/layers.py (Layer.__call__:1521,
create_parameter:755, __setattr__ auto-registration:1666, hooks:644,
state_dict:2085) and containers in nn/layer/container.py.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from paddle_tpu import profiler as _prof
from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.core.tensor import Parameter, Tensor


class _HookRemoveHelper:
    def __init__(self, hooks, key):
        self._hooks = hooks
        self._key = key

    def remove(self):
        self._hooks.pop(self._key, None)


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self._forward_pre_hooks: Dict[int, Callable] = OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = OrderedDict()
        self._hook_id = 0
        self.training = True
        self._dtype = dtype_mod.dtype_name(dtype_mod.to_jax_dtype(dtype))
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ----------------------------------------------------------- registration

    def __setattr__(self, name, value):
        if isinstance(value, Parameter):
            self._parameters[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            self._sub_layers[name] = value
            self.__dict__.pop(name, None)
        else:
            if name in getattr(self, "_parameters", {}):
                if value is None:
                    del self._parameters[name]
                    object.__setattr__(self, name, value)
                    return
            if name in getattr(self, "_sub_layers", {}) and not isinstance(value, Layer):
                del self._sub_layers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def create_parameter(self, shape, dtype=None, default_initializer=None,
                         is_bias=False, attr=None) -> Parameter:
        """Reference: layers.py:755. attr may carry an initializer or a
        parallel PartitionSpec (TPU extension, see paddle_tpu.parallel)."""
        from paddle_tpu.nn import initializer as I

        dtype = dtype or self._dtype
        init = default_initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        value = init(shape, dtype)
        p = Parameter(value)
        if isinstance(attr, dict) and "sharding" in attr:
            p._sharding = attr["sharding"]
        return p

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # ----------------------------------------------------------- traversal

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def sublayers(self, include_self=False) -> List["Layer"]:
        out = [l for _, l in self.named_sublayers(include_self=include_self)]
        return out

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, l in self.named_children():
            if id(l) in layers_set:
                continue
            p = prefix + ("." if prefix else "") + name
            yield from l.named_sublayers(prefix=p, include_self=True,
                                         layers_set=layers_set)

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters()]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for layer_prefix, layer in self.named_sublayers(prefix=prefix,
                                                        include_self=True):
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (layer_prefix + ("." if layer_prefix else "") + name, p)

    def named_buffers(self, prefix="", persistable_only=False):
        seen = set()
        for layer_prefix, layer in self.named_sublayers(prefix=prefix,
                                                        include_self=True):
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                if persistable_only and name in layer._non_persistable_buffer_names:
                    continue
                seen.add(id(b))
                yield (layer_prefix + ("." if layer_prefix else "") + name, b)

    def buffers(self):
        return [b for _, b in self.named_buffers()]

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ----------------------------------------------------------- state dict

    def state_dict(self, include_non_persistable_buffer=False) -> Dict[str, Tensor]:
        out = OrderedDict()
        for name, p in self.named_parameters():
            out[name] = p
        for name, b in self.named_buffers(
            persistable_only=not include_non_persistable_buffer
        ):
            out[name] = b
        return out

    def set_state_dict(self, state_dict):
        with _prof.always_span("model.set_state_dict"):
            own = self.state_dict(include_non_persistable_buffer=True)
            kept = self.state_dict()
            missing, unexpected = [], []
            for name, t in own.items():
                if name in state_dict:
                    src = state_dict[name]
                    v = (src._value if isinstance(src, Tensor)
                         else np.asarray(src))
                    t.copy_(Tensor._wrap(v))
                elif name in kept:  # a buffer no state dict keeps is not owed
                    missing.append(name)
            for name in state_dict:
                if name not in own:
                    unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    # ----------------------------------------------------------- modes

    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    def to(self, device=None, dtype=None, blocking=True):
        if dtype is not None:
            d = dtype_mod.to_jax_dtype(dtype)
            for _, p in self.named_parameters():
                if np.issubdtype(p.dtype, np.floating):
                    p._value = p._value.astype(d)
            for _, b in self.named_buffers():
                if np.issubdtype(b.dtype, np.floating):
                    b._value = b._value.astype(d)
            self._dtype = dtype_mod.dtype_name(d)
        return self

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # ----------------------------------------------------------- hooks/call

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return _HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, args)
            if result is not None:
                args = result if isinstance(result, tuple) else (result,)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, args, out)
            if result is not None:
                out = result
        return out

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self.named_children():
            child = repr(l).split("\n")
            child = [child[0]] + ["  " + c for c in child[1:]]
            lines.append(f"  ({name}): " + "\n".join(child))
        main = f"{self.__class__.__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    def full_name(self):
        return self._name_scope


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], OrderedDict):
            for name, l in layers[0].items():
                self.add_sublayer(name, l)
        else:
            for i, l in enumerate(layers):
                if isinstance(l, tuple):
                    self.add_sublayer(l[0], l[1])
                else:
                    self.add_sublayer(str(i), l)

    def forward(self, x):
        for l in self._sub_layers.values():
            x = l(x)
        return x

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self

    def __getitem__(self, idx):
        return list(self._parameters.values())[idx]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def update(self, sublayers):
        items = sublayers.items() if isinstance(sublayers, dict) else sublayers
        for name, l in items:
            self.add_sublayer(name, l)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, value):
        self.add_sublayer(key, value)

    def __contains__(self, key):
        return key in self._sub_layers

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()

    def __len__(self):
        return len(self._sub_layers)
