"""Layer — the module base class.

TPU-native analogue of the reference dygraph Layer
(reference: python/paddle/fluid/dygraph/layers.py; C++ side VarBase in
imperative/layer.h). Parameters are eager Tensors whose values live on
device as jax.Arrays; ``state_dict``/``set_state_dict`` match the reference
checkpoint contract.

The same Layer instance serves both eager execution and the compiled path:
``paddle_tpu.static.functional_call`` swaps parameter values for jit tracers,
so jax.jit / pjit trace straight through ``forward`` — the reference needed a
whole AST-translation subsystem (dygraph_to_static) for this.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Iterator, Optional, Tuple

import jax
import numpy as np

from ...core import dtype as dtype_mod
from ...framework.param_attr import ParamAttr
from ...framework.tensor import Parameter, Tensor
from ...profiler import trace as _ptrace
from .. import initializer as I

_name_counters = {}


def _unique_name(prefix: str) -> str:
    n = _name_counters.get(prefix, 0)
    _name_counters[prefix] = n + 1
    return f"{prefix}_{n}"


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self.training = True
        self._dtype = dtype_mod.convert_dtype(dtype)
        self._full_name = _unique_name(
            name_scope or type(self).__name__.lower())

    # -- attribute routing -------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Parameter):
            self._parameters[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            self._sub_layers[name] = value
            self.__dict__.pop(name, None)
        else:
            if name in getattr(self, "_parameters", {}):
                del self._parameters[name]
            if name in getattr(self, "_sub_layers", {}):
                del self._sub_layers[name]
            if name in getattr(self, "_buffers", {}):
                if isinstance(value, Tensor):
                    self._buffers[name] = value
                    return
                del self._buffers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in (self._parameters, self._sub_layers, self._buffers):
            if name in store:
                del store[name]
                return
        object.__delattr__(self, name)

    # -- registration ------------------------------------------------------
    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor],
                        persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> Optional[Parameter]:
        """reference: layers.py create_parameter + LayerHelper."""
        return build_parameter(shape, attr, dtype, is_bias,
                               default_initializer,
                               fallback_dtype=self._dtype)

    def create_tensor(self, name=None, persistable=None, dtype=None):
        import jax.numpy as jnp

        return Tensor(jnp.zeros([], dtype_mod.convert_dtype(dtype)
                                or self._dtype))

    # -- iteration ---------------------------------------------------------
    def parameters(self, include_sublayers: bool = True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix: str = "",
                         include_sublayers: bool = True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix,
                                                include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p

    def buffers(self, include_sublayers: bool = True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix,
                                                include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def sublayers(self, include_self: bool = False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix: str = "", include_self: bool = False):
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(prefix=p, include_self=True)

    # -- mode --------------------------------------------------------------
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def full_name(self):
        return self._full_name

    # -- hooks -------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        handle = _HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[handle._id] = hook
        return handle

    # -- state dict --------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "", use_hook: bool = True):
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            bare = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = owner._sub_layers[part]
            if bare not in owner._non_persistable_buffer_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                own[k].set_value(arr)
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            t0 = time.perf_counter()
            d = dtype_mod.convert_dtype(dtype)
            for p in self.parameters():
                if isinstance(p._value, jax.ShapeDtypeStruct):
                    # LazyGuard's placeholder: the type it will be drawn in
                    p._value = jax.ShapeDtypeStruct(p._value.shape, d)
                else:
                    p._value = p._value.astype(d)
            for b in self.buffers():
                import jax.numpy as jnp

                if jnp.issubdtype(b._value.dtype, jnp.floating):
                    b._value = b._value.astype(d)
            self._dtype = d
            _ptrace.charge_setup("cast", time.perf_counter() - t0)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- call --------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            res = hook(self, inputs)
            if res is not None:
                inputs = res if isinstance(res, tuple) else (res,)
        out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, inputs, out)
            if res is not None:
                out = res
        return out

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = "\n  ".join(sub_repr)
            lines.append(f"({name}): {sub_repr}")
        body = ("\n  " + "\n  ".join(lines) + "\n") if lines else ""
        return f"{type(self).__name__}({extra}{body})"


class _HookRemoveHelper:
    _counter = 0

    def __init__(self, hooks_dict):
        self._hooks = hooks_dict
        self._id = _HookRemoveHelper._counter
        _HookRemoveHelper._counter += 1

    def remove(self):
        self._hooks.pop(self._id, None)


def build_parameter(shape, attr=None, dtype=None, is_bias=False,
                    default_initializer=None, name=None,
                    fallback_dtype="float32"):
    """Shared ParamAttr→Parameter resolution (Layer.create_parameter and
    static.create_parameter both delegate here so attr semantics cannot
    drift)."""
    attr = ParamAttr._to_attr(attr)
    if attr is None:
        return None
    dtype = dtype_mod.convert_dtype(dtype) or fallback_dtype
    init = attr.initializer or default_initializer or (
        I.Constant(0.0) if is_bias else I.XavierNormal())
    from ...framework.lazy import in_lazy_mode

    if in_lazy_mode():
        import jax as _jax
        import numpy as _np

        value = _jax.ShapeDtypeStruct(
            tuple(int(s) for s in shape), _np.dtype(dtype))
        p = Parameter(value, name=name or attr.name or
                      _unique_name("param"), trainable=attr.trainable)
        p._lazy_initializer = init
    else:
        t0 = time.perf_counter()
        value = init(shape, dtype)
        # the eager draw, one parameter at a time as the host dispatches it
        _ptrace.charge_setup("weights", time.perf_counter() - t0,
                             getattr(value, "nbytes", None), where="host")
        p = Parameter(value, name=name or attr.name or
                      _unique_name("param"), trainable=attr.trainable)
    p.optimize_attr["learning_rate"] = attr.learning_rate
    p.regularizer = attr.regularizer
    p.need_clip = attr.need_clip
    return p
