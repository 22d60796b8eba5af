"""The user-facing Tensor.

TPU-native equivalent of the reference's ``paddle::experimental::Tensor``
(``paddle/phi/api/include/tensor.h:83``) + the eager ``AutogradMeta``
(``paddle/fluid/eager/autograd_meta.h``) merged into one Python object: the
payload is a ``jax.Array`` (PJRT owns layout, HBM placement and streams — the
whole of phi/backends + fluid/memory collapses into this), while
``stop_gradient`` / ``_grad_node`` / ``_grad_value`` carry the autograd state.

Most math methods are monkey-patched onto this class by ``ops/__init__.py``,
mirroring how the reference patches ``VarBase``
(``fluid/dygraph/math_op_patch.py:66``).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd, device
from .dtype import convert_dtype, default_float_dtype


class Tensor:
    __slots__ = ("_value", "stop_gradient", "name", "persistable",
                 "_grad_node", "_out_idx", "_grad_value", "_grad_hooks",
                 "_process_mesh", "_shard_spec",  # auto_parallel annotations
                 "_lod",  # legacy LoD offsets (static.nn sequence_* ops)
                 "_leaf_alias",  # double-grad snapshot -> original leaf
                 "__weakref__")

    # auto_parallel annotations (set by parallel.auto_parallel.shard_tensor);
    # default None without paying per-construction init cost
    @property
    def process_mesh(self):
        try:
            return self._process_mesh
        except AttributeError:
            return None

    @process_mesh.setter
    def process_mesh(self, value):
        self._process_mesh = value

    @property
    def shard_spec(self):
        try:
            return self._shard_spec
        except AttributeError:
            return None

    @shard_spec.setter
    def shard_spec(self, value):
        self._shard_spec = value

    def __init__(self, value, stop_gradient: bool = True, name: Optional[str] = None,
                 _grad_node=None, _out_idx: int = 0):
        if isinstance(value, Tensor):
            value = value._value
        elif not isinstance(value, jax.Array):
            value = jnp.asarray(value)
        self._value = value
        self.stop_gradient = stop_gradient
        self.name = name
        self.persistable = False
        self._grad_node = _grad_node
        self._out_idx = _out_idx
        self._grad_value = None
        self._grad_hooks = []

    # -- metadata ----------------------------------------------------------
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self):
        devs = getattr(self._value, "devices", None)
        if devs is None:
            return device.current_place()
        try:
            d = next(iter(self._value.devices()))
            return device.Place(d.platform, d.id)
        except Exception:
            return device.current_place()

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def numel(self) -> int:
        return self.size

    # -- conversion --------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._value)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def astype(self, dtype) -> "Tensor":
        d = convert_dtype(dtype)
        return autograd.apply_op("cast", lambda x: x.astype(d), [self])

    cast = astype

    def _to_jax(self):
        return self._value

    # -- autograd ----------------------------------------------------------
    @property
    def grad(self) -> Optional["Tensor"]:
        if self._grad_value is None:
            return None
        return Tensor(self._grad_value, stop_gradient=True)

    @grad.setter
    def grad(self, value):
        self._grad_value = None if value is None else (
            value._value if isinstance(value, Tensor) else jnp.asarray(value))

    def backward(self, grad_tensor: Optional["Tensor"] = None,
                 retain_graph: bool = False) -> None:
        """Run reverse-mode AD from this tensor (ref ``egr::Backward``,
        ``eager/backward.cc:848``)."""
        if grad_tensor is None:
            g = jnp.ones(self._value.shape, self._value.dtype)
        else:
            g = grad_tensor._value if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)
        autograd.run_backward([self], [g], retain_graph=retain_graph)

    def clear_grad(self) -> None:
        self._grad_value = None

    def clear_gradient(self, set_to_zero: bool = False) -> None:
        if set_to_zero and self._grad_value is not None:
            self._grad_value = jnp.zeros_like(self._grad_value)
        else:
            self._grad_value = None

    def detach(self) -> "Tensor":
        return Tensor(self._value, stop_gradient=True, name=self.name)

    def clone(self) -> "Tensor":
        return autograd.apply_op("clone", lambda x: x + 0, [self])

    def register_hook(self, hook) -> "_HookHandle":
        """Gradient hook (ref ``egr::utils::RegisterGradientHookForTensor``)."""
        if self._grad_node is None:
            self._grad_hooks.append(hook)
            return _HookHandle(self._grad_hooks, hook)
        node = self._grad_node
        if node.hooks is None:
            node.hooks = {}
        node.hooks.setdefault(self._out_idx, []).append(hook)
        return _HookHandle(node.hooks[self._out_idx], hook)

    # -- in-place ----------------------------------------------------------
    def _set_value(self, value) -> None:
        """Replace the payload in place (optimizer update path)."""
        self._value = value._value if isinstance(value, Tensor) else value

    def set_value(self, value) -> None:
        if isinstance(value, Tensor):
            value = value._value
        self._value = jnp.asarray(value, dtype=self._value.dtype).reshape(self._value.shape)

    def copy_(self, other, blocking: bool = True) -> None:
        self.set_value(other)

    def fill_(self, value) -> "Tensor":
        self._value = jnp.full_like(self._value, value)
        return self

    def zero_(self) -> "Tensor":
        self._value = jnp.zeros_like(self._value)
        return self

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, idx) -> "Tensor":
        idx = _unwrap_index(idx)
        return autograd.apply_op("slice", lambda x: x[idx], [self])

    def __setitem__(self, idx, value) -> None:
        idx = _unwrap_index(idx)
        if not isinstance(value, Tensor):
            value = Tensor(jnp.asarray(value, dtype=self._value.dtype))
        out = autograd.apply_op(
            "set_value", lambda x, v: x.at[idx].set(v.astype(x.dtype)), [self, value])
        # In-place rebind: this tensor's identity now refers to the scatter
        # result, keeping the tape consistent (paddle set_value semantics).
        self._value = out._value
        self._grad_node = out._grad_node
        self._out_idx = out._out_idx
        self.stop_gradient = out.stop_gradient

    # -- python protocol ---------------------------------------------------
    def __len__(self):
        if not self._value.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __iter__(self):
        # leading-dim slices (paddle Tensor iteration).  Without this,
        # Python's __getitem__ fallback loops forever: jnp indexing clamps
        # out-of-range instead of raising IndexError.
        if not self._value.shape:
            raise TypeError("iteration over a 0-d tensor")
        return (self[i] for i in range(self._value.shape[0]))

    def __bool__(self):
        return bool(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __repr__(self):
        prefix = "Tensor"
        try:
            val = np.array2string(self.numpy(), precision=4, separator=", ")
        except Exception:
            val = f"<traced {self._value}>"
        return (f"{prefix}(shape={self.shape}, dtype={self._value.dtype.name}, "
                f"stop_gradient={self.stop_gradient},\n       {val})")

    def __hash__(self):
        return id(self)

    # -- dunder math (fuller set patched in ops/__init__.py) ---------------
    def _binop(self, other, fn, name):
        if not isinstance(other, Tensor):
            other = Tensor(jnp.asarray(other))
        return autograd.apply_op(name, fn, [self, other])

    def __add__(self, o):
        return self._binop(o, lambda a, b: a + b, "add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, lambda a, b: a - b, "subtract")

    def __rsub__(self, o):
        return self._binop(o, lambda a, b: b - a, "rsubtract")

    def __mul__(self, o):
        return self._binop(o, lambda a, b: a * b, "multiply")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, lambda a, b: a / b, "divide")

    def __rtruediv__(self, o):
        return self._binop(o, lambda a, b: b / a, "rdivide")

    def __floordiv__(self, o):
        return self._binop(o, lambda a, b: a // b, "floor_divide")

    def __mod__(self, o):
        return self._binop(o, lambda a, b: a % b, "remainder")

    def __pow__(self, o):
        return self._binop(o, lambda a, b: a ** b, "pow")

    def __rpow__(self, o):
        return self._binop(o, lambda a, b: b ** a, "rpow")

    def __and__(self, o):
        return self._binop(o, jnp.bitwise_and, "bitwise_and")

    def __or__(self, o):
        return self._binop(o, jnp.bitwise_or, "bitwise_or")

    def __xor__(self, o):
        return self._binop(o, jnp.bitwise_xor, "bitwise_xor")

    def __invert__(self):
        return autograd.apply_op("bitwise_not", jnp.bitwise_not, [self])

    def __matmul__(self, o):
        return self._binop(o, lambda a, b: a @ b, "matmul")

    def __neg__(self):
        return autograd.apply_op("neg", lambda x: -x, [self])

    def __abs__(self):
        return autograd.apply_op("abs", lambda x: jnp.abs(x), [self])

    def _cmp(self, other, fn, name):
        if not isinstance(other, Tensor):
            other = Tensor(jnp.asarray(other))
        with autograd.no_grad():
            return autograd.apply_op(name, fn, [self, other])

    def __eq__(self, o):
        return self._cmp(o, lambda a, b: a == b, "equal")

    def __ne__(self, o):
        return self._cmp(o, lambda a, b: a != b, "not_equal")

    def __lt__(self, o):
        return self._cmp(o, lambda a, b: a < b, "less_than")

    def __le__(self, o):
        return self._cmp(o, lambda a, b: a <= b, "less_equal")

    def __gt__(self, o):
        return self._cmp(o, lambda a, b: a > b, "greater_than")

    def __ge__(self, o):
        return self._cmp(o, lambda a, b: a >= b, "greater_equal")

    def __invert__(self):
        with autograd.no_grad():
            return autograd.apply_op("logical_not", lambda x: ~x, [self])


class _HookHandle:
    def __init__(self, container, hook):
        self._container = container
        self._hook = hook

    def remove(self):
        try:
            self._container.remove(self._hook)
        except ValueError:
            pass


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(i._value if isinstance(i, Tensor) else i for i in idx)
    return idx


autograd._set_tensor_class(Tensor)

# jax pytree registration: a Tensor flattens to its payload, so Tensors can
# cross jit boundaries and live inside optimizer state trees.
jax.tree_util.register_pytree_node(
    Tensor,
    lambda t: ((t._value,), (t.stop_gradient, t.name)),
    lambda aux, children: Tensor(children[0], stop_gradient=aux[0], name=aux[1]),
)


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor equivalent."""
    if isinstance(data, Tensor):
        value = data._value
    else:
        if isinstance(data, (list, tuple)):
            data = np.asarray(data)
        if isinstance(data, np.ndarray) and dtype is None and data.dtype == np.float64:
            data = data.astype(np.float32)
        value = jnp.asarray(data, dtype=convert_dtype(dtype))
    if dtype is not None:
        value = value.astype(convert_dtype(dtype))
    if place is not None:
        if isinstance(place, str):
            dev_type, _, idx = place.partition(":")
            place = device.Place(dev_type, int(idx or 0))
        value = jax.device_put(value, place.jax_device)
    return Tensor(value, stop_gradient=stop_gradient)
