"""paddle.utils.dlpack (ref ``python/paddle/utils/dlpack.py:26-100``) —
zero-copy tensor exchange via the DLPack protocol (jax arrays implement
``__dlpack__``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor

__all__ = ["to_dlpack", "from_dlpack"]


def to_dlpack(x):
    """Encode a Tensor to a DLPack capsule (ref ``dlpack.py:26``).

    TPU buffers have no DLPack ABI (jax supports it for CPU/GPU only), so
    device tensors round-trip through host memory — the CUDA zero-copy of
    the reference becomes copy-through-host here."""
    if not isinstance(x, Tensor):
        raise TypeError(
            f"The type of 'x' in to_dlpack must be paddle.Tensor, but "
            f"received {type(x)}.")
    try:
        return x._value.__dlpack__()
    except (BufferError, RuntimeError):
        # BufferError: platform has no DLPack ABI; RuntimeError: the
        # PJRT plug-in does not implement external references
        import numpy as np
        # np.asarray of a jax array is readonly, which DLPack can't signal
        return np.array(x._value, copy=True).__dlpack__()


def from_dlpack(dlpack):
    """Decode a DLPack capsule (or any object with ``__dlpack__``) to a
    Tensor (ref ``dlpack.py:62``)."""
    import numpy as np
    if hasattr(dlpack, "__dlpack__"):
        try:
            return Tensor(jnp.from_dlpack(dlpack))
        except (BufferError, RuntimeError):  # TPU producer: via host
            return Tensor(jnp.asarray(np.asarray(dlpack)))
    t = str(type(dlpack))
    if "PyCapsule" not in t:
        raise TypeError(
            f"The type of 'dlpack' in from_dlpack must be PyCapsule object,"
            f" but received {type(dlpack)}.")

    class _CapsuleShim:
        """Adapter: numpy/jax from_dlpack consume producers, not raw
        capsules — present the capsule as a CPU DLPack producer."""

        def __init__(self, cap):
            self._cap = cap

        def __dlpack__(self, stream=None):
            return self._cap

        def __dlpack_device__(self):
            return (1, 0)  # kDLCPU

    return Tensor(jnp.asarray(np.from_dlpack(_CapsuleShim(dlpack))))
