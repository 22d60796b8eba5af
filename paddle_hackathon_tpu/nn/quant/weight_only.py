"""Post-training weight-only quantization for serving.

The QAT layers in ``quant_layers.py`` simulate quantization during
training; this module is the deployment half: weights are STORED as int8
(or fp8-e4m3) with one f32 scale per output channel and dequantized
inside the GEMM (``incubate/nn/kernels/quant_matmul.py``), while
activations stay bf16 — the LLM.int8 / AWQ weight-only recipe, where
quality survives because only the bandwidth-bound operand is narrowed.

Three entry points:

- :func:`quantize_weights` — pure pytree transform over a name-keyed
  param dict: each matching 2-D weight becomes (int8 array +
  ``<name>_scale`` f32 per-output-channel entry).  This is what
  ``save_for_serving(..., quant=...)`` writes into the artifact.
- :class:`WeightOnlyLinear` — the serving layer: drop-in for
  ``nn.Linear`` whose forward routes to the fused dequant kernel.
  ``apply_weight_only`` swaps a live model's Linears over (the
  quantize-at-load step ``load_for_serving`` runs).
- :func:`convert_to_weight_only` — the QAT export story: a tree trained
  with ``QuantizedLinear`` fake-quant wrappers converts so the LEARNED
  per-channel scales feed the serving quantizer instead of being
  recomputed from the weights (same quantization grid: the QAT
  ``_ste_quant_dequant`` rounds to ``round(w / absmax * qmax)``, and the
  serving scale is exactly ``absmax / qmax``).

Scale/zero-point convention: symmetric absmax per OUTPUT channel (the
axis the per-channel scale can commute out of the GEMM), no zero point.
``scheme="fp8"`` is shorthand for fp8-e4m3, behind the same interface.
"""

from __future__ import annotations

import jax.numpy as jnp

from ...core.autograd import apply_op
from ...core.tensor import Tensor
from ..layer import Layer
from ..layers.common import Linear
from ..parameter import Parameter
from .quant_layers import QuantizedLinear, channel_absmax

__all__ = [
    "quantize_weights", "quantize_array", "WeightOnlyLinear",
    "apply_weight_only", "convert_to_weight_only", "resolve_scheme",
]

SCHEMES = ("int8", "fp8-e4m3")


def resolve_scheme(scheme):
    """Normalize a user-facing scheme name ("fp8" = "fp8-e4m3")."""
    if scheme is None:
        return None
    if scheme == "fp8":
        scheme = "fp8-e4m3"
    if scheme not in SCHEMES:
        raise ValueError(
            f"unknown weight-only scheme {scheme!r}; expected one of "
            f"{SCHEMES} (or 'fp8')")
    return scheme


def _qmax(scheme):
    # int8: symmetric [-127, 127]; e4m3: largest finite magnitude
    return 127.0 if scheme == "int8" else 448.0


def _qdtype(scheme):
    return jnp.int8 if scheme == "int8" else jnp.float8_e4m3fn


def quantize_array(w, scheme="int8", axis=-1, absmax=None):
    """Quantize one weight: returns ``(w_q, scale)`` with ``scale`` f32
    per-channel over ``axis`` (default last = output channels for the
    (in, out) Linear layout).  ``absmax`` supplies a LEARNED per-channel
    statistic (QAT export) instead of measuring the tensor."""
    scheme = resolve_scheme(scheme)
    w = jnp.asarray(w)
    axis = axis % w.ndim
    if absmax is None:
        absmax = channel_absmax(w, axis)
    qmax = _qmax(scheme)
    # dead channels (absmax 0) would divide by zero; their rows are all
    # zero anyway, so any positive scale reproduces them exactly
    scale = jnp.maximum(jnp.asarray(absmax, jnp.float32) / qmax, 1e-9)
    shape = [1] * w.ndim
    shape[axis] = scale.shape[0]
    q = w.astype(jnp.float32) / scale.reshape(shape)
    if scheme == "int8":
        q = jnp.clip(jnp.round(q), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(q, -qmax, qmax).astype(_qdtype(scheme))
    return q, scale


def default_quant_predicate(name, arr):
    """Which params the serving quantizer touches by default: 2-D float
    matmul weights — the attention/MLP projections — and NOT embeddings
    (``wte``/``wpe``: gathers, not GEMMs, and the tied wte is also the
    logits head, which stays bf16 for output quality)."""
    if not name.endswith(".weight") or arr.ndim != 2:
        return False
    dtype = jnp.asarray(arr).dtype
    # itemsize 1 excludes fp8 (jnp-floating!) alongside int8: an
    # already-quantized weight must never quantize twice
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize == 1:
        return False
    lowered = name.lower()
    return not any(t in lowered for t in ("wte", "wpe", "embed"))


def quantize_weights(params, scheme="int8", predicate=None):
    """Post-training quantize a name-keyed param dict.  Returns
    ``(new_params, manifest)``: quantized entries replaced in place with
    the narrow array plus an added ``<name>_scale`` f32 entry, and
    ``manifest`` listing the quantized names (recorded in the artifact's
    config.json so the loader knows which Linears to swap)."""
    scheme = resolve_scheme(scheme)
    predicate = predicate or default_quant_predicate
    out, manifest = {}, []
    for name, arr in params.items():
        if predicate(name, arr):
            q, scale = quantize_array(arr, scheme)
            out[name] = q
            out[name + "_scale"] = scale
            manifest.append(name)
        else:
            out[name] = arr
    return out, manifest


class WeightOnlyLinear(Layer):
    """Serving-time Linear over a quantized weight: ``weight`` is int8 /
    fp8-e4m3 in the (in, out) Paddle layout, ``weight_scale`` is the f32
    per-output-channel dequant scale, and forward routes to the fused
    Pallas GEMM (jnp reference off-TPU).  Inference-only: the quantized
    params are non-trainable."""

    def __init__(self, in_features, out_features, scheme="int8",
                 has_bias=True):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.scheme = resolve_scheme(scheme)
        self.weight = Parameter(
            jnp.zeros((in_features, out_features), _qdtype(self.scheme)),
            trainable=False)
        self.weight_scale = Parameter(jnp.ones((out_features,), jnp.float32),
                                      trainable=False)
        self.bias = Parameter(jnp.zeros((out_features,)),
                              trainable=False) if has_bias else None

    # pht-lint: hot-root (every decode-tick projection routes here)
    def forward(self, x):
        from ...incubate.nn.kernels.quant_matmul import quant_matmul
        x = x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
        if self.bias is not None:
            return apply_op("weight_only_linear", quant_matmul,
                            [x, self.weight, self.weight_scale, self.bias])
        return apply_op("weight_only_linear", quant_matmul,
                        [x, self.weight, self.weight_scale])

    def extra_repr(self):
        return (f"in_features={self.in_features}, "
                f"out_features={self.out_features}, scheme={self.scheme}")

    def _load_quantized(self, w_q, scale, bias=None):
        scale = jnp.asarray(scale, jnp.float32)
        if scale.shape != (self.out_features,):
            # the layer's contract is ONE scale per output channel; a
            # silent mis-shaped store would only surface as a shape
            # mismatch at artifact load time
            raise ValueError(
                f"weight_scale must be per-output-channel "
                f"({self.out_features},); got {tuple(scale.shape)}")
        self.weight._set_value(jnp.asarray(w_q))
        self.weight_scale._set_value(scale)
        if bias is not None and self.bias is not None:
            self.bias._set_value(
                bias._value if isinstance(bias, Tensor) else jnp.asarray(bias))
        return self

    @classmethod
    def from_linear(cls, linear, scheme="int8"):
        """Quantize a live ``nn.Linear`` (measured absmax scales).  The
        bias Parameter is SHARED, not copied — callers swapping layers
        in place keep external references valid."""
        w = linear.weight._value
        q, scale = quantize_array(w, scheme, axis=-1)
        lay = cls(w.shape[0], w.shape[1], scheme=scheme, has_bias=False)
        lay.bias = linear.bias
        return lay._load_quantized(q, scale)

    @classmethod
    def from_qat(cls, qlayer, scheme="int8"):
        """Convert a QAT ``QuantizedLinear`` using its LEARNED absmax
        (the ``_fake_quant_weight.scale`` buffer) so serving quantizes on
        the exact grid training simulated — per-channel from a
        ``channel_wise_abs_max`` quantizer, or the default per-tensor
        ``abs_max`` scalar broadcast across output channels (same grid
        either way).  A wrapper whose observer never ran (all-zero
        scale) falls back to measuring."""
        w = qlayer.weight._value
        out = w.shape[1]
        fq = qlayer._fake_quant_weight
        quant_axis = getattr(fq, "_quant_axis", None)
        if quant_axis is not None and quant_axis % w.ndim != w.ndim - 1:
            # per-IN-channel scales cannot commute out of the GEMM as a
            # per-output-channel epilogue — shape-sniffing would
            # silently mis-apply them (undetectably so for square
            # weights), so refuse with the remedy instead
            raise ValueError(
                f"convert_to_weight_only needs per-OUTPUT-channel QAT "
                f"scales (weight_quant_axis={w.ndim - 1}); this layer "
                f"learned quant_axis={quant_axis}.  Re-run QAT with "
                f"weight_quant_axis={w.ndim - 1} or quantize from the "
                f"weights instead (apply_weight_only / "
                f"save_for_serving(quant=...)).")
        absmax = fq.scale._value
        if not bool(jnp.any(absmax > 0)):
            absmax = None
        elif quant_axis is None:
            # per-tensor abs_max observer: one scalar, same grid on
            # every output channel
            absmax = jnp.broadcast_to(absmax.reshape(-1)[:1], (out,))
        q, scale = quantize_array(w, scheme, axis=-1, absmax=absmax)
        lay = cls(w.shape[0], w.shape[1], scheme=scheme, has_bias=False)
        lay.bias = qlayer.bias
        return lay._load_quantized(q, scale)


def apply_weight_only(model, scheme="int8", names=None):
    """Swap a live model's Linears for :class:`WeightOnlyLinear`.

    ``names=None`` quantizes-in-place every Linear whose weight passes
    :func:`default_quant_predicate` (measured scales).  ``names`` — the
    artifact manifest of ``<path>.weight`` entries — instead installs
    EMPTY quantized shells at exactly those paths, for the loader to fill
    via ``set_state_dict`` (quantize-at-load: the wide weights never
    materialize).  Returns the number of layers swapped."""
    scheme = resolve_scheme(scheme)
    if names is not None:
        swapped = 0
        for pname in names:
            path = pname[:-len(".weight")].split(".")
            parent = model
            for seg in path[:-1]:
                parent = parent._sub_layers[seg]
            old = parent._sub_layers[path[-1]]
            lay = WeightOnlyLinear(old.weight.shape[0], old.weight.shape[1],
                                   scheme=scheme, has_bias=False)
            lay.bias = old.bias
            parent._sub_layers[path[-1]] = lay
            swapped += 1
        return swapped
    swapped = 0
    for lname, layer in list(model.named_sublayers(include_self=True)):
        for name, sub in list(layer._sub_layers.items()):
            # the predicate sees the REAL dotted path, so its
            # embedding-name exclusions apply to a live tree exactly as
            # they do to the save_for_serving(quant=) param dict
            full = f"{lname}.{name}.weight" if lname else f"{name}.weight"
            if type(sub) is Linear and default_quant_predicate(
                    full, sub.weight._value):
                layer._sub_layers[name] = WeightOnlyLinear.from_linear(
                    sub, scheme)
                swapped += 1
    return swapped


def convert_to_weight_only(layer_tree, scheme="int8"):
    """QAT export: replace every ``QuantizedLinear`` fake-quant wrapper
    in ``layer_tree`` with a :class:`WeightOnlyLinear` built from its
    learned scales (``WeightOnlyLinear.from_qat``).  In-place; returns
    the number of layers converted.  The converted tree then saves
    through ``save_for_serving`` like any quantized model (its weights
    are already narrow, so ``quant=`` must NOT be passed again)."""
    converted = 0
    for layer in layer_tree.sublayers(include_self=True):
        for name, sub in list(layer._sub_layers.items()):
            if isinstance(sub, QuantizedLinear):
                layer._sub_layers[name] = WeightOnlyLinear.from_qat(
                    sub, scheme)
                converted += 1
    return converted
