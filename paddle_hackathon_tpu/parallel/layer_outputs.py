"""What a layer hands the train step beside its output.

Two side channels, one walk each, which the default loss of
``parallel.api.make_sharded_train_step`` reads in the frame that traced the
forward:

- ``l_aux``: an auxiliary loss the layer computed (the capacity MoE's
  load balance, the sparse attention's indexer KL), weighted by the
  layer's own ``aux_weight`` where it has one, else by the model's MoE
  weight.  ``parallel.moe.collect_moe_aux`` walks it, for the default
  loss, the hapi trainers and the pipeline alike.
- ``layer_counters``: a vector the layer counted while it ran (an expert
  layer's routed rows, the sparse attention's selected pairs and tiles).
  The step returns them beside the loss, keyed by the layer's path.
"""

from __future__ import annotations

from ..core.tensor import Tensor


def collect_layer_counters(model) -> dict:
    """``{layer path: vector}`` over every layer whose forward, just
    traced, left ``layer_counters``; ``{}`` where none did.  Raw jax
    values: the train step returns them as program outputs beside the
    loss, and the program observatory keeps the newest
    (``ProgramRegistry.note_counters``)."""
    out = {}
    for name, layer in model.named_sublayers(include_self=True):
        c = getattr(layer, "layer_counters", None)
        if c is not None:
            out[name] = c._value if isinstance(c, Tensor) else c
    return out
