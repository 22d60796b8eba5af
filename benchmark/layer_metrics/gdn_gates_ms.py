"""Exclusive device time a step under ``gdn/gdn_gates`` (the Gated
DeltaNet mixers' L2 norms, the heads' repeat, gates, float32 casts and
head reshapes, and the output's norm x SiLU gate), forward and backward,
in ms.  The note gives ``gdn_proj`` and ``gdn_gates`` by phase, the placed
ops under ``gdn`` by part, and what is left under ``gdn`` outside every
part (the layer's norm and residual)."""

from benchmark import placed_times


def read(run):
    return placed_times.mixer_parts_ms(run, "gdn", "gdn_gates", "gdn_proj")
