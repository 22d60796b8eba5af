"""Exclusive device time a step under ``kda/kda_gates`` (the Kimi delta
attention mixers' L2 norms, gates, float32 casts and head reshapes, and
the output's norm x sigmoid gate), forward and backward, in ms; the
backward's holds the rebuilt forward.  The note gives ``kda_proj`` and
``kda_gates`` by phase, the placed ops under ``kda`` by part, and what is
left under ``kda`` outside every part (the layer's norm and residual)."""

from benchmark import placed_times


def read(run):
    return placed_times.mixer_parts_ms(run, "kda", "kda_gates", "kda_proj")
