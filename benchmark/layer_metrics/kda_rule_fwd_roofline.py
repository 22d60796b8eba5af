"""The channel-decay delta rule's forward share of its roofline, in %: the
least time the chip could take for one layer's recurrence at the cell's
shapes (``kda_work.channel_decay_rule_work``) over the mean device time a
layer of the ops under the scope ``kda_rule`` in the forward phase,
whatever implements the rule (today: chunked ``jax.numpy`` under a
``lax.scan``)."""

from benchmark import flops, kda_work, phase_times, scope_times

PHASES = ("fwd",)
BACKWARD = False


def read(run, phases=PHASES, backward=BACKWARD):
    times = phase_times.phase_times(run)
    if times is None or run["peaks"] is None:
        return None
    spent = scope_times.scope_ms(times, "kda", "kda_rule", phases) / 1e3
    if spent <= 0:
        return None
    cfg, f = run["config"], run["facts"]
    layers = sum((i + 1) % cfg["layer_group_size"] != 0
                 for i in range(cfg["num_hidden_layers"]))
    work = kda_work.channel_decay_rule_work(
        f["batch"], f["seqlen"], cfg["num_attention_heads"], cfg["head_dim"],
        cfg["head_dim"], 2, backward)
    least = flops.roofline_seconds(work, run["peaks"])
    run["notes"].append(
        f"{'bwd' if backward else 'fwd'} channel-decay delta rule: "
        f"{least['bound']}-bound, least {least['seconds']:.3e} s a layer, "
        f"measured {spent / layers:.3e} s a layer over {layers} layers")
    return 100.0 * least["seconds"] * layers / spent
