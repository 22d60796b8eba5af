"""The gated delta rule's forward share of its roofline, in %: the least
time the chip could take for one layer's recurrence at the cell's shapes
(``mixer_work.gated_delta_rule_work``) over the mean device time a layer
of the ops under the scope ``gdn_rule`` in the forward phase, whatever
implements the rule (today: chunked ``jax.numpy`` under a ``lax.scan``)."""

from benchmark import flops, mixer_work, phase_times, scope_times

PHASES = ("fwd",)
BACKWARD = False


def read(run, phases=PHASES, backward=BACKWARD):
    times = phase_times.phase_times(run)
    if times is None or run["peaks"] is None:
        return None
    spent = scope_times.scope_ms(times, "gdn", "gdn_rule", phases) / 1e3
    if spent <= 0:
        return None
    cfg, f = run["config"], run["facts"]
    layers = sum((i + 1) % cfg["full_attention_interval"] != 0
                 for i in range(cfg["num_hidden_layers"]))
    work = mixer_work.gated_delta_rule_work(
        f["batch"], f["seqlen"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], 2, backward)
    least = flops.roofline_seconds(work, run["peaks"])
    run["notes"].append(
        f"{'bwd' if backward else 'fwd'} gated delta rule: "
        f"{least['bound']}-bound, least {least['seconds']:.3e} s a layer, "
        f"measured {spent / layers:.3e} s a layer over {layers} layers")
    return 100.0 * least["seconds"] * layers / spent
