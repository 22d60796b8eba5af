"""Latent attention's forward share of its roofline as it trains, in %: the
least time the chip could take for one layer's causal attention with keys
of nope + rope and values of ``v_head_dim``
(``kda_work.causal_attention_work``: the useful work, so columns that a
kernel pads onto ``v`` lower the share) over the mean device time of one
call of the kernel that does it today."""

from benchmark import flops, kda_work, trace_reduce

EVENTS = ("flash_packed_fwd",)
BACKWARD = False


def read(run, events=EVENTS, backward=BACKWARD):
    traced, peaks = run["facts"]["traced"], run["peaks"]
    if traced is None or peaks is None:
        return None
    calls = trace_reduce.count_of(traced["ops"], events[:1])
    spent = trace_reduce.time_of(traced["ops"], events) / 1e9
    if not calls or spent <= 0:
        return None
    cfg, f = run["config"], run["facts"]
    work = kda_work.causal_attention_work(
        f["batch"], cfg["num_attention_heads"], f["seqlen"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], 2, backward)
    least = flops.roofline_seconds(work, peaks)
    run["notes"].append(
        f"{'bwd' if backward else 'fwd'} latent attention: "
        f"{least['bound']}-bound, least {least['seconds']:.3e} s a layer, "
        f"measured {spent / calls:.3e} s over {calls} calls")
    return 100.0 * least["seconds"] * calls / spent
