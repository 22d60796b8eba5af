"""Sparse attention's forward share of its roofline, in %: the least time
the chip could take for one layer's attention over the selected pairs
(``dsa_work.sparse_attention_work``: min(t + 1, topk) keys a query, so the
pairs a kernel computes and masks lower the share) over the mean device
time a layer of the ops under ``dsa/dsa_attn`` in the forward phase,
whatever implements it."""

from benchmark import dsa_work, flops, phase_times, scope_times

PHASES = ("fwd",)
BACKWARD = False


def read(run, phases=PHASES, backward=BACKWARD):
    times = phase_times.phase_times(run)
    if times is None or run["peaks"] is None:
        return None
    spent = scope_times.scope_ms(times, "dsa", "dsa_attn", phases) / 1e3
    if spent <= 0:
        return None
    cfg, f = run["config"], run["facts"]
    layers = cfg["num_hidden_layers"]
    work = dsa_work.sparse_attention_work(
        f["batch"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
        f["seqlen"], cfg["head_dim"], cfg["sa_config"]["topk"], 2, backward)
    least = flops.roofline_seconds(work, run["peaks"])
    run["notes"].append(
        f"{'bwd' if backward else 'fwd'} sparse attention: "
        f"{least['bound']}-bound, least {least['seconds']:.3e} s a layer "
        f"({work['flops']:.4e} operations), measured "
        f"{spent / layers:.3e} s a layer over {layers} layers")
    return 100.0 * least["seconds"] * layers / spent
