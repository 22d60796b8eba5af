"""Causal attention forward's share of its roofline, in %: the least time
the chip could take for one layer's attention at the cell's shapes
(``flops.causal_attention_work``) over the mean device time of one call of
the kernel that does it today.  A PR that swaps the kernel adds a reader
naming its events; the work stays as computed here from shapes."""

from benchmark import flops, trace_reduce

EVENTS = ("flash_packed_fwd",)
BACKWARD = False


def read(run, events=EVENTS, backward=BACKWARD, calls_of=None):
    traced, peaks = run["facts"]["traced"], run["peaks"]
    if traced is None or peaks is None:
        return None
    calls = trace_reduce.count_of(traced["ops"], calls_of or events[:1])
    spent = trace_reduce.time_of(traced["ops"], events) / 1e9
    if not calls or spent <= 0:
        return None
    cfg, f = run["config"], run["facts"]
    work = flops.causal_attention_work(
        f["batch"], cfg["num_heads"], f["seqlen"],
        cfg["hidden_size"] // cfg["num_heads"], 2, backward)
    least = flops.roofline_seconds(work, peaks)
    run["notes"].append(f"{'bwd' if backward else 'fwd'} attention: "
                        f"{least['bound']}-bound, least {least['seconds']:.3e}"
                        f" s a layer, measured {spent / calls:.3e} s over "
                        f"{calls} calls")
    return 100.0 * least["seconds"] * calls / spent
