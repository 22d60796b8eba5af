"""The lightning indexer's share of its roofline, in %: the least time
for one layer's indexer scores over the causal pairs and the KL's
gradient over the selected pairs (``dsa_work.indexer_work``, both phases)
over the mean device time a layer under ``dsa/dsa_index`` and
``dsa/dsa_kl``, both phases.  That time also holds what the KL recomputes
of the attention's probabilities, which the work does not count."""

from benchmark import dsa_work, flops, phase_times, scope_times


def read(run):
    times = phase_times.phase_times(run)
    if times is None or run["peaks"] is None:
        return None
    parts = {part: scope_times.scope_ms(times, "dsa", part) / 1e3
             for part in ("dsa_index", "dsa_kl")}
    spent = sum(parts.values())
    if spent <= 0:
        return None
    cfg, f = run["config"], run["facts"]
    sa = cfg["sa_config"]
    layers = cfg["num_hidden_layers"]
    work = dsa_work.indexer_work(f["batch"], f["seqlen"],
                                 sa["indexer_num_heads"],
                                 sa["indexer_head_dim"], sa["topk"], 2)
    least = flops.roofline_seconds(work, run["peaks"])
    run["notes"].append(
        f"indexer: {least['bound']}-bound, least {least['seconds']:.3e} s a "
        f"layer; measured a layer " + ", ".join(
            f"{k} {v / layers:.3e} s" for k, v in parts.items()))
    return 100.0 * least["seconds"] * layers / spent
