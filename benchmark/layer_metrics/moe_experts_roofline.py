"""The routed experts' share of their roofline, forward and backward
together, in %: per layer the least time for the three grouped products
over the rows the program's counters say were routed to the held experts
(``mixer_work.grouped_expert_work``), summed over the layers, over the
device time a step under the scope ``experts`` (sort, gather and the
products, with XLA's ``ragged-dot`` kernels, which carry no scope)."""

from benchmark import flops, mixer_work, phase_times, scope_times

UNSCOPED = ("ragged-dot",)


def experts_ms(run, times):
    return scope_times.scope_ms(times, "moe", "experts") \
        + scope_times.unscoped_ms(run, times, UNSCOPED)


def read(run):
    times = phase_times.phase_times(run)
    counters = scope_times.program_counters(run)
    if times is None or run["peaks"] is None or not counters:
        return None
    spent = experts_ms(run, times) / 1e3
    if spent <= 0:
        return None
    cfg = run["config"]
    least = sum(flops.roofline_seconds(mixer_work.grouped_expert_work(
        rows[0], cfg["num_experts"], cfg["hidden_size"],
        cfg["moe_intermediate_size"], 2), run["peaks"])["seconds"]
        for rows in counters.values())
    run["notes"].append(
        f"routed experts: least {least:.3e} s a step over {len(counters)} "
        f"layers, measured {spent:.3e} s a step under `experts`")
    return 100.0 * least / spent
