"""Cross-round performance gate (ref ``tools/ci_op_benchmark.sh:117`` /
``ci_model_benchmark.sh`` — the reference's CI rejects changes that regress
op or model benchmarks; it compares against an external benchmark repo, here
the history lives in-tree).

Two checks:

1. **Model gate** — the headline `bench.py` metric against the best prior
   `BENCH_r*.json`: fail when the current run is more than ``--tolerance``
   (default 5%) below the best recorded round.
2. **Op gate** — `cost_model/static_op_benchmark.json` regenerated (or a
   fresh file passed via ``--ops``) against the committed snapshot: fail
   when any op regresses more than ``--op-tolerance`` (default 25%; a
   hand-set bound — the run-to-run spread of the op microbenchmarks has
   not been measured on the current toolchain, ROADMAP A6).

The ``--suite`` run additionally checks the telemetry each bench row
embeds (``"metrics"``, from the observability registry): a serving row
whose jit-build count grew between the warm phase and the measured
steady-state phase recompiled mid-run and fails the gate
(``compare_metrics``).

Usage::

    python tools/perf_gate.py                 # model gate only (fast)
    python tools/perf_gate.py --ops new.json  # + op gate vs snapshot

Exit code 0 = pass, 1 = regression, 2 = cannot evaluate (no history).
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def best_recorded():
    sys.path.insert(0, ROOT)
    from bench import load_bench_history  # single owner of the file format
    return load_bench_history(ROOT)


def run_bench():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"bench.py failed:\n{out.stderr[-2000:]}")
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


def model_gate(tolerance):
    history = best_recorded()
    if not history:
        print("perf_gate: no BENCH_r*.json history — nothing to gate "
              "against")
        return 2
    best_round, best_value, metric = max(history, key=lambda r: r[1])
    cur = run_bench()
    value = float(cur["value"])
    floor = best_value * (1.0 - tolerance)
    status = "PASS" if value >= floor else "FAIL"
    print(f"perf_gate[model] {status}: {cur['metric']} = {value:,.0f} "
          f"{cur.get('unit', '')} vs best {best_value:,.0f} "
          f"(round {best_round}); floor at -{tolerance:.0%} = {floor:,.0f}")
    return 0 if status == "PASS" else 1


OP_SNAPSHOT = os.path.join(ROOT, "paddle_hackathon_tpu", "cost_model",
                           "static_op_benchmark.json")
MODEL_SNAPSHOT = os.path.join(ROOT, "paddle_hackathon_tpu", "cost_model",
                              "model_bench_baseline.json")


def _op_times(d):
    out = {}
    for entry in (d if isinstance(d, list) else d.get("ops", [])):
        name = entry.get("op") or entry.get("name")
        t = entry.get("paddle_gpu_time") or entry.get("time_ms")
        if name is not None and t:
            out[name] = float(t)
    return out


def compare_ops(old_t, new_t, op_tolerance):
    """[(name, old, new)] for ops slower than old*(1+tolerance)."""
    return [(name, t_old, new_t[name]) for name, t_old in old_t.items()
            if name in new_t and new_t[name] > t_old * (1.0 + op_tolerance)]


def op_gate(new_path, op_tolerance):
    snap_path = OP_SNAPSHOT
    if not os.path.exists(snap_path):
        print("perf_gate[ops]: no committed op snapshot — skip")
        return 0
    with open(snap_path) as fh:
        snap = json.load(fh)
    with open(new_path) as fh:
        new = json.load(fh)

    old_t, new_t = _op_times(snap), _op_times(new)
    regressed = compare_ops(old_t, new_t, op_tolerance)
    if regressed:
        print(f"perf_gate[ops] FAIL: {len(regressed)} ops regressed "
              f">{op_tolerance:.0%}:")
        for name, t_old, t_new in sorted(regressed,
                                         key=lambda r: r[2] / r[1],
                                         reverse=True)[:20]:
            print(f"  {name}: {t_old:.4f} -> {t_new:.4f} ms "
                  f"({t_new / t_old:.2f}x)")
        return 1
    print(f"perf_gate[ops] PASS: {len(old_t)} ops within "
          f"{op_tolerance:.0%} of snapshot "
          f"({len(new_t)} measured)")
    return 0


def _valued(rows):
    """{metric: value} over rows that actually carry a numeric value —
    error rows ({"error": ...} from bench.py run_suite) have none and
    are gated by compare_error_rows instead of crashing the parser."""
    return {r["metric"]: float(r["value"]) for r in rows
            if r.get("metric") and r.get("value") is not None}


def compare_suite(baseline, rows, tolerance):
    """[(metric, base, cur)] rows below baseline*(1-tolerance); baseline
    metrics the run didn't produce are reported as missing (regression)."""
    cur = _valued(rows)
    bad = []
    for metric, base in baseline.items():
        v = cur.get(metric)
        if v is None or v < float(base) * (1.0 - tolerance):
            bad.append((metric, float(base), v))
    return bad


def compare_error_rows(rows):
    """[(name, error_tail)] for rows bench.py recorded as crashed
    (``{"error": ...}`` — run_suite keeps sweeping past a crashing row
    instead of aborting the whole record, cf. the r04 rc=1 dtype crash
    that cost a full round's bench history).  The gate fails LOUDLY on
    each one: a crashed row must be a named failure with its stderr
    tail, never a silently missing metric."""
    return [(r.get("suite_row") or r.get("metric") or "?",
             str(r["error"])[:300])
            for r in rows if r.get("error")]


# Floor for the MoE flagship's embedded same-run ratio: the row itself
# runs its dense reference at matched ACTIVE params (bench_gpt2_moe), so
# the gate works identically on device and host-timed (CPU smoke) runs.
MOE_ACTIVE_RATIO_FLOOR = 0.60


def compare_moe_active_ratio(rows):
    """[(metric, ratio)] for MoE rows whose embedded
    ``vs_dense_active_params`` same-run ratio fell below the floor —
    the MoE tax (capacity-padded expert einsums + dispatch/combine) must
    stay under 40% of the matched-active-params dense throughput."""
    return [(r["metric"], float(r["vs_dense_active_params"]))
            for r in rows
            if r.get("vs_dense_active_params") is not None
            and float(r["vs_dense_active_params"]) < MOE_ACTIVE_RATIO_FLOOR]


# Same-run ratio gates: (metric, reference_metric, min_ratio).  Unlike the
# baseline comparison these need no committed number, so a NEW metric is
# gated from its first suite run.  hapi_fit is the compiled Model.fit
# path; it must stay within 10% of the hand-rolled jitted step it wraps
# (the acceptance bar for the fit fast path).  serving_spec is the
# speculative draft-and-verify tick over the identical serving workload:
# exact greedy equivalence means speculation must never LOSE throughput,
# so the bar is >= 1.0x the same-run non-speculative row.
RATIO_GATES = [
    ("hapi_fit_tokens_per_sec",
     "gpt2_small_pretrain_tokens_per_sec_per_chip", 0.90),
    # ZeRO-1 sharded optimizer through the identical Model.fit recipe:
    # the reduce-scatter/shard-update/all-gather path must hold tokens/s
    # within 10% of the replicated-update hapi_fit row (the per-tensor
    # gathers are designed to overlap the update tail inside the scanned
    # program — a serialized gather shows up here); the row additionally
    # embeds opt_state_bytes_vs_replicated ~ 1/dp as the HBM evidence
    ("hapi_fit_zero1_tokens_per_sec", "hapi_fit_tokens_per_sec", 0.90),
    # ZeRO-offload vs resident ZeRO-1: the offloaded update streams
    # every moment shard h2d and back d2h each step, so tokens/s is a
    # STATED capacity trade, not parity.  Curve: the pipe double-buffers
    # (offload_depth tensors in flight), so a healthy run hides most of
    # the transfer under the per-tensor update compute and the grads
    # program — 0.3x is the floor where the pipe has collapsed
    # (serialized h2d/d2h, a per-step recompile, or the ring draining
    # synchronously), not the expected steady state.  The capacity side
    # of the trade is gated separately: compare_zero_offload requires
    # device opt-state bytes ~ 0 with the host bytes stated.
    ("hapi_fit_offload_tokens_per_sec",
     "hapi_fit_zero1_tokens_per_sec", 0.30),
    ("gpt2_serving_spec_8stream_device_tokens_per_sec_per_chip",
     "gpt2_serving_8stream_device_tokens_per_sec_per_chip", 1.00),
    # paged KV at 2x the admitted streams must not lose aggregate
    # throughput to the dense layout: attention reads each slot's actual
    # length through the page table where dense reads max_len rows, so
    # the indirection has to pay for itself on the same-run workload
    ("gpt2_serving_paged_16stream_device_tokens_per_sec_per_chip",
     "gpt2_serving_8stream_device_tokens_per_sec_per_chip", 1.00),
    # weight-only int8 serving: decode is weight-HBM-bandwidth-bound, so
    # halving the bytes each tick streams must buy >= 1.3x the same-run
    # bf16 row on device timing (host-timed fallback rows are caught by
    # compare_timing_fallbacks instead of wall-clock-gated here)
    ("gpt2_serving_int8_8stream_device_tokens_per_sec_per_chip",
     "gpt2_serving_8stream_device_tokens_per_sec_per_chip", 1.30),
    # NOTE deliberately NO cross-row gate for gpt2_moe vs the gpt2
    # headline: the rows run different batch sizes (16 vs 32 — HBM
    # headroom for the 3.4x-total-params MoE), so a cross-row ratio
    # would conflate the MoE tax with batch effects.  The row gates
    # itself: bench_gpt2_moe embeds vs_dense_active_params from a
    # dense reference run in the SAME process at the SAME batch/seq,
    # held >= 0.60 by compare_moe_active_ratio below.
    # MoE serving sanity floor vs the same-run dense row (identical
    # workload/streams on both rows, so cross-row is sound): at matched
    # active params the MoE decode streams ~2.6x the weight bytes of the
    # dense model (8 experts x 2h resident vs one 4h MLP), so on a
    # weight-bandwidth-bound tick ~0.38x is the theoretical ceiling —
    # the floor catches the routed tick falling off a cliff (recompiles,
    # host syncs), not parity with dense
    ("gpt2_moe_serving_8stream_device_tokens_per_sec_per_chip",
     "gpt2_serving_8stream_device_tokens_per_sec_per_chip", 0.25),
    # multi-turn conversational serving: session suffix-caching removes
    # the per-turn history re-prefill, which must pay for the paged
    # indirection — aggregate tokens/s holds >= 1.0x the same-run dense
    # serving row (the turn-N TTFT improvement itself is gated by
    # compare_chat_ttft below, which works on host-timed runs too: both
    # TTFTs come from the same clock in the same process)
    ("gpt2_serving_chat_8conv_device_tokens_per_sec_per_chip",
     "gpt2_serving_8stream_device_tokens_per_sec_per_chip", 1.00),
]


def compare_ratios(rows):
    """[(metric, ref, ratio, floor)] for ratio gates that fail; gates
    whose metrics the run didn't produce are skipped (the baseline
    comparison already flags missing rows)."""
    cur = _valued(rows)
    bad = []
    for metric, ref, floor in RATIO_GATES:
        if metric in cur and ref in cur and cur[ref] > 0:
            ratio = cur[metric] / cur[ref]
            if ratio < floor:
                bad.append((metric, ref, ratio, floor))
    return bad


def compare_metrics(rows):
    """[(metric, warm, total)] for rows whose embedded telemetry shows
    jit builds GROWING between the warm (prefill + compile) phase and the
    measured steady-state phase — a program recompiled mid-run.  The
    serving bench rows embed ``metrics.jit_builds_warm/total`` (bench.py)
    exactly for this tripwire; rows without the keys are skipped."""
    bad = []
    for r in rows:
        m = r.get("metrics") or {}
        warm, total = m.get("jit_builds_warm"), m.get("jit_builds_total")
        if warm is not None and total is not None and total > warm:
            bad.append((r["metric"], int(warm), int(total)))
    return bad


def retrace_causes(rows, metric):
    """Recorded retrace causes for a failing row's ``programs`` block
    (the program-observatory evidence bench rows embed): ``(site,
    cause)`` pairs, build order.  Empty when the row predates the
    observatory — the caller prints a pointer instead of guessing."""
    for r in rows:
        if r.get("metric") != metric:
            continue
        out = []
        for site, s in ((r.get("programs") or {}).get("sites") or {}).items():
            out.extend((site, c) for c in s.get("causes") or ())
        return out
    return []


def compare_zero_sharding(rows):
    """[(metric, reason)] for ZeRO bench rows whose sharding evidence is
    vacuous or absent: a row claiming ``zero_stage>=1`` must have run on
    >1 data-axis devices (``dp``) AND show
    ``opt_state_bytes_vs_replicated`` strictly below 1.0 (the ~1/dp
    shrink).  A single-device bench environment — or a mesh the trainer
    silently degraded on — would otherwise green-light the
    ``hapi_fit_zero1`` ratio gate while both rows ran the identical
    replicated program, measuring nothing."""
    bad = []
    for r in rows:
        if not r.get("zero_stage"):
            continue
        dp = int(r.get("dp") or 0)
        ratio = r.get("opt_state_bytes_vs_replicated")
        if dp <= 1:
            bad.append((r["metric"],
                        f"ran on dp={dp} — ZeRO measured nothing"))
        elif ratio is None or float(ratio) >= 1.0:
            bad.append((r["metric"],
                        f"opt_state_bytes_vs_replicated={ratio!r} on "
                        f"dp={dp} — the optimizer state did not shard"))
    return bad


def compare_zero_offload(rows):
    """[(metric, reason)] for ZeRO-OFFLOAD bench rows whose evidence is
    vacuous (mirror of compare_zero_sharding): a row claiming
    ``zero_offload`` must have run on >1 data-axis devices, must show
    ``opt_state_bytes_vs_replicated`` ~ 0 (the moments really left the
    devices — a resident-looking ratio means the offload silently
    degraded), and must state a positive ``opt_state_host_bytes`` (the
    host side of the trade; 0 would mean no state existed at all and
    the tokens/s gate measured an empty update)."""
    bad = []
    for r in rows:
        if not r.get("zero_offload"):
            continue
        dp = int(r.get("dp") or 0)
        ratio = r.get("opt_state_bytes_vs_replicated")
        host = r.get("opt_state_host_bytes")
        if dp <= 1:
            bad.append((r["metric"],
                        f"ran on dp={dp} — offload measured nothing"))
        elif ratio is None or float(ratio) > 0.05:
            bad.append((r["metric"],
                        f"opt_state_bytes_vs_replicated={ratio!r} on "
                        f"dp={dp} — optimizer state stayed device-"
                        f"resident"))
        elif not host:
            bad.append((r["metric"],
                        f"opt_state_host_bytes={host!r} — no host-side "
                        f"state backs the offload claim"))
    return bad


def compare_timing_fallbacks(rows):
    """[metric] for rows measuring a *device* metric that fell back to
    HOST wall-clock timing.  bench.py only host-times under
    ``JAX_PLATFORMS=cpu`` and renames those rows ``*_cpu_smoke``; a row
    that still carries a device metric's name with ``"timing": "host"``
    must never be gated against committed device baselines — fail with a
    named cause instead of an unexplained throughput shift."""
    return [r["metric"] for r in rows
            if r.get("timing") == "host" and "device" in r.get("metric", "")]


# A returning turn that resumes its retained session skips the whole
# conversation-history prefill, so its TTFT must sit well below turn
# 1's full-prefill TTFT.  The floor is deliberately loose (turn-1
# prefills ~4x the suffix a resumed turn does, so a healthy run lands
# far under it) — it catches the resume path silently degrading to
# re-prefill, not timing noise.
CHAT_TTFT_RATIO_CEILING = 0.80


def compare_chat_ttft(rows):
    """[(metric, turn1_ms, turnN_ms)] for conversational serving rows
    whose returning-turn TTFT is NOT measurably below the turn-1 TTFT
    (``metrics.ttft_turnN_ms`` must be <= CHAT_TTFT_RATIO_CEILING x
    ``metrics.ttft_turn1_ms``): session resume fell back to
    re-prefilling the conversation.  Both stamps come from the same
    process and clock, so this gate holds on host-timed (CPU) runs
    too; rows without the keys are skipped."""
    bad = []
    for r in rows:
        m = r.get("metrics") or {}
        t1, tn = m.get("ttft_turn1_ms"), m.get("ttft_turnN_ms")
        if t1 is None or tn is None:
            continue
        if float(tn) > float(t1) * CHAT_TTFT_RATIO_CEILING:
            bad.append((r["metric"], float(t1), float(tn)))
    return bad


# SLO-aware scheduling gates (PR 17), over the serving_slo row's
# embedded same-run FIFO-vs-priority pair (both runs in one process on
# one clock, so the gates hold on host-timed CPU runs too).  Batch
# goodput is batch tokens per wall second: preempted work re-queues
# rather than aborting, so completed COUNTS always match — what
# preemption can crater is the time those tokens take (replay cost),
# and the floor holds that to 20%.  The interactive ceiling is loose
# by design (a healthy run lands far under it): it catches the
# scheduler degrading to FIFO, not timing noise.
SLO_BATCH_GOODPUT_FLOOR = 0.80
SLO_INTERACTIVE_TTFT_CEILING = 0.75


def compare_slo_scheduling(rows):
    """[(metric, reason)] for mixed-priority serving rows whose embedded
    FIFO-vs-priority evidence fails: interactive ttft_p99 must land at
    <= SLO_INTERACTIVE_TTFT_CEILING x the FIFO run's, batch goodput
    must hold >= SLO_BATCH_GOODPUT_FLOOR x FIFO, and scheduling must
    be lossless: every request in both runs delivers its full token
    budget (preemption re-queues and replays, never truncates).  Rows
    without the keys are skipped."""
    bad = []
    for r in rows:
        m = r.get("metrics") or {}
        ti_p = m.get("interactive_ttft_p99_ms_priority")
        ti_f = m.get("interactive_ttft_p99_ms_fifo")
        gp_p = m.get("batch_goodput_tokens_per_s_priority")
        gp_f = m.get("batch_goodput_tokens_per_s_fifo")
        if ti_p is None or ti_f is None or gp_p is None or gp_f is None:
            continue
        if float(ti_p) > float(ti_f) * SLO_INTERACTIVE_TTFT_CEILING:
            bad.append((r["metric"],
                        f"interactive ttft_p99 {float(ti_p):.1f}ms is "
                        f"not materially below FIFO's {float(ti_f):.1f}ms "
                        f"(ceiling {SLO_INTERACTIVE_TTFT_CEILING:.2f}x) "
                        f"— the scheduler degraded to FIFO"))
        if float(gp_p) < float(gp_f) * SLO_BATCH_GOODPUT_FLOOR:
            bad.append((r["metric"],
                        f"batch goodput {float(gp_p):.1f} tok/s fell "
                        f"below {SLO_BATCH_GOODPUT_FLOOR:.2f}x FIFO's "
                        f"{float(gp_f):.1f} tok/s — preemption/replay "
                        f"is cratering batch throughput"))
        if m.get("scheduling_lossless") is False:
            bad.append((r["metric"],
                        "a request finished short of its token budget "
                        "or errored — preemption/priority scheduling "
                        "dropped work instead of re-queueing it"))
    return bad


def compare_fleet_telemetry(rows):
    """[(metric, reason)] for fleet serving rows (``metrics.
    fleet_replicas`` present) whose armed-telemetry evidence is
    vacuous: the row must carry real dispatch-latency percentiles (the
    router's own ``fleet_dispatch_seconds`` histogram observed every
    placement), a stated retry rate, and the jit_builds_warm/total
    pair — compare_metrics holds that pair to zero growth, which for
    THIS row is the claim that the armed observability plane (spans,
    trace-context plumbing, federation labels) compiled nothing.  A
    row missing the builds pair would silently exempt itself from that
    gate, so its absence fails here by name.  Non-fleet rows are
    skipped."""
    bad = []
    for r in rows:
        m = r.get("metrics") or {}
        if m.get("fleet_replicas") is None:
            continue
        if (m.get("fleet_dispatch_p50_ms") is None
                or m.get("fleet_dispatch_p99_ms") is None):
            bad.append((r["metric"],
                        "no dispatch-latency percentiles — the router's "
                        "fleet_dispatch_seconds histogram observed no "
                        "placement"))
        if m.get("fleet_retry_rate") is None:
            bad.append((r["metric"],
                        "fleet_retry_rate missing from the embedded "
                        "telemetry"))
        if (m.get("jit_builds_warm") is None
                or m.get("jit_builds_total") is None):
            bad.append((r["metric"],
                        "jit_builds_warm/total missing — cannot prove "
                        "the armed telemetry plane compiled nothing"))
    return bad


def compare_pool_leaks(rows):
    """[(metric, leaked)] for paged serving rows whose KV page pool did
    not return to 0 allocated after the drain + prefix-cache drop
    (bench.py embeds ``metrics.kv_pages_leaked``): a refcount bug leaks
    HBM a page at a time in production — fail the gate instead."""
    bad = []
    for r in rows:
        leaked = (r.get("metrics") or {}).get("kv_pages_leaked")
        if leaked is not None and int(leaked) > 0:
            bad.append((r["metric"], int(leaked)))
    return bad


def suite_gate(tolerance, rows=None):
    """Gate EVERY driver model config (ERNIE/1.3B/long-context/
    ResNet + gpt2) against the committed best values — the round-2 gate
    only covered the gpt2 headline, so 4 of 5 driver configs could
    regress silently (VERDICT r2 weak #3)."""
    if not os.path.exists(MODEL_SNAPSHOT):
        print("perf_gate[suite]: no committed model baseline — skip")
        return 0
    with open(MODEL_SNAPSHOT) as fh:
        baseline = json.load(fh)
    if rows is None:
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py"), "--suite"],
            capture_output=True, text=True,
            timeout=42000)  # 13 rows x 2 attempts x 1500s + slack
        if out.returncode != 0:
            raise RuntimeError(f"bench.py --suite failed:\n"
                               f"{out.stderr[-2000:]}")
        rows = [json.loads(line) for line in out.stdout.splitlines()
                if line.startswith("{")]
    bad = compare_suite(baseline, rows, tolerance)
    bad_ratio = compare_ratios(rows)
    bad_metrics = compare_metrics(rows)
    bad_leaks = compare_pool_leaks(rows)
    bad_timing = compare_timing_fallbacks(rows)
    bad_errors = compare_error_rows(rows)
    bad_moe = compare_moe_active_ratio(rows)
    bad_zero = compare_zero_sharding(rows)
    bad_offload = compare_zero_offload(rows)
    bad_chat = compare_chat_ttft(rows)
    bad_slo = compare_slo_scheduling(rows)
    bad_fleet = compare_fleet_telemetry(rows)
    if (bad or bad_ratio or bad_metrics or bad_leaks or bad_timing
            or bad_errors or bad_moe or bad_zero or bad_offload
            or bad_chat or bad_slo or bad_fleet):
        if bad:
            print(f"perf_gate[suite] FAIL: {len(bad)} configs regressed "
                  f">{tolerance:.0%}:")
            for metric, base, v in bad:
                print(f"  {metric}: {base:,.0f} -> "
                      f"{'missing' if v is None else format(v, ',.0f')}")
        for name, err in bad_errors:
            print(f"perf_gate[suite] FAIL: suite row {name} CRASHED "
                  f"(recorded error row): {err}")
        for metric, ref, ratio, floor in bad_ratio:
            print(f"perf_gate[suite] FAIL: {metric} at {ratio:.2f}x of "
                  f"{ref} (floor {floor:.2f}x)")
        for metric, ratio in bad_moe:
            print(f"perf_gate[suite] FAIL: {metric} at {ratio:.2f}x of "
                  f"its same-run dense reference at matched active "
                  f"params (floor {MOE_ACTIVE_RATIO_FLOOR:.2f}x)")
        for metric, warm, total in bad_metrics:
            print(f"perf_gate[suite] FAIL: {metric} recompiled in steady "
                  f"state ({warm} jit builds after warm-up, {total} after "
                  f"the measured run)")
            causes = retrace_causes(rows, metric)
            for site, cause in causes:
                print(f"    retrace cause: {site}: {cause}")
            if not causes:
                print("    (no recorded causes — row carries no programs "
                      "block; see /debug/programs on a live run)")
        for metric, reason in bad_zero:
            print(f"perf_gate[suite] FAIL: {metric} ZeRO evidence is "
                  f"vacuous ({reason})")
        for metric, reason in bad_offload:
            print(f"perf_gate[suite] FAIL: {metric} ZeRO-offload "
                  f"evidence is vacuous ({reason})")
        for metric, t1, tn in bad_chat:
            print(f"perf_gate[suite] FAIL: {metric} turn-N TTFT "
                  f"{tn:.1f}ms is not measurably below turn-1 "
                  f"{t1:.1f}ms (ceiling "
                  f"{CHAT_TTFT_RATIO_CEILING:.2f}x) — session resume "
                  f"degraded to re-prefilling the conversation")
        for metric, reason in bad_slo:
            print(f"perf_gate[suite] FAIL: {metric} {reason}")
        for metric, reason in bad_fleet:
            print(f"perf_gate[suite] FAIL: {metric} fleet telemetry "
                  f"evidence is vacuous ({reason})")
        for metric, leaked in bad_leaks:
            print(f"perf_gate[suite] FAIL: {metric} leaked {leaked} KV "
                  f"pool pages (pages_in_use != 0 after drain + "
                  f"prefix-cache drop — a refcount bug)")
        for metric in bad_timing:
            print(f"perf_gate[suite] FAIL: {metric} was host-timed "
                  f"(profiler trace had no device events) — a device "
                  f"metric cannot be gated from wall clock")
        return 1
    print(f"perf_gate[suite] PASS: {len(baseline)} configs within "
          f"{tolerance:.0%} of the committed baseline; "
          f"{len(RATIO_GATES)} ratio gates hold; no error rows; no "
          f"steady-state recompilation; no KV pool leaks")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="allowed model-bench drop vs best round (0.05 = 5%%)")
    ap.add_argument("--op-tolerance", type=float, default=0.25,
                    help="allowed per-op slowdown vs snapshot")
    ap.add_argument("--ops", help="fresh op-benchmark json to gate")
    ap.add_argument("--suite", action="store_true",
                    help="gate every driver model config (slow)")
    ap.add_argument("--suite-tolerance", type=float, default=0.07)
    args = ap.parse_args()

    rc = model_gate(args.tolerance)
    if args.ops:
        rc = max(rc, op_gate(args.ops, args.op_tolerance))
    if args.suite:
        rc = max(rc, suite_gate(args.suite_tolerance))
    return rc


if __name__ == "__main__":
    sys.exit(main())
