"""precommit: the docs/STATIC_ANALYSIS.md pre-PR checklist as ONE command.

    python tools/precommit.py [--stats]

Chains, in order:

1. **pht-lint --changed** — lints the .py files your change touches
   (worktree + index + untracked + commits since the merge-base with
   main); PHT003's lock graph still spans the whole scope.
2. **fault drills** — deterministic ``PHT_FAULTS`` drills against
   host-only stubs (no tick program compiles).  The fleet
   dispatch-failover drill — an injected ``fleet.dispatch`` fault
   plus a submit-time replica death must re-dispatch cleanly (retry
   books, survivor completes); the fleet-telemetry drill — a forced
   mid-request failover must land router + both replicas' spans on ONE
   rid-stitched swimlane in the merged chrome trace, with the
   federated exposition labeled per replica and zero leaked pages.
   The started-stream loud-failure path and mid-flight kills live in
   ``tests/test_fleet.py``'s acceptance drills, not here.  Add new
   drills to ``_DRILLS``.

Exit codes (perf_gate convention): 0 = every step that ran passed,
1 = at least one step failed, 2 = usage error.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ``PHT_FAULTS`` fault drills run as step 2: (name, env-spec, script).
# Each script runs in a fresh interpreter with the spec armed through
# the environment (the same delivery the crash drills use), against
# host-only stubs — no tick program compiles, so the step stays cheap.
_FLEET_DRILL = """
import numpy as np, threading, itertools
from paddle_hackathon_tpu.inference.fleet import (
    FleetRouter, StreamInterruptedError)

_ids = itertools.count()
class Req:
    def __init__(self, prompt, n, on_token=None):
        self.rid = next(_ids); self.prompt = np.asarray(prompt, np.int32)
        self.tokens = []; self.done = False; self.error = None
        self._event = threading.Event(); self.on_token = on_token; self.n = n
    def finish(self):
        self.tokens = list(range(self.n)); self.done = True
        self._event.set()
    def result(self):
        if self.error is not None:
            raise RuntimeError('failed') from self.error
        return np.concatenate([self.prompt, np.asarray(self.tokens, np.int32)])

class Stub:
    def __init__(self, name, headroom):
        self.engine_id = name; self.headroom = headroom; self.submitted = []
    def load_report(self):
        return {'version': 1, 'engine': self.engine_id, 'draining': False,
                'slots': {'max': 8, 'active': 0, 'free': 8},
                'queue': {'depth': 0, 'oldest_wait_s': 0.0},
                'admission': {'headroom_tokens': self.headroom}}
    def submit(self, prompt, max_new_tokens, deadline_s=None,
               on_token=None, **kw):
        r = Req(prompt, max_new_tokens, on_token)
        self.submitted.append(r); r.finish(); return r
    def drain(self, timeout=None): pass
    def shutdown(self, timeout=None): pass

a, b = Stub('drill-a', 9000), Stub('drill-b', 100)
router = FleetRouter([a, b], backoff_s=0.001, breaker_failures=1)
# PHT_FAULTS fleet.dispatch=fail@1 kills the FIRST placement attempt:
# the retry must land the request anyway and book exactly one retry
fr = router.submit([1, 2, 3], 4)
assert fr.wait(10) and fr.error is None, fr.error
assert list(fr.result()) == [1, 2, 3, 0, 1, 2, 3]
assert fr.retries == 0  # placement retry, not a failover
from paddle_hackathon_tpu.observability import get_registry
assert get_registry().total('fleet_retries_total',
                            fleet=router.fleet_id) == 1
# replica death before any token: failover to the survivor
dead = Stub('drill-c', 9000); live = Stub('drill-d', 10)
dead.submit = lambda *a, **k: (_ for _ in ()).throw(
    RuntimeError('replica down'))
r2 = FleetRouter([dead, live], backoff_s=0.001, breaker_failures=1)
fr2 = r2.submit([7], 2)
assert fr2.wait(10) and fr2.replica == 'drill-d'
print('fleet drill: dispatch-fault retry + failover OK')
"""

# Session eviction under drain, both layers.  Engine side: a draining
# replica must DONATE every retained session chain to its prefix cache
# (returning conversations replay from cached pages, and nothing leaks
# — construction-only, no tick compiles: the session record is
# fabricated white-box and drain() on an idle sync engine is pure
# host work).  Fleet side: the armed fleet.dispatch fault kills the
# session turn's first placement; the retry must still land AND pin,
# the pin must stick, and draining the pinned replica must clear it so
# the next turn migrates to the survivor carrying the session kwarg.
_SESSION_DRILL = """
import numpy as np, threading, itertools
from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_hackathon_tpu.inference.serving import ServingEngine, _Session

cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                num_heads=4, max_position_embeddings=128,
                hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                use_flash_attention=False)
m = GPTForCausalLM(cfg); m.eval()
eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4, auto_run=False,
                    cache_mode="paged", page_size=8, num_pages=12)
# fabricate a retained 20-token session (3 pages, 2 of them full)
pages = eng._pool.alloc(3)
sess = _Session("drill")
sess.tokens = np.arange(20, dtype=np.int32)
sess.kv_len = 20
sess.pages = list(pages)
eng._sessions["drill"] = sess
assert eng.kv_pages_in_use == 3
eng.drain(timeout=10)
# drain donated the chain: session record gone, the 2 FULL pages now
# live in the prefix cache, the partial tail page was freed
assert not eng._sessions
assert int(eng._c["sessions_evicted"].value) == 1
assert eng.kv_pages_in_use == 2
eng.drop_prefix_cache()
assert eng.kv_pages_in_use == 0     # zero leak
eng.shutdown(timeout=5)

from paddle_hackathon_tpu.inference.fleet import FleetRouter
_ids = itertools.count()
class Req:
    def __init__(self, prompt, n, on_token=None):
        self.rid = next(_ids); self.prompt = np.asarray(prompt, np.int32)
        self.tokens = []; self.done = False; self.error = None
        self._event = threading.Event(); self.on_token = on_token; self.n = n
    def finish(self):
        self.tokens = list(range(self.n)); self.done = True
        self._event.set()
    def result(self):
        return np.concatenate([self.prompt, np.asarray(self.tokens,
                                                       np.int32)])

class Stub:
    def __init__(self, name, headroom):
        self.engine_id = name; self.headroom = headroom
        self.sessions_seen = []
    def load_report(self):
        return {'version': 1, 'engine': self.engine_id, 'draining': False,
                'slots': {'max': 8, 'active': 0, 'free': 8},
                'queue': {'depth': 0, 'oldest_wait_s': 0.0},
                'admission': {'headroom_tokens': self.headroom}}
    def submit(self, prompt, max_new_tokens, deadline_s=None,
               on_token=None, **kw):
        self.sessions_seen.append(kw.get('session'))
        r = Req(prompt, max_new_tokens, on_token)
        r.finish(); return r
    def drain(self, timeout=None): pass
    def shutdown(self, timeout=None): pass

a, b = Stub('sess-a', 9000), Stub('sess-b', 100)
router = FleetRouter([a, b], backoff_s=0.001, breaker_failures=3)
# the armed fleet.dispatch=fail@1 kills THIS turn's first placement:
# the retry must land it anyway and still record the pin
fr = router.submit([1, 2, 3], 4, session='chat')
assert fr.wait(10) and fr.error is None, fr.error
pinned = router._session_pins.get('chat')
assert pinned == fr.replica and pinned in ('sess-a', 'sess-b')
assert router.introspect_requests()['session_pins'] == 1
# second turn sticks to the pin regardless of headroom
fr2 = router.submit([1, 2, 3, 9], 4, session='chat')
assert fr2.wait(10) and fr2.replica == pinned
# draining the pinned replica clears the pin; the next turn migrates
# to the survivor and re-pins there, session kwarg intact
router.drain(pinned)
assert 'chat' not in router._session_pins
other = 'sess-b' if pinned == 'sess-a' else 'sess-a'
fr3 = router.submit([1, 2, 3, 9, 9], 4, session='chat')
assert fr3.wait(10) and fr3.replica == other
assert router._session_pins.get('chat') == other
survivor = a if other == 'sess-a' else b
assert survivor.sessions_seen[-1] == 'chat'
router.shutdown()
print('session drill: drain donation + pin migration under '
      'dispatch fault OK')
"""

# Fleet-telemetry drill (PR 19).  Two host-only stub replicas behind a
# FleetRouter, span sink armed; the PHT_FAULTS ``serving.tick[tele-a]``
# point (which the stub fires after accepting a request, the same point
# a real engine's tick loop owns) kills the first placement AFTER
# submit succeeded — a genuine failover, not a placement retry.  The
# drill then closes the whole observability loop: federated exposition
# carries both replicas under bounded ``replica=`` labels plus the
# fleet-only series, ``load_report()`` serializes, and the merged
# chrome trace (``--stitch-fleet`` pass) shows router dispatch +
# failover spans AND both replicas' lifecycle spans — including a
# rid-only tick span mapped via the rid bridge — on ONE
# ``fleet_rid`` swimlane.  Fake KV page accounting on the stubs must
# read zero after the failover (the dead attempt released its pages).
_TELEMETRY_DRILL = """
import itertools, json, os, tempfile, threading, time
import numpy as np
from paddle_hackathon_tpu.observability import faults as _faults
from paddle_hackathon_tpu.observability import tracing as tr
from paddle_hackathon_tpu.inference.fleet import FleetRouter
from paddle_hackathon_tpu.profiler.cross_stack import merge_traces

_ids = itertools.count(100)
class Req:
    def __init__(self, prompt, n):
        self.rid = next(_ids); self.prompt = np.asarray(prompt, np.int32)
        self.tokens = []; self.done = False; self.error = None
        self._event = threading.Event()

class Stub:
    # host-only replica with fake KV page accounting, a per-replica
    # exposition, and the same lifecycle spans ServingEngine emits:
    # serving.request carries rid + fleet_rid, the per-tick span
    # carries rid ONLY (the stitch pass must bridge it via the rid map)
    def __init__(self, name, headroom):
        self.engine_id = name; self.headroom = headroom
        self.pages_in_use = 0
    def load_report(self):
        return {'version': 1, 'engine': self.engine_id, 'draining': False,
                'slots': {'max': 8, 'active': 0, 'free': 8},
                'queue': {'depth': 0, 'oldest_wait_s': 0.0},
                'admission': {'headroom_tokens': self.headroom}}
    def metrics_text(self):
        return ('# HELP pht_stub_pages fake page gauge\\n'
                '# TYPE pht_stub_pages gauge\\n'
                'pht_stub_pages{engine="%s"} %d\\n'
                % (self.engine_id, self.pages_in_use))
    def submit(self, prompt, max_new_tokens, deadline_s=None,
               on_token=None, trace_ctx=None, **kw):
        r = Req(prompt, max_new_tokens)
        self.pages_in_use += 2
        fa = ({'fleet_rid': trace_ctx['fleet_rid']} if trace_ctx else {})
        sp = tr.start_span('serving.request', _tid=r.rid, rid=r.rid,
                           engine=self.engine_id, **fa)
        t0 = time.perf_counter_ns()
        tr.add_span('serving.decode', t0, t0 + 1000, _tid=r.rid,
                    rid=r.rid, engine=self.engine_id, slot=0)
        try:
            _faults.point('serving.tick[%s]' % self.engine_id)
        except Exception as e:
            # armed tick fault kills the request AFTER placement with
            # zero tokens streamed: the router must fail it over
            r.error = e; self.pages_in_use -= 2
            sp.end(error=type(e).__name__); r._event.set(); return r
        r.tokens = list(range(max_new_tokens)); r.done = True
        self.pages_in_use -= 2
        sp.end(tokens=len(r.tokens)); r._event.set(); return r
    def drain(self, timeout=None): pass
    def shutdown(self, timeout=None): pass

spans = []
tr.set_span_sink(lambda name, t0, t1, tid, attrs: spans.append(
    {'name': name, 'ph': 'X', 'pid': 0, 'tid': tid, 'ts': t0 / 1e3,
     'dur': max((t1 - t0) / 1e3, 0.001), 'args': dict(attrs or {})}))
tr.enable_tracing()
# headroom skew makes tele-a the deterministic first pick: the armed
# serving.tick[tele-a] fault then forces the failover onto tele-b
a, b = Stub('tele-a', 9000), Stub('tele-b', 100)
router = FleetRouter([a, b], backoff_s=0.001)
fr = router.submit([1, 2, 3], 4)
assert fr.wait(10) and fr.error is None, fr.error
assert fr.replica == 'tele-b' and fr.retries == 1, (fr.replica, fr.retries)
tr.disable_tracing(); tr.set_span_sink(None)

# federation: both replicas under bounded replica= labels + fleet series
text = router.expose_text()
assert 'replica="tele-a"' in text and 'replica="tele-b"' in text, text
assert 'fleet_dispatch_seconds' in text and 'fleet_retries_total' in text
json.dumps(router.load_report())      # aggregated report serializes

d = tempfile.mkdtemp()
p = os.path.join(d, 'trace.json')
with open(p, 'w') as f:
    json.dump({'traceEvents': spans}, f)
merged = merge_traces([p], stitch_fleet=True)
ev = merged['traceEvents']
meta = [e for e in ev if e.get('ph') == 'M'
        and e.get('name') == 'process_name'
        and 'rid-stitched' in (e.get('args') or {}).get('name', '')]
assert meta, 'stitched fleet process missing'
fpid = meta[0]['pid']
lane = [e for e in ev if e.get('ph') != 'M' and e['pid'] == fpid
        and e['tid'] == fr.fleet_rid]
names = set(e['name'] for e in lane)
assert {'fleet.route', 'fleet.dispatch', 'fleet.failover',
        'serving.request', 'serving.decode'} <= names, names
engines = set((e.get('args') or {}).get('engine') for e in lane
              if e['name'] == 'serving.request')
assert engines == {'tele-a', 'tele-b'}, engines
assert a.pages_in_use == 0 and b.pages_in_use == 0, 'page leak'
router.shutdown()
print('telemetry drill: failover stitched onto one fleet lane, '
      'federation labeled per replica, zero page leak OK')
"""

# Priority-inversion drain drill (PR 17).  A real (tiny, CPU) engine
# behind a FleetRouter: a batch stream fills the page pool, an
# interactive arrival preempts it mid-decode (pages released, request
# re-queued), and the replica is drained WHILE the preempted stream
# sits in the queue.  Drain must complete — a scheduler that refused to
# re-admit the demoted request while draining would wedge the drain on
# a priority inversion — the preempted stream must still produce its
# full token count (re-queued work is never lost), and the pool must
# read zero after the cache drop (preemption releases/donates pages,
# never leaks them).  The one drill that compiles tick programs
# (~tens of seconds): preempt-while-draining needs real ticks.
_PRIORITY_DRILL = """
import time
import numpy as np
from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_hackathon_tpu.inference.serving import ServingEngine
from paddle_hackathon_tpu.inference.fleet import FleetRouter

cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                num_heads=4, max_position_embeddings=128,
                hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                use_flash_attention=False)
m = GPTForCausalLM(cfg); m.eval()
# pool sized so the batch request's footprint (8 pages) fills the
# usable pool: the interactive arrival (3 pages) can only admit by
# preempting it
eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                    cache_mode="paged", page_size=8, num_pages=9)
router = FleetRouter([eng])
name = eng._engine_id
rb = router.submit(np.arange(16, dtype=np.int32), 40, priority="batch")
end = time.monotonic() + 120
while not rb.tokens and time.monotonic() < end:
    time.sleep(0.01)
assert rb.tokens, "batch stream never started decoding"
ri = router.submit(np.arange(8, dtype=np.int32) + 3, 8,
                   priority="interactive")
while int(eng._c["preemptions"].value) < 1 and time.monotonic() < end:
    time.sleep(0.01)
assert int(eng._c["preemptions"].value) >= 1, "no preemption fired"
# drain WHILE the preempted batch stream sits re-queued: the drain
# must re-admit and finish it, not wedge on the inversion
router.drain(name, timeout=120)
assert rb.done and rb.error is None, rb.error
assert ri.done and ri.error is None, ri.error
assert len(rb.tokens) == 40, (len(rb.tokens), "preempted work lost")
assert len(ri.tokens) == 8
eng.drop_prefix_cache()
assert eng.kv_pages_in_use == 0, eng.kv_pages_in_use
print('priority drill: preempt mid-decode + drain-under-inversion '
      'completed, zero page leak OK')
"""

# ZeRO x pp composition smoke (PR 18).  zero_stage>=1 must compose
# with the pipeline trainer: moments dp-sharded WITHIN each stage (or
# host numpy under zero_offload), and the composed flat namespace must
# dp-reshard through restore_like.  On jax>=0.6 (partial-manual
# shard_map available) the drill also runs one composed superstep
# under the donation sanitizer; on this container's jax<0.6 the
# superstep path is structurally gated (same gate as the pp test
# files), so the drill exercises construction, placement, and the
# dp2->dp4 reshard-resume instead — the pieces that run everywhere.
_ZERO_PP_SMOKE = """
import os
import tempfile
# the pp2 x dp2 mesh needs the virtual 8-device CPU topology the test
# conftest arranges; this subprocess must arrange it before jax imports
_flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()
import jax
import numpy as np
import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import parallel
from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                         param_sharding_spec)
from paddle_hackathon_tpu.observability import sanitizers
from paddle_hackathon_tpu.parallel.checkpointing import (
    CheckpointManager, flatten_train_state, restore_like)

def build(mesh_dims, **kw):
    n = int(np.prod(list(mesh_dims.values())))
    mesh = parallel.create_mesh(mesh_dims, devices=jax.devices()[:n])
    paddle.seed(123)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=4, num_heads=2,
        intermediate_size=32, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        use_flash_attention=False))
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
        zero_stage=1, grad_clip_norm=None, **kw)
    return step, state

k = 'gpt.blocks.$stacked.attn.qkv_proj.weight'
with sanitizers.donation_sanitizer():
    step, state = build({'pp': 2, 'dp': 2})
    mom = state['opt_state'][k]['m']
    spec = tuple(mom.sharding.spec)
    axes = [a for s in spec if s is not None
            for a in (s if isinstance(s, tuple) else (s,))]
    assert spec[0] == 'pp' and 'dp' in axes, spec
    if hasattr(jax, 'set_mesh'):
        r = np.random.RandomState(0)
        ids = np.asarray(r.randint(0, 64, (8, 16)))
        labels = np.asarray(r.randint(0, 64, (8, 16)))
        state, loss = step(state, ids, labels, jax.random.key(0))
        assert np.isfinite(float(loss)), loss
        mode = 'superstep loss %.4f' % float(loss)
    else:
        _, st_off = build({'pp': 2, 'dp': 2}, zero_offload=True)
        assert isinstance(st_off['opt_state'][k]['m'], np.ndarray)
        key_order = list(state['params'])
        flat = flatten_train_state(
            state['params'],
            [state['opt_state'][q] for q in key_order], state['step'])
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(flat, step=0, block=True)
            mgr.close()
            _, state2 = build({'pp': 2, 'dp': 4})
            flat2 = flatten_train_state(
                state2['params'],
                [state2['opt_state'][q] for q in key_order],
                state2['step'])
            placed, _ = restore_like(d, flat2)
        i = key_order.index(k)
        np.testing.assert_array_equal(
            np.asarray(placed['opt::%d::m' % i]),
            np.asarray(flat['opt::%d::m' % i]))
        mode = 'placement + dp2->dp4 reshard (superstep gated)'
print('zero-pp smoke: composed state sharded pp x dp, ' + mode
      + ', donation-sanitizer clean OK')
"""

# Program-observatory retrace drill: drive one instrumented site with a
# changed shape (numpy callable — construction only, no jax compile) and
# assert the forensics landed end-to-end: the registry's cause record
# names the changed argument, the flight event carries the same cause,
# the jit_builds_total/jit_compile_seconds series exist, and both CLI
# renderers (metrics_dump over the metric snapshot, program_report over
# the registry snapshot) show the new rows.
_PROGRAM_DRILL = """
import io
import numpy as np
from paddle_hackathon_tpu import observability as obs
from paddle_hackathon_tpu.observability import metrics, programs
from tools import metrics_dump, program_report

prog = programs.get_program_registry()

def tick(ids, mask):
    return ids.sum() + mask.sum()

w = obs.instrument_jit(tick, site='drill.tick')
a = np.zeros((8, 16), np.float32)
m = np.ones((8,), np.float32)
w(a, m); w(a, m)                       # build 1, then steady-state
w(np.zeros((8, 24), np.float32), m)    # forced retrace: seqlen change

site = prog.snapshot()['sites']['drill.tick']
assert site['builds'] == 2, site
cause = site['history'][-1]['cause']
for frag in ('arg[0]', '`ids`', '8,16', '8,24'):
    assert frag in cause, (frag, cause)
ev = [e for e in obs.get_flight_recorder().events()
      if e.get('kind') == 'program_build' and e.get('site') == 'drill.tick']
assert len(ev) == 2 and ev[-1]['cause'] == cause, ev
reg = metrics.get_registry()
assert reg.total('jit_builds_total', site='drill.tick') == 2.0
out = io.StringIO()
metrics_dump.render(reg.snapshot(), out=out)
assert 'jit_compile_seconds{site=drill.tick}' in out.getvalue()
out = io.StringIO()
program_report.render(prog.snapshot(), out=out)
program_report.render_causes(prog.snapshot(), out=out, site='drill.tick')
assert 'drill.tick' in out.getvalue() and cause in out.getvalue()
print('program drill: retrace cause %r recorded, flight + metrics + '
      'reports agree OK' % cause)
"""

_DRILLS = [
    ("fleet-drill", "fleet.dispatch=fail@1", _FLEET_DRILL),
    ("session-drill", "fleet.dispatch=fail@1", _SESSION_DRILL),
    ("telemetry-drill", "serving.tick[tele-a]=fail@1", _TELEMETRY_DRILL),
    ("priority-drill", "", _PRIORITY_DRILL),
    ("zero-pp-smoke", "", _ZERO_PP_SMOKE),
    ("program-drill", "", _PROGRAM_DRILL),
]


def _run_step(name: str, argv, results, display=None, env=None) -> None:
    print(f"== {name}: {display or ' '.join(argv)}")
    run_env = None
    if env:
        run_env = dict(os.environ)
        run_env.update(env)
    proc = subprocess.run(argv, cwd=REPO_ROOT, env=run_env)
    ok = proc.returncode == 0
    results.append((name, "PASS" if ok else f"FAIL (rc={proc.returncode})"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/precommit.py",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        description=__doc__)
    ap.add_argument("--stats", action="store_true",
                    help="pass --stats through to pht-lint (per-rule "
                         "counts + per-pass wall time)")
    args = ap.parse_args(argv)

    results = []

    lint_cmd = [sys.executable, "-m", "tools.pht_lint", "--changed"]
    if args.stats:
        lint_cmd.append("--stats")
    _run_step("pht-lint", lint_cmd, results)

    for name, spec, script in _DRILLS:
        _run_step(name, [sys.executable, "-c", script], results,
                  display=f"PHT_FAULTS='{spec}' python -c "
                          f"'<host-only {name}>'",
                  env={"PHT_FAULTS": spec})

    print("\nprecommit summary:")
    width = max(len(n) for n, _ in results)
    for name, status in results:
        print(f"  {name:<{width}}  {status}")
    return 1 if any(s.startswith("FAIL") for _, s in results) else 0


if __name__ == "__main__":
    sys.exit(main())
