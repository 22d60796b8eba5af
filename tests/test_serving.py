"""Continuous-batching serving engine (VERDICT r4 missing #2 / directive #2).

Ref serving runtime: ``fleet_executor/dist_model.cc`` (multi-rank
inference) and the thread-safe ``AnalysisPredictor::ZeroCopyRun``
(``inference/api/analysis_predictor.h:182``). Here: one jitted tick over a
slot-based static KV cache; chunked prefill batches into the decode
program; under pp the interleaved-wave schedule fills the pipeline
bubble."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import parallel
from paddle_hackathon_tpu.core.tensor import Tensor
from paddle_hackathon_tpu.inference import ServingEngine
from paddle_hackathon_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)




def _model(num_layers=2):
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=num_layers,
                    num_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _ref(model, prompt, n=8):
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    return np.asarray(model.generate(
        Tensor(ids), max_new_tokens=n, temperature=0.0).numpy())[0]


def _prompts(k, lens=(6, 9, 5, 11, 7, 8, 10, 6)):
    rs = np.random.RandomState(5)
    return [rs.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(k)]


def test_single_request_matches_generate():
    m = _model()
    (p,) = _prompts(1)
    ref = _ref(m, p)
    eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4)
    req = eng.submit(p, max_new_tokens=8)
    assert req.wait(300)
    np.testing.assert_array_equal(req.result(), ref)
    eng.shutdown()


def test_chunked_prefill_long_prompt():
    """A prompt longer than the chunk prefills over several ticks and
    still matches the one-shot-prefill generate()."""
    m = _model()
    p = np.random.RandomState(7).randint(0, 128, (23,)).astype(np.int32)
    ref = _ref(m, p, n=6)
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4)
    req = eng.submit(p, max_new_tokens=6)
    assert req.wait(300)
    np.testing.assert_array_equal(req.result(), ref)
    eng.shutdown()


def test_staggered_admission_parity():
    """Requests joining mid-flight (the continuous part of continuous
    batching) must not perturb streams already decoding."""
    m = _model()
    prompts = _prompts(3)
    refs = [_ref(m, p) for p in prompts]
    eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4,
                        auto_run=False)
    r0 = eng.submit(prompts[0], 8)
    for _ in range(3):
        eng.step()
    r1 = eng.submit(prompts[1], 8)
    for _ in range(2):
        eng.step()
    r2 = eng.submit(prompts[2], 8)
    eng.run_until_idle()
    for req, ref in zip((r0, r1, r2), refs):
        assert req.done
        np.testing.assert_array_equal(req.result(), ref)


def test_queueing_beyond_capacity():
    """More requests than slots: the FIFO admits as slots free."""
    m = _model()
    prompts = _prompts(5)
    refs = [_ref(m, p, n=4) for p in prompts]
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4)
    reqs = [eng.submit(p, 4) for p in prompts]
    for req, ref in zip(reqs, refs):
        assert req.wait(300)
        np.testing.assert_array_equal(req.result(), ref)
    eng.shutdown()


def test_concurrent_generate_threads():
    """The ZeroCopyRun-concurrency contract: caller threads share the
    engine; requests batch into the same ticks instead of serializing."""
    m = _model()
    prompts = _prompts(4)
    refs = [_ref(m, p) for p in prompts]
    eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4)
    outs = [None] * 4

    def worker(i):
        outs[i] = eng.generate(prompts[i], max_new_tokens=8, timeout=300)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    eng.shutdown()


def test_eos_early_stop():
    m = _model()
    (p,) = _prompts(1)
    ref = _ref(m, p, n=8)
    eos = int(ref[len(p)])  # the first generated token
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                        eos_token_id=eos)
    req = eng.submit(p, max_new_tokens=8)
    assert req.wait(300)
    assert req.tokens == [eos]
    eng.shutdown()


def _truncate_at_eos(gen, eos):
    """Expected engine stream: generated tokens up to and INCLUDING the
    first eos occurrence."""
    gen = list(int(t) for t in gen)
    return gen[:gen.index(eos) + 1] if eos in gen else gen


def test_eos_mid_stream_truncates_and_frees_slot_for_pending():
    """A slot hitting EOS mid-stream frees immediately: its tokens
    truncate AT the eos, and with max_slots=1 the queued second request
    can only complete by reusing the freed slot."""
    m = _model()
    p0, p1 = _prompts(2)
    ref0, ref1 = _ref(m, p0, n=10), _ref(m, p1, n=10)
    eos = int(ref0[len(p0) + 2])  # third generated token of stream 0
    want0 = _truncate_at_eos(ref0[len(p0):], eos)
    want1 = _truncate_at_eos(ref1[len(p1):], eos)
    eng = ServingEngine(m, max_slots=1, max_len=64, chunk=4,
                        eos_token_id=eos, auto_run=False)
    r0 = eng.submit(p0, 10)
    r1 = eng.submit(p1, 10)  # pending until r0's slot frees
    eng.run_until_idle()
    assert r0.done and r1.done
    assert r0.tokens == want0 and len(r0.tokens) < 10  # truncated early
    assert r1.tokens == want1
    assert eng.stats["requests"] == 2


def test_eos_mid_stream_spec_tick_truncates():
    """Same contract through the speculative verify tick: an EOS inside
    an accepted run of tokens truncates the commit there."""
    m = _model()
    p0, p1 = _prompts(2)
    ref0, ref1 = _ref(m, p0, n=10), _ref(m, p1, n=10)
    eos = int(ref0[len(p0) + 2])
    want0 = _truncate_at_eos(ref0[len(p0):], eos)
    want1 = _truncate_at_eos(ref1[len(p1):], eos)
    eng = ServingEngine(m, max_slots=1, max_len=64, chunk=4,
                        eos_token_id=eos, auto_run=False, spec_k=4)
    r0 = eng.submit(p0, 10)
    r1 = eng.submit(p1, 10)
    eng.run_until_idle()
    assert r0.done and r1.done
    assert r0.tokens == want0 and r1.tokens == want1


def test_aggregate_throughput_scales_with_streams():
    """K concurrent streams finish in ~the tick count of ONE stream
    (slots advance in the same tick), i.e. aggregate tokens/tick ~ K x
    single-stream — the VERDICT r4 directive-2 'done' criterion, with
    tick count as the device-time proxy (each tick is one fixed-shape
    program execution)."""
    m = _model()
    p = _prompts(1)[0]
    eng1 = ServingEngine(m, max_slots=4, max_len=64, chunk=4,
                         auto_run=False)
    q = eng1.submit(p, 8)
    eng1.run_until_idle()
    assert q.done
    t1 = eng1.stats["ticks"]

    eng4 = ServingEngine(m, max_slots=4, max_len=64, chunk=4,
                         auto_run=False)
    reqs = [eng4.submit(p, 8) for _ in range(4)]
    eng4.run_until_idle()
    assert all(r.done for r in reqs)
    t4 = eng4.stats["ticks"]
    assert eng4.stats["tokens"] == 4 * eng1.stats["tokens"]
    # all four streams ride the very same ticks
    assert t4 == t1, (t4, t1)


def test_mp_sharded_engine_parity():
    """TP-sharded serving: params placed on dp x mp; the tick composes
    the same GSPMD collectives as the sharded generate()."""
    m = _model()
    prompts = _prompts(2)
    refs = [_ref(m, p) for p in prompts]
    mesh = parallel.create_mesh({"dp": 2, "mp": 2},
                                devices=jax.devices()[:4])
    try:
        parallel.shard_params(m, mesh, rule=param_sharding_spec)
        assert m._param_mesh() is not None
        eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4)
        reqs = [eng.submit(p, 8) for p in prompts]
        for req, ref in zip(reqs, refs):
            assert req.wait(300)
            np.testing.assert_array_equal(req.result(), ref)
        eng.shutdown()
    finally:
        parallel.set_mesh(None)


class TestPipelineInterleaved:
    """pp serving: the interleaved-wave schedule — every stage computes a
    DIFFERENT wave each tick, so multi-stream throughput fills the
    single-stream pipeline bubble."""

    def _setup(self):
        m = _model(num_layers=4)
        prompts = _prompts(2)
        refs = [_ref(m, p) for p in prompts]
        return m, prompts, refs

    def test_pp2_parity_two_streams(self):
        m, prompts, refs = self._setup()
        parallel.create_mesh({"pp": 2}, devices=jax.devices()[:2])
        try:
            eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4)
            assert eng._pp == 2
            reqs = [eng.submit(p, 8) for p in prompts]
            for req, ref in zip(reqs, refs):
                assert req.wait(300)
                np.testing.assert_array_equal(req.result(), ref)
            eng.shutdown()
        finally:
            parallel.set_mesh(None)

    def test_pp2_staggered_join(self):
        """A stream admitted while another wave is mid-pipeline."""
        m, prompts, refs = self._setup()
        parallel.create_mesh({"pp": 2}, devices=jax.devices()[:2])
        try:
            eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                                auto_run=False)
            r0 = eng.submit(prompts[0], 8)
            for _ in range(3):
                eng.step()
            r1 = eng.submit(prompts[1], 8)
            eng.run_until_idle()
            for req, ref in zip((r0, r1), refs):
                assert req.done
                np.testing.assert_array_equal(req.result(), ref)
        finally:
            parallel.set_mesh(None)

    def test_pp2_bubble_fill(self):
        """Two streams (one per wave) sustain ~2x one stream's
        tokens/tick: the single stream occupies one wave and idles the
        other stage — VERDICT r4 asks bubble-fill > 1.5x."""
        m, prompts, _ = self._setup()
        parallel.create_mesh({"pp": 2}, devices=jax.devices()[:2])
        try:
            eng1 = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                                 auto_run=False)
            q = eng1.submit(prompts[0], 8)
            eng1.run_until_idle()
            assert q.done
            rate1 = eng1.stats["tokens"] / eng1.stats["ticks"]

            eng2 = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                                 auto_run=False)
            reqs = [eng2.submit(p, 8) for p in prompts]
            eng2.run_until_idle()
            assert all(r.done for r in reqs)
            rate2 = eng2.stats["tokens"] / eng2.stats["ticks"]
            assert rate2 > 1.5 * rate1, (rate2, rate1)
        finally:
            parallel.set_mesh(None)

    def test_pp2_eos_mid_stream_frees_and_reuses_slot(self):
        """EOS on the pp path: the wave's exit commit truncates at eos,
        frees the slot, and a pending request admits into it."""
        m = _model(num_layers=4)
        prompts = _prompts(3)
        refs = [_ref(m, p) for p in prompts]
        eos = int(refs[0][len(prompts[0]) + 2])

        def want(i):
            return _truncate_at_eos(refs[i][len(prompts[i]):], eos)

        parallel.create_mesh({"pp": 2}, devices=jax.devices()[:2])
        try:
            eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                                eos_token_id=eos, auto_run=False)
            reqs = [eng.submit(p, 8) for p in prompts]  # 3rd queues
            eng.run_until_idle()
            assert all(r.done for r in reqs)
            assert reqs[0].tokens == want(0) and len(reqs[0].tokens) < 8
            for i in (1, 2):
                assert reqs[i].tokens == want(i)
            assert eng.stats["requests"] == 3
        finally:
            parallel.set_mesh(None)

    def test_pp2_dp2_composes(self):
        """pp x dp mesh: the tick's manual axis is pp; dp rides GSPMD."""
        m, prompts, refs = self._setup()
        parallel.create_mesh({"pp": 2, "dp": 2}, devices=jax.devices()[:4])
        try:
            eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4)
            reqs = [eng.submit(p, 8) for p in prompts]
            for req, ref in zip(reqs, refs):
                assert req.wait(300)
                np.testing.assert_array_equal(req.result(), ref)
            eng.shutdown()
        finally:
            parallel.set_mesh(None)

    def test_pp2_mp2_composes(self):
        """pp x mp: stage slabs TP-sharded by the rule; GSPMD inserts the
        in-tick mp collectives inside the manual-pp region (the engine
        analog of the pp x mp single-stream decode parity)."""
        m, prompts, refs = self._setup()
        parallel.create_mesh({"pp": 2, "mp": 2}, devices=jax.devices()[:4])
        try:
            eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4)
            reqs = [eng.submit(p, 8) for p in prompts]
            for req, ref in zip(reqs, refs):
                assert req.wait(300)
                np.testing.assert_array_equal(req.result(), ref)
            eng.shutdown()
        finally:
            parallel.set_mesh(None)


def test_sampling_path_smoke():
    """temperature>0 exercises the in-tick sampling with the per-program
    PRNG domains (single-step tag 0, multi-window tag 1): requests
    complete, tokens are in-vocab, and two engines with the same seed
    produce the same streams (keys derive from the engine's fixed key)."""
    m = _model()
    p = _prompts(1)[0]

    def run():
        eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                            temperature=0.8, top_k=20, auto_run=False)
        req = eng.submit(p, 10)
        eng.run_until_idle()
        assert req.done
        return req.result()

    out1, out2 = run(), run()
    assert out1.shape == (len(p) + 10,)
    assert ((out1 >= 0) & (out1 < 128)).all()
    np.testing.assert_array_equal(out1, out2)  # deterministic per engine


def test_capacity_guard():
    m = _model()
    eng = ServingEngine(m, max_slots=2, max_len=32, chunk=4)
    with pytest.raises(ValueError, match="cache rows"):
        eng.submit(np.arange(20, dtype=np.int32), max_new_tokens=16)
    eng.shutdown()


def test_second_driver_rejected_while_auto_loop_runs():
    """Single-driver contract (ADVICE r5): while the auto_run loop is
    live, step()/run_until_idle() from another thread must raise instead
    of re-entering the jitted tick with donated caches."""
    m = _model()
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4, auto_run=False)
    # simulate a live loop owned by another thread deterministically
    other = threading.Thread(target=lambda: None)
    with eng._lock:
        eng._running = True
        eng._loop_thread = other
    with pytest.raises(RuntimeError, match="auto_run loop"):
        eng.step()
    with pytest.raises(RuntimeError, match="auto_run loop"):
        eng.run_until_idle()
    with eng._lock:
        eng._running = False
        eng._loop_thread = None
    # with the loop drained, synchronous driving works again
    (p,) = _prompts(1)
    req = eng.submit(p, max_new_tokens=4)
    eng.run_until_idle()
    assert req.done
    # and the real auto_run path still completes end-to-end
    eng2 = ServingEngine(m, max_slots=2, max_len=64, chunk=4)
    req2 = eng2.submit(p, max_new_tokens=4)
    assert req2.wait(300)
    np.testing.assert_array_equal(req2.result(), req.result())
    eng2.shutdown()


def test_bf16_save_load_generate_roundtrip(tmp_path):
    """bf16 params survive save_for_serving -> load_for_serving (ADVICE
    r5 medium: np.savez round-trips ml_dtypes bfloat16 as '|V2' void) and
    the reloaded model generates token-for-token identically."""
    from paddle_hackathon_tpu.inference.serving import (load_for_serving,
                                                        save_for_serving)

    m = _model()
    for _, p in m.named_parameters():
        if jnp.issubdtype(p._value.dtype, jnp.floating):
            p._set_value(p._value.astype(jnp.bfloat16))
    (p,) = _prompts(1)
    ref = _ref(m, p)
    d = str(tmp_path / "bf16_model")
    save_for_serving(m, d)
    m2 = load_for_serving(d)
    for (k, a), (k2, b) in zip(sorted(m.named_parameters()),
                               sorted(m2.named_parameters())):
        assert k == k2 and a._value.dtype == b._value.dtype, (k, b._value.dtype)
    np.testing.assert_array_equal(_ref(m2, p), ref)
    # float32 artifacts stay loadable too (no dtype views involved)
    m3 = _model()
    d3 = str(tmp_path / "f32_model")
    save_for_serving(m3, d3)
    m4 = load_for_serving(d3)
    np.testing.assert_array_equal(_ref(m4, p), _ref(m3, p))
