"""Keye-VL-2.0's language model (``models/keye_vl2.py``) and DeepSeek Sparse
Attention (``incubate/nn/functional/sparse_attention.py``, kernels in
``incubate/nn/kernels/dsa_attention.py``, interpreted on the CPU) against
plain float32 ``jax.numpy`` at toy sizes: the benchmark's reference for
the whole model, and dense attention with an explicit mask for the
kernels."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_hackathon_tpu.incubate.nn.functional import \
    sparse_attention as sa  # noqa: E402
from paddle_hackathon_tpu.incubate.nn.kernels import \
    dsa_attention as kern  # noqa: E402

# a toy of the cell's configuration file: s 128 with top-k 16, so that
# most queries choose among more keys than they keep
TOY = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "rope_theta": 10000000, "num_experts": 16, "num_experts_published": 16,
    "experts_held": [0, 16], "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "index_rope_dim": 8,
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 128,
                  "q_chunk_size": 128, "topk": 16}}
S = 128


def _program(cfg, params):
    from benchmark.program_configs import keye_vl2 as pc
    from paddle_hackathon_tpu.models import KeyeVL2ForCausalLM
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "num_experts", "num_experts_published",
            "experts_held", "num_experts_per_tok", "moe_intermediate_size",
            "norm_topk_prob", "rms_norm_eps", "sa_config", "index_rope_dim")
    model = KeyeVL2ForCausalLM(pc.config(**{k: cfg[k] for k in keys}))
    for k, p in model.named_parameters():
        p._set_value(params[k])
    return model


@pytest.fixture(scope="module")
def toy():
    from benchmark import weights
    from benchmark.reference import keye_vl2_f32 as ref
    params = weights.make_params(7, ref.param_spec(TOY), jnp.float32)
    (ids, labels), = weights.make_batches(7, 1, 2, S, TOY["vocab_size"])
    return params, ids, labels


def _program_loss(model, params, ids, labels):
    """The default loss of ``make_sharded_train_step``, as a function of
    the parameters: mean cross-entropy plus the layers' weighted aux."""
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.nn.layer import functional_call
    from paddle_hackathon_tpu.parallel.moe import collect_moe_aux

    def loss(p):
        out = functional_call(model, p, (Tensor(ids),))
        logits = out._value if isinstance(out, Tensor) else out
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        ce = jnp.mean(jax.nn.logsumexp(logits, -1) - picked)
        return ce + collect_moe_aux(model), logits

    return loss


def test_logits_loss_and_every_gradient_against_the_reference(toy):
    from benchmark.reference import keye_vl2_f32 as ref
    params, ids, labels = toy
    model = _program(TOY, params)
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            _program_loss(model, params, ids, labels), has_aux=True)(params)
        want_logits, want_kl = ref.logits_fn(params, ids, TOY)
        got = {}
        want_loss = ref.grads_pass(params, ids, labels, TOY,
                                   got.__setitem__)
    scale = float(jnp.abs(want_logits).max())
    assert float(jnp.abs(logits - want_logits).max()) <= 2e-4 * scale
    # the indexer's loss is in both, and is no small part of either
    assert float(want_kl) > 0.05
    assert abs(float(loss) - float(want_loss)) <= 1e-4 * float(want_loss)
    assert set(grads) == set(got) == set(ref.param_spec(TOY))
    for k, g in got.items():
        size = float(jnp.abs(g).max())
        # all 16 experts are held here, so the routers train too
        assert size > 0, k
        assert float(jnp.abs(grads[k] - g).max()) <= 3e-3 * size, k


def test_the_compiled_hapi_trainer_takes_the_indexers_loss_at_weight_one(toy):
    """``Model.fit``'s compiled trainer adds the layers' aux terms as the
    default loss does: the indexer's KL at its own weight 1, not at the
    MoE balance weight (0.01 where the model names none)."""
    from benchmark.reference import keye_vl2_f32 as ref
    from paddle_hackathon_tpu import hapi
    from paddle_hackathon_tpu import optimizer as optim
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.hapi.compiled import CompiledTrainer
    params, ids, labels = toy

    def ce(logits, lab):
        lg, lab = logits._value, lab._value
        picked = jnp.take_along_axis(lg, lab[..., None], -1)[..., 0]
        return Tensor(jnp.mean(jax.nn.logsumexp(lg, -1) - picked))

    # the trainer donates its state: the model gets copies of the leaves
    model = _program(TOY, {k: jnp.array(v) for k, v in params.items()})
    m = hapi.Model(model)
    m.prepare(optimizer=optim.SGD(learning_rate=0.0,
                                  parameters=model.parameters()), loss=ce)
    with jax.default_matmul_precision("highest"):
        trainer = CompiledTrainer(m)
        xs, ys = (ids[None],), (labels[None],)
        trainer.ensure_program(xs, ys)
        got = float(trainer.run(xs, ys)[0])
        logits, kl = ref.logits_fn(params, ids, TOY)
        want = float(ce(Tensor(logits), Tensor(labels))._value) + float(kl)
    assert float(kl) > 0.05
    assert abs(got - want) <= 1e-4 * want


def test_logits_past_one_block_of_the_references_rows(toy):
    """At s = 4 x ``ATTN_ROWS`` the reference computes attention in four
    blocks of query rows under its ``lax.scan``: the blocks' outputs go
    back to their own tokens, and the program's logits match them."""
    from benchmark import weights
    from benchmark.reference import keye_vl2_f32 as ref
    params, _, _ = toy
    s = 4 * ref.ATTN_ROWS
    (ids, _), = weights.make_batches(8, 1, 1, s, TOY["vocab_size"])
    model = _program(TOY, params)
    from paddle_hackathon_tpu.core.tensor import Tensor
    with jax.default_matmul_precision("highest"):
        logits = model(Tensor(ids))._value
        want, _ = ref.logits_fn(params, ids, TOY)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(logits - want).max()) <= 2e-4 * scale


def test_the_indexer_is_trained_by_its_loss_alone(toy):
    """The cross-entropy reaches nothing of the indexer (its input is
    detached and the selection has no gradient), and its KL reaches
    nothing else."""
    params, ids, labels = toy
    model = _program(TOY, params)
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.nn.layer import functional_call

    def raw(v):
        return v._value if isinstance(v, Tensor) else v

    def ce(p):
        logits = raw(functional_call(model, p, (Tensor(ids),)))
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    def aux(p):
        functional_call(model, p, (Tensor(ids),))
        return sum(raw(layer.self_attn.l_aux) for layer in model.layers)

    g_ce, g_aux = jax.grad(ce)(params), jax.grad(aux)(params)
    for k in params:
        indexer = ".indexer." in k
        assert (float(jnp.abs(g_ce[k]).max()) == 0) == indexer, k
        assert (float(jnp.abs(g_aux[k]).max()) > 0) == indexer, k


def test_attention_over_every_causal_key_fails_the_comparison(toy):
    """The fault: the selection left out (top-k as large as the sequence)
    moves the logits far past the comparison's tolerance."""
    from benchmark.reference import keye_vl2_f32 as ref
    params, ids, _ = toy
    dense = dict(TOY, sa_config=dict(TOY["sa_config"], topk=S))
    model = _program(dense, params)
    from paddle_hackathon_tpu.core.tensor import Tensor
    with jax.default_matmul_precision("highest"):
        logits = model(Tensor(ids))._value
        want, _ = ref.logits_fn(params, ids, TOY)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(logits - want).max()) > 50 * 2e-4 * scale


def _inputs(seed, b, s, heads, kv_heads, d, ih, idim, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.key(seed), 6)

    def f(k, shape):
        return jax.random.normal(k, shape).astype(dtype)

    return (f(ks[0], (b, s, heads * d)), f(ks[1], (b, s, kv_heads * d)),
            f(ks[2], (b, s, kv_heads * d)), f(ks[3], (b, ih, s, idim)),
            f(ks[4], (b, s, idim)), jax.random.normal(ks[5], (b, s, ih)))


def _scores(qi, ki, w):
    dots = jnp.einsum("bhtd,bsd->bhts", qi.astype(jnp.float32),
                      ki.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum("bth,bhts->bts", w, jax.nn.relu(dots),
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("s, topk", [(256, 32), (1024, 100), (2048, 256)])
def test_the_selection_is_a_sorts(s, topk, monkeypatch):
    """The bits ``select_keys`` packs are, for every query, the ``topk``
    earlier keys of largest score by a stable sort (ties to the earlier
    key), all of them where fewer are there; its log-sum-exp is over
    them.  Read in chunks of 256 columns, each group of rows reads from
    one chunk to all of them."""
    monkeypatch.setattr(kern, "SELECT_CHUNK", 256)
    _, _, _, qi, ki, w = _inputs(1, 2, s, 4, 2, 32, 4, 64)
    index = _scores(qi, ki, w)
    words, lse_i = sa.select_keys(qi, w, ki, topk)
    got = np.asarray(sa.selection_mask(words, s))
    causal = np.tril(np.ones((s, s), bool))
    masked = np.where(causal, np.asarray(index), -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")[..., :topk]
    want = np.zeros_like(got)
    np.put_along_axis(want, order, True, -1)
    want &= causal
    assert (got == want).all()
    assert got.sum() == 2 * sum(min(t + 1, topk) for t in range(s))
    lse = jax.nn.logsumexp(jnp.where(want, index, -jnp.inf), -1)
    np.testing.assert_allclose(np.asarray(lse_i[:, 0]), np.asarray(lse),
                               rtol=1e-5, atol=1e-5)
    pairs, causal_tiles, live = np.asarray(sa.dsa_counters(words))
    n = s // kern.blocks(s)
    assert pairs == got.sum() and causal_tiles == 2 * n * (n + 1) // 2
    assert 0 < live <= causal_tiles


def test_the_threshold_kernel_keeps_what_top_k_keeps():
    """Ties, signed zeros and rows with fewer finite scores than ``k``:
    the kept columns are ``jax.lax.top_k``'s, and the log-sum-exp is over
    them."""
    rng = np.random.default_rng(3)
    s, k = 512, 40
    x = rng.standard_normal((2, 32, s)).astype(np.float32)
    x[0, 0] = 0.0                               # every score alike
    x[0, 1, ::3] = 1.5                          # ties at the threshold
    x[0, 2, :9] = -0.0
    x[1, :, 30:] = -np.inf                      # fewer than k finite
    x[1, 5, :] = -np.inf
    x[1, 5, 7] = 2.0
    x = jnp.asarray(x)
    thr, cut, lse = kern.select_threshold(x, k)
    key = kern.order_keys(x)
    cols = jnp.arange(s)
    got = (key > thr) | ((key == thr) & (cols <= cut))
    _, idx = jax.lax.top_k(x, k)
    want = np.zeros(got.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, -1)
    assert (np.asarray(got) == want).all()
    np.testing.assert_allclose(
        np.asarray(lse[..., 0]),
        np.asarray(jax.nn.logsumexp(jnp.where(want, x, -jnp.inf), -1)),
        rtol=1e-5)


def _causal_rows(seed, row0, rows, s, kind):
    """(1, rows, s) float32 scores of the queries ``row0 ...``, ``-inf``
    past each one's position, as ``dsa_index`` writes them."""
    x = np.random.default_rng(seed).standard_normal((1, rows, s))
    if kind == "ties":                  # few values: ties at thresholds
        x = np.round(x * 2) / 2
    elif kind == "signed zeros":        # the threshold among the zeros
        x = np.where(x < 0.5, 0.0, x)
        x[..., ::2] = np.where(x[..., ::2] == 0.0, -0.0, x[..., ::2])
    pos = row0 + np.arange(rows)[:, None]
    x = np.where(np.arange(s)[None] <= pos, x, -np.inf)
    return jnp.asarray(x.astype(np.float32)), pos


@pytest.mark.parametrize("row0, kind", [
    (0, "normal"),                      # every row with fewer than k keys
    (192, "normal"),                    # rows on both sides of k
    (1024, "normal"),                   # every row past k
    (1920, "normal"),                   # the last group reads every chunk
    (1024, "ties"),
    (512, "signed zeros"),
])
@pytest.mark.parametrize("chunk", [256, kern.SELECT_CHUNK])
def test_reading_up_to_the_rows_positions_keeps_what_reading_all_keeps(
        row0, kind, chunk, monkeypatch):
    """``select_threshold`` told the rows' first position reads each
    group's columns up to its last row alone: the columns kept at or
    before each row's position, and the log-sum-exp, are those of reading
    every column, and ``jax.lax.top_k``'s."""
    monkeypatch.setattr(kern, "SELECT_CHUNK", chunk)
    rows, s, k = 128, 2048, 256
    x, pos = _causal_rows(row0, row0, rows, s, kind)
    cols = np.arange(s)[None]

    def kept(thr, cut):
        key = np.asarray(kern.order_keys(x))[0]
        thr, cut = np.asarray(thr)[0], np.asarray(cut)[0]
        return ((key > thr) | ((key == thr) & (cols <= cut))) & (cols <= pos)

    thr, cut, lse = kern.select_threshold(x, k, row0)
    want_thr, want_cut, want_lse = kern.select_threshold(x, k)
    assert (kept(thr, cut) == kept(want_thr, want_cut)).all()
    assert np.array_equal(np.asarray(lse), np.asarray(want_lse))
    _, idx = jax.lax.top_k(x, k)
    top = np.zeros((rows, s), bool)
    np.put_along_axis(top, np.asarray(idx)[0], True, -1)
    assert (kept(thr, cut) == (top & (cols <= pos))).all()


def test_ties_go_to_the_earlier_key():
    """Equal scores: with every score of a row alike, the first ``topk``
    keys are the ones kept."""
    b, s, topk = 1, 256, 16
    qi = jnp.ones((b, 2, s, 16), jnp.bfloat16)
    ki = jnp.ones((b, s, 16), jnp.bfloat16)
    w = jnp.ones((b, s, 2))
    words, _ = sa.select_keys(qi, w, ki, topk)
    got = np.asarray(sa.selection_mask(words, s))[0]
    want = np.tril(np.ones((s, s), bool)) & (np.arange(s)[None] < topk)
    assert (got == want).all()


def _dense(q, k, v, mask, heads, scale):
    b, s, _ = q.shape
    d = q.shape[-1] // heads
    kv = k.shape[-1] // d
    qh = q.astype(jnp.float32).reshape(b, s, heads, d)
    kh, vh = (jnp.repeat(x.astype(jnp.float32).reshape(b, s, kv, d),
                         heads // kv, 2) for x in (k, v))
    logits = jnp.einsum("bthd,bshd->bhts", qh, kh,
                        precision=jax.lax.Precision.HIGHEST) * scale
    logits = jnp.where(mask[:, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, -1)
    out = jnp.einsum("bhts,bshd->bthd", probs, vh,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(b, s, heads * d), probs, jax.nn.logsumexp(logits, -1)


def test_the_masked_kernels_against_dense_attention_with_the_mask():
    b, s, heads, kv, d = 2, 1024, 4, 2, 128
    q, k, v, qi, ki, w = _inputs(2, b, s, heads, kv, d, 2, 64)
    words, lse_i = sa.select_keys(qi, w, ki, 100)
    mask = sa.selection_mask(words, s)
    scale = d ** -0.5
    o, lse = kern.masked_attention(q, k, v, words, heads, scale)
    want, probs, want_lse = _dense(q, k, v, mask, heads, scale)
    assert float(jnp.abs(o.astype(jnp.float32) - want).max()) < 2e-2
    assert float(jnp.abs(lse[:, :, 0] - want_lse).max()) < 1e-4
    do = jax.random.normal(jax.random.key(3), q.shape).astype(q.dtype)

    def through(fn, *xs):
        return jnp.sum(fn(*xs).astype(jnp.float32) * do.astype(jnp.float32))

    got = jax.grad(lambda *x: through(
        lambda *y: kern.masked_attention(*y, words, heads, scale)[0], *x),
        (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *x: through(
        lambda *y: _dense(*y, mask, heads, scale)[0], *x),
        (0, 1, 2))(*(x.astype(jnp.float32) for x in (q, k, v)))
    for g, r in zip(got, ref):
        rel = jnp.abs(g.astype(jnp.float32) - r).max() / jnp.abs(r).max()
        assert float(rel) < 1e-2
    # the indexer's loss and its gradient against the dense mean of the
    # heads' probabilities
    wt = jnp.swapaxes(w, 1, 2)[:, :, None, :]
    p = jnp.mean(probs, 1)

    def kl_dense(qi, ki, w):
        log_q = jax.nn.log_softmax(
            jnp.where(mask, _scores(qi, ki, w), -jnp.inf), -1)
        live = mask & (p > 0)
        return jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(live, p, 1))
                                            - jnp.where(mask, log_q, 0)),
                                 0), -1)

    kl = kern.indexer_kl(q, k, lse, qi, ki, wt, words, lse_i, heads, scale)
    np.testing.assert_allclose(np.asarray(kl[:, 0]),
                               np.asarray(kl_dense(qi, ki, w)), atol=1e-4)
    ct = jax.random.uniform(jax.random.key(4), (b, s))
    got = jax.grad(lambda *x: jnp.sum(kern.indexer_kl(
        q, k, lse, *x[:2], x[2], words, lse_i, heads, scale)[:, 0] * ct),
        (0, 1, 2))(qi, ki, wt)
    ref = jax.grad(lambda *x: jnp.sum(kl_dense(*x) * ct), (0, 1, 2))(
        qi.astype(jnp.float32), ki.astype(jnp.float32), w)
    ref = (ref[0], ref[1], jnp.swapaxes(ref[2], 1, 2)[:, :, None, :])
    for g, r in zip(got, ref):
        rel = jnp.abs(g.astype(jnp.float32) - r).max() / jnp.abs(r).max()
        assert float(rel) < 1e-2


def test_the_selection_words_across_query_groups():
    """Past 4,096 queries a word column holds the next group: the bits of
    a long sequence still unpack to the selection, and the kernels' keep
    masks read the same bits."""
    s = 8192
    rng = np.random.default_rng(0)
    sel = rng.random((1, s, s)) < 0.001
    t = np.arange(s)
    words = np.zeros((1, s, kern.word_columns(s)), np.int64)
    cols = (t // kern.GROUP_QUERIES) * kern.LANES + t % kern.LANES
    bits = (t % kern.GROUP_QUERIES) // kern.LANES
    for q in range(s):
        np.bitwise_or.at(words[0], (np.nonzero(sel[0, q])[0], cols[q]),
                         1 << int(bits[q]))
    words = jnp.asarray(words.astype(np.uint32).view(np.int32))
    assert (np.asarray(sa.selection_mask(words, s)) == sel).all()
    qb = 4096 // 512 + 3                  # a tile of the second group
    tile = np.asarray(kern._keep(words[0, :512, cols[qb * 512]:][:, :128],
                                 qb, 512))
    assert (tile == sel[0, qb * 512:(qb + 1) * 512, :512].T).all()


@pytest.mark.parametrize("tokens, every_token", [(24, False), (1024, True)])
def test_four_shares_of_four_experts_add_up_to_the_uncut_layer(
        tokens, every_token):
    """A chip's share (16 experts held of 16, none shared) is the sum of
    four chips' shares of 4: what every chip computes alike is nothing
    here (no shared expert).  At 48 tokens a share's rows fit one slice
    and run sorted; at 2,048 they can fill two and every token through
    every held expert is 8,192 rows, two slices': the shares run on
    every token under its gate, and add up alike."""
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu.parallel import moe
    from paddle_hackathon_tpu.parallel.moe import DroplessMoELayer
    paddle.seed(5)
    whole = DroplessMoELayer(64, 32, 16, 4, experts_held=(0, 16),
                             shared_hidden=0)
    x = jax.random.normal(jax.random.key(6), (2, tokens, 64))
    want = np.asarray(whole(x)._value)
    assert moe._every_token(2 * tokens, 4, 4, 16) == every_token
    assert not moe._every_token(2 * tokens, 4, 16, 16)
    total = 0
    for i in range(4):
        part = DroplessMoELayer(64, 32, 16, 4, experts_held=(4 * i, 4),
                                shared_hidden=0)
        part.router.weight._set_value(whole.router.weight._value)
        part.experts_gate_up._set_value(
            whole.experts_gate_up._value[4 * i:4 * i + 4])
        part.experts_down._set_value(
            whole.experts_down._value[4 * i:4 * i + 4])
        total = total + np.asarray(part(x)._value)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_no_shared_expert_means_no_shared_leaves_and_no_scope():
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu.parallel.moe import DroplessMoELayer
    paddle.seed(8)
    layer = DroplessMoELayer(64, 32, 16, 4, experts_held=(0, 8),
                             shared_hidden=0)
    assert sorted(k for k, _ in layer.named_parameters()) == [
        "experts_down", "experts_gate_up", "router.weight"]
    assert not layer.shared_gated
    x = jnp.ones((1, 8, 64))

    def fwd(x):
        return layer(x)._value

    import re
    scopes = set(re.findall(r'op_name="([^"]*)"',
                            jax.jit(fwd).lower(x).compile().as_text()))
    assert any("experts" in s for s in scopes)
    assert not any("shared_expert" in s for s in scopes)
    with_shared = DroplessMoELayer(64, 32, 16, 4, experts_held=(0, 8),
                                   shared_hidden=32)
    assert "shared_gate_up.weight" in dict(with_shared.named_parameters())
