"""Every Pallas kernel family lowers for the TPU platform at the shapes
the main path dispatches — no chip needed.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic-MLIR step on any host, so a jax bump that breaks a
kernel's lowering fails here on the CPU instead of at the first chip run
(PR 21 found the fp8 weight path dead that way: no direct
``float8_e4m3fn -> bfloat16`` cast in Mosaic on jax 0.9.0).  Whether
Mosaic then accepts the layouts and the VMEM footprint is what
``chip_smoke.py`` establishes on the chip.

Also here: what happens to the kernels in a program partitioned over a
multi-device mesh, where jax refuses to lower a bare Mosaic call
(``kernels/mesh.py``).
"""

import math

import jax
import jax.numpy as jnp
import pytest

from paddle_hackathon_tpu.incubate.nn.kernels import \
    delta_rule_inverse as dri
from paddle_hackathon_tpu.incubate.nn.kernels import dsa_attention as dsa
from paddle_hackathon_tpu.incubate.nn.kernels import flash_attention as fa
from paddle_hackathon_tpu.incubate.nn.kernels import \
    flash_attention_packed as fap
from paddle_hackathon_tpu.incubate.nn.kernels import paged_attention as pa
from paddle_hackathon_tpu.incubate.nn.kernels import quant_matmul as qm


@pytest.fixture(autouse=True)
def _compiled_not_interpreted(monkeypatch):
    # each module binds the shared predicate by name
    for mod in (dri, dsa, fa, fap, pa, qm):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _tpu_kernels(fn, *avals):
    """Kernel names of the Mosaic custom calls in fn's TPU lowering."""
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    import re
    return re.findall(r'kernel_name = "(\w+)"', text)


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("b,s,heads,d,causal,dropout", [
    (32, 1024, 12, 64, True, 0.0),     # gpt2-small train step
    (32, 1024, 12, 64, True, 0.1),     # ... with attention dropout
    (6, 1024, 16, 128, True, 0.0),     # GPT-3 1.3B
    (16, 1024, 16, 64, True, 0.0),     # gpt2-medium: G = 8, four strips
    (16, 768, 16, 64, True, 0.0),      # a 256-edge plan: whole-tile cells
    (64, 512, 12, 64, False, 0.0),     # ERNIE-base, non-causal
    (4, 4096, 12, 64, True, 0.0),      # long context
])
def test_packed_flash_fwd_and_bwd_lower(b, s, heads, d, causal, dropout):
    seed = jnp.zeros((1,), jnp.int32) if dropout else None

    def loss(qkv):
        return jnp.sum(fap.flash_attention_packed(
            qkv, heads, causal, 1.0 / math.sqrt(d), dropout, seed
        ).astype(jnp.float32))
    names = _tpu_kernels(jax.grad(loss),
                         _aval((b, s, 3 * heads * d), jnp.bfloat16))
    assert sorted(names) == ["flash_packed_bwd_dkdv", "flash_packed_bwd_dq",
                             "flash_packed_fwd"]


@pytest.fixture(scope="module")
def described_v5e():
    """Four v5e chips (2 x 2) that are described, not attached: the TPU's
    own compiler then says what the chip's would (layouts Mosaic refuses,
    scoped VMEM), which the lowering above cannot.  Described inside a
    fixture, so that every worker collects the same tests and only this
    file's loads the TPU's library."""
    import os
    pytest.importorskip("libtpu", reason="the TPU's compiler is not here")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # with the library installed, any failure to describe the chip fails
    # the tests: a skip would hide the one guard this side of the chip
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices


@pytest.fixture(scope="module")
def one_described_chip(described_v5e):
    return jax.sharding.SingleDeviceSharding(described_v5e[0])


_DESCRIBED_V5E_SHAPES = [
    (16, 1024, 16, 64, True, 0.0),     # gpt2-medium.train-s1024's plan
    (6, 1024, 16, 128, True, 0.0),     # gpt3-1.3b.train-s1024's
    (16, 768, 16, 64, True, 0.0),      # 256-edge: whole-tile diagonal
    (64, 512, 12, 64, False, 0.0),     # ERNIE: one whole tile a row
    (32, 1024, 12, 64, True, 0.1),     # dropout, G = 6
]


@pytest.mark.parametrize("b,s,heads,d,causal,dropout", _DESCRIBED_V5E_SHAPES)
def test_packed_flash_forward_compiles_for_a_described_v5e(
        one_described_chip, b, s, heads, d, causal, dropout):
    """The kv-major forward slices statistics rows, contracts axis 0 of
    both PV operands and transposes its accumulator once: each was a
    question for Mosaic's compiler, not for the lowering (a lane slice
    of a replicated row lowered and then failed with "Invalid input
    layout")."""
    plan = fap._plan(s, s, heads, d, jnp.bfloat16)
    qkv = jax.ShapeDtypeStruct((b, s, 3 * heads * d), jnp.bfloat16,
                               sharding=one_described_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32,
                                sharding=one_described_chip)
    compiled = fap._fwd.lower(
        qkv, seed, heads=heads, causal=causal, sm_scale=1.0 / math.sqrt(d),
        dropout_p=dropout, plan=plan, interpret=False).compile()
    assert "flash_packed_fwd" in compiled.as_text()


@pytest.mark.parametrize("b,s,heads,d,causal,dropout", _DESCRIBED_V5E_SHAPES)
def test_packed_flash_backward_compiles_for_a_described_v5e(
        one_described_chip, b, s, heads, d, causal, dropout):
    """The dq kernel reads an output block back (delta, written in a
    row's first step and held in VMEM for the kv sweep) and contracts a
    0 / 1 selector against the float32 products' bf16 pieces: questions
    for Mosaic's compiler.  And the compiled backward is the two kernels and
    the lane concat: XLA computes no delta, so no float32 array of
    b x s x hidden elements exists and none is copied into another
    layout (the parent's preamble wrote one, turned it and read it back,
    24 times a step: PERF.md §6, PR 31)."""
    import re
    plan = fap._plan(s, s, heads, d, jnp.bfloat16)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_described_chip)
    hidden = heads * d
    text = fap._bwd.lower(
        aval((b, s, 3 * hidden), jnp.bfloat16),         # qkv
        aval((b, s, hidden), jnp.bfloat16),             # out
        aval((b, heads, 8, s), jnp.float32),            # lse
        aval((1,), jnp.int32),                          # seed
        aval((b, s, hidden), jnp.bfloat16),             # dO
        heads=heads, causal=causal, sm_scale=1.0 / math.sqrt(d),
        dropout_p=dropout, plan=plan, interpret=False).compile().as_text()
    calls = {name: line for line in text.splitlines() for name in
             re.findall(r"^\s*%(flash_packed_\w+?)[.\d]* = ", line)}
    assert sorted(calls) == ["flash_packed_bwd_dkdv", "flash_packed_bwd_dq"]
    # the mechanism: dq has two results (dq and the delta rows) and seven
    # operands (q, k, v, dO, O, lse, seed)
    result, operands = re.match(
        r"\s*%\S+ = \((.*?)\) custom-call\((.*?)\), custom_call_target",
        calls["flash_packed_bwd_dq"]).groups()
    assert re.findall(r"(\w+)\[([\d,]+)\]", result) == [
        ("bf16", f"{b},{s},{hidden}"), ("f32", f"{b},{heads},8,{s}")]
    assert len(re.findall(r"%[\w.-]+", operands)) == 7
    elements = b * s * hidden
    for dims in re.findall(r"\bf32\[([\d,]+)\]", text):     # fused ones too
        assert math.prod(int(n) for n in dims.split(",")) != elements, \
            f"a float32 array of b x s x hidden elements: f32[{dims}]"
    for line in text.splitlines():
        if re.search(r" copy\(", line):
            assert re.search(r"= s32\[1\]", line), line   # the seed to SMEM


@pytest.mark.parametrize("shape", [
    (64, 2, 32, 64, 64),               # qwen3-next-80b-a3b.train-s4096's
    (64, 1, 32, 64, 64),               # ling-3.0-flash.train-s4096's
])
def test_delta_rule_inverse_compiles_for_a_described_v5e(
        one_described_chip, shape):
    """The row of 128 matrices is a sublane-strided load and a transpose
    in, a transpose and a strided store out, and the inverses' rows take
    2 MB of VMEM beside the two buffers of each 4 MB block: questions for
    Mosaic's compiler, which the lowering does not ask."""
    a = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_described_chip)
    text = dri._inverse.lower(a, interpret=False).compile().as_text()
    from paddle_hackathon_tpu.observability.programs import mosaic_kernels
    assert mosaic_kernels(text) == {"delta_rule_inverse": 1}


@pytest.mark.parametrize("axes,kernels", [
    ((("dp", 2),), 1),                 # the hybrids' data-parallel replicas
    ((("dp", 2), ("mp", 2)), 1),       # batch and heads both split
    ((("dp", 4),), 0),                 # 4 does not divide the batch of 2
    ((("dp", 2), ("sp", 2)), 0),       # an axis kernels/mesh.py leaves alone
])
def test_the_rule_compiles_on_a_described_multi_chip_v5e(
        described_v5e, axes, kernels):
    """jax will not partition a Mosaic call: on a mesh that splits the
    batch (the trainers' dp replicas) or the heads the inverse runs on each
    chip's own matrices, one kernel a shard; a mesh ``kernels/mesh.py``
    does not cover takes XLA's triangular solve.  The whole rule, forward
    and backward, compiled for described chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_hackathon_tpu.incubate.nn.functional.gated_delta_rule \
        import gated_delta_rule_chunked
    from paddle_hackathon_tpu.observability.programs import mosaic_kernels
    names, sizes = zip(*axes)
    mesh = Mesh(np.asarray(described_v5e[:math.prod(sizes)]).reshape(sizes),
                names)
    b, s, h, d = 2, 128, 32, 128
    split = P("dp") if b % dict(axes)["dp"] == 0 else P()

    def aval(*tail, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((b, s) + tail, dtype,
                                    sharding=NamedSharding(mesh, split))

    def loss(*a):
        return jnp.sum(gated_delta_rule_chunked(*a).astype(jnp.float32))
    args = (aval(h, d), aval(h, d), aval(h, d),
            aval(h, dtype=jnp.float32), aval(h, dtype=jnp.float32))
    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
    assert mosaic_kernels(text).get("delta_rule_inverse", 0) == kernels


def _sparse_attention_grads(b, s):
    """Forward and backward of the sparse attention at the Keye cell's
    widths (32 / 4 heads x 128, an indexer of 16 x 64, top-k 2,048)."""
    from paddle_hackathon_tpu.incubate.nn.functional.sparse_attention \
        import sparse_attention

    def loss(q, k, v, qi, ki, w):
        o, kl, _ = sparse_attention(q, k, v, qi, ki, w, heads=32, topk=2048,
                                    scale=128 ** -0.5)
        return jnp.sum(o.astype(jnp.float32)) + jnp.mean(kl)

    shapes = [((b, s, 32 * 128), jnp.bfloat16), ((b, s, 4 * 128),
              jnp.bfloat16), ((b, s, 4 * 128), jnp.bfloat16),
              ((b, 16, s, 64), jnp.bfloat16), ((b, s, 64), jnp.bfloat16),
              ((b, s, 16), jnp.float32)]
    return jax.jit(jax.grad(loss, argnums=tuple(range(6)))), shapes


def test_the_sparse_attention_kernels_compile_for_a_described_v5e(
        one_described_chip):
    """The seven kernels at the cell's own sequence, 16,384: the selection's
    words unpacked by a shift a bit plane, tiles skipped above the
    diagonal, 32 heads a cell in the KL kernels with 96 MB of scoped
    VMEM, a row's threshold counted bit by bit.  Each is built once a
    layer, the indexer's and the threshold's inside the scan over blocks
    of queries."""
    from paddle_hackathon_tpu.observability.programs import mosaic_kernels
    fn, shapes = _sparse_attention_grads(1, 16384)
    text = fn.lower(*(jax.ShapeDtypeStruct(s, d, sharding=one_described_chip)
                      for s, d in shapes)).compile().as_text()
    assert mosaic_kernels(text) == {name: 1 for name in dsa.kernel_names()}


def test_the_sparse_attention_compiles_on_a_described_dp_mesh(
        described_v5e):
    """On a mesh that splits the batch every kernel runs on each chip's
    own rows under ``shard_map`` (jax will not partition a Mosaic
    call)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_hackathon_tpu.observability.programs import mosaic_kernels
    mesh = Mesh(np.asarray(described_v5e[:2]), ("dp",))
    fn, shapes = _sparse_attention_grads(2, 1024)
    with jax.set_mesh(mesh):
        text = fn.lower(*(jax.ShapeDtypeStruct(
            s, d, sharding=NamedSharding(mesh, P("dp")))
            for s, d in shapes)).compile().as_text()
    assert mosaic_kernels(text) == {name: 1 for name in dsa.kernel_names()}


def _described_train_step(chip, workload, **config):
    """The compiled text of a rehearsal cell's train step, its
    configuration's keys overridden by ``config``, built ahead of time for
    ``chip`` as ``benchmark/rehearse.py`` builds a cell's: the program
    places its parameters with ``jax.device_put``, which a described
    device cannot hold (stood in for by the shape), and the kernels ask
    ``jax.default_backend()`` whether to interpret themselves.  The
    builder makes its mesh of the described chip the process's current
    one, which a later test of this worker would place arrays on: the
    mesh that was current is put back."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmark import run as harness
    from benchmark.drivers import train_steps
    from paddle_hackathon_tpu import parallel
    wl = harness.load_json("workloads", workload)
    cfg = dict(harness.load_json("configs", wl["config"]), **config)
    cell = harness.Cell(wl, cfg, seed=0)
    ref = harness.config_module(cfg, "reference", "reference")
    dtype = jnp.dtype(cfg["training"]["param_dtype"])
    params = {k: jnp.zeros(shape, dtype)
              for k, shape in ref.param_spec(cfg).items()}
    put, devices, backend = jax.device_put, jax.devices, jax.default_backend
    current = parallel.get_mesh()
    jax.device_put = lambda x, device=None, **kw: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=device)
    jax.devices = lambda *a, **k: list(chip.device_set)
    try:
        step, state, _ = train_steps.build_program(cell, params)
        jax.default_backend = lambda: "tpu"
        mesh = jax.tree.leaves(state["params"])[0].sharding.mesh
        traffic = wl["traffic"]
        tokens = jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seqlen"]), jnp.int32,
            sharding=NamedSharding(mesh, P("dp")))
        with jax.set_mesh(mesh):
            lowered = step._jitted.lower(
                state["params"], state["opt_state"], state["step"],
                (tokens, tokens), jax.eval_shape(lambda: jax.random.key(0)),
                jax.ShapeDtypeStruct((), jnp.float32))
    finally:
        jax.device_put, jax.devices = put, devices
        jax.default_backend = backend
        parallel.set_mesh(current)
    return lowered.compile().as_text()


@pytest.mark.parametrize("workload,config,calls", [
    # 3 DeltaNet layers and 1 of attention, as qwen3-next-80b-a3b's period
    ("qwen3-next-tiny-rehearsal.train-s64", {}, 3),
    # ling-3.0-flash's 7: KDA + dense MLP, 4 x KDA + experts, MLA, KDA
    ("ling3-tiny-rehearsal.train-s64",
     {"num_hidden_layers": 7, "layer_group_size": 6}, 6),
])
def test_a_hybrid_train_step_calls_the_inverse_once_a_layer(
        one_described_chip, workload, config, calls):
    """The kernel census of the AOT-built training step (what
    ``analysis["mosaic_kernels"]`` reads on the chip) counts the inverse
    once a delta-rule layer: built in the forward, kept, and not rebuilt
    in the backward's recomputation of a mixer (``ling-3.0-flash``'s
    layers rebuild theirs), at the two cells' layer stacks; the widths
    are the rehearsal's."""
    from paddle_hackathon_tpu.observability.programs import mosaic_kernels
    text = _described_train_step(one_described_chip, workload, **config)
    assert mosaic_kernels(text).get("delta_rule_inverse") == calls


@pytest.mark.parametrize("b,s,heads,d", [
    (16, 1024, 16, 64),                # gpt2-medium.train-s1024's layer
    (6, 1024, 16, 128),                # gpt3-1.3b.train-s1024's
])
def test_packed_qkv_cotangent_is_not_built_in_hbm_on_a_described_v5e(
        one_described_chip, b, s, heads, d):
    """The qkv cotangent is the lane concat of (dq, dk, dv), and XLA is
    meant to fuse it into its three consumers: the projection's weight
    gradient, its input gradient and its bias gradient.  Whether it does
    is decided where the consumers are, so this compiles a projection
    and the attention behind it, not ``_bwd`` alone: when the dq call
    gained its second result a ``concatenate`` was built in HBM instead,
    three dynamic-update-slice fusions a layer, which cost more than the
    change had won (PERF.md §6, PR 31)."""
    import re
    hidden = heads * d

    def loss(x, w, bias, g):
        qkv = jnp.einsum("bsh,hk->bsk", x, w) + bias
        out = fap.flash_attention_packed(qkv, heads, True,
                                         1.0 / math.sqrt(d))
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=one_described_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        aval(b, s, hidden), aval(hidden, 3 * hidden), aval(3 * hidden),
        aval(b, s, hidden)).compile().as_text()
    entry = text[text.index("ENTRY "):]
    packed = re.findall(
        r"^\s*(?:ROOT )?%%(\S+) = bf16\[%d,%d,%d\]" % (b, s, 3 * hidden),
        entry, flags=re.M)
    # the forward projection's output (and the compiler's asynchronous
    # copy of it to its other memory), nothing the backward wrote
    assert packed and all(
        re.match(r"convolution|copy-done", name) for name in packed), packed


@pytest.mark.parametrize("bh,s,d,causal,dtype", [
    (384, 1024, 64, True, jnp.bfloat16),
    (96, 1024, 128, True, jnp.bfloat16),
    (768, 512, 64, False, jnp.bfloat16),   # ERNIE through the bhd API
    (24, 1024, 64, True, jnp.float32),     # f32 operands: 512-edge blocks
])
def test_bhd_flash_fwd_and_bwd_lower(bh, s, d, causal, dtype):
    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_bhd(
            q, k, v, causal, 1.0 / math.sqrt(d)).astype(jnp.float32))
    names = _tpu_kernels(jax.grad(loss, argnums=(0, 1, 2)),
                         *[_aval((bh, s, d), dtype)] * 3)
    assert sorted(names) == ["flash_bhd_bwd_dkdv", "flash_bhd_bwd_dq",
                             "flash_bhd_fwd"]


@pytest.mark.parametrize("heads,d", [(12, 64), (16, 128)])
def test_paged_decode_lowers(heads, d):
    slots, page, pages = 8, 16, 14
    names = _tpu_kernels(
        pa.paged_attention_decode,
        _aval((slots, 1, heads, d), jnp.bfloat16),
        _aval((slots * pages + 1, page, heads, d), jnp.bfloat16),
        _aval((slots * pages + 1, page, heads, d), jnp.bfloat16),
        _aval((slots, pages), jnp.int32), _aval((slots,), jnp.int32))
    assert names == ["paged_decode"]


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("k,n,w_dtype", [
    (768, 2304, jnp.int8), (3072, 768, jnp.int8), (8192, 2048, jnp.int8),
    (768, 2304, jnp.float8_e4m3fn),    # widened through f32 in the kernel
])
def test_quant_matmul_lowers(m, k, n, w_dtype):
    assert qm.supported(k, n, w_dtype)
    names = _tpu_kernels(qm.quant_matmul_kernel,
                         _aval((m, k), jnp.bfloat16), _aval((k, n), w_dtype),
                         _aval((n,), jnp.float32))
    assert names == ["quant_matmul"]


# ---------------------------------------------------------------------------
# multi-device programs: "Mosaic kernels cannot be automatically partitioned"
# ---------------------------------------------------------------------------

@pytest.fixture()
def dp_mp_mesh():
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))


def test_flash_runs_per_batch_and_head_shard_on_a_dp_mp_mesh(dp_mp_mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.incubate.nn import functional as IF
    heads, d = 12, 64
    aval = jax.ShapeDtypeStruct(
        (32, 1024, 3 * heads * d), jnp.bfloat16,
        sharding=NamedSharding(dp_mp_mesh, P("dp", None, "mp")))

    def wrapped(qkv):
        out = IF.flash_attention_qkv_packed(Tensor(qkv), heads, causal=True)
        return jnp.sum(out._value.astype(jnp.float32))

    def bare(qkv):
        return jnp.sum(fap.flash_attention_packed(
            qkv, heads, True, 1.0 / math.sqrt(d)).astype(jnp.float32))
    with jax.set_mesh(dp_mp_mesh):
        assert sorted(_tpu_kernels(jax.grad(wrapped), aval)) == [
            "flash_packed_bwd_dkdv", "flash_packed_bwd_dq",
            "flash_packed_fwd"]
        # what the wrapper is for: jax will not partition the bare call
        with pytest.raises(NotImplementedError,
                           match="automatically partitioned"):
            _tpu_kernels(bare, aval)
        # uncovered meshes say so through the dispatch predicate
        assert IF.packed_flash_plan(32, 1024, heads, d, jnp.bfloat16) \
            .head_shards == 2
        assert IF.packed_flash_plan(31, 1024, heads, d, jnp.bfloat16) is None
    pp = jax.sharding.Mesh(dp_mp_mesh.devices, ("pp", "dp"))
    with jax.set_mesh(pp):
        assert IF.packed_flash_plan(32, 1024, heads, d, jnp.bfloat16) is None


def test_sharded_flash_equals_single_device(dp_mp_mesh, monkeypatch):
    """Interpreter-mode numerics: the per-shard calls reassemble to the
    one-device result exactly (no collective, no re-association)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.incubate.nn import functional as IF
    for mod in (fa, fap):
        monkeypatch.setattr(mod, "_interpret", lambda: True)
    b, s, heads, d = 4, 64, 4, 64
    qkv = jax.random.normal(jax.random.key(0), (b, s, 3 * heads * d),
                            jnp.float32).astype(jnp.bfloat16)

    def loss(x):
        out = IF.flash_attention_qkv_packed(Tensor(x), heads, causal=True)
        return jnp.sum(out._value.astype(jnp.float32) ** 2)
    want = jax.jit(jax.grad(loss))(qkv)
    sharded = jax.device_put(
        qkv, NamedSharding(dp_mp_mesh, P("dp", None, "mp")))
    with jax.set_mesh(dp_mp_mesh):
        got = jax.jit(jax.grad(loss))(sharded)
    assert jnp.array_equal(want, got)

    # dropout: the seed is decorrelated per shard, so the four shards
    # draw different masks (same local indices, different seeds)
    def dropped(x):
        return IF.flash_attention_qkv_packed(
            Tensor(x), heads, causal=True, dropout_p=0.5,
            seed=jnp.asarray([7], jnp.int32))._value
    same_rows = jnp.tile(qkv[:1, :, :3 * 2 * d], (b, 1, 2))  # all shards equal
    with jax.set_mesh(dp_mp_mesh):
        out = jax.jit(dropped)(jax.device_put(same_rows, sharded.sharding))
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert not jnp.array_equal(out[0], out[b // 2])      # dp shards differ


def test_paged_and_quant_dispatch_reference_on_a_multi_device_mesh(
        dp_mp_mesh):
    """Not wrapped per shard yet: dispatch states the rule instead of
    letting the lowering raise inside a TP-sharded engine."""
    assert pa.use_kernel(16, 64) and qm.use_kernel(768, 2304, jnp.int8)
    with jax.set_mesh(dp_mp_mesh):
        assert not pa.use_kernel(16, 64)
        assert not qm.use_kernel(768, 2304, jnp.int8)
