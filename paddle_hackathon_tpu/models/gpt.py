"""GPT-style decoder-only LM — the flagship model.

Capability target: the GPT-3 1.3B hybrid-parallel driver config (BASELINE.json)
and ERNIE-base pretraining throughput. Architecturally the paddle analog is
``PaddleNLP`` GPT + the reference's ``FusedMultiTransformer``
(``incubate/nn/layer/fused_transformer.py:914``) — here the transformer block
is built from this framework's layers, attention routes to the Pallas flash
kernel (``incubate/``), and parallelism is applied from outside via sharding
specs (see :func:`param_sharding_spec` and ``parallel/``): TP shards attention
heads / MLP, 'sp' shards the sequence axis, 'data'+'sharding' shard the batch
(DP x ZeRO), matching the reference's 4-D topology (``topology.py:52``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax

from .. import ops
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..nn.layers.common import Dropout, Embedding, Linear
from ..nn.layers.norm import LayerNorm
from ..nn.parameter import ParamAttr


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    use_flash_attention: bool = None  # None = auto (seq-length heuristic)
    # MoE (GPT-MoE family): >0 replaces selected blocks' MLP with a
    # MoELayer whose expert dim shards over the 'ep' mesh axis.
    # moe_every_n selects WHICH blocks route: every n-th block (counting
    # from 1, so every_n=2 makes blocks 1, 3, 5, ... MoE and the rest
    # dense — the interleaved GPT-MoE layout); 1 = every block.
    moe_num_experts: int = 0
    moe_topk: int = 2
    moe_gate: str = "naive"
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    moe_every_n: int = 1
    # dispatch token-group size (None = auto; parallel/moe.py docstring)
    moe_group_size: Optional[int] = None

    @property
    def ffn_size(self):
        return self.intermediate_size or 4 * self.hidden_size

    def block_uses_moe(self, layer_idx: int) -> bool:
        """Whether block ``layer_idx`` (0-based) routes through experts."""
        if self.moe_num_experts <= 0:
            return False
        n = max(1, int(self.moe_every_n))
        return (layer_idx + 1) % n == 0


_GPT_PRESETS = {
    # name: (layers, hidden, heads) — paddle fleetx GPT configs
    "gpt2-small-en": (12, 768, 12),         # 124M
    "gpt2-medium-en": (24, 1024, 16),       # 350M
    "gpt2-large-en": (36, 1280, 20),        # 774M
    "gpt3-1.3B-en": (24, 2048, 16),         # driver config #4
    "gpt3-2.7B-en": (32, 2560, 32),
    "gpt3-6.7B-en": (32, 4096, 32),
}


def gpt_config(name: str, **overrides) -> GPTConfig:
    layers, hidden, heads = _GPT_PRESETS[name]
    cfg = GPTConfig(num_layers=layers, hidden_size=hidden, num_heads=heads)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


from ..nn.layers.transformer import SequenceParallelMixin


class GPTAttention(SequenceParallelMixin, Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        init = I.Normal(0.0, config.initializer_range)
        self.num_heads = config.num_heads
        self.head_dim = h // config.num_heads
        self.qkv_proj = Linear(h, 3 * h, weight_attr=ParamAttr(initializer=init))
        self.out_proj = Linear(h, h, weight_attr=ParamAttr(initializer=init))
        self.dropout_p = config.attention_dropout_prob
        self.use_flash = config.use_flash_attention

    def _packed_flash_ok(self, qkv, s):
        from ..core import flags
        from ..incubate.nn.functional import packed_flash_plan
        # mirror scaled_dot_product_attention's dispatch: explicit
        # use_flash=True forces flash at any supported length; None (auto)
        # applies the measured min-seqlen crossover
        if self.use_flash is False or not flags.flag("use_fused_kernels"):
            return False
        if self.use_flash is None and \
                s < flags.flag("flash_attention_min_seqlen"):
            return False
        from ..core.tensor import Tensor
        dtype = qkv._value.dtype if isinstance(qkv, Tensor) else qkv.dtype
        # the kernel's geometry AND the mesh the trace runs under: a
        # Mosaic call cannot be auto-partitioned, so on a multi-device
        # mesh it runs per (batch, heads) shard or not at all
        return packed_flash_plan(qkv.shape[0], s, self.num_heads,
                                 self.head_dim, dtype) is not None

    def forward(self, x, cache=None, cache_pos=None, page_table=None):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        if page_table is not None:
            # paged KV (serving engine cache_mode="paged"): ``cache`` is a
            # global page-pool pair ((num_pages, page_size, H, D)) shared
            # by every slot; ``page_table`` (B, pages_per_slot) maps each
            # slot's logical rows to physical pages and ``cache_pos`` is
            # the per-slot write offset.  Write-through-the-table, then
            # gather-attention (the Pallas decode kernel on TPU at width
            # 1, the exact-jnp reference otherwise) — same math, masking
            # and dtypes as the dense static-cache branch below, so paged
            # greedy decode is token-exact against it.
            if cache_pos is None:
                raise ValueError("page_table requires cache_pos")
            from ..incubate.nn.kernels import paged_attention as _pa
            qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
            q, k, v = ops.unstack(qkv, axis=2)

            def fn(qv, kv, vv, kp, vp, pt, pos):
                import jax.numpy as jnp
                pos = jnp.asarray(pos, jnp.int32)
                kp = _pa.paged_write(kp, kv, pt, pos)
                vp = _pa.paged_write(vp, vv, pt, pos)
                ctx = _pa.paged_attention(qv, kp, vp, pt, pos)
                return ctx.reshape(ctx.shape[0], ctx.shape[1], -1), kp, vp
            from ..core.autograd import apply_op
            out, new_k, new_v = apply_op(
                "gpt_paged_cache_attn", fn,
                [q, k, v, cache[0], cache[1], page_table, cache_pos],
                n_outputs=3)
            return self.out_proj(out), (new_k, new_v)
        if self._sp_enabled() and cache is None and cache_pos is None:
            # sequence-parallel training: the seq dim is sharded over the
            # 'sp' mesh axis; attention runs the ring/ulysses schedule
            # (parallel/sequence.py — flash-in-ring on TPU) against the
            # mesh enable_sequence_parallel() captured
            qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
            q, k, v = ops.unstack(qkv, axis=2)
            out = self._sp_attention(q, k, v, causal=True)
            out = ops.reshape(out, [b, s, h])
            return self.out_proj(out)
        if cache_pos is not None:
            # static-cache decode (jit-once generation): cache is a fixed
            # (B, max_len, H, D) pair — the train-time layout, so the
            # per-step cache write is an in-place contiguous
            # dynamic_update_slice (a head-major variant measured 68
            # us/step of full-cache copies when XLA lost the aliasing).
            # This call's k/v land at [cache_pos, cache_pos+s); queries
            # attend over cached positions <= their global position.
            # Compiled shapes never change across decode steps.
            import math as _math

            import jax
            import jax.numpy as jnp
            qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
            q, k, v = ops.unstack(qkv, axis=2)

            def fn(qv, kv, vv, kb, vb, pos):
                pos = jnp.asarray(pos, jnp.int32)
                if pos.ndim == 0:
                    zero = jnp.zeros((), jnp.int32)
                    start = (zero, pos, zero, zero)
                    kb = jax.lax.dynamic_update_slice(
                        kb, kv.astype(kb.dtype), start)
                    vb = jax.lax.dynamic_update_slice(
                        vb, vv.astype(vb.dtype), start)
                    qpos = pos + jnp.arange(qv.shape[1])[:, None]
                    kpos = jnp.arange(kb.shape[1])[None, :]
                    mask = (kpos <= qpos)[None, None]  # (1,1,s,T)
                else:
                    # per-slot positions (continuous-batching serving:
                    # each batch row is an independent request at its own
                    # cache depth). Statically unrolled per-row
                    # dynamic_update_slice, NOT vmap — vmapping the write
                    # over traced per-row offsets lowers to scatter,
                    # which measured ~3x the whole tick's decode time on
                    # TPU; a DUS chain stays an in-place slice write.
                    def rows_write(buf, upd):
                        zero = jnp.zeros((), jnp.int32)
                        for i in range(buf.shape[0]):
                            buf = jax.lax.dynamic_update_slice(
                                buf, upd[i:i + 1].astype(buf.dtype),
                                (jnp.asarray(i, jnp.int32), pos[i],
                                 zero, zero))
                        return buf
                    kb = rows_write(kb, kv)
                    vb = rows_write(vb, vv)
                    qpos = pos[:, None] + jnp.arange(qv.shape[1])[None, :]
                    kpos = jnp.arange(kb.shape[1])[None, None, :]
                    mask = (kpos <= qpos[..., None])[:, None]  # (b,1,s,T)
                # NOTE round-4: three Pallas fused-decode-attention
                # variants (3-D VPU, per-head MXU dots, head-batched
                # dot_general) measured 23/37/49 us/layer vs ~21 us for
                # this XLA composition at b8 T192 — kernel fixed costs
                # dominate at decode shapes; the composition stays
                # (round-4 decode trace, old toolchain — git history;
                # not re-measured on the current one)
                scale = 1.0 / _math.sqrt(qv.shape[-1])
                logits = jnp.einsum("bshe,bthe->bhst", qv,
                                    kb.astype(qv.dtype)) * scale
                logits = jnp.where(mask, logits,
                                   jnp.asarray(-1e30, logits.dtype))
                probs = jax.nn.softmax(logits, -1)
                ctx = jnp.einsum("bhst,bthe->bshe", probs,
                                 vb.astype(probs.dtype))
                return ctx.reshape(ctx.shape[0], ctx.shape[1], -1), kb, vb
            from ..core.autograd import apply_op
            out, new_k, new_v = apply_op(
                "gpt_static_cache_attn", fn,
                [q, k, v, cache[0], cache[1], cache_pos], n_outputs=3)
            return self.out_proj(out), (new_k, new_v)
        if cache is None and self._packed_flash_ok(qkv, s):
            # fast path: flash attention on the projection-native packed
            # layout — no head split/merge copies in HBM
            from ..incubate.nn.functional import flash_attention_qkv_packed
            out = flash_attention_qkv_packed(
                qkv, self.num_heads, causal=True,
                dropout_p=self.dropout_p if self.training else 0.0)
            return self.out_proj(out)
        qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = ops.unstack(qkv, axis=2)
        attn_mask = None
        is_causal = True
        if cache is not None:
            past_len = cache[0].shape[1]
            k = ops.concat([cache[0], k], axis=1)
            v = ops.concat([cache[1], v], axis=1)
            cache = (k, v)
            is_causal = False
            if s > 1:
                # chunked prefill: query position i (global past_len+i) may
                # attend to keys [0, past_len+i]
                import jax.numpy as jnp
                total = past_len + s
                causal = jnp.arange(total)[None, :] <= (
                    past_len + jnp.arange(s))[:, None]
                attn_mask = Tensor(
                    jnp.where(causal, 0.0, -1e30)[None, None].astype("float32"))
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=is_causal,
            dropout_p=self.dropout_p if self.training else 0.0,
            training=self.training, use_flash=self.use_flash)
        out = ops.reshape(out, [b, s, h])
        out = self.out_proj(out)
        return out if cache is None else (out, cache)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.fc_in = Linear(config.hidden_size, config.ffn_size,
                            weight_attr=ParamAttr(initializer=init))
        self.fc_out = Linear(config.ffn_size, config.hidden_size,
                             weight_attr=ParamAttr(initializer=init))

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTBlock(Layer):
    """Pre-LN transformer block (the fused_multi_transformer layout)."""

    def __init__(self, config: GPTConfig, use_moe: Optional[bool] = None):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size)
        # use_moe=None keeps the historical contract (any block of an MoE
        # config routes); GPTModel passes config.block_uses_moe(i) so
        # moe_every_n can interleave dense and routed blocks
        if (config.moe_num_experts > 0 if use_moe is None else use_moe):
            from ..parallel.moe import MoELayer
            self.mlp = MoELayer(
                config.hidden_size, config.ffn_size,
                config.moe_num_experts, gate=config.moe_gate,
                topk=config.moe_topk,
                capacity_factor=config.moe_capacity_factor,
                group_size=config.moe_group_size)
        else:
            self.mlp = GPTMLP(config)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, cache=None, cache_pos=None, page_table=None):
        # the scopes name the block's two halves (each with its LayerNorm
        # and residual) in the compiled program's op metadata: the phase
        # census of observability/programs.py reads them; no index, the
        # blocks aggregate
        with jax.named_scope("attn"):
            attn_out = self.attn(self.ln_1(x), cache=cache,
                                 cache_pos=cache_pos, page_table=page_table)
            if cache is not None:
                attn_out, cache = attn_out
            x = x + self.dropout(attn_out)
        with jax.named_scope("mlp"):
            x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x if cache is None else (x, cache)


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.wte = Embedding(config.vocab_size, config.hidden_size,
                             weight_attr=ParamAttr(initializer=init))
        self.wpe = Embedding(config.max_position_embeddings,
                             config.hidden_size,
                             weight_attr=ParamAttr(initializer=init))
        self.drop = Dropout(config.hidden_dropout_prob)
        self.blocks = LayerList([GPTBlock(config,
                                          use_moe=config.block_uses_moe(i))
                                 for i in range(config.num_layers)])
        self.ln_f = LayerNorm(config.hidden_size)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None, page_table=None):
        b, s = input_ids.shape
        # paged caches are (num_pages, page_size, H, D) pools — their
        # leading dims say nothing about past length; cache_pos does
        past_len = (caches[0][0].shape[1]
                    if caches is not None and page_table is None else 0)
        max_pos = self.wpe.weight.shape[0]
        if cache_pos is not None:
            # static-cache decode: positions come from the dynamic write
            # offset, not the (fixed, max_len) cache shape
            import jax.numpy as jnp
            from ..core.tensor import Tensor as _T
            pv = cache_pos._value if isinstance(cache_pos, _T) else cache_pos
            pv = jnp.asarray(pv, jnp.int32)
            if pv.ndim == 0:
                pos_idx = jnp.clip(
                    pv + jnp.arange(s, dtype=jnp.int32),
                    0, max_pos - 1)[None, :]
                pos_emb = self.wpe(_T(jnp.broadcast_to(pos_idx, (1, s))))
            else:
                # per-slot positions (serving engine): (B,) starts -> (B, s)
                pos_idx = jnp.clip(
                    pv[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :],
                    0, max_pos - 1)
                pos_emb = self.wpe(_T(pos_idx))
        elif position_ids is None and past_len + s <= max_pos:
            # Default positions are a contiguous arange, so the lookup is a
            # row slice of the weight — not a gather.  The slice's transpose
            # is a pad (identity when s == max_position_embeddings), which
            # keeps the wpe gradient off the batch-scatter path that GSPMD
            # can only reshard onto the ZeRO-3 param layout via involuntary
            # full rematerialization (spmd_partitioner.cc warning).
            pos_emb = ops.reshape(
                ops.slice(self.wpe.weight, axes=[0], starts=[past_len],
                          ends=[past_len + s]),
                [1, s, -1])
        else:
            if position_ids is None:
                # decode past max_position_embeddings: match gather's
                # clamped out-of-bounds behavior instead of crashing
                position_ids = ops.clip(
                    ops.arange(past_len, past_len + s, dtype="int32"),
                    0, max_pos - 1)
                position_ids = ops.reshape(position_ids, [1, s])
            pos_emb = self.wpe(position_ids)
        with jax.named_scope("embed"):
            x = self.wte(input_ids) + pos_emb
            x = self.drop(x)
        new_caches = []
        for i, block in enumerate(self.blocks):
            if caches is None:
                x = block(x)
            else:
                x, c = block(x, cache=caches[i], cache_pos=cache_pos,
                             page_table=page_table)
                new_caches.append(c)
        with jax.named_scope("ln_f"):
            x = self.ln_f(x)
        return x if caches is None else (x, new_caches)

    def gen_empty_caches(self, batch_size, dtype="float32"):
        from ..ops import creation
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        return [(creation.zeros([batch_size, 0, cfg.num_heads, head_dim], dtype),
                 creation.zeros([batch_size, 0, cfg.num_heads, head_dim], dtype))
                for _ in range(cfg.num_layers)]


class GPTForCausalLM(Layer):
    """LM head ties the embedding matrix (paddle GPTForPretraining)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None, page_table=None):
        hidden = self.gpt(input_ids, position_ids, caches=caches,
                          cache_pos=cache_pos, page_table=page_table)
        if caches is not None:
            hidden, caches = hidden
        with jax.named_scope("lm_head"):
            logits = ops.matmul(hidden, self.gpt.wte.weight,
                                transpose_y=True)
        return logits if caches is None else (logits, caches)

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k: Optional[int] = None, jit_decode: bool = True,
                 top_p: Optional[float] = None, spec_k: int = 0,
                 drafter=None):
        """Greedy / top-k / nucleus sampling with a KV cache.

        ``jit_decode=True`` (default) preallocates a static
        (B, prompt+max_new, H, D) cache and compiles ONE fused program —
        prefill plus a ``lax.fori_loop`` over decode steps with in-jit
        sampling — cached per (batch, prompt, max_new, sampling) shape
        and reused across calls (the TPU-idiomatic serving loop; the
        growing-concat path recompiles every step because each step's
        cache shape is new, and pays a host round trip per token).

        ``spec_k > 0`` switches to speculative draft-and-verify decoding:
        a drafter (``drafter='ngram'`` prompt-lookup by default, or a
        small ``GPTForCausalLM``) proposes up to ``spec_k`` tokens per
        step and ONE widened forward verifies all of them, committing the
        longest prefix matching the target's greedy argmax — output is
        token-for-token identical to the non-speculative greedy path.
        Greedy only (``temperature`` must be 0.0).
        """
        from .. import ops as O

        self.eval()
        if spec_k:
            if temperature != 0.0:
                raise ValueError(
                    "spec_k requires temperature=0.0: speculative "
                    "acceptance matches the target's greedy argmax, so "
                    "only greedy decoding is exactly preserved")
            if not jit_decode:
                raise ValueError(
                    "spec_k requires jit_decode=True: the draft-and-"
                    "verify loop runs over the jitted static-cache "
                    "programs (the eager concat path has no verify step)")
            out = self._generate_spec(input_ids, max_new_tokens,
                                      int(spec_k), drafter)
            if out is not None:
                return out
            # pp mesh: no spec verify program — fall through to the
            # pipelined decode (same greedy tokens, just unsped)
        if jit_decode:
            return self._generate_static(input_ids, max_new_tokens,
                                         temperature, top_k, top_p)
        logits, caches = self(input_ids,
                              caches=self.gpt.gen_empty_caches(
                                  input_ids.shape[0]))
        out_ids = input_ids
        for _ in range(max_new_tokens):
            nxt = self._sample(logits._value[:, -1, :], temperature, top_k,
                               top_p=top_p)
            nxt_t = Tensor(nxt.astype(out_ids._value.dtype))
            out_ids = O.concat([out_ids, nxt_t], axis=1)
            logits, caches = self(nxt_t, caches=caches)
        return out_ids

    @staticmethod
    def _nucleus_mask(scaled, top_p):
        """Mask logits outside the nucleus: keep the smallest set of
        tokens whose probability mass reaches ``top_p`` (the top-1 token
        is always kept).  ``top_p`` is a scalar or a broadcastable (B, 1)
        per-row array."""
        import jax
        import jax.numpy as jnp
        probs = jax.nn.softmax(scaled, axis=-1)
        desc = -jnp.sort(-probs, axis=-1)
        csum = jnp.cumsum(desc, axis=-1)
        # token kept while the mass BEFORE it is still under p
        keep = (csum - desc) < jnp.maximum(top_p, 1e-9)
        kth = jnp.sum(keep, axis=-1, keepdims=True)  # >= 1 per row
        minp = jnp.take_along_axis(desc, kth - 1, axis=-1)
        return jnp.where(probs < minp, -1e30, scaled)

    @staticmethod
    def _sample(last, temperature, top_k, key=None, top_p=None):
        """Single owner of the sampling math (greedy / temperature /
        top-k / nucleus top-p) for every decode path.  ``key=None`` draws
        from the global RNG (eager concat path); the jit paths pass a
        traced key.

        Scalar mode (python-number ``temperature``): one config for the
        whole batch — the historical behavior, bit-for-bit.  Vector mode
        (array ``temperature``/``top_k``/``top_p`` of shape (B,)): each
        row samples under its own config — the serving engine's
        per-request sampling params; ``top_k=0`` / ``top_p=1.0`` disable
        the respective filter for that row, ``temperature=0`` makes the
        row greedy (identical argmax to the scalar greedy path: both
        argmax the same f32 ``logits / 1e-6``)."""
        import jax
        import jax.numpy as jnp

        from ..core import random as core_random
        last = last.astype(jnp.float32)
        if isinstance(temperature, (int, float)):
            last = last / max(temperature, 1e-6)
            if top_k is not None:
                cutoff = jax.lax.top_k(last, top_k)[0][:, -1:]
                last = jnp.where(last < cutoff, -1e30, last)
            if top_p is not None:
                last = GPTForCausalLM._nucleus_mask(last, float(top_p))
            if temperature == 0.0:
                return jnp.argmax(last, axis=-1, keepdims=True)
            if key is None:
                key = core_random.split_key()
            return jax.random.categorical(key, last)[:, None]
        temperature = jnp.asarray(temperature, jnp.float32)
        scaled = last / jnp.maximum(temperature, 1e-6)[:, None]
        greedy = jnp.argmax(scaled, axis=-1, keepdims=True)
        if top_k is not None:
            kk = jnp.asarray(top_k, jnp.int32)
            vocab = scaled.shape[-1]
            desc = -jnp.sort(-scaled, axis=-1)
            cut = jnp.take_along_axis(
                desc, jnp.clip(kk - 1, 0, vocab - 1)[:, None], axis=-1)
            scaled = jnp.where((kk > 0)[:, None] & (scaled < cut),
                               -1e30, scaled)
        if top_p is not None:
            scaled = GPTForCausalLM._nucleus_mask(
                scaled, jnp.asarray(top_p, jnp.float32)[:, None])
        if key is None:
            key = core_random.split_key()
        sampled = jax.random.categorical(key, scaled)[:, None]
        return jnp.where((temperature == 0.0)[:, None], greedy, sampled)

    def _param_mesh(self):
        """The device mesh the model's parameters are placed on, or None.

        When ``parallel.shard_params`` placed the weights (TP serving: a
        model that needs 'mp' to fit), the decode program composes the
        same mesh: KV caches shard their heads dim on 'mp', the batch on
        the data axes, and GSPMD inserts the in-decode collectives — the
        reference's ``fused_multi_transformer_op.cu`` runs its allreduce
        inside the fused decode step the same way (ring id argument), and
        ``DistModel`` serves multi-rank (``dist_model.cc``)."""
        from jax.sharding import NamedSharding
        sh = getattr(self.gpt.wte.weight._value, "sharding", None)
        if isinstance(sh, NamedSharding) and any(
                sh.mesh.shape.get(a, 1) > 1
                for a in ("mp", "dp", "sharding", "ep")):
            # 'ep' counts: the embedding itself is replicated over it,
            # but expert stacks shard on it, and decode must compose the
            # same mesh (batch over the data axes incl. 'ep') or GSPMD
            # gathers every expert to every rank per tick
            return sh.mesh
        return None

    def _generate_static(self, input_ids, max_new_tokens, temperature,
                         top_k, top_p=None):
        """One compiled program generates ALL tokens: prefill + a
        ``lax.fori_loop`` decode loop with in-jit sampling over a static
        KV cache.  No per-token host round trips: sampling stays in the
        program, so the host dispatches once per generate() call."""
        import jax
        import jax.numpy as jnp

        from ..nn.layer import functional_call

        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if max_new_tokens <= 0:
            # prefill always samples one token, so the jitted program is
            # only built for >=1 new tokens; the eager path returns the
            # prompt unchanged for the same input
            return Tensor(ids)
        pp_mesh = None
        from ..parallel.api import get_mesh as _get_mesh
        amb = _get_mesh()
        if amb is not None and amb.shape.get("pp", 1) > 1:
            pp_mesh = amb
        if pp_mesh is not None:
            return self._generate_static_pp(ids, max_new_tokens,
                                            temperature, top_k, pp_mesh,
                                            top_p)
        b, prompt = ids.shape
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        max_len = prompt + max_new_tokens
        dtype = self.gpt.wte.weight._value.dtype
        caches = [(jnp.zeros((b, max_len, cfg.num_heads, head_dim), dtype),
                   jnp.zeros((b, max_len, cfg.num_heads, head_dim), dtype))
                  for _ in range(cfg.num_layers)]
        mesh = self._param_mesh()
        if mesh is not None:
            # TP/DP-sharded decode: caches shard heads on 'mp' (the qkv
            # projection's natural output sharding) and batch on the data
            # axes; ids likewise.  GSPMD then inserts the out_proj psum
            # and the vocab-parallel argmax/sample collectives inside the
            # one decode program.
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.api import batch_spec, decode_cache_sharding
            cache_sh = decode_cache_sharding(mesh)
            bspec = batch_spec(mesh)
            bax = bspec[0] if len(bspec) else None
            caches = [(jax.device_put(k, cache_sh),
                       jax.device_put(v, cache_sh)) for k, v in caches]
            ids = jax.device_put(ids, NamedSharding(mesh, P(bax, None)))
        params, buffers = self.functional_state()
        # programs are cached per decode configuration — rebuilding the
        # closure every call would recompile every call (jax's jit cache
        # keys on function identity)
        cache_key = (b, prompt, max_new_tokens, temperature == 0.0,
                     float(temperature), top_k, top_p, str(dtype))

        def fwd(params, ids_in, caches, pos):
            return functional_call(
                self, params, (Tensor(ids_in),),
                kwargs={"caches": caches, "cache_pos": pos},
                buffers=buffers, training=False)

        return self._run_decode_program(
            cache_key, fwd, params, ids, caches, temperature, top_k,
            b, prompt, max_new_tokens, top_p=top_p)

    def _run_decode_program(self, cache_key, fwd, params, ids, caches,
                            temperature, top_k, b, prompt, max_new_tokens,
                            mesh=None, top_p=None):
        """Build-or-reuse the jitted decode program and invoke it —
        scaffolding shared by the single/mp path and the pp path (only
        ``fwd(params, ids_in, caches, pos) -> (logits, caches)``
        differs).  Prefill + ``lax.fori_loop`` token loop + in-jit
        sampling + in-program concat; the greedy key is created ONCE per
        program (the sampler never reads it, and an eager key per call
        is one more host dispatch ahead of every generation)."""
        import contextlib

        import jax
        import jax.numpy as jnp

        from ..core import random as core_random

        greedy = temperature == 0.0
        gen_cache = self.__dict__.setdefault("_gen_program_cache", {})
        if cache_key not in gen_cache:
            def sample(last, key):
                return self._sample(last, temperature, top_k, key=key,
                                    top_p=top_p)

            @jax.jit
            def run(params, ids, caches, key):
                logits, caches_ = fwd(params, ids, caches,
                                      jnp.asarray(0, jnp.int32))
                nxt = sample(logits[:, -1, :],
                             jax.random.fold_in(key, 0)).astype(ids.dtype)
                outbuf = jnp.zeros((b, max_new_tokens), ids.dtype)
                outbuf = jax.lax.dynamic_update_slice(outbuf, nxt, (0, 0))

                def body(t, carry):
                    caches_, cur, outbuf = carry
                    logits, caches2 = fwd(params, cur, caches_,
                                          (prompt + t).astype(jnp.int32))
                    nx = sample(logits[:, -1, :],
                                jax.random.fold_in(key, t + 1)
                                ).astype(ids.dtype)
                    outbuf = jax.lax.dynamic_update_slice(
                        outbuf, nx, (jnp.asarray(0, jnp.int32), t + 1))
                    return caches2, nx, outbuf

                _, _, outbuf = jax.lax.fori_loop(
                    0, max_new_tokens - 1, body, (caches_, nxt, outbuf))
                # concat INSIDE the program: an eager concat after the
                # call would be one more host round trip per generate()
                return jnp.concatenate([ids, outbuf], axis=1)

            if len(gen_cache) >= 32:  # FIFO bound: variable-length serving
                gen_cache.pop(next(iter(gen_cache)))  # must not grow
            gen_cache[cache_key] = (run, jax.random.key(0) if greedy
                                    else None)
        run, greedy_key = gen_cache[cache_key]
        key = greedy_key if greedy else core_random.split_key()
        from jax import set_mesh as _set_mesh
        ctx = (_set_mesh(mesh) if mesh is not None
               else contextlib.nullcontext())
        with ctx:  # partial-manual shard_map (pp) needs the ambient mesh
            return Tensor(run(params, ids, caches, key))

    # pht-lint: hot-root (host draft-and-verify loop)
    def _generate_spec(self, input_ids, max_new_tokens, spec_k, drafter):
        """Speculative draft-and-verify greedy decoding (single-request
        path).  Two jitted programs — a prompt prefill and a (B, K+1)-wide
        VERIFY step that scores every proposal position in one forward
        over the static cache — plus a host loop that proposes drafts,
        accepts the longest argmax-matching prefix, and commits
        ``accepted+1`` tokens per round trip.  Rejected tails need no
        cache rollback: attention reads only ``kpos <= qpos`` and the
        next verify rewrites ``[length, length+K]``, so stale rows are
        never attended (the serving engine's tick shares this invariant).

        Output is bit-identical to ``_generate_static(temperature=0.0)``:
        both commit ``argmax(logits/1e-6)`` given the same committed
        prefix.  Returns None under a pp mesh (the caller falls back to
        the pipelined non-spec program — same tokens, no speedup).

        Acceptance counters land on ``self._last_spec_stats`` for the
        bench rows ({"proposed", "accepted", "ticks"})."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..nn.decode import accept_lengths, get_drafter
        from ..nn.layer import functional_call

        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if max_new_tokens <= 0:
            return Tensor(ids)
        from ..parallel.api import get_mesh as _get_mesh
        amb = _get_mesh()
        if amb is not None and amb.shape.get("pp", 1) > 1:
            return None
        b, prompt = ids.shape
        cfg = self.config
        K = int(spec_k)
        head_dim = cfg.hidden_size // cfg.num_heads
        # K extra rows: the last verify before a row finishes starts at
        # length prompt+max_new-1 and writes K+1 wide
        cache_len = prompt + max_new_tokens + K + 1
        dtype = self.gpt.wte.weight._value.dtype
        caches = [(jnp.zeros((b, cache_len, cfg.num_heads, head_dim), dtype),
                   jnp.zeros((b, cache_len, cfg.num_heads, head_dim), dtype))
                  for _ in range(cfg.num_layers)]
        mesh = self._param_mesh()
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.api import (batch_spec, decode_cache_sharding,
                                        token_batch_sharding)
            cache_sh = decode_cache_sharding(mesh)
            bspec = batch_spec(mesh)
            bax = bspec[0] if len(bspec) else None
            caches = [(jax.device_put(k, cache_sh),
                       jax.device_put(v, cache_sh)) for k, v in caches]
            ids = jax.device_put(ids, NamedSharding(mesh, P(bax, None)))
            tok_sh = token_batch_sharding(mesh)
        else:
            tok_sh = None
        params, buffers = self.functional_state()
        cache_key = ("spec", b, prompt, max_new_tokens, K, str(dtype))
        gen_cache = self.__dict__.setdefault("_gen_program_cache", {})
        if cache_key not in gen_cache:
            def prefill(params, ids_in, caches):
                logits, caches = functional_call(
                    self, params, (Tensor(ids_in),),
                    kwargs={"caches": caches,
                            "cache_pos": jnp.asarray(0, jnp.int32)},
                    buffers=buffers, training=False)
                nxt = self._sample(logits[:, -1, :], 0.0, None)
                return caches, nxt[:, 0].astype(jnp.int32)

            def verify(params, caches, toks, pos):
                logits, caches = functional_call(
                    self, params, (Tensor(toks),),
                    kwargs={"caches": caches, "cache_pos": pos},
                    buffers=buffers, training=False)
                out = self._sample(
                    logits.reshape(b * (K + 1), -1), 0.0, None)
                return caches, out[:, 0].reshape(b, K + 1).astype(jnp.int32)

            if len(gen_cache) >= 32:  # same FIFO bound as the fused loop
                gen_cache.pop(next(iter(gen_cache)))
            from ..observability.sanitizers import sanitize_donation
            gen_cache[cache_key] = (
                sanitize_donation(jax.jit(prefill, donate_argnums=(2,)),
                                  donate_argnums=(2,),
                                  site="gpt.spec_prefill"),
                sanitize_donation(jax.jit(verify, donate_argnums=(1,)),
                                  donate_argnums=(1,),
                                  site="gpt.spec_verify"))
        run_prefill, run_verify = gen_cache[cache_key]

        # resolve-once per (drafter, K): a ModelDrafter's jitted
        # ingest/propose programs live on the instance, so rebuilding it
        # every generate() would re-trace the draft model per call.  The
        # entry keeps a strong ref to the user's argument, so the id()
        # key cannot alias a recycled object.
        dcache = self.__dict__.setdefault("_spec_drafter_cache", {})
        entry = dcache.get((id(drafter), K))
        if entry is None or entry[0] is not drafter:
            if len(dcache) >= 8:
                dcache.pop(next(iter(dcache)))
            entry = (drafter, get_drafter(drafter, K))
            dcache[(id(drafter), K)] = entry
        dr = entry[1]
        dr.begin(b, cache_len)
        # explicit fetches (jax.device_get, not np.asarray-on-Array):
        # these are the loop's designed device->host syncs — one for the
        # prompt mirror, one per verify round trip — and the explicit
        # form is what the transfer-guard sanitizer whitelists
        np_ids = np.asarray(jax.device_get(ids), np.int32)
        dr.ingest(np_ids, np.zeros(b, np.int32),
                  np.full(b, prompt, np.int32))
        caches, tok0 = run_prefill(params, ids, caches)
        tok0 = jax.device_get(tok0)
        out = np.zeros((b, max_new_tokens), np.int32)
        out[:, 0] = tok0
        ngen = np.ones(b, np.int64)
        lengths = np.full(b, prompt, np.int32)  # committed cache rows
        last = tok0.copy()
        stats = {"proposed": 0, "accepted": 0, "ticks": 0}
        while (ngen < max_new_tokens).any():
            drafts, ndraft = dr.propose(last, lengths)
            ndraft = np.where(ngen >= max_new_tokens, 0, ndraft)
            toks = np.concatenate([last[:, None], drafts], axis=1)
            toks_j = jnp.asarray(toks)
            pos_j = jnp.asarray(lengths)
            if tok_sh is not None:
                toks_j = jax.device_put(toks_j, tok_sh)
                pos_j = jax.device_put(pos_j, tok_sh)
            caches, ver = run_verify(params, caches, toks_j, pos_j)
            ver = jax.device_get(ver)   # the round trip's designed fetch
            acc = accept_lengths(drafts, ndraft, ver)
            stats["ticks"] += 1
            ingest_nvalid = np.zeros(b, np.int32)
            old_lengths = lengths.copy()
            for i in range(b):
                if ngen[i] >= max_new_tokens:
                    continue  # frozen: re-verifies in place, commits nothing
                rem = max_new_tokens - int(ngen[i])
                # cap at the row's remaining budget: drafts past it are
                # discarded, and counting them would overstate the
                # acceptance rate the bench rows report
                stats["proposed"] += min(int(ndraft[i]), rem)
                stats["accepted"] += min(int(acc[i]), rem)
                take = min(int(acc[i]) + 1, rem)
                out[i, ngen[i]:ngen[i] + take] = ver[i, :take]
                ngen[i] += take
                if ngen[i] < max_new_tokens:
                    ingest_nvalid[i] = int(acc[i]) + 1
                    lengths[i] += int(acc[i]) + 1
                    last[i] = ver[i, int(acc[i])]
            if getattr(dr, "ingest_after_verify", True):
                # self-ingesting drafters already wrote these rows in
                # propose(); replaying them would recompute identical KV
                dr.ingest(toks, old_lengths, ingest_nvalid)
        self._last_spec_stats = stats
        return Tensor(jnp.concatenate(
            [ids, jnp.asarray(out).astype(ids.dtype)], axis=1))

    def _generate_static_pp(self, ids, max_new_tokens, temperature, top_k,
                            mesh, top_p=None):
        """Pipeline-sharded one-program decode: block params stacked over
        layers and sharded on 'pp'; each token crosses the stages via
        ``pipeline_decode_apply`` (masked sequential schedule), with the
        embedding/head replicated and 'mp'/'dp' riding GSPMD — the
        serving-side counterpart of the pp train step (the reference
        serves pipelined models through ``DistModel``'s per-stage
        processes, ``dist_model.cc``)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..nn.layer import functional_call
        from ..parallel.api import batch_spec, stack_block_params
        from ..parallel.pipeline import pipeline_decode_apply

        b, prompt = ids.shape
        cfg = self.config
        L = cfg.num_layers
        pp = mesh.shape.get("pp", 1)
        if L % pp:
            raise ValueError(
                f"num_layers={L} must divide evenly over pp={pp} stages "
                "for pipeline-sharded decode")
        head_dim = cfg.hidden_size // cfg.num_heads
        max_len = prompt + max_new_tokens
        max_pos = cfg.max_position_embeddings
        dtype = self.gpt.wte.weight._value.dtype
        prefix = self.pipeline_stage_spec()["block_prefix"]

        # stacking + placement reuse the train step's machinery and are
        # cached per (mesh, live param identity): fixed-weight serving
        # pays it once, a weight update (rebinding the tensors)
        # invalidates it.  Identity is tracked with WEAK refs — an id()
        # tuple alone could false-hit after CPython recycles a freed
        # array's address, while strong refs would pin the whole previous
        # parameter set in device memory until the next call
        import weakref
        live = tuple(p._value for _, p in self.named_parameters())
        mesh_key = tuple(sorted(mesh.shape.items()))
        placed = self.__dict__.setdefault("_pp_decode_param_cache", {})
        refs = placed.get("refs", ())
        hit = (placed.get("mesh") == mesh_key and len(refs) == len(live)
               and all(r() is v for r, v in zip(refs, live)))
        if not hit:
            placed["mesh"] = mesh_key
            placed["refs"] = tuple(weakref.ref(v) for v in live)
            placed["value"] = stack_block_params(
                self, mesh, param_sharding_spec, prefix, L)
        other, stacked = placed["value"]

        bspec = batch_spec(mesh)
        bax = bspec[0] if len(bspec) else None
        hax = "mp" if mesh.shape.get("mp", 1) > 1 else None
        cache_sh = NamedSharding(mesh, P("pp", bax, None, hax, None))
        zeros = jnp.zeros((L, b, max_len, cfg.num_heads, head_dim), dtype)
        caches = (jax.device_put(zeros, cache_sh),
                  jax.device_put(zeros, cache_sh))
        ids = jax.device_put(ids, NamedSharding(mesh, P(bax, None)))

        template = self.gpt.blocks[0]
        ln_f = self.gpt.ln_f

        def layer_step(lp, cache, x, pos):
            kc, vc = cache
            y, (nk, nv) = functional_call(
                template, lp, (Tensor(x),),
                kwargs={"cache": (kc, vc), "cache_pos": pos},
                training=False)
            return y, (nk, nv)

        def fwd(params, ids_in, caches, pos):
            other_p, stacked_p = params
            s = ids_in.shape[1]
            pos_idx = jnp.clip(pos + jnp.arange(s, dtype=jnp.int32),
                               0, max_pos - 1)
            x = (jnp.take(other_p["gpt.wte.weight"], ids_in, axis=0)
                 + jnp.take(other_p["gpt.wpe.weight"], pos_idx,
                            axis=0)[None])
            y, caches = pipeline_decode_apply(
                layer_step, stacked_p, caches, x, pos, mesh)
            xn = functional_call(
                ln_f, {"weight": other_p["gpt.ln_f.weight"],
                       "bias": other_p["gpt.ln_f.bias"]}, (Tensor(y),),
                training=False)
            logits = xn @ other_p["gpt.wte.weight"].T
            return logits, caches

        cache_key = ("pp", tuple(sorted(mesh.shape.items())), b, prompt,
                     max_new_tokens, temperature == 0.0,
                     float(temperature), top_k, top_p, str(dtype))
        return self._run_decode_program(
            cache_key, fwd, (other, stacked), ids, caches, temperature,
            top_k, b, prompt, max_new_tokens, mesh=mesh, top_p=top_p)

    def enable_sequence_parallel(self, axis: str = "sp", mesh=None,
                                 mode: str = "auto"):
        """Switch every attention layer to the ring/ulysses schedule over
        mesh axis ``axis`` (sequence/context parallelism inside the
        one-program train step — SURVEY §5.7, a capability the reference
        lacks). Delegates to the model-agnostic
        ``parallel.enable_sequence_parallel`` walker (any model whose
        attention carries ``supports_sequence_parallel`` works the same
        way); kept as a method for API compatibility.

        Persists on the model (like ``shard_params`` placement) until
        ``disable_sequence_parallel()``; ``make_sharded_train_step``
        enables/disables this automatically from the mesh's 'sp' axis."""
        from ..parallel.sequence import enable_sequence_parallel
        enable_sequence_parallel(self, axis, mesh, mode)

    def disable_sequence_parallel(self):
        from ..parallel.sequence import disable_sequence_parallel
        disable_sequence_parallel(self)

    def loss(self, input_ids, labels, position_ids=None):
        logits = self(input_ids, position_ids)
        return F.cross_entropy(
            ops.reshape(logits, [-1, self.config.vocab_size]),
            ops.reshape(labels, [-1]))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def pipeline_stage_spec(self) -> dict:
        """Pipeline decomposition consumed by
        ``parallel.make_sharded_train_step`` when the mesh has a 'pp' axis
        (ref ``PipelineLayer`` segmentation ``parallel_layers/pp_layers.py:162``
        and ``PipelineParallel.forward_backward_pipeline``
        ``pipeline_parallel.py:82-152``).

        The embedding head/tail run replicated over 'pp' — the tied ``wte``
        is the reference's ``SharedLayerDesc`` (``pp_layers.py:77``); its
        cross-stage grad allreduce (``pipeline_parallel.py:149``) falls out
        of AD on the replicated placement.  The block stack is sharded over
        'pp' with a stacked leading layer dim.

        Returns dict with:
          block_prefix: param-name prefix of the per-layer block params
          num_layers:   total transformer layers
          pre_fn(params, buffers, ids, key)  -> (b, s, h) hidden states
          layer_fn(layer_params, x)          -> x  (one block, pure)
          post_fn(params, x, labels)         -> scalar loss
        Each mirrors the corresponding slice of ``GPTModel.forward`` /
        ``GPTForCausalLM.loss`` exactly (parity-tested vs the non-pp path).
        """
        import jax
        import jax.numpy as jnp
        from ..core import random as core_random
        from ..nn.layer import functional_call
        from ..nn.functional.loss import fused_softmax_ce_rows
        from ..parallel.moe import collect_moe_aux

        moe = self.config.moe_num_experts > 0
        if moe and max(1, int(self.config.moe_every_n)) != 1:
            # the pipeline schedule stacks ONE block template's params
            # over the layer dim (stack_block_params) — interleaved
            # dense/MoE blocks have different param sets and cannot
            # stack; ep/mp/dp compositions serve moe_every_n fine
            raise ValueError(
                "pipeline parallelism requires homogeneous blocks: "
                f"moe_every_n={self.config.moe_every_n} interleaves dense "
                "and MoE blocks — use moe_every_n=1 under a 'pp' mesh")
        template = self.gpt.blocks[0]
        drop = self.gpt.drop
        ln_f = self.gpt.ln_f
        vocab = self.config.vocab_size

        def pre_fn(params, buffers, ids, key):
            wte = params["gpt.wte.weight"]
            wpe = params["gpt.wpe.weight"]
            s = ids.shape[1]
            # row slice of wpe == GPTModel.forward's slice+reshape path
            pos = jax.lax.slice_in_dim(wpe, 0, s, axis=0)[None]
            x = jnp.take(wte, ids, axis=0) + pos
            with core_random.rng_scope(key):
                x = functional_call(drop, {}, (Tensor(x),))
            return x

        def layer_fn(layer_params, x):
            h = functional_call(template, layer_params, (Tensor(x),))
            if not moe:
                return h
            # MoE: the load-balance aux the forward just left on the
            # layer, weighted as the loss takes it, is consumed INSIDE the
            # stage scan (pipeline_apply accumulates it across
            # layers/microbatches — the side channel collect_moe_aux
            # reads cannot escape a lax.scan)
            aux = collect_moe_aux(template.mlp,
                                  weight=self.config.moe_aux_weight)
            if aux is None:
                aux = jnp.zeros((), jnp.float32)
            return h, aux

        def post_fn(params, x, labels):
            xn = functional_call(
                ln_f, {"weight": params["gpt.ln_f.weight"],
                       "bias": params["gpt.ln_f.bias"]}, (Tensor(x),))
            logits = xn @ params["gpt.wte.weight"].T
            return jnp.mean(fused_softmax_ce_rows(
                logits.reshape(-1, vocab), labels.reshape(-1)))

        return {"block_prefix": "gpt.blocks.",
                "num_layers": self.config.num_layers,
                "pre_fn": pre_fn, "layer_fn": layer_fn, "post_fn": post_fn,
                "layer_aux": moe}


def param_sharding_spec(name: str, shape) -> tuple:
    """Named-axis PartitionSpec entries for each GPT parameter.

    The TP plan mirrors the reference's Megatron-style split
    (``parallel_layers/mp_layers.py``): qkv/fc_in are column-parallel (output
    dim on 'mp'), out_proj/fc_out are row-parallel (input dim on 'mp'), the
    embedding is vocab-parallel; everything else is replicated over 'mp'.
    ZeRO-3 ('sharding' axis) additionally shards the first remaining dim.
    Returns a tuple usable as jax.sharding.PartitionSpec(*spec).
    """
    if name.endswith(".weight_scale"):
        # weight-only quantization scales (nn/quant/weight_only.py): one
        # f32 per OUTPUT channel, so they follow the weight's out-feature
        # placement — sharded on 'mp' where the projection is column-
        # parallel, replicated where it is row-parallel.  Checked before
        # the weight rules: "qkv_proj.weight" substring-matches the
        # scale name too.
        if "qkv_proj." in name or "fc_in." in name:
            return ("mp",)
        return (None,)
    if "qkv_proj.weight" in name or "fc_in.weight" in name:
        return (None, "mp")       # (in, out): split output columns
    if "out_proj.weight" in name or "fc_out.weight" in name:
        return ("mp", None)       # split input rows
    if "qkv_proj.bias" in name or "fc_in.bias" in name:
        return ("mp",)
    # MoE expert stacks: expert dim on 'ep', hidden split on 'mp'
    # (same plan the MoELayer pspec annotations declare)
    if ".mlp.w1" in name:
        return ("ep", None, "mp")
    if ".mlp.b1" in name:
        return ("ep", "mp")
    if ".mlp.w2" in name:
        return ("ep", "mp", None)
    if ".mlp.b2" in name:
        return ("ep", None)
    if ".mlp.gate.weight" in name:
        return (None, None)       # router replicated
    if "wte.weight" in name:
        # vocab-parallel embedding (c_embedding); ZeRO-3 stacks 'sharding'
        # onto the vocab rows too — row-sharded gather/scatter-add partition
        # cleanly, while feature-dim sharding forces GSPMD to fully
        # rematerialize the batch-sharded cotangent (involuntary-remat).
        return (("mp", "sharding"), None)
    if "wpe.weight" in name:
        # ZeRO-3 would otherwise shard the *feature* dim; Shardy then
        # propagates that layout onto the batch-sharded activation cotangent
        # and GSPMD can only reach it via involuntary full rematerialization.
        # Row (position) sharding partitions the slice/pad grad path cleanly.
        return ("sharding", None)
    return tuple(None for _ in shape)
