"""A language model that is not a ``GPTForCausalLM``, for the harness's
rehearsal alone: an embedding with no positions, blocks of RMSNorm and a
gated causal convolution (one fused input projection [a | g], a depthwise
convolution over the last ``conv_kernel`` tokens of ``a``, a SiLU gate, an
output projection, the residual), a final RMSNorm and an untied head.  It
is the program's side of ``benchmark/configs/gated-conv-tiny-rehearsal
.json``, written with the package's public layers as a user writes a
model; the benchmark's side (plain reference, operation count,
configuration, workload) is files under ``benchmark/``, and the harness
finds all of it by the names in those files.  No cell runs it.
"""

import dataclasses

import jax

import paddle_hackathon_tpu as paddle
import paddle_hackathon_tpu.nn.functional as F
from paddle_hackathon_tpu import nn


@dataclasses.dataclass
class GatedConvConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    inner_size: int
    conv_kernel: int


class GatedConvBlock(nn.Layer):
    def __init__(self, c: GatedConvConfig):
        super().__init__()
        self.norm = nn.RMSNorm(c.hidden_size)
        self.in_proj = nn.Linear(c.hidden_size, 2 * c.inner_size,
                                 bias_attr=False)
        self.conv = self.create_parameter([c.conv_kernel, c.inner_size])
        self.out_proj = nn.Linear(c.inner_size, c.hidden_size,
                                  bias_attr=False)

    def forward(self, x):
        with jax.named_scope("mixer"):
            a, g = paddle.split(self.in_proj(self.norm(x)), 2, axis=-1)
            taps, s = self.conv.shape[0], a.shape[1]
            past = paddle.zeros([a.shape[0], taps - 1, a.shape[2]], a.dtype)
            a = paddle.concat([past, a], axis=1)
            mixed = sum(a[:, taps - 1 - j:taps - 1 - j + s] * self.conv[j]
                        for j in range(taps))
            return x + self.out_proj(mixed * F.silu(g))


class GatedConvLM(nn.Layer):
    def __init__(self, c: GatedConvConfig):
        super().__init__()
        self.embed = nn.Embedding(c.vocab_size, c.hidden_size)
        self.blocks = nn.LayerList(
            [GatedConvBlock(c) for _ in range(c.num_layers)])
        self.norm_f = nn.RMSNorm(c.hidden_size)
        self.head = nn.Linear(c.hidden_size, c.vocab_size, bias_attr=False)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed(input_ids)
        for block in self.blocks:
            x = block(x)
        with jax.named_scope("lm_head"):
            return self.head(self.norm_f(x))


def replicated(name: str, shape) -> tuple:
    """The sharding rule: every leaf whole on every chip."""
    return (None,) * len(shape)
