"""PP-YOLOE-style anchor-free detector — the conv-heavy static-graph
driver config (BASELINE.json #5: "PP-YOLOE / PP-OCRv3-class detection model
via jit/static path").

Capability reference: PaddleDetection's PP-YOLOE (CSPResNet backbone,
CSPPAN neck, ET-head with distribution-focal regression); the reference
repo itself ships only the detection *ops* this builds on
(``python/paddle/vision/ops.py``: yolo-era ops, nms, deform conv). The
architecture here is a compact TPU-first re-design: plain SiLU ConvBN
blocks with CSP splits (XLA fuses BN+SiLU into the conv epilogue), an
anchor-free decoupled head, center-prior assignment for the training loss
(the task-aligned assigner simplified), and decode+NMS through
``vision.ops.nms`` for eval.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

import jax.numpy as jnp

from .. import ops
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..nn.layers.common import Linear
from ..nn.layers.conv import Conv2D
from ..nn.layers.norm import BatchNorm2D
from ..nn.parameter import ParamAttr

__all__ = ["PPYOLOEConfig", "CSPResNet", "CSPPAN", "PPYOLOEHead", "PPYOLOE",
           "ppyoloe_s"]


@dataclasses.dataclass
class PPYOLOEConfig:
    num_classes: int = 80
    # width/depth multipliers: (0.33, 0.50) ~ the "s" scale
    depth_mult: float = 0.33
    width_mult: float = 0.50
    reg_max: int = 16             # DFL distribution bins
    strides: Sequence[int] = (8, 16, 32)


def _c(ch, width_mult):
    return max(8, int(round(ch * width_mult / 8)) * 8)


def _n(n, depth_mult):
    return max(1, int(round(n * depth_mult)))


class ConvBNAct(Layer):
    def __init__(self, cin, cout, k=3, stride=1, groups=1, act=True):
        super().__init__()
        self.conv = Conv2D(cin, cout, k, stride=stride, padding=k // 2,
                           groups=groups, bias_attr=False)
        self.bn = BatchNorm2D(cout)
        self.act = act

    def forward(self, x):
        x = self.bn(self.conv(x))
        return F.silu(x) if self.act else x


class CSPBlock(Layer):
    """CSP stage: split, run residual ConvBN bottlenecks on one branch,
    concat, fuse — the backbone building block."""

    def __init__(self, cin, cout, n_blocks):
        super().__init__()
        mid = cout // 2
        self.left = ConvBNAct(cin, mid, 1)
        self.right = ConvBNAct(cin, mid, 1)
        self.blocks = LayerList([
            LayerList([ConvBNAct(mid, mid, 3), ConvBNAct(mid, mid, 3)])
            for _ in range(n_blocks)])
        self.fuse = ConvBNAct(2 * mid, cout, 1)

    def forward(self, x):
        left = self.left(x)
        y = self.right(x)
        for pair in self.blocks:
            y = y + pair[1](pair[0](y))
        return self.fuse(ops.concat([left, y], axis=1))


class CSPResNet(Layer):
    """Backbone: stem + 3 downsampling CSP stages -> feature pyramid
    (strides 8/16/32)."""

    def __init__(self, cfg: PPYOLOEConfig):
        super().__init__()
        w, d = cfg.width_mult, cfg.depth_mult
        # stem downsamples 4x; each of the 3 stages downsamples 2x more,
        # so the pyramid comes out at true strides 8 / 16 / 32 (matching
        # PPYOLOEHead.strides — a 4th stage would shift them to 16/32/64)
        chs = [_c(64, w), _c(256, w), _c(512, w), _c(1024, w)]
        self.out_channels = chs[1:]
        self.stem = LayerList([
            ConvBNAct(3, chs[0] // 2, 3, stride=2),
            ConvBNAct(chs[0] // 2, chs[0], 3, stride=2),
        ])
        self.stages = LayerList()
        n = _n(3, d)
        for cin, cout in zip(chs[:-1], chs[1:]):
            self.stages.append(LayerList([
                ConvBNAct(cin, cout, 3, stride=2),
                CSPBlock(cout, cout, n),
            ]))

    def forward(self, x) -> List:
        for s in self.stem:
            x = s(x)
        feats = []
        for down, csp in self.stages:
            x = csp(down(x))
            feats.append(x)       # strides 8, 16, 32
        return feats


class CSPPAN(Layer):
    """PAN neck: top-down then bottom-up fusion with CSP blocks."""

    def __init__(self, in_channels, cfg: PPYOLOEConfig):
        super().__init__()
        self.reduces = LayerList([ConvBNAct(c, in_channels[0], 1)
                                  for c in in_channels])
        n = _n(3, cfg.depth_mult)
        c = in_channels[0]
        self.td_blocks = LayerList([CSPBlock(2 * c, c, n)
                                    for _ in in_channels[:-1]])
        self.downs = LayerList([ConvBNAct(c, c, 3, stride=2)
                                for _ in in_channels[:-1]])
        self.bu_blocks = LayerList([CSPBlock(2 * c, c, n)
                                    for _ in in_channels[:-1]])
        self.out_channels = [c] * len(in_channels)

    def forward(self, feats):
        feats = [r(f) for r, f in zip(self.reduces, feats)]
        # top-down: upsample deeper levels into shallower
        td = [feats[-1]]
        for i in range(len(feats) - 2, -1, -1):
            up = F.interpolate(td[0], scale_factor=2, mode="nearest")
            td.insert(0, self.td_blocks[i](ops.concat([feats[i], up],
                                                      axis=1)))
        # bottom-up
        outs = [td[0]]
        for i in range(len(feats) - 1):
            d = self.downs[i](outs[-1])
            outs.append(self.bu_blocks[i](ops.concat([d, td[i + 1]],
                                                     axis=1)))
        return outs


class PPYOLOEHead(Layer):
    """Decoupled anchor-free head: per-level cls logits and DFL-style
    distance distributions over ``reg_max`` bins per side."""

    def __init__(self, in_channels, cfg: PPYOLOEConfig):
        super().__init__()
        self.num_classes = cfg.num_classes
        self.reg_max = cfg.reg_max
        self.strides = tuple(cfg.strides)
        c = in_channels[0]
        self.cls_convs = LayerList([ConvBNAct(c, c, 3) for _ in in_channels])
        self.reg_convs = LayerList([ConvBNAct(c, c, 3) for _ in in_channels])
        prior = -math.log((1 - 0.01) / 0.01)   # focal-style cls bias prior
        self.cls_preds = LayerList([
            Conv2D(c, cfg.num_classes, 3, padding=1,
                   bias_attr=ParamAttr(initializer=I.Constant(prior)))
            for _ in in_channels])
        self.reg_preds = LayerList([
            Conv2D(c, 4 * cfg.reg_max, 3, padding=1) for _ in in_channels])
        self.proj = Tensor(jnp.arange(cfg.reg_max, dtype=jnp.float32))

    def forward(self, feats):
        cls_logits, reg_dists = [], []
        for i, f in enumerate(feats):
            cls_logits.append(self.cls_preds[i](self.cls_convs[i](f)))
            reg_dists.append(self.reg_preds[i](self.reg_convs[i](f)))
        return cls_logits, reg_dists

    def decode(self, cls_logits, reg_dists):
        """(B, sum HW, 4) boxes in input pixels + (B, sum HW, C) scores."""
        boxes, scores = [], []
        for lvl, (cl, rd) in enumerate(zip(cls_logits, reg_dists)):
            b, ncls, h, w = cl.shape
            stride = self.strides[lvl]
            clv = cl._value if isinstance(cl, Tensor) else cl
            rdv = rd._value if isinstance(rd, Tensor) else rd
            # distribution -> expected distances (l, t, r, b) per cell
            dist = rdv.reshape(b, 4, self.reg_max, h, w)
            dist = jnp.einsum("bkshw,s->bkhw", jnp.exp(
                dist - jnp.max(dist, axis=2, keepdims=True)) /
                jnp.sum(jnp.exp(dist - jnp.max(dist, axis=2, keepdims=True)),
                        axis=2, keepdims=True), self.proj._value)
            ys = (jnp.arange(h, dtype=jnp.float32) + 0.5)[:, None]
            xs = (jnp.arange(w, dtype=jnp.float32) + 0.5)[None, :]
            cx = jnp.broadcast_to(xs, (h, w)) * stride
            cy = jnp.broadcast_to(ys, (h, w)) * stride
            x1 = cx - dist[:, 0] * stride
            y1 = cy - dist[:, 1] * stride
            x2 = cx + dist[:, 2] * stride
            y2 = cy + dist[:, 3] * stride
            bx = jnp.stack([x1, y1, x2, y2], axis=-1).reshape(b, h * w, 4)
            sc = F.sigmoid(Tensor(clv))._value.transpose(0, 2, 3, 1)
            boxes.append(bx)
            scores.append(sc.reshape(b, h * w, ncls))
        return (Tensor(jnp.concatenate(boxes, axis=1)),
                Tensor(jnp.concatenate(scores, axis=1)))


class PPYOLOE(Layer):
    """Backbone + neck + head; ``loss`` trains with center-prior
    assignment (BCE cls + L1 on DFL-expected distances); ``predict``
    decodes and NMS-filters."""

    def __init__(self, config: Optional[PPYOLOEConfig] = None):
        super().__init__()
        self.config = config or PPYOLOEConfig()
        self.backbone = CSPResNet(self.config)
        self.neck = CSPPAN(self.backbone.out_channels, self.config)
        self.head = PPYOLOEHead(self.neck.out_channels, self.config)

    def forward(self, images):
        return self.head(self.neck(self.backbone(images)))

    def loss(self, images, gt_boxes, gt_labels):
        """Simplified training objective: each gt is assigned to the cell
        containing its center at every level; cls BCE everywhere + L1
        distance regression on assigned cells.

        Targets are pure functions of the ground truth (no gradient), so
        they are built with raw jnp and enter the graph as constants; the
        prediction path stays in taped Tensor ops end-to-end so
        ``loss(...).backward()`` works in eager mode and the same code
        traces under jit (the driver's compiled-executor config)."""
        cls_logits, reg_dists = self(images)
        gb = gt_boxes._value if isinstance(gt_boxes, Tensor) else gt_boxes
        gl = gt_labels._value if isinstance(gt_labels, Tensor) else gt_labels
        total = None
        ncls = self.config.num_classes
        for lvl, (cl, rd) in enumerate(zip(cls_logits, reg_dists)):
            stride = self.head.strides[lvl]
            b, _, h, w = cl.shape
            # ---- constant targets (raw jnp; stop-gradient by design) ----
            cx = (gb[..., 0] + gb[..., 2]) / 2.0 / stride    # (B, G)
            cy = (gb[..., 1] + gb[..., 3]) / 2.0 / stride
            gi = jnp.clip(cx.astype(jnp.int32), 0, w - 1)
            gj = jnp.clip(cy.astype(jnp.int32), 0, h - 1)
            flat = gj * w + gi                               # (B, G)
            onehot = jnp.eye(ncls)[gl]                       # (B, G, C)
            valid = (gb[..., 2] > gb[..., 0])[..., None]     # (B, G, 1)
            tgt = jnp.clip(
                jnp.zeros((b, h * w, ncls)).at[
                    jnp.arange(b)[:, None], flat].add(onehot * valid),
                0.0, 1.0)
            gd = jnp.stack([
                cx - gi.astype(jnp.float32),                 # gt l in cells
                cy - gj.astype(jnp.float32),
                gi.astype(jnp.float32) + 1.0 - cx,
                gj.astype(jnp.float32) + 1.0 - cy,
            ], axis=1)                                       # (B, 4, G)
            tgt_t = Tensor(tgt)
            gd_t = Tensor(gd)
            valid_t = Tensor(jnp.transpose(
                jnp.broadcast_to(valid, valid.shape[:2] + (4,)),
                (0, 2, 1)).astype(jnp.float32))              # (B, 4, G)
            flat4 = Tensor(jnp.broadcast_to(flat[:, None, :],
                                            (b, 4, flat.shape[1])))
            denom = Tensor(jnp.maximum(
                jnp.sum(valid.astype(jnp.float32)), 1.0) * 4.0)
            # ---- taped prediction path ----
            logits = ops.reshape(ops.transpose(cl, [0, 2, 3, 1]),
                                 [b, h * w, ncls])
            cls_loss = F.binary_cross_entropy_with_logits(
                logits, tgt_t, reduction="mean")
            dist = ops.reshape(rd, [b, 4, self.config.reg_max, h * w])
            sm = F.softmax(dist, axis=2)
            proj = Tensor(self.head.proj._value.reshape(1, 1, -1, 1))
            exp_d = ops.sum(sm * proj, axis=2)               # (B, 4, HW)
            picked = ops.take_along_axis(exp_d, flat4, axis=2)
            reg_sum = ops.sum(ops.abs(picked - gd_t) * valid_t)
            reg_loss = reg_sum / denom
            lvl_loss = cls_loss + 0.5 * reg_loss
            total = lvl_loss if total is None else total + lvl_loss
        return total

    def predict(self, images, score_threshold=0.4, iou_threshold=0.5,
                top_k=100):
        """Decoded, NMS-filtered detections for a single image batch."""
        from ..vision.ops import nms
        self.eval()
        cls_logits, reg_dists = self(images)
        boxes, scores = self.head.decode(cls_logits, reg_dists)
        out = []
        bv, sv = boxes._value, scores._value
        for i in range(bv.shape[0]):
            conf = sv[i].max(-1)
            labels = sv[i].argmax(-1)
            m = conf >= score_threshold
            bi = Tensor(jnp.asarray(bv[i][m]))
            if bi.shape[0] == 0:
                out.append((bi, Tensor(jnp.zeros((0,))),
                            Tensor(jnp.zeros((0,), jnp.int32))))
                continue
            keep = nms(bi, iou_threshold, scores=Tensor(jnp.asarray(
                conf[m])), top_k=top_k)
            kv = keep._value if isinstance(keep, Tensor) else jnp.asarray(keep)
            out.append((Tensor(bv[i][m][kv]),
                        Tensor(conf[m][kv]),
                        Tensor(labels[m][kv].astype(jnp.int32))))
        return out


def ppyoloe_s(num_classes: int = 80) -> PPYOLOE:
    """The "s" scale (depth 0.33 / width 0.50)."""
    return PPYOLOE(PPYOLOEConfig(num_classes=num_classes))
