#!/usr/bin/env python
"""End-to-end SSD-style detector training — the reference's ``example/ssd``
flow on a toy synthetic task: images containing one axis-aligned bright box
whose class is its color channel; a small conv backbone with multibox heads
trains against ``contrib.MultiBoxTarget`` and decodes with
``contrib.MultiBoxDetection``.

Demonstrates the full detection stack composing for TRAINING (prior
generation → target matching with hard-negative mining → cls + smooth-L1
losses → decode + NMS), not just per-op correctness.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_batch(rs, n, size=64):
    """Images with one colored rectangle; labels (n, 1, 5) [cls,x1,y1,x2,y2]."""
    import numpy as np
    x = np.zeros((n, 3, size, size), np.float32)
    labels = np.zeros((n, 1, 5), np.float32)
    for i in range(n):
        w = rs.randint(size // 4, size // 2)
        h = rs.randint(size // 4, size // 2)
        x0 = rs.randint(0, size - w)
        y0 = rs.randint(0, size - h)
        cls = rs.randint(0, 3)
        x[i, cls, y0:y0 + h, x0:x0 + w] = 1.0
        labels[i, 0] = [cls, x0 / size, y0 / size, (x0 + w) / size,
                        (y0 + h) / size]
    return x, labels


def build_net(num_classes=3, num_anchors=3):
    from mxtpu.gluon import nn

    class ToySSD(nn.HybridSequential):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.backbone = nn.HybridSequential()
                for ch in (16, 32, 64):
                    self.backbone.add(
                        nn.Conv2D(ch, 3, strides=2, padding=1,
                                  activation="relu"))
                self.cls_head = nn.Conv2D(num_anchors * (num_classes + 1), 3,
                                          padding=1)
                self.loc_head = nn.Conv2D(num_anchors * 4, 3, padding=1)

        def forward(self, x):
            feat = self.backbone(x)
            return feat, self.cls_head(feat), self.loc_head(feat)

    return ToySSD()


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--batch-size", type=int, default=16)
    # note the step is doubly normalized: the loss divides by num_pos*B and
    # trainer.step(batch_size) divides by B again — lr is calibrated for that
    p.add_argument("--lr", type=float, default=0.4)
    p.add_argument("--eval-iou", type=float, default=0.4)
    args = p.parse_args()

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    import numpy as np

    import mxtpu as mx
    from mxtpu import autograd, gluon, nd

    mx.rng.seed(0)  # deterministic init regardless of ambient rng state

    num_classes = 3
    sizes, ratios = (0.35, 0.6), (1.0, 2.0)
    num_anchors = len(sizes) + len(ratios) - 1
    net = build_net(num_classes, num_anchors)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    cls_loss = gluon.loss.SoftmaxCrossEntropyLoss()
    loc_loss = gluon.loss.HuberLoss()
    rs = np.random.RandomState(0)

    def heads(xb):
        feat, cls_raw, loc_raw = net(xb)
        B = cls_raw.shape[0]
        anchors = nd.contrib.MultiBoxPrior(feat, sizes=sizes, ratios=ratios)
        # priors enumerate position-major then anchor ((i*W+j)*A + a), so both
        # heads go NCHW -> NHWC -> (pos, anchor) before flattening
        cp = cls_raw.transpose((0, 2, 3, 1))            # (B, h, w, A*(C+1))
        cp = cp.reshape((B, -1, num_classes + 1))       # (B, hw*A, C+1)
        cls_preds = cp.transpose((0, 2, 1))             # (B, C+1, hw*A)
        loc_preds = loc_raw.transpose((0, 2, 3, 1)).reshape((B, -1))
        return anchors, cls_preds, loc_preds

    first = last = None
    for step in range(args.steps):
        xb_np, lb_np = make_batch(rs, args.batch_size)
        xb, lb = nd.array(xb_np), nd.array(lb_np)
        with autograd.record():
            anchors, cls_preds, loc_preds = heads(xb)
            loc_t, loc_m, cls_t = nd.contrib.MultiBoxTarget(
                anchors, lb, cls_preds, negative_mining_ratio=3.0)
            # cls: (B, C+1, A) -> per-anchor CE; mined-out anchors carry the
            # -1 ignore label and must be masked (sample_weight), exactly like
            # the reference's SoftmaxOutput ignore_label usage
            valid = cls_t >= 0
            lc = cls_loss(cls_preds.transpose((0, 2, 1)), nd.relu(cls_t),
                          sample_weight=valid)
            ll = loc_loss(loc_preds * loc_m, loc_t * loc_m)
            # normalize by matched-anchor count (standard SSD normalization):
            # per-sample means dilute the few contributing anchors otherwise
            A = cls_t.shape[1]
            num_pos = nd.sum(loc_m) / 4.0 + 1.0
            loss = (nd.sum(lc) + nd.sum(ll)) * A / (num_pos * cls_t.shape[0])
        loss.backward()
        trainer.step(args.batch_size)
        v = float(loss.asscalar())
        first = v if first is None else first
        last = v
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {v:.4f}")

    # evaluate: decode detections on a fresh batch, report mean IoU@top-1
    xe_np, le_np = make_batch(rs, 32)
    with autograd.predict_mode():
        anchors, cls_preds, loc_preds = heads(nd.array(xe_np))
        probs = nd.softmax(cls_preds, axis=1)
        det = nd.contrib.MultiBoxDetection(probs, loc_preds, anchors,
                                           nms_threshold=0.45)
    d = det.asnumpy()
    ious, hits = [], 0
    for i in range(32):
        rows = d[i][d[i][:, 0] >= 0]
        if not len(rows):
            ious.append(0.0)
            continue
        best = rows[0]
        gt = le_np[i, 0]
        x1, y1, x2, y2 = np.maximum(best[2], gt[1]), np.maximum(best[3], gt[2]), \
            np.minimum(best[4], gt[3]), np.minimum(best[5], gt[4])
        inter = max(0, x2 - x1) * max(0, y2 - y1)
        a1 = (best[4] - best[2]) * (best[5] - best[3])
        a2 = (gt[3] - gt[1]) * (gt[4] - gt[2])
        iou = inter / max(a1 + a2 - inter, 1e-9)
        ious.append(iou)
        hits += int(best[0] == gt[0] and iou > args.eval_iou)
    print(f"loss {first:.3f} -> {last:.3f}; mean IoU {np.mean(ious):.3f}; "
          f"cls+IoU>{args.eval_iou} hits {hits}/32")
    return first, last, float(np.mean(ious)), hits


if __name__ == "__main__":
    main()
