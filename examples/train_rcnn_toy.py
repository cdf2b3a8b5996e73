#!/usr/bin/env python
"""Two-stage (Faster-RCNN-style) detector training through the SYMBOLIC
executor — the reference's ``example/rcnn`` flow on a toy task.

The full pipeline composes in one Symbol graph, exactly the reference's
architecture (rcnn/symbol/symbol_vgg.py analog):

  backbone convs → RPN head (objectness SoftmaxOutput w/ ignore labels +
  smooth_l1 bbox regression via make_loss) → ``contrib.Proposal`` (NMS'd
  region proposals from the live RPN outputs) → ``ROIPooling`` on the shared
  feature map → FC classifier head whose labels are assigned IN-GRAPH by a
  proposal-target subgraph (box_iou → pick/take/where) — the role of the
  reference's proposal_target operator.

RPN anchor targets are computed host-side per batch (the reference does the
same in its AnchorLoader, rcnn/core/loader.py). Training drives the raw
``simple_bind`` executor — forward / backward / SGD on the arg arrays — i.e.
the Module-API internals, on the GraphExecutor-equivalent.

Toy task: images contain one bright axis-aligned rectangle; its color channel
is its class (like examples/train_ssd_toy.py).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZE = 64          # input image H=W
STRIDE = 8         # backbone downsampling
FEAT = SIZE // STRIDE
SCALES = (2.0, 4.0)
RATIOS = (1.0,)
A = len(SCALES) * len(RATIOS)
POST_NMS = 8       # proposals kept per image


def make_batch(rs, n):
    """One colored rectangle per image; returns images, gt corner boxes
    (pixels), gt classes."""
    import numpy as np
    x = np.zeros((n, 3, SIZE, SIZE), np.float32)
    boxes = np.zeros((n, 4), np.float32)
    cls = np.zeros((n,), np.float32)
    for i in range(n):
        w = rs.randint(SIZE // 4, SIZE // 2)
        h = rs.randint(SIZE // 4, SIZE // 2)
        x0 = rs.randint(0, SIZE - w)
        y0 = rs.randint(0, SIZE - h)
        c = rs.randint(0, 3)
        x[i, c, y0:y0 + h, x0:x0 + w] = 1.0
        boxes[i] = [x0, y0, x0 + w - 1, y0 + h - 1]
        cls[i] = c
    return x, boxes, cls


def anchors_hw_a():
    """The Proposal op's anchor grid, in its (h, w, A) layout. The reference's
    rcnn example ships the same generate_anchors math the op uses
    (rcnn/processing/generate_anchor.py mirroring proposal.cc)."""
    import numpy as np

    from mxtpu.ops.detection import _rpn_anchors
    return np.asarray(_rpn_anchors(FEAT, FEAT, STRIDE, SCALES, RATIOS))


def rpn_targets(anchors, gt_boxes):
    """Host-side anchor targets (AnchorLoader parity): objectness labels in
    {1 pos, 0 neg, -1 ignore} + bbox regression targets/weights, laid out to
    match the (2A|4A, h, w) conv heads."""
    import numpy as np

    n = gt_boxes.shape[0]
    K = anchors.shape[0]                       # FEAT*FEAT*A, (h, w, A) order
    aw = anchors[:, 2] - anchors[:, 0] + 1.0
    ah = anchors[:, 3] - anchors[:, 1] + 1.0
    ax = anchors[:, 0] + 0.5 * (aw - 1)
    ay = anchors[:, 1] + 0.5 * (ah - 1)

    labels = np.full((n, K), -1.0, np.float32)
    targets = np.zeros((n, K, 4), np.float32)
    weights = np.zeros((n, K, 4), np.float32)
    for i in range(n):
        g = gt_boxes[i]
        ix1 = np.maximum(anchors[:, 0], g[0])
        iy1 = np.maximum(anchors[:, 1], g[1])
        ix2 = np.minimum(anchors[:, 2], g[2])
        iy2 = np.minimum(anchors[:, 3], g[3])
        inter = np.clip(ix2 - ix1 + 1, 0, None) * np.clip(iy2 - iy1 + 1, 0, None)
        area_a = aw * ah
        area_g = (g[2] - g[0] + 1) * (g[3] - g[1] + 1)
        iou = inter / (area_a + area_g - inter)
        neg = iou < 0.3
        pos = iou >= 0.5
        pos[np.argmax(iou)] = True             # best anchor is always positive
        # subsample negatives to ~3x positives (AnchorLoader fg_fraction
        # parity) so the objectness head is not swamped by background
        neg_idx = np.flatnonzero(neg & ~pos)
        keep = min(len(neg_idx), 3 * int(pos.sum()) + 4)
        neg_keep = np.random.RandomState(i + 1).choice(neg_idx, keep,
                                                       replace=False)
        labels[i, neg_keep] = 0.0
        labels[i, pos] = 1.0
        gw = g[2] - g[0] + 1.0
        gh = g[3] - g[1] + 1.0
        gx = g[0] + 0.5 * (gw - 1)
        gy = g[1] + 0.5 * (gh - 1)
        targets[i, :, 0] = (gx - ax) / aw
        targets[i, :, 1] = (gy - ay) / ah
        targets[i, :, 2] = np.log(gw / aw)
        targets[i, :, 3] = np.log(gh / ah)
        weights[i, pos] = 1.0

    # (h, w, A) → the conv heads' channel-major layouts
    lab = labels.reshape(n, FEAT, FEAT, A).transpose(0, 3, 1, 2).reshape(n, -1)
    tgt = targets.reshape(n, FEAT, FEAT, A * 4).transpose(0, 3, 1, 2)
    wgt = weights.reshape(n, FEAT, FEAT, A * 4).transpose(0, 3, 1, 2)
    return lab, tgt, wgt


def build_symbol(batch, num_classes=3):
    """The full two-stage graph (symbol_vgg.py get_vgg_train analog)."""
    from mxtpu import symbol as sym

    data = sym.Variable("data")
    im_info = sym.Variable("im_info")
    rpn_label = sym.Variable("rpn_label")
    bbox_target = sym.Variable("bbox_target")
    bbox_weight = sym.Variable("bbox_weight")
    gt_boxes = sym.Variable("gt_boxes")
    gt_cls = sym.Variable("gt_cls")

    x = data
    for i, ch in enumerate((16, 32, 64)):
        x = sym.Convolution(x, num_filter=ch, kernel=(3, 3), stride=(2, 2),
                            pad=(1, 1), name=f"conv{i}")
        x = sym.Activation(x, act_type="relu")
    feat = x                                               # (N, 64, 8, 8)

    rpn = sym.Activation(
        sym.Convolution(feat, num_filter=32, kernel=(3, 3), pad=(1, 1),
                        name="rpn_conv"), act_type="relu")
    score = sym.Convolution(rpn, num_filter=2 * A, kernel=(1, 1),
                            name="rpn_cls_score")          # (N, 2A, h, w)
    bbox = sym.Convolution(rpn, num_filter=4 * A, kernel=(1, 1),
                           name="rpn_bbox_pred")           # (N, 4A, h, w)

    # RPN losses
    score_rs = sym.reshape(score, shape=(batch, 2, A * FEAT * FEAT))
    rpn_cls_loss = sym.SoftmaxOutput(score_rs, rpn_label, multi_output=True,
                                     use_ignore=True, ignore_label=-1,
                                     normalization="valid",
                                     name="rpn_cls_loss")
    rpn_bbox_loss = sym.make_loss(
        sym.sum(sym.smooth_l1((bbox - bbox_target) * bbox_weight, scalar=3.0)),
        grad_scale=1.0 / batch, name="rpn_bbox_loss")

    # proposals from the LIVE rpn outputs (gradients blocked, like the
    # reference where Proposal is non-differentiable)
    prob = sym.softmax(score_rs, axis=1)
    prob4 = sym.reshape(prob, shape=(batch, 2 * A, FEAT, FEAT))
    rois = sym.contrib.Proposal(
        cls_prob=sym.BlockGrad(prob4), bbox_pred=sym.BlockGrad(bbox),
        im_info=im_info, feature_stride=STRIDE, scales=SCALES, ratios=RATIOS,
        rpn_pre_nms_top_n=32, rpn_post_nms_top_n=POST_NMS, threshold=0.7,
        rpn_min_size=4, name="proposal")                   # (N*POST_NMS, 5)

    # proposal-target subgraph (in-graph role of proposal_target.py):
    # label each roi by IoU with its own image's gt box
    roi_boxes = sym.slice_axis(rois, axis=1, begin=1, end=5)
    roi_img = sym.reshape(sym.slice_axis(rois, axis=1, begin=0, end=1),
                          shape=(batch * POST_NMS,))
    iou = sym.contrib.box_iou(roi_boxes, gt_boxes, format="corner")
    own_iou = sym.pick(iou, roi_img)                       # (R,)
    roi_gt = sym.take(gt_cls, roi_img)                     # (R,)
    roi_label = sym.where(own_iou > 0.5, roi_gt + 1.0, sym.zeros_like(roi_gt))

    # stage-2 head on pooled features — trained on FROZEN shared features
    # (BlockGrad on feat): the in-graph rendering of the reference's
    # alternating-training schedule. Joint training at any useful ROI loss
    # scale lets the background-dominated stage-2 gradient swamp the shared
    # convs and collapse the RPN score map to the positive base rate; with
    # the feature path blocked, the head trains at full scale while the
    # RPN alone owns the backbone.
    pooled = sym.ROIPooling(sym.BlockGrad(feat), rois, pooled_size=(4, 4),
                            spatial_scale=1.0 / STRIDE)    # (R, 64, 4, 4)
    h1 = sym.Activation(sym.FullyConnected(sym.Flatten(pooled), num_hidden=64,
                                           name="fc6"), act_type="relu")
    cls_score = sym.FullyConnected(h1, num_hidden=num_classes + 1, name="cls")
    roi_cls_loss = sym.SoftmaxOutput(cls_score, sym.BlockGrad(roi_label),
                                     grad_scale=1.0, normalization="batch",
                                     name="roi_cls_loss")

    from mxtpu.symbol import Group
    return Group([rpn_cls_loss, rpn_bbox_loss, roi_cls_loss,
                  sym.BlockGrad(rois), sym.BlockGrad(roi_label)])


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import mxtpu as mx
    from mxtpu import nd

    mx.rng.seed(0)
    rs = np.random.RandomState(0)
    N = args.batch_size
    out = build_symbol(N)
    anchors = anchors_hw_a()

    input_shapes = {
        "data": (N, 3, SIZE, SIZE), "im_info": (N, 3),
        "rpn_label": (N, A * FEAT * FEAT),
        "bbox_target": (N, 4 * A, FEAT, FEAT),
        "bbox_weight": (N, 4 * A, FEAT, FEAT),
        "gt_boxes": (N, 4), "gt_cls": (N,),
    }
    grad_req = {n: ("null" if n in input_shapes else "write")
                for n in out.list_arguments()}
    ex = out.simple_bind(ctx=mx.current_context(), grad_req=grad_req,
                         **input_shapes)
    # Xavier init for weights, zeros for biases
    init = mx.initializer.Xavier(magnitude=2.0)
    for name, arr in ex.arg_dict.items():
        if name in input_shapes:
            continue
        if name.endswith("_bias"):
            arr._set_data(arr.data * 0)
        else:
            init(name, arr)

    im_info = np.tile([SIZE, SIZE, 1.0], (N, 1)).astype(np.float32)
    weight_names = [n for n in out.list_arguments() if n not in input_shapes]

    last = {}
    for step in range(args.steps):
        imgs, gtb, gtc = make_batch(rs, N)
        lab, tgt, wgt = rpn_targets(anchors, gtb)
        ex.forward(is_train=True, data=nd.array(imgs), im_info=nd.array(im_info),
                   rpn_label=nd.array(lab), bbox_target=nd.array(tgt),
                   bbox_weight=nd.array(wgt), gt_boxes=nd.array(gtb),
                   gt_cls=nd.array(gtc))
        ex.backward()
        for n in weight_names:                  # plain SGD on the executor
            ex.arg_dict[n]._set_data(
                ex.arg_dict[n].data - args.lr * ex.grad_dict[n].data)

        rpn_prob, _, roi_prob, rois, roi_label = [o.asnumpy() for o in ex.outputs]
        # metrics: RPN objectness accuracy on labeled anchors, ROI head accuracy
        fg_prob = rpn_prob[:, 1, :]
        labeled = lab >= 0
        rpn_acc = float((((fg_prob > 0.5) == (lab > 0.5)) & labeled).sum()
                        / max(labeled.sum(), 1))
        roi_acc = float((roi_prob.argmax(axis=1) == roi_label).mean())
        pos_frac = float((roi_label > 0).mean())
        last = {"rpn_acc": rpn_acc, "roi_acc": roi_acc, "pos_frac": pos_frac}
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:3d}: rpn_acc={rpn_acc:.3f} "
                  f"roi_acc={roi_acc:.3f} roi_pos_frac={pos_frac:.2f}")
    return last


if __name__ == "__main__":
    stats = main()
    print(f"final: {stats}")
