"""Detection ops (paddle.vision.ops parity).

Reference parity: paddle/fluid/operators/detection/ — multiclass_nms_op.cc,
yolo_box_op.cc, roi_align_op.cc, prior_box_op.cc, box_coder_op.cc (18k LoC of CUDA/C++
post-processing). TPU-native design: static-shape implementations (XLA requirement):
NMS returns a fixed `max_out` set with a validity mask and -1 padding instead of
dynamic LoD outputs; the O(n^2) IoU matrix is MXU/VPU-friendly.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ..core.dispatch import apply
from ..core.tensor import Tensor


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(np.asarray(x))


def _iou_matrix(boxes):
    # boxes [n,4] xyxy
    area = jnp.maximum(boxes[:, 2] - boxes[:, 0], 0) * jnp.maximum(boxes[:, 3] - boxes[:, 1], 0)
    lt = jnp.maximum(boxes[:, None, :2], boxes[None, :, :2])
    rb = jnp.minimum(boxes[:, None, 2:], boxes[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0)
    inter = wh[..., 0] * wh[..., 1]
    union = area[:, None] + area[None, :] - inter
    return inter / jnp.maximum(union, 1e-9)


def nms_mask(boxes, scores, iou_threshold=3e-1, score_threshold=None, top_k=None,
             use_pallas=None):
    """Pure static-shape NMS: returns keep mask [n].

    On TPU the greedy sweep runs as a single-VMEM Pallas kernel
    (ops/nms_pallas.py); elsewhere (or when `use_pallas=False`) it is a
    lax.scan over the precomputed IoU matrix."""
    from ..ops import nms_pallas as _np_kernel

    n = boxes.shape[0]
    order = jnp.argsort(-scores)
    if use_pallas is None:
        use_pallas = _np_kernel.supported(n)
    if use_pallas:
        # a Mosaic lowering/compile failure raises: the scan below is the
        # off-TPU path, not a net under the kernel
        keep_sorted_full = _np_kernel.nms_keep_mask_pallas(
            boxes[order], iou_threshold)
        keep = jnp.zeros(n, dtype=bool).at[order].set(keep_sorted_full)
        return _nms_mask_filters(keep, scores, score_threshold, top_k,
                                 order, n)
    iou = _iou_matrix(boxes)
    iou_sorted = iou[order][:, order]

    def body(keep, i):
        # suppressed if any earlier kept box overlaps > threshold
        sup = jnp.any(keep & (jnp.arange(n) < i) & (iou_sorted[i] > iou_threshold))
        keep = keep.at[i].set(~sup)
        return keep, None

    keep0 = jnp.zeros(n, dtype=bool).at[0].set(True)
    keep_sorted, _ = jax.lax.scan(body, keep0, jnp.arange(1, n))
    keep = jnp.zeros(n, dtype=bool).at[order].set(keep_sorted)
    return _nms_mask_filters(keep, scores, score_threshold, top_k, order, n)


def _nms_mask_filters(keep, scores, score_threshold, top_k, order, n):
    if score_threshold is not None:
        keep = keep & (scores > score_threshold)
    if top_k is not None:
        rank = jnp.zeros(n, jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
        keep = keep & (rank < top_k)
    return keep


def nms(boxes, iou_threshold=0.3, scores=None, category_idxs=None, categories=None, top_k=None):
    """paddle.vision.ops.nms parity: returns kept indices sorted by score.

    Eager op (dynamic output count — uses host filtering like the reference's CPU
    kernel); inside jit use `nms_mask` for the static-shape variant.
    """
    b = _t(boxes)._data
    s = _t(scores)._data if scores is not None else jnp.ones(b.shape[0])
    if category_idxs is not None:
        # category-aware: offset boxes per class so cross-class boxes never overlap
        c = _t(category_idxs)._data.astype(b.dtype)
        offset = c[:, None] * (jnp.max(b) + 1.0)
        mask = nms_mask(b + offset, s, iou_threshold)
    else:
        mask = nms_mask(b, s, iou_threshold)
    mask_np = np.asarray(mask)
    s_np = np.asarray(s)
    idxs = np.nonzero(mask_np)[0]
    idxs = idxs[np.argsort(-s_np[idxs])]
    if top_k is not None:
        idxs = idxs[:top_k]
    return Tensor(jnp.asarray(idxs.astype(np.int64)))


def multiclass_nms(bboxes, scores, score_threshold=0.05, nms_top_k=400, keep_top_k=100,
                   nms_threshold=0.3, normalized=True, background_label=0, name=None):
    """multiclass_nms_op.cc parity (static-shape): bboxes [N,M,4], scores [N,C,M].

    Returns (out [N, keep_top_k, 6] (label, score, x1,y1,x2,y2; -1 padded),
             valid counts [N]).
    """
    bv = _t(bboxes)._data
    sv = _t(scores)._data

    def per_image(boxes, score):
        C, M = score.shape
        all_entries = []
        for c in range(C):
            if c == background_label:
                continue
            sc = score[c]
            k = min(nms_top_k, M)
            top_s, top_i = jax.lax.top_k(sc, k)
            bx = boxes[top_i]
            keep = nms_mask(bx, top_s, nms_threshold, score_threshold)
            entry = jnp.concatenate(
                [jnp.full((k, 1), c, boxes.dtype), top_s[:, None], bx], axis=1
            )
            entry = jnp.where(keep[:, None], entry, jnp.full_like(entry, -1.0))
            all_entries.append(entry)
        if not all_entries:
            # every class is background (C==1 with background_label=0):
            # the reference emits an empty LoD result; here all-(-1) padding
            return (jnp.full((keep_top_k, 6), -1.0, boxes.dtype),
                    jnp.zeros((), jnp.int32))
        cat = jnp.concatenate(all_entries, axis=0)
        # rank by score, take keep_top_k
        k2 = min(keep_top_k, cat.shape[0])
        _, order = jax.lax.top_k(cat[:, 1], k2)
        out = cat[order]
        valid = jnp.sum(out[:, 1] > 0).astype(jnp.int32)
        if k2 < keep_top_k:
            out = jnp.concatenate([out, jnp.full((keep_top_k - k2, 6), -1.0, out.dtype)], axis=0)
        return out, valid

    outs, valids = jax.vmap(per_image)(bv, sv)
    return Tensor(outs), Tensor(valids)


def yolo_box(x, img_size, anchors, class_num, conf_thresh=0.01, downsample_ratio=32,
             clip_bbox=True, scale_x_y=1.0, iou_aware=False, iou_aware_factor=0.5, name=None):
    """yolo_box_op.cc parity: decode YOLO head [N, an*(5+C), H, W] -> boxes+scores."""
    xv = _t(x)._data
    img = _t(img_size)._data

    an = len(anchors) // 2
    anchors_wh = jnp.asarray(np.array(anchors, np.float32).reshape(an, 2))

    def fn(v, imsz):
        N, _, H, W = v.shape
        v = v.reshape(N, an, 5 + class_num, H, W)
        gx = jnp.arange(W, dtype=v.dtype).reshape(1, 1, 1, W)
        gy = jnp.arange(H, dtype=v.dtype).reshape(1, 1, H, 1)
        sig = jax.nn.sigmoid
        bx = (sig(v[:, :, 0]) * scale_x_y - (scale_x_y - 1) / 2 + gx) / W
        by = (sig(v[:, :, 1]) * scale_x_y - (scale_x_y - 1) / 2 + gy) / H
        bw = jnp.exp(v[:, :, 2]) * anchors_wh[:, 0].reshape(1, an, 1, 1) / (downsample_ratio * W)
        bh = jnp.exp(v[:, :, 3]) * anchors_wh[:, 1].reshape(1, an, 1, 1) / (downsample_ratio * H)
        conf = sig(v[:, :, 4])
        cls = sig(v[:, :, 5:])
        scores = conf[:, :, None] * cls  # [N, an, C, H, W]
        imh = imsz[:, 0].reshape(N, 1, 1, 1).astype(v.dtype)
        imw = imsz[:, 1].reshape(N, 1, 1, 1).astype(v.dtype)
        x1 = (bx - bw / 2) * imw
        y1 = (by - bh / 2) * imh
        x2 = (bx + bw / 2) * imw
        y2 = (by + bh / 2) * imh
        if clip_bbox:
            x1 = jnp.clip(x1, 0, imw - 1)
            y1 = jnp.clip(y1, 0, imh - 1)
            x2 = jnp.clip(x2, 0, imw - 1)
            y2 = jnp.clip(y2, 0, imh - 1)
        boxes = jnp.stack([x1, y1, x2, y2], axis=-1).reshape(N, an * H * W, 4)
        mask = (conf > conf_thresh).reshape(N, an, 1, H, W)
        scores = (scores * mask).transpose(0, 1, 3, 4, 2).reshape(N, an * H * W, class_num)
        return boxes, scores

    boxes, scores = fn(xv, img)
    return Tensor(boxes), Tensor(scores)


def roi_align(x, boxes, boxes_num, output_size, spatial_scale=1.0, sampling_ratio=-1,
              aligned=True, name=None):
    """roi_align_op.cc parity via bilinear grid sampling."""
    xv = _t(x)
    bv = _t(boxes).detach()
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    ph, pw = output_size

    def fn(feat, rois):
        # rois: [R, 4] xyxy in input scale; all on image 0 unless boxes_num used
        R = rois.shape[0]
        C, H, W = feat.shape[1], feat.shape[2], feat.shape[3]
        off = 0.5 if aligned else 0.0
        x1 = rois[:, 0] * spatial_scale - off
        y1 = rois[:, 1] * spatial_scale - off
        x2 = rois[:, 2] * spatial_scale - off
        y2 = rois[:, 3] * spatial_scale - off
        rw = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
        rh = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
        # sample centers
        ys = y1[:, None] + (jnp.arange(ph) + 0.5)[None, :] * (rh[:, None] / ph)  # [R, ph]
        xs = x1[:, None] + (jnp.arange(pw) + 0.5)[None, :] * (rw[:, None] / pw)  # [R, pw]

        def bilinear(img, yy, xx):
            y0 = jnp.floor(yy).astype(jnp.int32)
            x0 = jnp.floor(xx).astype(jnp.int32)
            y1i = jnp.clip(y0 + 1, 0, H - 1)
            x1i = jnp.clip(x0 + 1, 0, W - 1)
            y0c = jnp.clip(y0, 0, H - 1)
            x0c = jnp.clip(x0, 0, W - 1)
            wy = yy - y0
            wx = xx - x0
            v00 = img[:, y0c][:, :, x0c]
            v01 = img[:, y0c][:, :, x1i]
            v10 = img[:, y1i][:, :, x0c]
            v11 = img[:, y1i][:, :, x1i]
            return (v00 * (1 - wy)[None, :, None] * (1 - wx)[None, None, :]
                    + v01 * (1 - wy)[None, :, None] * wx[None, None, :]
                    + v10 * wy[None, :, None] * (1 - wx)[None, None, :]
                    + v11 * wy[None, :, None] * wx[None, None, :])

        def per_roi(r):
            return bilinear(feat[0], ys[r], xs[r])  # [C, ph, pw]

        return jax.vmap(per_roi)(jnp.arange(R))

    return apply(lambda f, r: fn(f, r), xv, bv)


def box_coder(prior_box, prior_box_var, target_box, code_type="encode_center_size",
              box_normalized=True, axis=0, name=None):
    """box_coder_op.cc parity (encode/decode center-size)."""

    def fn(pb, pbv, tb):
        pw = pb[:, 2] - pb[:, 0] + (0 if box_normalized else 1)
        ph = pb[:, 3] - pb[:, 1] + (0 if box_normalized else 1)
        px = pb[:, 0] + pw * 0.5
        py = pb[:, 1] + ph * 0.5
        if code_type == "encode_center_size":
            tw = tb[:, 2] - tb[:, 0] + (0 if box_normalized else 1)
            th = tb[:, 3] - tb[:, 1] + (0 if box_normalized else 1)
            tx = tb[:, 0] + tw * 0.5
            ty = tb[:, 1] + th * 0.5
            out = jnp.stack([
                (tx - px) / pw / pbv[:, 0],
                (ty - py) / ph / pbv[:, 1],
                jnp.log(tw / pw) / pbv[:, 2],
                jnp.log(th / ph) / pbv[:, 3],
            ], axis=1)
        else:  # decode
            dx, dy, dw, dh = tb[:, 0], tb[:, 1], tb[:, 2], tb[:, 3]
            cx = dx * pbv[:, 0] * pw + px
            cy = dy * pbv[:, 1] * ph + py
            w = jnp.exp(dw * pbv[:, 2]) * pw
            h = jnp.exp(dh * pbv[:, 3]) * ph
            out = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], axis=1)
        return out

    pbv = _t(prior_box_var) if prior_box_var is not None else Tensor(np.ones((1, 4), np.float32))
    return apply(fn, _t(prior_box).detach(), pbv.detach(), _t(target_box))


def prior_box(input, image, min_sizes, max_sizes=None, aspect_ratios=(1.0,),
              variance=(0.1, 0.1, 0.2, 0.2), flip=False, clip=False, steps=(0.0, 0.0),
              offset=0.5, name=None):
    """prior_box_op.cc parity (SSD anchors)."""
    H, W = input.shape[2], input.shape[3]
    img_h, img_w = image.shape[2], image.shape[3]
    step_h = steps[1] or img_h / H
    step_w = steps[0] or img_w / W
    ars = list(aspect_ratios)
    if flip:
        ars = ars + [1.0 / a for a in ars if a != 1.0]
    boxes = []
    for h in range(H):
        for w in range(W):
            cx = (w + offset) * step_w
            cy = (h + offset) * step_h
            for k, ms in enumerate(min_sizes):
                for ar in ars:
                    bw = ms * np.sqrt(ar) / 2
                    bh = ms / np.sqrt(ar) / 2
                    boxes.append([(cx - bw) / img_w, (cy - bh) / img_h,
                                  (cx + bw) / img_w, (cy + bh) / img_h])
                if max_sizes:
                    s = np.sqrt(ms * max_sizes[k]) / 2
                    boxes.append([(cx - s) / img_w, (cy - s) / img_h,
                                  (cx + s) / img_w, (cy + s) / img_h])
    b = np.asarray(boxes, np.float32).reshape(H, W, -1, 4)
    if clip:
        b = b.clip(0, 1)
    var = np.broadcast_to(np.asarray(variance, np.float32), b.shape).copy()
    return Tensor(jnp.asarray(b)), Tensor(jnp.asarray(var))


from ..nn.layer.layers import Layer as _Layer


class DeformConv2D(_Layer):
    """Deformable conv v1/v2 Layer (reference python/paddle/vision/ops.py:598).

    Thin stateful wrapper over the functional `deform_conv2d` below: holds
    weight [out, in/groups, kh, kw] (Normal(0, sqrt(2/fan_in)) like the
    reference's default initializer) and optional bias; v2 (modulated) when
    `mask` is passed to forward."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, deformable_groups=1, groups=1,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        if in_channels % groups != 0:
            raise ValueError("in_channels must be divisible by groups.")

        def _pair(v):
            return [v, v] if isinstance(v, int) else list(v)

        from ..nn import initializer as I

        self._in_channels = in_channels
        self._out_channels = out_channels
        self._kernel_size = _pair(kernel_size)
        self._stride = _pair(stride)
        self._padding = _pair(padding)
        self._dilation = _pair(dilation)
        self._deformable_groups = deformable_groups
        self._groups = groups
        filter_shape = ([out_channels, in_channels // groups]
                        + self._kernel_size)
        std = (2.0 / (int(np.prod(self._kernel_size)) * in_channels)) ** 0.5
        self.weight = self.create_parameter(
            shape=filter_shape, attr=weight_attr,
            default_initializer=None
            if (weight_attr and getattr(weight_attr, "initializer", None))
            else I.Normal(0.0, std))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                shape=[out_channels], attr=bias_attr, is_bias=True)

    def forward(self, x, offset, mask=None):
        return deform_conv2d(
            x, offset, self.weight, bias=self.bias, stride=self._stride,
            padding=self._padding, dilation=self._dilation,
            deformable_groups=self._deformable_groups, groups=self._groups,
            mask=mask)


def iou_similarity(x, y, box_normalized=True, name=None):
    """detection/iou_similarity_op.cc parity: pairwise IoU of x [N,4] vs y [M,4]
    (xyxy). box_normalized=False adds +1 to widths/heights like the reference."""
    def fn(a, b):
        off = 0.0 if box_normalized else 1.0
        area_a = jnp.maximum(a[:, 2] - a[:, 0] + off, 0) * jnp.maximum(
            a[:, 3] - a[:, 1] + off, 0)
        area_b = jnp.maximum(b[:, 2] - b[:, 0] + off, 0) * jnp.maximum(
            b[:, 3] - b[:, 1] + off, 0)
        lt = jnp.maximum(a[:, None, :2], b[None, :, :2])
        rb = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
        wh = jnp.maximum(rb - lt + off, 0)
        inter = wh[..., 0] * wh[..., 1]
        union = area_a[:, None] + area_b[None, :] - inter
        return jnp.where(union > 0, inter / jnp.maximum(union, 1e-10), 0.0)

    return apply(fn, _t(x), _t(y))


def bipartite_match(dist_matrix, match_type="bipartite", overlap_threshold=0.5,
                    name=None):
    """detection/bipartite_match_op.cc parity: greedy global-max bipartite
    matching on dist [R, C]. Returns (match_indices [C] int32 — matched row or
    -1, match_dist [C]). match_type='per_prediction' then assigns every still-
    unmatched column its argmax row when that overlap >= overlap_threshold.

    TPU design: lax.scan of min(R, C) greedy steps, each picking the global
    argmax of the live sub-matrix — no python loops over entries.
    """
    def fn(dist):
        R, C = dist.shape
        eps = 1e-6

        def step(carry, _):
            live, col_row, col_dist = carry  # live [R, C] mask
            masked = jnp.where(live, dist, -jnp.inf)
            flat = jnp.argmax(masked)
            i, j = flat // C, flat % C
            best = masked[i, j]
            ok = best > eps
            col_row = jnp.where(ok, col_row.at[j].set(i.astype(jnp.int32)), col_row)
            col_dist = jnp.where(ok, col_dist.at[j].set(best), col_dist)
            live = jnp.where(ok, live & (jnp.arange(R)[:, None] != i)
                             & (jnp.arange(C)[None, :] != j), live)
            return (live, col_row, col_dist), None

        init = (jnp.ones((R, C), bool), jnp.full((C,), -1, jnp.int32),
                jnp.zeros((C,), dist.dtype))
        (live, col_row, col_dist), _ = jax.lax.scan(
            step, init, None, length=min(R, C))
        if match_type == "per_prediction":
            best_row = jnp.argmax(dist, axis=0).astype(jnp.int32)
            best_val = jnp.max(dist, axis=0)
            fill = (col_row == -1) & (best_val >= overlap_threshold)
            col_row = jnp.where(fill, best_row, col_row)
            col_dist = jnp.where(fill, best_val, col_dist)
        return col_row, col_dist

    idx, d = apply(fn, _t(dist_matrix).detach())
    idx.stop_gradient = True
    d.stop_gradient = True
    return idx, d


def matrix_nms(bboxes, scores, score_threshold, post_threshold=0.0,
               nms_top_k=400, keep_top_k=200, use_gaussian=False,
               gaussian_sigma=2.0, background_label=0, normalized=True,
               return_index=False, return_rois_num=True, name=None):
    """detection/matrix_nms_op.cc parity (SOLOv2 Matrix NMS): scores decay by
    min_j f(iou_ij, max_iou_j) instead of hard suppression — one IoU matrix,
    no sequential sweep: ideal for the MXU. bboxes [N, M, 4], scores [N, C, M].

    Returns (out [N, keep_top_k, 6] (-1 padded rows), rois_num [N][, index]).
    """
    bv = _t(bboxes)._data
    sv = _t(scores)._data

    def per_image(boxes, score):
        C, M = score.shape
        off = 0.0 if normalized else 1.0
        outs = []
        for c in range(C):
            if c == background_label:
                continue
            sc = score[c]
            k = min(nms_top_k, M)
            top_s, top_i = jax.lax.top_k(sc, k)
            bsel = boxes[top_i]
            area = jnp.maximum(bsel[:, 2] - bsel[:, 0] + off, 0) * jnp.maximum(
                bsel[:, 3] - bsel[:, 1] + off, 0)
            lt = jnp.maximum(bsel[:, None, :2], bsel[None, :, :2])
            rb = jnp.minimum(bsel[:, None, 2:], bsel[None, :, 2:])
            wh = jnp.maximum(rb - lt + off, 0)
            inter = wh[..., 0] * wh[..., 1]
            union = area[:, None] + area[None, :] - inter
            iou = jnp.where(union > 0, inter / jnp.maximum(union, 1e-10), 0.0)
            upper = jnp.tril(iou, k=-1)      # iou[i, j] for j < i lives at [i, :i]
            max_iou = jnp.max(upper, axis=1)  # per box: max IoU vs higher-scored
            if use_gaussian:
                decay = jnp.exp((max_iou[None, :] ** 2 - upper ** 2)
                                * gaussian_sigma)
            else:
                decay = (1.0 - upper) / jnp.maximum(1.0 - max_iou[None, :], 1e-10)
            # min over j < i (mask j >= i to 1)
            jj = jnp.arange(k)
            mask_lower = jj[None, :] < jj[:, None]
            decay = jnp.where(mask_lower, decay, 1.0)
            decayed = top_s * jnp.min(decay, axis=1)
            valid = top_s > score_threshold
            if post_threshold > 0:
                valid = valid & (decayed > post_threshold)
            entry = jnp.concatenate(
                [jnp.full((k, 1), float(c)), decayed[:, None], bsel], axis=1)
            entry = jnp.where(valid[:, None], entry, -1.0)
            outs.append((entry, jnp.where(valid, decayed, -jnp.inf), top_i))
        if not outs:  # every class was the background label
            kk = min(keep_top_k, M)
            return (jnp.full((kk, 6), -1.0), jnp.zeros((), jnp.int32),
                    jnp.zeros((kk,), jnp.int32))
        all_e = jnp.concatenate([e for e, _, _ in outs], axis=0)
        all_s = jnp.concatenate([s for _, s, _ in outs], axis=0)
        all_i = jnp.concatenate([i for _, _, i in outs], axis=0)
        kk = min(keep_top_k, all_e.shape[0])
        sel_s, sel = jax.lax.top_k(all_s, kk)
        out = jnp.where((sel_s > -jnp.inf)[:, None], all_e[sel], -1.0)
        n_valid = jnp.sum(sel_s > -jnp.inf)
        return out, n_valid, all_i[sel]

    outs, nums, idxs = [], [], []
    for n in range(bv.shape[0]):
        o, nv, ix = per_image(bv[n], sv[n])
        outs.append(o)
        nums.append(nv)
        idxs.append(ix)
    out = Tensor(jnp.stack(outs))
    nums_t = Tensor(jnp.stack(nums).astype(jnp.int32))
    if return_index:
        return (out, nums_t, Tensor(jnp.stack(idxs))) if return_rois_num else (out, Tensor(jnp.stack(idxs)))
    return (out, nums_t) if return_rois_num else out


def roi_pool(x, boxes, boxes_num, output_size, spatial_scale=1.0, name=None):
    """roi_pool_op.cc parity: max pooling per bin with the reference's rounded
    integer-grid bin layout. x [N,C,H,W]; boxes [R,4] xyxy; boxes_num [N]."""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    ph_n, pw_n = output_size

    xv = _t(x)
    bv = _t(boxes).detach()
    bn = np.asarray(_t(boxes_num)._data).astype(np.int64)
    img_of_roi = np.repeat(np.arange(len(bn)), bn)

    def fn(feat, rois):
        N, C, H, W = feat.shape
        img_idx = jnp.asarray(img_of_roi, jnp.int32)

        def one(roi, im):
            x1 = jnp.round(roi[0] * spatial_scale)
            y1 = jnp.round(roi[1] * spatial_scale)
            x2 = jnp.round(roi[2] * spatial_scale)
            y2 = jnp.round(roi[3] * spatial_scale)
            rh = jnp.maximum(y2 - y1 + 1, 1.0)
            rw = jnp.maximum(x2 - x1 + 1, 1.0)
            fmap = feat[im]                      # [C, H, W]
            ys = jnp.arange(H, dtype=jnp.float32)
            xs = jnp.arange(W, dtype=jnp.float32)

            def bin_val(phw):
                ph, pw = phw // pw_n, phw % pw_n
                hs = jnp.floor(ph * rh / ph_n) + y1
                he = jnp.ceil((ph + 1) * rh / ph_n) + y1
                ws = jnp.floor(pw * rw / pw_n) + x1
                we = jnp.ceil((pw + 1) * rw / pw_n) + x1
                hs, he = jnp.clip(hs, 0, H), jnp.clip(he, 0, H)
                ws, we = jnp.clip(ws, 0, W), jnp.clip(we, 0, W)
                m = ((ys[:, None] >= hs) & (ys[:, None] < he)
                     & (xs[None, :] >= ws) & (xs[None, :] < we))
                empty = (he <= hs) | (we <= ws)
                v = jnp.max(jnp.where(m[None], fmap, -jnp.inf), axis=(1, 2))
                return jnp.where(empty, 0.0, v)

            vals = jax.vmap(bin_val)(jnp.arange(ph_n * pw_n))  # [ph*pw, C]
            return vals.T.reshape(C, ph_n, pw_n)

        return jax.vmap(one)(rois, img_idx)

    return apply(fn, xv, bv)


def psroi_pool(x, boxes, boxes_num, output_size, spatial_scale=1.0, name=None):
    """psroi_pool_op.cc parity: position-sensitive average pooling — output
    channel c at bin (ph, pw) averages input channel (c*ph_n + ph)*pw_n + pw."""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    ph_n, pw_n = output_size

    xv = _t(x)
    bv = _t(boxes).detach()
    bn = np.asarray(_t(boxes_num)._data).astype(np.int64)
    img_of_roi = np.repeat(np.arange(len(bn)), bn)

    def fn(feat, rois):
        N, C, H, W = feat.shape
        c_out = C // (ph_n * pw_n)
        img_idx = jnp.asarray(img_of_roi, jnp.int32)
        ys = jnp.arange(H, dtype=jnp.float32)
        xs = jnp.arange(W, dtype=jnp.float32)

        def one(roi, im):
            x1 = jnp.round(roi[0]) * spatial_scale
            y1 = jnp.round(roi[1]) * spatial_scale
            x2 = jnp.round(roi[2] + 1.0) * spatial_scale
            y2 = jnp.round(roi[3] + 1.0) * spatial_scale
            rh = jnp.maximum(y2 - y1, 0.1)
            rw = jnp.maximum(x2 - x1, 0.1)
            bin_h, bin_w = rh / ph_n, rw / pw_n
            fmap = feat[im]

            def bin_val(phw):
                ph, pw = phw // pw_n, phw % pw_n
                hs = jnp.floor(y1 + ph * bin_h)
                he = jnp.ceil(y1 + (ph + 1) * bin_h)
                ws = jnp.floor(x1 + pw * bin_w)
                we = jnp.ceil(x1 + (pw + 1) * bin_w)
                hs, he = jnp.clip(hs, 0, H), jnp.clip(he, 0, H)
                ws, we = jnp.clip(ws, 0, W), jnp.clip(we, 0, W)
                m = ((ys[:, None] >= hs) & (ys[:, None] < he)
                     & (xs[None, :] >= ws) & (xs[None, :] < we))
                cnt = jnp.maximum(jnp.sum(m), 1)
                ch = (jnp.arange(c_out) * ph_n + ph) * pw_n + pw  # [c_out]
                v = jnp.sum(jnp.where(m[None], fmap[ch], 0.0), axis=(1, 2))
                empty = (he <= hs) | (we <= ws)
                return jnp.where(empty, 0.0, v / cnt)

            vals = jax.vmap(bin_val)(jnp.arange(ph_n * pw_n))  # [ph*pw, c_out]
            return vals.T.reshape(c_out, ph_n, pw_n)

        return jax.vmap(one)(rois, img_idx)

    return apply(fn, xv, bv)


def distribute_fpn_proposals(fpn_rois, min_level, max_level, refer_level,
                             refer_scale, pixel_offset=False, rois_num=None,
                             name=None):
    """distribute_fpn_proposals_op.cc parity: route each RoI to its FPN level
    by sqrt(area): level = floor(refer_level + log2(sqrt(wh)/refer_scale)).
    Eager op (dynamic per-level counts, like the reference's CPU kernel).
    Returns (multi_rois list, restore_index [R, 1][, multi_level_rois_num])."""
    rv = np.asarray(_t(fpn_rois)._data)
    off = 1.0 if pixel_offset else 0.0
    w = np.maximum(rv[:, 2] - rv[:, 0] + off, 0)
    h = np.maximum(rv[:, 3] - rv[:, 1] + off, 0)
    scale = np.sqrt(w * h)
    lvl = np.floor(refer_level + np.log2(scale / refer_scale + 1e-8))
    lvl = np.clip(lvl, min_level, max_level).astype(np.int64)
    multi, nums, order = [], [], []
    for l in range(min_level, max_level + 1):
        idx = np.nonzero(lvl == l)[0]
        multi.append(Tensor(jnp.asarray(rv[idx])))
        nums.append(len(idx))
        order.extend(idx.tolist())
    restore = np.zeros((len(rv), 1), np.int32)
    restore[np.asarray(order, np.int64), 0] = np.arange(len(rv), dtype=np.int32)
    out = (multi, Tensor(jnp.asarray(restore)))
    if rois_num is not None:
        out = out + (Tensor(jnp.asarray(np.asarray(nums, np.int32))),)
    return out


def generate_proposals(scores, bbox_deltas, img_size, anchors, variances,
                       pre_nms_top_n=6000, post_nms_top_n=1000,
                       nms_thresh=0.5, min_size=0.1, eta=1.0,
                       pixel_offset=False, return_rois_num=True, name=None):
    """detection/generate_proposals_v2_op.cc parity (RPN proposal stage),
    static-shape: decode deltas on anchors, clip to image, drop boxes smaller
    than min_size, keep top pre_nms_top_n, greedy-NMS, emit post_nms_top_n
    rows (zero-padded) + per-image valid count. scores [N, A, H, W],
    bbox_deltas [N, 4A, H, W], anchors [H, W, A, 4] or [H*W*A, 4]."""
    sv = _t(scores).detach()._data
    dv = _t(bbox_deltas).detach()._data
    iv = np.asarray(_t(img_size)._data, np.float32)
    av = _t(anchors)._data.reshape(-1, 4)
    vv = _t(variances)._data.reshape(-1, 4)
    off = 1.0 if pixel_offset else 0.0

    def per_image(sc, dl, im_hw):
        A = av.shape[0] // (sc.shape[1] * sc.shape[2])
        s = jnp.transpose(sc, (1, 2, 0)).reshape(-1)             # [H*W*A]
        d = jnp.transpose(dl, (1, 2, 0)).reshape(-1, 4)          # [H*W*A, 4]
        aw = av[:, 2] - av[:, 0] + off
        ah = av[:, 3] - av[:, 1] + off
        acx = av[:, 0] + 0.5 * aw
        acy = av[:, 1] + 0.5 * ah
        cx = vv[:, 0] * d[:, 0] * aw + acx
        cy = vv[:, 1] * d[:, 1] * ah + acy
        bw = aw * jnp.exp(jnp.minimum(vv[:, 2] * d[:, 2], np.log(1000.0 / 16)))
        bh = ah * jnp.exp(jnp.minimum(vv[:, 3] * d[:, 3], np.log(1000.0 / 16)))
        x1 = cx - 0.5 * bw
        y1 = cy - 0.5 * bh
        x2 = cx + 0.5 * bw - off
        y2 = cy + 0.5 * bh - off
        H_img, W_img = im_hw[0], im_hw[1]
        x1 = jnp.clip(x1, 0, W_img - off)
        x2 = jnp.clip(x2, 0, W_img - off)
        y1 = jnp.clip(y1, 0, H_img - off)
        y2 = jnp.clip(y2, 0, H_img - off)
        boxes = jnp.stack([x1, y1, x2, y2], axis=1)
        keep = ((x2 - x1 + off) >= min_size) & ((y2 - y1 + off) >= min_size)
        s = jnp.where(keep, s, -jnp.inf)
        k = min(pre_nms_top_n, s.shape[0])
        top_s, top_i = jax.lax.top_k(s, k)
        bsel = boxes[top_i]
        mask = nms_mask(bsel, top_s, nms_thresh) & (top_s > -jnp.inf)
        # order kept boxes by score (they already are), compact to post_nms_top_n
        rank = jnp.cumsum(mask) - 1
        kk = post_nms_top_n
        sel = jnp.where(mask & (rank < kk), rank, kk)  # kk = dump slot
        out_rois = jnp.zeros((kk + 1, 4), boxes.dtype).at[sel].set(bsel)[:kk]
        out_sc = jnp.zeros((kk + 1,), s.dtype).at[sel].set(top_s)[:kk]
        n_valid = jnp.minimum(jnp.sum(mask), kk)
        return out_rois, out_sc, n_valid

    rois, rsc, nums = [], [], []
    for n in range(sv.shape[0]):
        r, scs, nv = per_image(sv[n], dv[n], iv[n])
        rois.append(r)
        rsc.append(scs)
        nums.append(nv)
    rois_t = Tensor(jnp.stack(rois))
    sc_t = Tensor(jnp.stack(rsc))
    if return_rois_num:
        return rois_t, sc_t, Tensor(jnp.stack(nums).astype(jnp.int32))
    return rois_t, sc_t


def deform_conv2d(x, offset, weight, bias=None, stride=1, padding=0,
                  dilation=1, deformable_groups=1, groups=1, mask=None,
                  name=None):
    """deformable_conv_op.cu parity (v1; v2/modulated when `mask` given).

    TPU design: for each kernel tap (i, j) the whole feature map is bilinearly
    resampled at (base_grid + learned offset) in one gather — kh*kw vectorized
    samples instead of the reference's per-output im2col loop — then the
    conv collapses to an einsum over (tap, in-channel).
    x [N,Cin,H,W]; offset [N, 2*dg*kh*kw, Ho, Wo]; mask [N, dg*kh*kw, Ho, Wo];
    weight [Cout, Cin/groups, kh, kw].
    """
    def _pair(v):
        return (v, v) if isinstance(v, int) else tuple(v)

    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    dh, dw = _pair(dilation)

    args = [_t(x), _t(offset), _t(weight)]
    if mask is not None:
        args.append(_t(mask))
    if bias is not None:
        args.append(_t(bias))

    def fn(xv, ov, wv, *rest):
        rest = list(rest)
        mv = rest.pop(0) if mask is not None else None
        bvv = rest.pop(0) if bias is not None else None
        N, Cin, H, W = xv.shape
        Cout, Cin_g, kh, kw = wv.shape
        Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
        Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1
        dg = deformable_groups
        ov = ov.reshape(N, dg, kh * kw, 2, Ho, Wo)  # reference layout: (..., [y, x], ...)
        base_y = jnp.arange(Ho) * sh - ph
        base_x = jnp.arange(Wo) * sw - pw

        def sample(fmap, py, px):
            # fmap [C', H, W]; py/px [Ho, Wo] absolute float positions
            y0 = jnp.floor(py)
            x0 = jnp.floor(px)
            wy = py - y0
            wx = px - x0

            def at(yy, xx):
                inb = (yy >= 0) & (yy <= H - 1) & (xx >= 0) & (xx <= W - 1)
                yc = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
                xc = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
                v = fmap[:, yc, xc]                      # [C', Ho, Wo]
                return jnp.where(inb[None], v, 0.0)

            return (at(y0, x0) * (1 - wy) * (1 - wx)
                    + at(y0, x0 + 1) * (1 - wy) * wx
                    + at(y0 + 1, x0) * wy * (1 - wx)
                    + at(y0 + 1, x0 + 1) * wy * wx)

        cin_per_dg = Cin // dg

        def one_image(xi, oi, mi):
            taps = []
            for i in range(kh):
                for j in range(kw):
                    t = i * kw + j
                    per_dg = []
                    for g in range(dg):
                        py = base_y[:, None] + i * dh + oi[g, t, 0]
                        px = base_x[None, :] + j * dw + oi[g, t, 1]
                        sm = sample(xi[g * cin_per_dg:(g + 1) * cin_per_dg],
                                    py, px)
                        if mi is not None:
                            sm = sm * mi[g, t][None]
                        per_dg.append(sm)
                    taps.append(jnp.concatenate(per_dg, axis=0))  # [Cin, Ho, Wo]
            return jnp.stack(taps)                                # [kh*kw, Cin, Ho, Wo]

        if mv is not None:
            mi_all = mv.reshape(N, dg, kh * kw, Ho, Wo)
            cols = jax.vmap(one_image)(xv, ov, mi_all)
        else:
            cols = jax.vmap(lambda a, b: one_image(a, b, None))(xv, ov)
        # grouped conv reduce: weight [Cout, Cin/groups, kh, kw]
        outs = []
        cout_g = Cout // groups
        cin_pg = Cin // groups
        for g in range(groups):
            wg = wv[g * cout_g:(g + 1) * cout_g]                 # [cout_g, cin_pg, kh, kw]
            cg = cols[:, :, g * cin_pg:(g + 1) * cin_pg]          # [N, khkw, cin_pg, Ho, Wo]
            wgf = wg.reshape(cout_g, cin_pg, kh * kw)
            outs.append(jnp.einsum("ock,nkchw->nohw", wgf, cg))
        out = jnp.concatenate(outs, axis=1)
        if bvv is not None:
            out = out + bvv.reshape(1, -1, 1, 1)
        return out

    return apply(fn, *args)


def anchor_generator(input, anchor_sizes, aspect_ratios, variances,
                     stride, offset=0.5, name=None):
    """detection/anchor_generator_op.h parity: per-cell anchors over the
    feature map. input [N, C, H, W] (only H, W used). Returns
    (anchors [H, W, A, 4], variances [H, W, A, 4]); anchor order is
    aspect_ratio-major, size-minor like the reference (:62-64)."""
    H, W = int(input.shape[2]), int(input.shape[3])
    sw, sh = float(stride[0]), float(stride[1])
    whs = []
    for ar in aspect_ratios:
        area = sw * sh
        base_w = np.round(np.sqrt(area / ar))
        base_h = np.round(base_w * ar)
        for s in anchor_sizes:
            whs.append((s / sw * base_w, s / sh * base_h))
    whs = jnp.asarray(np.asarray(whs, np.float32))          # [A, 2]
    x_ctr = jnp.arange(W, dtype=jnp.float32) * sw + offset * (sw - 1)
    y_ctr = jnp.arange(H, dtype=jnp.float32) * sh + offset * (sh - 1)
    xc = jnp.broadcast_to(x_ctr[None, :, None], (H, W, whs.shape[0]))
    yc = jnp.broadcast_to(y_ctr[:, None, None], (H, W, whs.shape[0]))
    aw = whs[None, None, :, 0]
    ah = whs[None, None, :, 1]
    anchors = jnp.stack([xc - 0.5 * (aw - 1), yc - 0.5 * (ah - 1),
                         xc + 0.5 * (aw - 1), yc + 0.5 * (ah - 1)], axis=-1)
    var = jnp.broadcast_to(jnp.asarray(variances, jnp.float32),
                           anchors.shape)
    a = Tensor(anchors)
    v = Tensor(var)
    a.stop_gradient = True
    v.stop_gradient = True
    return a, v


def box_clip(input, im_info, name=None):
    """detection/box_clip_op.h parity: clip [N, M, 4] (or [M, 4]) boxes to
    the image: [0, round(h/scale) - 1] x [0, round(w/scale) - 1];
    im_info rows are (height, width, scale)."""
    def fn(b, info):
        batched = b.ndim == 3
        if not batched:
            b = b[None]
            info = info.reshape(1, -1)
        im_h = jnp.round(info[:, 0] / info[:, 2]).reshape(-1, 1)
        im_w = jnp.round(info[:, 1] / info[:, 2]).reshape(-1, 1)
        x1 = jnp.clip(b[..., 0], 0, im_w - 1)
        y1 = jnp.clip(b[..., 1], 0, im_h - 1)
        x2 = jnp.clip(b[..., 2], 0, im_w - 1)
        y2 = jnp.clip(b[..., 3], 0, im_h - 1)
        out = jnp.stack([x1, y1, x2, y2], axis=-1)
        return out if batched else out[0]

    return apply(fn, _t(input), _t(im_info).detach())


def target_assign(input, matched_indices, negative_indices=None,
                  mismatch_value=0, name=None):
    """detection/target_assign_op.h parity: out[b, p] = input[b, match[b, p]]
    (mismatch rows filled with mismatch_value, weight 0; negative_indices
    entries get mismatch_value with weight 1 — SSD negative mining)."""
    args = [_t(input).detach(), _t(matched_indices).detach()]
    if negative_indices is not None:
        args.append(_t(negative_indices).detach())

    def fn(x, mi, *neg):
        B, P = mi.shape
        mi = mi.astype(jnp.int32)
        matched = mi >= 0
        safe = jnp.where(matched, mi, 0)
        out = jnp.take_along_axis(
            x, safe[:, :, None] if x.ndim == 3 else safe, axis=1)
        fill = jnp.asarray(mismatch_value, x.dtype)
        out = jnp.where(matched[:, :, None] if x.ndim == 3 else matched,
                        out, fill)
        wt = matched.astype(jnp.float32)
        if neg:
            ni = neg[0].astype(jnp.int32)                    # [B, Q]
            bidx = jnp.broadcast_to(jnp.arange(B)[:, None], ni.shape)
            valid = ni >= 0
            dump = jnp.where(valid, ni, P)
            wt = jnp.concatenate([wt, jnp.zeros((B, 1), wt.dtype)], axis=1)
            wt = wt.at[bidx.reshape(-1), dump.reshape(-1)].set(1.0)[:, :P]
            if x.ndim == 3:
                out = jnp.concatenate(
                    [out, jnp.zeros((B, 1, out.shape[2]), out.dtype)], axis=1
                ).at[bidx.reshape(-1), dump.reshape(-1)].set(fill)[:, :P]
            else:
                out = jnp.concatenate(
                    [out, jnp.zeros((B, 1), out.dtype)], axis=1
                ).at[bidx.reshape(-1), dump.reshape(-1)].set(fill)[:, :P]
        return out, (wt[:, :, None] if x.ndim == 3 else wt)

    out, wt = apply(fn, *args)
    out.stop_gradient = True
    wt.stop_gradient = True
    return out, wt


def yolov3_loss(x, gt_box, gt_label, anchors, anchor_mask, class_num,
                ignore_thresh, downsample_ratio, gt_score=None,
                use_label_smooth=True, scale_x_y=1.0, name=None):
    """detection/yolov3_loss_op.h parity (vectorized; loss per image [N]).

    x [N, mask_num*(5+C), H, W]; gt_box [N, B, 4] normalized (cx, cy, w, h);
    gt_label [N, B]; anchors = flat [a0w, a0h, ...]; anchor_mask = this
    level's anchor indices. Per-gt best-anchor matching scatters positives;
    objectness cells whose predicted box IoUs any gt above ignore_thresh are
    excluded from the negative term (obj target semantics of :384-397). The
    whole thing is differentiable through XLA (no hand-written grad kernel).
    """
    mask_num = len(anchor_mask)
    an_np = np.asarray(anchors, np.float32).reshape(-1, 2)   # [an_num, 2]
    an_masked = an_np[list(anchor_mask)]                     # [mask_num, 2]
    scale, bias = scale_x_y, -0.5 * (scale_x_y - 1.0)

    args = [_t(x), _t(gt_box).detach(), _t(gt_label).detach()]
    if gt_score is not None:
        args.append(_t(gt_score).detach())

    smooth = min(1.0 / class_num, 1.0 / 40) if use_label_smooth else 0.0
    pos_lab, neg_lab = 1.0 - smooth, smooth

    def sce(logit, label):
        return jnp.maximum(logit, 0) - logit * label + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))

    def fn(xv, gb, gl, *gs):
        N, _, H, W = xv.shape
        input_size = downsample_ratio * H
        xv = xv.reshape(N, mask_num, 5 + class_num, H, W)
        score = (gs[0] if gs else jnp.ones(gb.shape[:2], xv.dtype))
        gl = gl.astype(jnp.int32)
        valid = (gb[..., 2] > 0) & (gb[..., 3] > 0)          # [N, B]

        amw = jnp.asarray(an_masked[:, 0])
        amh = jnp.asarray(an_masked[:, 1])
        # predicted boxes (for the ignore mask)
        gx = (jnp.arange(W)[None, :] + jax.nn.sigmoid(xv[:, :, 0]) * scale
              + bias) / W
        gy = (jnp.arange(H)[:, None] + jax.nn.sigmoid(xv[:, :, 1]) * scale
              + bias) / H
        gw = jnp.exp(xv[:, :, 2]) * amw[None, :, None, None] / input_size
        gh = jnp.exp(xv[:, :, 3]) * amh[None, :, None, None] / input_size

        def iou_cwh(ax, ay, aw_, ah_, bx, by, bw, bh):
            ax1, ay1 = ax - aw_ / 2, ay - ah_ / 2
            ax2, ay2 = ax + aw_ / 2, ay + ah_ / 2
            bx1, by1 = bx - bw / 2, by - bh / 2
            bx2, by2 = bx + bw / 2, by + bh / 2
            iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0)
            ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0)
            inter = iw * ih
            return inter / jnp.maximum(aw_ * ah_ + bw * bh - inter, 1e-10)

        # best IoU of each predicted box vs any valid gt: [N, mask, H, W]
        ious = iou_cwh(
            gx[:, :, :, :, None], gy[:, :, :, :, None],
            gw[:, :, :, :, None], gh[:, :, :, :, None],
            gb[:, None, None, None, :, 0], gb[:, None, None, None, :, 1],
            gb[:, None, None, None, :, 2], gb[:, None, None, None, :, 3])
        ious = jnp.where(valid[:, None, None, None, :], ious, 0.0)
        ignore = jnp.max(ious, axis=-1) > ignore_thresh      # [N, mask, H, W]

        # per-gt best anchor over ALL anchors (wh IoU at origin)
        all_aw = jnp.asarray(an_np[:, 0]) / input_size
        all_ah = jnp.asarray(an_np[:, 1]) / input_size
        inter = (jnp.minimum(gb[..., 2:3], all_aw[None, None, :])
                 * jnp.minimum(gb[..., 3:4], all_ah[None, None, :]))
        union = (gb[..., 2:3] * gb[..., 3:4]
                 + all_aw[None, None, :] * all_ah[None, None, :] - inter)
        best_n = jnp.argmax(inter / jnp.maximum(union, 1e-10), axis=-1)  # [N, B]
        # map to this level's mask slot (-1 if not ours)
        mask_arr = jnp.asarray(np.asarray(anchor_mask, np.int64))
        mask_idx = jnp.argmax(mask_arr[None, None, :] == best_n[..., None],
                              axis=-1)
        ours = jnp.any(mask_arr[None, None, :] == best_n[..., None], axis=-1)
        take = valid & ours                                   # [N, B]

        gi = jnp.clip((gb[..., 0] * W).astype(jnp.int32), 0, W - 1)
        gj = jnp.clip((gb[..., 1] * H).astype(jnp.int32), 0, H - 1)

        # gather predictions at each gt's cell: [N, B, 5+C]
        flat = xv.reshape(N, mask_num, 5 + class_num, H * W)
        cell = gj * W + gi                                    # [N, B]
        midx = jnp.where(take, mask_idx, 0).astype(jnp.int32)
        pred = jnp.take_along_axis(
            jnp.take_along_axis(
                flat, midx[:, :, None, None] *
                jnp.ones((1, 1, 5 + class_num, H * W), jnp.int32), axis=1),
            cell[:, :, None, None] *
            jnp.ones((1, 1, 5 + class_num, 1), jnp.int32), axis=3)[:, :, :, 0]

        tx = gb[..., 0] * W - gi
        ty = gb[..., 1] * H - gj
        aw_t = jnp.take(jnp.asarray(an_np[:, 0]), best_n)
        ah_t = jnp.take(jnp.asarray(an_np[:, 1]), best_n)
        tw = jnp.log(jnp.maximum(gb[..., 2] * input_size / aw_t, 1e-9))
        th = jnp.log(jnp.maximum(gb[..., 3] * input_size / ah_t, 1e-9))
        loc_scale = (2.0 - gb[..., 2] * gb[..., 3]) * score
        loc = (sce(pred[..., 0], tx) + sce(pred[..., 1], ty)
               + jnp.abs(pred[..., 2] - tw) + jnp.abs(pred[..., 3] - th)
               ) * loc_scale
        cls_t = jax.nn.one_hot(gl, class_num) * (pos_lab - neg_lab) + neg_lab
        cls = jnp.sum(sce(pred[..., 5:], cls_t), axis=-1) * score
        per_gt = jnp.where(take, loc + cls, 0.0)              # [N, B]

        # objectness target map: later gts win on cell collisions (reference
        # loop order). JAX scatter-set with duplicate indices is unordered, so
        # pick the winner deterministically: scatter-max each gt's (t+1) into
        # the cell, then only the gt matching that rank contributes its score.
        Bn = gb.shape[1]
        dest = jnp.where(take, midx * H * W + cell, mask_num * H * W)
        bidx = jnp.broadcast_to(jnp.arange(N)[:, None], dest.shape)
        ranks = jnp.broadcast_to(jnp.arange(1, Bn + 1)[None, :], dest.shape)
        order = jnp.zeros((N, mask_num * H * W + 1), jnp.int32).at[
            bidx.reshape(-1), dest.reshape(-1)].max(
                jnp.where(take, ranks, 0).reshape(-1))
        winner = take & (jnp.take_along_axis(order, dest, axis=1) == ranks)
        obj_t = jnp.zeros((N, mask_num * H * W + 1), xv.dtype).at[
            bidx.reshape(-1), dest.reshape(-1)].add(
                jnp.where(winner, score, 0.0).reshape(-1))
        obj_t = obj_t[:, :mask_num * H * W].reshape(N, mask_num, H, W)
        conf = xv[:, :, 4]
        pos = obj_t > 1e-5
        obj_loss = jnp.where(pos, sce(conf, 1.0) * obj_t,
                             jnp.where(ignore, 0.0, sce(conf, 0.0)))
        return jnp.sum(per_gt, axis=1) + jnp.sum(obj_loss, axis=(1, 2, 3))

    return apply(fn, *args)


_DENSITY_PRIOR_CACHE = {}


def density_prior_box(input, image, densities, fixed_sizes, fixed_ratios,
                      variances, clip=False, steps=(0.0, 0.0), offset=0.5,
                      flatten_to_2d=False, name=None):
    """detection/density_prior_box_op.h parity: per-cell density-sampled SSD
    priors. input [N, C, H, W] feature map, image [N, C, Hi, Wi]. Returns
    (boxes [H, W, P, 4] normalized (or [H*W*P, 4] when flatten_to_2d),
    variances same shape)."""
    H, W = int(input.shape[2]), int(input.shape[3])
    img_h, img_w = int(image.shape[2]), int(image.shape[3])
    key = (H, W, img_h, img_w, tuple(densities), tuple(fixed_sizes),
           tuple(fixed_ratios), tuple(np.ravel(variances)), bool(clip),
           tuple(steps), float(offset))
    cached = _DENSITY_PRIOR_CACHE.get(key)
    if cached is None:
        step_w = steps[0] if steps[0] > 0 else img_w / W
        step_h = steps[1] if steps[1] > 0 else img_h / H
        step_avg = int(0.5 * (step_w + step_h))

        # vectorized over the grid: per-cell prior geometry is identical, so
        # build the per-cell offsets once and broadcast-add the cell centers
        cxs = (np.arange(W) + offset) * step_w                  # [W]
        cys = (np.arange(H) + offset) * step_h                  # [H]
        rel = []                                                # per-prior (dx, dy, bw, bh)
        for fs, density in zip(fixed_sizes, densities):
            shift = step_avg // density
            base = -step_avg / 2.0 + shift / 2.0
            for fr in fixed_ratios:
                bw = fs * np.sqrt(fr)
                bh = fs / np.sqrt(fr)
                for di in range(density):
                    for dj in range(density):
                        rel.append((base + dj * shift, base + di * shift,
                                    bw, bh))
        rel = np.asarray(rel, np.float32)                       # [P, 4]
        P = rel.shape[0]
        cxt = cxs[None, :, None] + rel[None, None, :, 0]        # [1, W, P]
        cyt = cys[:, None, None] + rel[None, None, :, 1]        # [H, 1, P]
        cxt = np.broadcast_to(cxt, (H, W, P))
        cyt = np.broadcast_to(cyt, (H, W, P))
        bw = rel[None, None, :, 2]
        bh = rel[None, None, :, 3]
        arr = np.stack([
            np.maximum((cxt - bw / 2.0) / img_w, 0.0),
            np.maximum((cyt - bh / 2.0) / img_h, 0.0),
            np.minimum((cxt + bw / 2.0) / img_w, 1.0),
            np.minimum((cyt + bh / 2.0) / img_h, 1.0),
        ], axis=-1).astype(np.float32)
        if clip:
            arr = np.clip(arr, 0.0, 1.0)
        var = np.broadcast_to(np.asarray(variances, np.float32),
                              arr.shape).copy()
        cached = (arr, var)
        _DENSITY_PRIOR_CACHE[key] = cached
    arr, var = cached
    if flatten_to_2d:
        arr = arr.reshape(-1, 4)
        var = var.reshape(-1, 4)
    b = Tensor(jnp.asarray(arr))
    v = Tensor(jnp.asarray(var))
    b.stop_gradient = True
    v.stop_gradient = True
    return b, v


def collect_fpn_proposals(multi_rois, multi_scores, min_level, max_level,
                          post_nms_top_n, rois_num_per_level=None, name=None):
    """detection/collect_fpn_proposals_op.h parity: merge per-level RoIs,
    keep the global top post_nms_top_n by score (inverse of
    distribute_fpn_proposals). Eager, single-image LoD-free form."""
    rois = np.concatenate([np.asarray(_t(r)._data).reshape(-1, 4)
                           for r in multi_rois], axis=0)
    scores = np.concatenate([np.asarray(_t(s)._data).reshape(-1)
                             for s in multi_scores], axis=0)
    k = min(post_nms_top_n, len(scores))
    order = np.argsort(-scores, kind="stable")[:k]
    out = Tensor(jnp.asarray(rois[order]))
    out.stop_gradient = True
    if rois_num_per_level is not None:
        return out, Tensor(jnp.asarray(np.asarray([k], np.int32)))
    return out


def polygon_box_transform(input, name=None):
    """detection/polygon_box_transform_op.cc parity (EAST-style geometry →
    quad coordinates): even channels out = 4*w_idx - in, odd channels
    out = 4*h_idx - in."""
    def fn(v):
        N, C, H, W = v.shape
        wk = 4.0 * jnp.arange(W, dtype=v.dtype)[None, None, None, :]
        hk = 4.0 * jnp.arange(H, dtype=v.dtype)[None, None, :, None]
        even = jnp.arange(C) % 2 == 0
        return jnp.where(even[None, :, None, None], wk - v, hk - v)

    return apply(fn, _t(input))


def mine_hard_examples(cls_loss, match_indices, match_dist, loc_loss=None,
                       neg_pos_ratio=3.0, neg_dist_threshold=0.5,
                       sample_size=0, mining_type="max_negative", name=None):
    """detection/mine_hard_examples_op.cc parity (SSD negative mining).

    cls_loss/match_dist [B, P]; match_indices [B, P] (-1 = unmatched).
    max_negative: eligible = unmatched priors with dist < neg_dist_threshold,
    keep the top num_pos*neg_pos_ratio by cls_loss. hard_example: every prior
    is eligible, loss = cls+loc, keep sample_size, and positives that are not
    selected get their match index erased. Returns (neg_indices list of [k_b]
    arrays, updated_match_indices [B, P])."""
    cl = np.asarray(_t(cls_loss)._data)
    mi = np.asarray(_t(match_indices)._data).astype(np.int64)
    md = np.asarray(_t(match_dist)._data)
    ll = np.asarray(_t(loc_loss)._data) if loc_loss is not None else None
    B, P = mi.shape
    neg_out, updated = [], mi.copy()
    for n in range(B):
        if mining_type == "max_negative":
            elig = (mi[n] == -1) & (md[n] < neg_dist_threshold)
            loss = cl[n]
            num_pos = int((mi[n] != -1).sum())
            cap = int(num_pos * neg_pos_ratio)
        elif mining_type == "hard_example":
            elig = np.ones(P, bool)
            loss = cl[n] + (ll[n] if ll is not None else 0.0)
            cap = sample_size
        else:
            raise ValueError("mining_type must be max_negative or hard_example")
        cand = np.nonzero(elig)[0]
        order = cand[np.argsort(-loss[cand], kind="stable")]
        sel = order[: min(cap, len(order))]
        sel_set = set(int(s) for s in sel)
        if mining_type == "hard_example":
            for m in range(P):
                if mi[n, m] > -1 and m not in sel_set:
                    updated[n, m] = -1
            neg = sorted(s for s in sel_set if mi[n, s] == -1)
        else:
            neg = sorted(sel_set)
        neg_out.append(Tensor(jnp.asarray(np.asarray(neg, np.int32))))
    upd = Tensor(jnp.asarray(updated))
    upd.stop_gradient = True
    return neg_out, upd


def rpn_target_assign(bbox_pred, cls_logits, anchor_box, gt_boxes, im_info,
                      rpn_batch_size_per_im=256, rpn_straddle_thresh=0.0,
                      rpn_fg_fraction=0.5, rpn_positive_overlap=0.7,
                      rpn_negative_overlap=0.3, use_random=True, name=None):
    """detection/rpn_target_assign_op.cc parity (Faster-RCNN RPN sampling,
    Detectron-matching two-direction assignment :190-205).

    Per image: fg = anchors holding any gt's max overlap OR IoU >=
    rpn_positive_overlap, subsampled to fg_fraction*batch_size; bg = anchors
    with max IoU < rpn_negative_overlap, subsampled to the remainder (bg
    sampling may demote sampled fg — the fg_fake/bbox_inside_weight dance at
    :235-250 is reproduced). Eager host op (dynamic output counts, like the
    reference CPU kernel). Returns (loc_index, score_index, tgt_bbox,
    tgt_lbl, bbox_inside_weight) for a single image.
    """
    anchors = np.asarray(_t(anchor_box)._data).reshape(-1, 4)
    gts = np.asarray(_t(gt_boxes)._data).reshape(-1, 4)
    A, G = len(anchors), len(gts)
    rng_ = np.random.RandomState(0)

    # IoU anchor x gt
    ov = np.zeros((A, G), np.float32)
    for j in range(G):
        ix1 = np.maximum(anchors[:, 0], gts[j, 0])
        iy1 = np.maximum(anchors[:, 1], gts[j, 1])
        ix2 = np.minimum(anchors[:, 2], gts[j, 2])
        iy2 = np.minimum(anchors[:, 3], gts[j, 3])
        iw = np.maximum(ix2 - ix1 + 1, 0)
        ih = np.maximum(iy2 - iy1 + 1, 0)
        inter = iw * ih
        aa = (anchors[:, 2] - anchors[:, 0] + 1) * (anchors[:, 3] - anchors[:, 1] + 1)
        ga = (gts[j, 2] - gts[j, 0] + 1) * (gts[j, 3] - gts[j, 1] + 1)
        ov[:, j] = inter / np.maximum(aa + ga - inter, 1e-10)
    a2g_max = ov.max(axis=1) if G else np.zeros(A, np.float32)
    a2g_arg = ov.argmax(axis=1) if G else np.zeros(A, np.int64)
    g2a_max = ov.max(axis=0) if G else np.zeros(0, np.float32)

    def reservoir(cands, k):
        cands = list(cands)
        if k <= 0 or len(cands) <= k:
            return cands
        if not use_random:
            return cands[:k]
        out = cands[:k]
        for i in range(k, len(cands)):
            j = rng_.randint(0, i + 1)
            if j < k:
                out[j] = cands[i]
        return out

    eps = 1e-5
    with_max = (np.abs(ov - g2a_max[None, :]) < eps).any(axis=1) if G else np.zeros(A, bool)
    fg_fake_inds = reservoir(
        np.nonzero(with_max | (a2g_max >= rpn_positive_overlap))[0],
        int(rpn_fg_fraction * rpn_batch_size_per_im))
    label = np.full(A, -1, np.int64)
    label[np.asarray(fg_fake_inds, np.int64)] = 1
    fg_fake_num = len(fg_fake_inds)

    bg_cands = np.nonzero(a2g_max < rpn_negative_overlap)[0]
    bg_sel = reservoir(bg_cands, rpn_batch_size_per_im - fg_fake_num)

    fg_fake, inside_w = [], []
    fake_num = 0
    for b in bg_sel:
        if label[b] == 1:  # demoted fg keeps a zero-weight loc slot
            fake_num += 1
            fg_fake.append(int(fg_fake_inds[0]))
            inside_w.extend([0.0] * 4)
        label[b] = 0
    inside_w.extend([1.0] * 4 * (fg_fake_num - fake_num))

    fg_inds = np.nonzero(label == 1)[0]
    bg_inds = np.nonzero(label == 0)[0]
    fg_fake.extend(int(i) for i in fg_inds)
    loc_index = np.asarray(fg_fake, np.int32)
    score_index = np.concatenate([fg_inds, bg_inds]).astype(np.int32)
    tgt_lbl = np.concatenate([np.ones(len(fg_inds), np.int32),
                              np.zeros(len(bg_inds), np.int32)])

    # box deltas anchor -> matched gt for each loc_index entry
    def deltas(aidx):
        a = anchors[aidx]
        g = gts[a2g_arg[aidx]] if G else a
        aw, ah = a[2] - a[0] + 1, a[3] - a[1] + 1
        acx, acy = a[0] + aw / 2, a[1] + ah / 2
        gw, gh = g[2] - g[0] + 1, g[3] - g[1] + 1
        gcx, gcy = g[0] + gw / 2, g[1] + gh / 2
        return [(gcx - acx) / aw, (gcy - acy) / ah,
                np.log(gw / aw), np.log(gh / ah)]

    tgt_bbox = np.asarray([deltas(i) for i in loc_index], np.float32).reshape(-1, 4)
    iw_arr = np.asarray(inside_w, np.float32).reshape(-1, 4)

    outs = [Tensor(jnp.asarray(loc_index)), Tensor(jnp.asarray(score_index)),
            Tensor(jnp.asarray(tgt_bbox)),
            Tensor(jnp.asarray(tgt_lbl.reshape(-1, 1))),
            Tensor(jnp.asarray(iw_arr))]
    for t in outs:
        t.stop_gradient = True
    return tuple(outs)


def generate_proposal_labels(rpn_rois, gt_classes, is_crowd, gt_boxes, im_info,
                             batch_size_per_im=256, fg_fraction=0.25,
                             fg_thresh=0.5, bg_thresh_hi=0.5, bg_thresh_lo=0.0,
                             bbox_reg_weights=(0.1, 0.1, 0.2, 0.2),
                             class_nums=81, use_random=True,
                             is_cls_agnostic=False, name=None):
    """detection/generate_proposal_labels_op.cc parity (Fast-RCNN stage-2
    sampler), single image: gt boxes join the candidate pool, fg = RoIs with
    max gt IoU >= fg_thresh (subsampled to fg_fraction*batch), bg = RoIs with
    IoU in [bg_thresh_lo, bg_thresh_hi) (fills the remainder, labeled 0).
    Returns (rois, labels_int32, bbox_targets, bbox_inside_weights,
    bbox_outside_weights) — targets one-hot-expanded per class like the
    reference (class-agnostic collapses to a single foreground slot)."""
    rois = np.asarray(_t(rpn_rois)._data).reshape(-1, 4)
    gts = np.asarray(_t(gt_boxes)._data).reshape(-1, 4)
    cls = np.asarray(_t(gt_classes)._data).reshape(-1).astype(np.int64)
    crowd = (np.asarray(_t(is_crowd)._data).reshape(-1).astype(np.int64)
             if is_crowd is not None else np.zeros(len(gts), np.int64))
    rng_ = np.random.RandomState(0)

    # gt boxes participate as candidates (reference appends them)
    pool = np.concatenate([rois, gts], axis=0) if len(gts) else rois
    P, G = len(pool), len(gts)
    ov = np.zeros((P, max(G, 1)), np.float32)
    for j in range(G):
        if crowd[j]:
            continue
        ix1 = np.maximum(pool[:, 0], gts[j, 0])
        iy1 = np.maximum(pool[:, 1], gts[j, 1])
        ix2 = np.minimum(pool[:, 2], gts[j, 2])
        iy2 = np.minimum(pool[:, 3], gts[j, 3])
        iw = np.maximum(ix2 - ix1 + 1, 0)
        ih = np.maximum(iy2 - iy1 + 1, 0)
        inter = iw * ih
        pa = (pool[:, 2] - pool[:, 0] + 1) * (pool[:, 3] - pool[:, 1] + 1)
        ga = (gts[j, 2] - gts[j, 0] + 1) * (gts[j, 3] - gts[j, 1] + 1)
        ov[:, j] = inter / np.maximum(pa + ga - inter, 1e-10)
    mx = ov.max(axis=1)
    arg = ov.argmax(axis=1)

    fg_cand = np.nonzero(mx >= fg_thresh)[0]
    bg_cand = np.nonzero((mx >= bg_thresh_lo) & (mx < bg_thresh_hi))[0]
    fg_per_im = int(np.floor(batch_size_per_im * fg_fraction))
    n_fg = min(fg_per_im, len(fg_cand))
    if use_random and len(fg_cand) > n_fg:
        fg_sel = rng_.choice(fg_cand, n_fg, replace=False)
    else:
        fg_sel = fg_cand[:n_fg]
    n_bg = min(batch_size_per_im - n_fg, len(bg_cand))
    if use_random and len(bg_cand) > n_bg:
        bg_sel = rng_.choice(bg_cand, n_bg, replace=False)
    else:
        bg_sel = bg_cand[:n_bg]

    sel = np.concatenate([fg_sel, bg_sel]).astype(np.int64)
    out_rois = pool[sel]
    labels = np.concatenate([
        cls[arg[fg_sel]] if G else np.zeros(len(fg_sel), np.int64),
        np.zeros(len(bg_sel), np.int64)]).astype(np.int32)

    # box regression targets (fg only), weighted like the reference
    wx, wy, ww, wh = bbox_reg_weights
    n_cls = 2 if is_cls_agnostic else class_nums
    targets = np.zeros((len(sel), 4 * n_cls), np.float32)
    inside = np.zeros_like(targets)
    for k, ridx in enumerate(fg_sel):
        a = pool[ridx]
        g = gts[arg[ridx]] if G else a
        aw, ah = a[2] - a[0] + 1, a[3] - a[1] + 1
        acx, acy = a[0] + aw / 2, a[1] + ah / 2
        gw, gh = g[2] - g[0] + 1, g[3] - g[1] + 1
        gcx, gcy = g[0] + gw / 2, g[1] + gh / 2
        d = [(gcx - acx) / aw / wx, (gcy - acy) / ah / wy,
             np.log(gw / aw) / ww, np.log(gh / ah) / wh]
        c = 1 if is_cls_agnostic else int(labels[k])
        targets[k, 4 * c: 4 * c + 4] = d
        inside[k, 4 * c: 4 * c + 4] = 1.0
    outside = (inside > 0).astype(np.float32)

    outs = [Tensor(jnp.asarray(out_rois.astype(np.float32))),
            Tensor(jnp.asarray(labels.reshape(-1, 1))),
            Tensor(jnp.asarray(targets)),
            Tensor(jnp.asarray(inside)),
            Tensor(jnp.asarray(outside))]
    for t in outs:
        t.stop_gradient = True
    return tuple(outs)


def yolo_loss(x, gt_box, gt_label, anchors, anchor_mask, class_num,
              ignore_thresh, downsample_ratio, gt_score=None,
              use_label_smooth=True, name=None, scale_x_y=1.0):
    """paddle.vision.ops.yolo_loss 2.x alias of yolov3_loss."""
    return yolov3_loss(x, gt_box, gt_label, anchors, anchor_mask, class_num,
                       ignore_thresh, downsample_ratio, gt_score=gt_score,
                       use_label_smooth=use_label_smooth, scale_x_y=scale_x_y)


def box_decoder_and_assign(prior_box, prior_box_var, target_box, box_score,
                           box_clip=4.135, name=None):
    """detection/box_decoder_and_assign_op.h parity (Cascade-RCNN): decode
    per-class deltas against each RoI (+1-width convention, dw/dh clipped to
    box_clip), then assign each RoI the decoded box of its best non-background
    class. Returns (decode_box [R, C*4], output_assign_box [R, 4])."""
    def fn(pb, pv, tb, sc):
        R = pb.shape[0]
        C = sc.shape[1]
        pw = pb[:, 2] - pb[:, 0] + 1
        ph = pb[:, 3] - pb[:, 1] + 1
        pcx = pb[:, 0] + pw / 2
        pcy = pb[:, 1] + ph / 2
        d = tb.reshape(R, C, 4)
        dw = jnp.minimum(pv[2] * d[..., 2], box_clip)
        dh = jnp.minimum(pv[3] * d[..., 3], box_clip)
        cx = pv[0] * d[..., 0] * pw[:, None] + pcx[:, None]
        cy = pv[1] * d[..., 1] * ph[:, None] + pcy[:, None]
        bw = jnp.exp(dw) * pw[:, None]
        bh = jnp.exp(dh) * ph[:, None]
        boxes = jnp.stack([cx - bw / 2, cy - bh / 2,
                           cx + bw / 2 - 1, cy + bh / 2 - 1], axis=-1)
        # best non-background class per roi (class 0 = background)
        masked = jnp.where(jnp.arange(C)[None, :] > 0, sc, -jnp.inf)
        best = jnp.argmax(masked, axis=1)
        assign = jnp.take_along_axis(
            boxes, jnp.broadcast_to(best[:, None, None].astype(jnp.int32),
                                    (boxes.shape[0], 1, 4)), axis=1)[:, 0]
        return boxes.reshape(R, C * 4), assign

    db, ab = apply(fn, _t(prior_box).detach(), _t(prior_box_var).detach(),
                   _t(target_box), _t(box_score).detach())
    return db, ab


def prroi_pool(x, boxes, boxes_num, output_size, spatial_scale=1.0, name=None):
    """prroi_pool_op parity (Precise RoI Pooling, Acquisition-of-Localization):
    each bin averages the EXACT integral of the bilinearly-interpolated
    feature over its continuous region — no sampling-point quantization.

    TPU design: the 2-D integral of a bilinear surface is separable, so the
    bin reduces to wx^T F wy / area where wx[i] / wy[j] are the integrals of
    the hat basis at column i / row j over the bin interval — two small
    matvecs per bin instead of the reference's per-pixel accumulation loop.
    Fully differentiable (the reference ships a hand-written grad kernel).
    """
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    ph_n, pw_n = output_size

    xv = _t(x)
    bv = _t(boxes).detach()
    bn = np.asarray(_t(boxes_num)._data).astype(np.int64)
    img_of_roi = np.repeat(np.arange(len(bn)), bn)

    def fn(feat, rois):
        N, C, H, W = feat.shape
        img_idx = jnp.asarray(img_of_roi, jnp.int32)

        def hat_weights(a, b, n):
            """Integral of each hat basis (center k, support [k-1, k+1]) over
            [a, b], vectorized over k = 0..n-1."""
            k = jnp.arange(n, dtype=jnp.float32)

            def seg(lo, hi, kk, rising):
                lo_c = jnp.maximum(lo, a)
                hi_c = jnp.minimum(hi, b)
                L = jnp.maximum(hi_c - lo_c, 0.0)
                mid = (lo_c + hi_c) / 2.0
                # hat value at midpoint integrates exactly (linear segment)
                val = jnp.where(rising, mid - (kk - 1), (kk + 1) - mid)
                return L * val

            return seg(k - 1, k, k, True) + seg(k, k + 1, k, False)

        def one(roi, im):
            x1 = roi[0] * spatial_scale
            y1 = roi[1] * spatial_scale
            x2 = roi[2] * spatial_scale
            y2 = roi[3] * spatial_scale
            rh = jnp.maximum(y2 - y1, 0.0)
            rw = jnp.maximum(x2 - x1, 0.0)
            bin_h = rh / ph_n
            bin_w = rw / pw_n
            fmap = feat[im]

            def bin_val(phw):
                ph, pw = phw // pw_n, phw % pw_n
                ya = y1 + ph * bin_h
                yb = ya + bin_h
                xa = x1 + pw * bin_w
                xb = xa + bin_w
                wy = hat_weights(ya, yb, H)
                wx = hat_weights(xa, xb, W)
                area = jnp.maximum(bin_h * bin_w, 1e-9)
                return jnp.einsum("h,chw,w->c", wy, fmap, wx) / area

            vals = jax.vmap(bin_val)(jnp.arange(ph_n * pw_n))
            return vals.T.reshape(C, ph_n, pw_n)

        return jax.vmap(one)(rois, img_idx)

    return apply(fn, xv, bv)


def locality_aware_nms(bboxes, scores, score_threshold, nms_top_k, keep_top_k,
                       nms_threshold=0.3, normalized=True,
                       background_label=-1, name=None):
    """detection/locality_aware_nms_op.cc parity (EAST text detection):
    a sequential pass over boxes in input order score-weighted-MERGES runs of
    mutually-overlapping boxes (:102-128), then standard multiclass NMS runs
    on the merged survivors. Eager host op (the merge is order-dependent).
    bboxes [N, M, 4], scores [N, C, M] -> (out [N, keep_top_k, 6], num [N])."""
    bv = np.asarray(_t(bboxes)._data)
    sv = np.asarray(_t(scores)._data)
    off = 0.0 if normalized else 1.0

    def iou(a, b):
        ix = max(0.0, min(a[2], b[2]) - max(a[0], b[0]) + off)
        iy = max(0.0, min(a[3], b[3]) - max(a[1], b[1]) + off)
        inter = ix * iy
        ar_a = max(0, a[2] - a[0] + off) * max(0, a[3] - a[1] + off)
        ar_b = max(0, b[2] - b[0] + off) * max(0, b[3] - b[1] + off)
        u = ar_a + ar_b - inter
        return inter / u if u > 0 else 0.0

    N, C, M = sv.shape
    outs, nums = [], []
    for n in range(N):
        entries = []
        for c in range(C):
            if c == background_label:
                continue
            boxes = bv[n].copy()
            sc = sv[n, c].copy()
            skip = np.ones(M, bool)
            idx = -1
            for i in range(M):
                if idx > -1:
                    if iou(boxes[i], boxes[idx]) > nms_threshold:
                        si, sx = sc[i], sc[idx]
                        boxes[idx] = (boxes[i] * si + boxes[idx] * sx) / (si + sx)
                        sc[idx] += sc[i]
                    else:
                        skip[idx] = False
                        idx = i
                else:
                    idx = i
            if idx > -1:
                skip[idx] = False
            keep = np.nonzero((~skip) & (sc > score_threshold))[0]
            keep = keep[np.argsort(-sc[keep], kind="stable")]
            if nms_top_k > -1:
                keep = keep[:nms_top_k]
            if len(keep):
                kmask = np.asarray(nms_mask(jnp.asarray(boxes[keep]),
                                            jnp.asarray(sc[keep]),
                                            nms_threshold))
                for k in keep[kmask]:
                    entries.append([float(c), sc[k], *boxes[k]])
        entries.sort(key=lambda e: -e[1])
        entries = entries[:keep_top_k]
        nums.append(len(entries))
        pad = [[-1.0] * 6] * (keep_top_k - len(entries))
        outs.append(np.asarray(entries + pad, np.float32).reshape(keep_top_k, 6))
    out_t = Tensor(jnp.asarray(np.stack(outs)))
    num_t = Tensor(jnp.asarray(np.asarray(nums, np.int32)))
    out_t.stop_gradient = True
    num_t.stop_gradient = True
    return out_t, num_t


def retinanet_detection_output(bboxes, scores, anchors, im_info,
                               score_threshold=0.05, nms_top_k=1000,
                               keep_top_k=100, nms_threshold=0.3, nms_eta=1.0,
                               name=None):
    """detection/retinanet_detection_output_op.cc parity: multi-level (FPN)
    RetinaNet post-processing — per level, threshold the [cells*A, C] sigmoid
    scores (last level thresholds at 0), keep nms_top_k, decode the top
    candidates' anchor deltas (+1 convention, clipped to the rescaled image),
    then per-class NMS over the union and keep_top_k. Single image, eager.
    bboxes/scores/anchors: lists per level ([M_l, 4], [M_l, C], [M_l, 4]);
    im_info (h, w, scale). Returns (out [k, 6], num)."""
    info = np.asarray(_t(im_info)._data).reshape(-1)
    im_h = round(float(info[0]) / float(info[2]))
    im_w = round(float(info[1]) / float(info[2]))
    scale = float(info[2])

    cand = []  # (class, score, box)
    L = len(scores)
    for l in range(L):
        sc = np.asarray(_t(scores[l])._data).reshape(-1)
        bx = np.asarray(_t(bboxes[l])._data).reshape(-1, 4)
        an = np.asarray(_t(anchors[l])._data).reshape(-1, 4)
        C = np.asarray(_t(scores[l])._data).shape[-1]
        thr = score_threshold if l < L - 1 else 0.0
        keep = np.nonzero(sc > thr)[0]
        keep = keep[np.argsort(-sc[keep], kind="stable")][:nms_top_k]
        for idx in keep:
            a, c = idx // C, idx % C
            aw = an[a, 2] - an[a, 0] + 1
            ah = an[a, 3] - an[a, 1] + 1
            acx = an[a, 0] + aw / 2
            acy = an[a, 1] + ah / 2
            cx = bx[a, 0] * aw + acx
            cy = bx[a, 1] * ah + acy
            bw = np.exp(bx[a, 2]) * aw
            bh = np.exp(bx[a, 3]) * ah
            box = np.array([cx - bw / 2, cy - bh / 2,
                            cx + bw / 2 - 1, cy + bh / 2 - 1]) / scale
            box[0::2] = np.clip(box[0::2], 0, im_w - 1)
            box[1::2] = np.clip(box[1::2], 0, im_h - 1)
            cand.append((int(c), float(sc[idx]), box))

    entries = []
    if cand:
        classes = sorted(set(c for c, _, _ in cand))
        for c in classes:
            cl = [(s, b) for cc, s, b in cand if cc == c]
            cl.sort(key=lambda e: -e[0])
            boxes_c = np.stack([b for _, b in cl])
            sc_c = np.asarray([s for s, _ in cl], np.float32)
            kmask = np.asarray(nms_mask(jnp.asarray(boxes_c),
                                        jnp.asarray(sc_c), nms_threshold,
                                        use_pallas=False))
            for k in np.nonzero(kmask)[0]:
                entries.append([float(c), sc_c[k], *boxes_c[k]])
        entries.sort(key=lambda e: -e[1])
        entries = entries[:keep_top_k]
    n = len(entries)
    pad = [[-1.0] * 6] * (keep_top_k - n)
    out = Tensor(jnp.asarray(np.asarray(entries + pad, np.float32)))
    num = Tensor(jnp.asarray(np.asarray([n], np.int32)))
    out.stop_gradient = True
    num.stop_gradient = True
    return out, num


def roi_perspective_transform(x, rois, transformed_height, transformed_width,
                              spatial_scale=1.0, name=None):
    """detection/roi_perspective_transform_op.cc parity (OCR text
    rectification): each RoI is a quadrilateral [x0 y0 .. x3 y3]; the op
    builds the projective map from the output rectangle onto the quad
    (:110-168 — width normalized by the quad's estimated aspect) and
    bilinearly samples the feature map (out-of-bounds reads 0).

    x [N, C, H, W]; rois [R, 8] with every RoI belonging to image 0..N-1 via
    `rois_num`-free single-image usage (reference uses LoD; here all RoIs
    sample image 0 unless rois has a leading batch column). Returns
    (out [R, C, th, tw], mask [R, 1, th, tw], transform_matrix [R, 9])."""
    th, tw = int(transformed_height), int(transformed_width)
    xv = _t(x)
    rv = _t(rois).detach()

    def fn(feat, quads):
        N, C, H, W = feat.shape
        R = quads.shape[0]

        def one(quad):
            qx = quad[0::2] * spatial_scale
            qy = quad[1::2] * spatial_scale
            len1 = jnp.sqrt((qx[0] - qx[1]) ** 2 + (qy[0] - qy[1]) ** 2)
            len2 = jnp.sqrt((qx[1] - qx[2]) ** 2 + (qy[1] - qy[2]) ** 2)
            len3 = jnp.sqrt((qx[2] - qx[3]) ** 2 + (qy[2] - qy[3]) ** 2)
            len4 = jnp.sqrt((qx[3] - qx[0]) ** 2 + (qy[3] - qy[0]) ** 2)
            est_h = (len2 + len4) / 2.0
            est_w = (len1 + len3) / 2.0
            nh = max(2, th)
            nw = jnp.clip(jnp.round(est_w * (nh - 1) / jnp.maximum(est_h, 1e-5)
                                    ) + 1, 2, tw)
            dx1, dx2 = qx[1] - qx[2], qx[3] - qx[2]
            dx3 = qx[0] - qx[1] + qx[2] - qx[3]
            dy1, dy2 = qy[1] - qy[2], qy[3] - qy[2]
            dy3 = qy[0] - qy[1] + qy[2] - qy[3]
            den = dx1 * dy2 - dx2 * dy1 + 1e-5
            m6 = (dx3 * dy2 - dx2 * dy3) / den / (nw - 1)
            m7 = (dx1 * dy3 - dx3 * dy1) / den / (nh - 1)
            m8 = 1.0
            m3 = (qy[1] - qy[0] + m6 * (nw - 1) * qy[1]) / (nw - 1)
            m4 = (qy[3] - qy[0] + m7 * (nh - 1) * qy[3]) / (nh - 1)
            m5 = qy[0]
            m0 = (qx[1] - qx[0] + m6 * (nw - 1) * qx[1]) / (nw - 1)
            m1 = (qx[3] - qx[0] + m7 * (nh - 1) * qx[3]) / (nh - 1)
            m2 = qx[0]
            mat = jnp.stack([m0, m1, m2, m3, m4, m5, m6, m7, m8])

            ww = jnp.arange(tw, dtype=jnp.float32)[None, :]
            hh = jnp.arange(th, dtype=jnp.float32)[:, None]
            u = m0 * ww + m1 * hh + m2
            v = m3 * ww + m4 * hh + m5
            w_ = m6 * ww + m7 * hh + m8
            in_w = u / w_
            in_h = v / w_
            # reference also zeroes output+mask when the source point falls
            # OUTSIDE the quadrilateral (roi_perspective_transform_op.cc:303)
            # — even-odd crossing test against the 4-gon
            inq = jnp.zeros(in_w.shape, bool)
            on_edge = jnp.zeros(in_w.shape, bool)
            for e in range(4):
                xi, yi = qx[e], qy[e]
                xj, yj = qx[(e + 3) % 4], qy[(e + 3) % 4]
                crosses = ((yi > in_h) != (yj > in_h)) & (
                    in_w < (xj - xi) * (in_h - yi) / (yj - yi + 1e-12) + xi)
                inq = inq ^ crosses
                # reference in_quad counts points ON an edge as inside (:46-60)
                cross = (xj - xi) * (in_h - yi) - (yj - yi) * (in_w - xi)
                seg_len = jnp.sqrt((xj - xi) ** 2 + (yj - yi) ** 2) + 1e-12
                near = jnp.abs(cross) / seg_len < 1e-3
                inseg = ((in_w >= jnp.minimum(xi, xj) - 1e-3)
                         & (in_w <= jnp.maximum(xi, xj) + 1e-3)
                         & (in_h >= jnp.minimum(yi, yj) - 1e-3)
                         & (in_h <= jnp.maximum(yi, yj) + 1e-3))
                on_edge = on_edge | (near & inseg)
            inq = inq | on_edge
            inb = (inq & (in_w > -0.5) & (in_w < W - 0.5)
                   & (in_h > -0.5) & (in_h < H - 0.5))

            x0 = jnp.floor(in_w)
            y0 = jnp.floor(in_h)
            wx = in_w - x0
            wy = in_h - y0

            def at(yy, xx):
                ok = (yy >= 0) & (yy <= H - 1) & (xx >= 0) & (xx <= W - 1)
                yc = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
                xc = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
                return feat[0][:, yc, xc] * ok[None]

            val = (at(y0, x0) * (1 - wy) * (1 - wx)
                   + at(y0, x0 + 1) * (1 - wy) * wx
                   + at(y0 + 1, x0) * wy * (1 - wx)
                   + at(y0 + 1, x0 + 1) * wy * wx)
            out = val * inb[None]
            return out, inb.astype(jnp.int32)[None], mat

        outs, masks, mats = jax.vmap(one)(quads)
        return outs, masks, mats

    o, m, t = apply(fn, xv, rv)
    m.stop_gradient = True
    t.stop_gradient = True
    return o, m, t


def retinanet_target_assign(bbox_pred, cls_logits, anchor_box, anchor_var,
                            gt_boxes, gt_labels, is_crowd, im_info,
                            num_classes=1, positive_overlap=0.5,
                            negative_overlap=0.4, name=None):
    """detection/rpn_target_assign_op.cc:875 RetinanetTargetAssign parity:
    the RPN two-direction assignment with NO subsampling (every anchor is
    labeled), fg targets carry the matched gt's CLASS label (not 1), bg = 0.
    Returns (loc_index, score_index, tgt_bbox, tgt_lbl, bbox_inside_weight,
    fg_num) for one image; fg_num = #fg + 1 (the reference's focal-loss
    normalizer convention)."""
    anchors = np.asarray(_t(anchor_box)._data).reshape(-1, 4)
    gts = np.asarray(_t(gt_boxes)._data).reshape(-1, 4)
    labels_np = np.asarray(_t(gt_labels)._data).reshape(-1).astype(np.int64)
    crowd = (np.asarray(_t(is_crowd)._data).reshape(-1).astype(np.int64)
             if is_crowd is not None else np.zeros(len(gts), np.int64))
    # gt boxes arrive in ORIGINAL image coords; anchors live on the resized
    # image — scale gts by im_scale like the reference (:~975)
    if im_info is not None:
        im_scale = float(np.asarray(_t(im_info)._data).reshape(-1)[2])
        gts = gts * im_scale
    keep_gt = crowd == 0
    gts = gts[keep_gt]
    labels_np = labels_np[keep_gt]
    A, G = len(anchors), len(gts)

    ov = np.zeros((A, max(G, 1)), np.float32)
    for j in range(G):
        ix1 = np.maximum(anchors[:, 0], gts[j, 0])
        iy1 = np.maximum(anchors[:, 1], gts[j, 1])
        ix2 = np.minimum(anchors[:, 2], gts[j, 2])
        iy2 = np.minimum(anchors[:, 3], gts[j, 3])
        iw = np.maximum(ix2 - ix1 + 1, 0)
        ih = np.maximum(iy2 - iy1 + 1, 0)
        inter = iw * ih
        aa = (anchors[:, 2] - anchors[:, 0] + 1) * (anchors[:, 3] - anchors[:, 1] + 1)
        ga = (gts[j, 2] - gts[j, 0] + 1) * (gts[j, 3] - gts[j, 1] + 1)
        ov[:, j] = inter / np.maximum(aa + ga - inter, 1e-10)
    a2g_max = ov.max(axis=1) if G else np.zeros(A, np.float32)
    a2g_arg = ov.argmax(axis=1) if G else np.zeros(A, np.int64)
    g2a_max = ov.max(axis=0) if G else np.zeros(0, np.float32)

    eps = 1e-5
    with_max = (np.abs(ov - g2a_max[None, :]) < eps).any(axis=1) if G else np.zeros(A, bool)
    fg_cand = with_max | (a2g_max >= positive_overlap)
    # reference bg loop (rpn_target_assign_op.cc:236-246) demotes fg anchors
    # whose max IoU is below negative_overlap back to background, keeping a
    # zero-weight loc slot (duplicated first fg candidate) for each
    below_neg = a2g_max < negative_overlap
    demoted = fg_cand & below_neg
    fg_mask = fg_cand & ~below_neg
    bg_mask = below_neg                      # includes the demoted anchors
    fg_inds = np.nonzero(fg_mask)[0]
    bg_inds = np.nonzero(bg_mask)[0]
    n_demoted = int(demoted.sum())
    fg_cand_inds = np.nonzero(fg_cand)[0]
    first_fg = int(fg_cand_inds[0]) if len(fg_cand_inds) else 0
    loc_index = np.concatenate([
        np.full(n_demoted, first_fg, np.int64), fg_inds]).astype(np.int64)
    inside_w = np.concatenate([
        np.zeros((n_demoted, 4), np.float32),
        np.ones((len(fg_inds), 4), np.float32)], axis=0)

    def deltas(aidx):
        a = anchors[aidx]
        g = gts[a2g_arg[aidx]] if G else a
        aw, ah = a[2] - a[0] + 1, a[3] - a[1] + 1
        acx, acy = a[0] + aw / 2, a[1] + ah / 2
        gw, gh = g[2] - g[0] + 1, g[3] - g[1] + 1
        gcx, gcy = g[0] + gw / 2, g[1] + gh / 2
        return [(gcx - acx) / aw, (gcy - acy) / ah,
                np.log(gw / aw), np.log(gh / ah)]

    tgt_bbox = np.asarray([deltas(i) for i in loc_index],
                          np.float32).reshape(-1, 4)
    tgt_lbl = np.concatenate([
        labels_np[a2g_arg[fg_inds]] if G else np.zeros(len(fg_inds), np.int64),
        np.zeros(len(bg_inds), np.int64)]).astype(np.int32)
    score_index = np.concatenate([fg_inds, bg_inds]).astype(np.int32)
    outs = [Tensor(jnp.asarray(loc_index.astype(np.int32))),
            Tensor(jnp.asarray(score_index)),
            Tensor(jnp.asarray(tgt_bbox)),
            Tensor(jnp.asarray(tgt_lbl.reshape(-1, 1))),
            Tensor(jnp.asarray(inside_w)),
            Tensor(jnp.asarray(np.asarray([len(loc_index) + 1], np.int32)))]
    for t in outs:
        t.stop_gradient = True
    return tuple(outs)


def deformable_psroi_pooling(input, rois, trans, no_trans=False,
                             spatial_scale=1.0, group_size=(1, 1),
                             pooled_height=1, pooled_width=1,
                             part_size=None, sample_per_part=1,
                             trans_std=0.1, position_sensitive=True,
                             boxes_num=None, name=None):
    """deformable_psroi_pooling_op.cu parity (deformable R-FCN head): each
    bin samples sample_per_part^2 bilinear points, shifted by the learned
    normalized offsets trans[r, 2, part_h, part_w]*trans_std*roi_size; the
    channel is picked position-sensitively via group_size. All RoIs read
    image 0 (single-image eager form). Returns [R, output_dim, ph, pw]."""
    ph_n, pw_n = int(pooled_height), int(pooled_width)
    gh_n, gw_n = (int(group_size[0]), int(group_size[1]))
    if part_size is None:
        part_size = (ph_n, pw_n)
    pth, ptw = int(part_size[0]), int(part_size[1])

    xv = _t(input)
    rv = _t(rois).detach()
    args = [xv, rv]
    if trans is not None and not no_trans:
        args.append(_t(trans))

    def fn(feat, rois_v, *tr):
        N, C, H, W = feat.shape
        out_dim = C // (gh_n * gw_n) if position_sensitive else C
        trans_v = tr[0] if tr else None

        def one(ri):
            roi = rois_v[ri]
            x1 = jnp.round(roi[0]) * spatial_scale - 0.5
            y1 = jnp.round(roi[1]) * spatial_scale - 0.5
            x2 = (jnp.round(roi[2]) + 1.0) * spatial_scale - 0.5
            y2 = (jnp.round(roi[3]) + 1.0) * spatial_scale - 0.5
            rw = jnp.maximum(x2 - x1, 0.1)
            rh = jnp.maximum(y2 - y1, 0.1)
            bh, bw = rh / ph_n, rw / pw_n
            sh, sw = bh / sample_per_part, bw / sample_per_part

            def bin_val(phw):
                ph, pw = phw // pw_n, phw % pw_n
                part_h = (ph * pth) // ph_n
                part_w = (pw * ptw) // pw_n
                tx = (trans_v[ri, 0, part_h, part_w] * trans_std
                      if trans_v is not None else 0.0)
                ty = (trans_v[ri, 1, part_h, part_w] * trans_std
                      if trans_v is not None else 0.0)
                ws = pw * bw + x1 + tx * rw
                hs = ph * bh + y1 + ty * rh
                gw = jnp.clip((pw * gw_n) // pw_n, 0, gw_n - 1)
                gh = jnp.clip((ph * gh_n) // ph_n, 0, gh_n - 1)
                if position_sensitive:
                    ch = (jnp.arange(out_dim) * gh_n + gh) * gw_n + gw
                else:
                    ch = jnp.arange(out_dim)
                fm = feat[0][ch]                       # [out_dim, H, W]

                ihs = jnp.arange(sample_per_part, dtype=jnp.float32)
                iws = jnp.arange(sample_per_part, dtype=jnp.float32)
                hh = hs + ihs[:, None] * sh            # [s, 1]
                wwv = ws + iws[None, :] * sw           # [1, s]
                hh = jnp.broadcast_to(hh, (sample_per_part, sample_per_part))
                wwv = jnp.broadcast_to(wwv, (sample_per_part, sample_per_part))
                inb = ((wwv >= -0.5) & (wwv <= W - 0.5)
                       & (hh >= -0.5) & (hh <= H - 0.5))
                wc = jnp.clip(wwv, 0.0, W - 1.0)
                hc = jnp.clip(hh, 0.0, H - 1.0)
                x0 = jnp.floor(wc)
                y0 = jnp.floor(hc)
                ax = wc - x0
                ay = hc - y0

                def at(yy, xx):
                    yi = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
                    xi = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
                    return fm[:, yi, xi]               # [out_dim, s, s]

                val = (at(y0, x0) * (1 - ay) * (1 - ax)
                       + at(y0, x0 + 1) * (1 - ay) * ax
                       + at(y0 + 1, x0) * ay * (1 - ax)
                       + at(y0 + 1, x0 + 1) * ay * ax)
                cnt = jnp.maximum(jnp.sum(inb), 1)
                return jnp.sum(val * inb[None], axis=(1, 2)) / cnt

            vals = jax.vmap(bin_val)(jnp.arange(ph_n * pw_n))
            return vals.T.reshape(out_dim, ph_n, pw_n)

        return jax.vmap(one)(jnp.arange(rois_v.shape[0]))

    return apply(fn, *args)


def generate_mask_labels(im_info, gt_classes, is_crowd, gt_segms, rois,
                         labels_int32, num_classes, resolution, name=None):
    """detection/generate_mask_labels_op.cc parity (Mask R-CNN mask targets):
    each fg RoI (label > 0) is matched (IoU vs the polygons' bounding boxes,
    in unscaled image coords) to a non-crowd gt; the gt's polygons are
    rasterized within the RoI at resolution^2 (even-odd point-in-polygon on
    the bin-center grid, the Polys2MaskWrtBox recipe) and one-hot-expanded to
    [fg, num_classes*res^2] with -1 outside the class slot. Eager host op.

    gt_segms: list (per gt) of lists of flat polygons [x0, y0, x1, y1, ...].
    Returns (mask_rois [fg, 4], roi_has_mask_int32 [fg, 1], mask_int32)."""
    info = np.asarray(_t(im_info)._data).reshape(-1)
    im_scale = float(info[2])
    cls = np.asarray(_t(gt_classes)._data).reshape(-1).astype(np.int64)
    crowd = np.asarray(_t(is_crowd)._data).reshape(-1).astype(np.int64)
    rois_np = np.asarray(_t(rois)._data).reshape(-1, 4)
    labels = np.asarray(_t(labels_int32)._data).reshape(-1).astype(np.int64)

    keep = [(i, gt_segms[i]) for i in range(len(cls))
            if cls[i] > 0 and crowd[i] == 0]
    gt_polys = [p for _, p in keep]
    gt_ids = [i for i, _ in keep]
    boxes = np.zeros((len(gt_polys), 4), np.float32)
    for k, polys in enumerate(gt_polys):
        pts = np.concatenate([np.asarray(p, np.float32).reshape(-1, 2)
                              for p in polys])
        boxes[k] = [pts[:, 0].min(), pts[:, 1].min(),
                    pts[:, 0].max(), pts[:, 1].max()]

    fg_inds = np.nonzero(labels > 0)[0]
    res = int(resolution)
    M = res * res
    mask_t = -np.ones((max(len(fg_inds), 1), num_classes * M), np.int32)
    out_rois = np.zeros((max(len(fg_inds), 1), 4), np.float32)

    def in_polys(px, py, polys):
        inside = np.zeros(px.shape, bool)
        for poly in polys:
            pts = np.asarray(poly, np.float32).reshape(-1, 2)
            n = len(pts)
            acc = np.zeros(px.shape, bool)
            j = n - 1
            for i in range(n):
                xi, yi = pts[i]
                xj, yj = pts[j]
                crosses = ((yi > py) != (yj > py)) & (
                    px < (xj - xi) * (py - yi) / (yj - yi + 1e-12) + xi)
                acc ^= crosses
                j = i
            inside |= acc
        return inside

    for k, ridx in enumerate(fg_inds):
        roi = rois_np[ridx] / im_scale
        out_rois[k] = rois_np[ridx]
        if len(boxes):
            ix1 = np.maximum(roi[0], boxes[:, 0])
            iy1 = np.maximum(roi[1], boxes[:, 1])
            ix2 = np.minimum(roi[2], boxes[:, 2])
            iy2 = np.minimum(roi[3], boxes[:, 3])
            inter = np.maximum(ix2 - ix1, 0) * np.maximum(iy2 - iy1, 0)
            ra = (roi[2] - roi[0]) * (roi[3] - roi[1])
            ba = (boxes[:, 2] - boxes[:, 0]) * (boxes[:, 3] - boxes[:, 1])
            best = int(np.argmax(inter / np.maximum(ra + ba - inter, 1e-10)))
            polys = gt_polys[best]
        else:
            polys = []
        # the mask goes into the RoI's OWN class slot (reference gathers
        # mask_class_labels from labels_int32); the matched gt only supplies
        # the polygon geometry
        c = int(labels[ridx])
        w = max(roi[2] - roi[0], 1e-3)
        h = max(roi[3] - roi[1], 1e-3)
        gx = roi[0] + (np.arange(res) + 0.5) * w / res
        gy = roi[1] + (np.arange(res) + 0.5) * h / res
        px, py = np.meshgrid(gx, gy)
        m = in_polys(px, py, polys).astype(np.int32).reshape(-1)
        c = min(max(c, 0), num_classes - 1)
        mask_t[k, c * M:(c + 1) * M] = m

    n_fg = len(fg_inds)
    outs = (Tensor(jnp.asarray(out_rois[:max(n_fg, 1)])),
            Tensor(jnp.asarray(fg_inds.astype(np.int32).reshape(-1, 1)
                               if n_fg else np.zeros((1, 1), np.int32))),
            Tensor(jnp.asarray(mask_t)))
    for t in outs:
        t.stop_gradient = True
    return outs
