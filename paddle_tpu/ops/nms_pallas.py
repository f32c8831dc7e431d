"""Pallas greedy-NMS kernel for TPU (BASELINE.json config #5: detection post-proc).

Reference parity: the CUDA NMS kernels behind multiclass_nms
(paddle/fluid/operators/detection/multiclass_nms_op.cc) compute a pairwise-IoU bitmask
then greedily sweep it. TPU-native design: the whole problem (boxes sorted by score,
N <= ~4k) fits VMEM, so one kernel computes each row's IoU against all boxes with VPU
ops and runs the sequential greedy sweep in a fori_loop — zero HBM round-trips between
the O(N^2) IoU work and the O(N) suppression chain, where the off-TPU lax.scan path
re-reads the mask every step.

keep[i] = no kept j < i has IoU(i, j) > threshold (boxes pre-sorted by score desc).
"""
import functools

import jax
import jax.numpy as jnp

from ..core.device import on_tpu

LANE = 128  # pad N to a lane multiple so [1, N] rows tile cleanly


def _nms_kernel(boxes_ref, thresh_ref, keep_ref, *, n_pad):
    """boxes_ref: [4, n_pad] f32 rows x1,y1,x2,y2 (score-desc order; pads are
    zero-area at the tail). keep_ref: [1, n_pad] int32.

    No dynamic indexing (unsupported in Mosaic lowering): box i's scalars are
    extracted with a lane-mask select + full reduction each sweep step — still
    O(N) VPU work per step, same order as the IoU row itself."""
    x1 = boxes_ref[0, :].reshape(1, n_pad)
    y1 = boxes_ref[1, :].reshape(1, n_pad)
    x2 = boxes_ref[2, :].reshape(1, n_pad)
    y2 = boxes_ref[3, :].reshape(1, n_pad)
    area = jnp.maximum(x2 - x1, 0.0) * jnp.maximum(y2 - y1, 0.0)
    thresh = thresh_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)

    keep_ref[...] = jnp.ones((1, n_pad), jnp.int32)

    def body(i, _):
        sel = lane == i

        def pick(row):
            return jnp.sum(jnp.where(sel, row, 0.0))

        bx1, by1, bx2, by2 = pick(x1), pick(y1), pick(x2), pick(y2)
        barea = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
        iw = jnp.maximum(jnp.minimum(x2, bx2) - jnp.maximum(x1, bx1), 0.0)
        ih = jnp.maximum(jnp.minimum(y2, by2) - jnp.maximum(y1, by1), 0.0)
        inter = iw * ih
        iou = inter / jnp.maximum(area + barea - inter, 1e-9)  # [1, n_pad]

        kept = keep_ref[...]
        kept_i = jnp.sum(jnp.where(sel, kept, 0))
        # suppress every later box overlapping a *kept* box i
        supp = (iou > thresh) & (lane > i) & (kept_i > 0)
        keep_ref[...] = jnp.where(supp, 0, kept)
        return 0

    jax.lax.fori_loop(0, n_pad, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def nms_keep_mask_pallas(boxes, iou_threshold, interpret=False):
    """boxes: [N, 4] sorted by score desc. Returns keep mask [N] bool.

    Pads N up to a lane multiple; padded boxes are zero-area (IoU 0) so they
    never suppress real boxes.
    """
    from jax.experimental import pallas as pl

    n = boxes.shape[0]
    n_pad = ((n + LANE - 1) // LANE) * LANE
    boxes_p = jnp.zeros((n_pad, 4), jnp.float32).at[:n].set(
        boxes.astype(jnp.float32))
    thresh = jnp.full((1, 1), iou_threshold, jnp.float32)

    keep = pl.pallas_call(
        functools.partial(_nms_kernel, n_pad=n_pad),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(boxes_p.T, thresh)
    return keep[0, :n] > 0


# ---------------------------------------------------------------------------
# static audit manifest (analysis/pallas_audit.py, ISSUE 13)
# ---------------------------------------------------------------------------


def audit_manifest():
    """One entry at the supported() cap: the whole problem lives in VMEM
    (no grid streaming), so the audit checks the worst-case residency."""
    n_pad = 8192   # supported() upper bound, already lane-aligned
    return [{
        "kernel": f"nms.sweep[n={n_pad}]", "op": "nms",
        "in_dtype": "float32", "matmul": False,
        "grid": {"n": (n_pad, LANE)},
        "buffers": [
            {"name": "boxes", "block": (4, n_pad), "dtype": "float32",
             "stream": False},
            {"name": "thresh", "block": (1, 1), "dtype": "float32",
             "stream": False},
            {"name": "keep", "block": (1, n_pad), "dtype": "int32",
             "stream": False}]}]


def supported(n_boxes):
    """VMEM budget: [n_pad, 4] boxes + a few [1, n_pad] rows — generous cap.
    A pure size/platform predicate (the platform test raises rather than
    answering "no" for a backend that cannot start)."""
    return n_boxes <= 8192 and on_tpu()
