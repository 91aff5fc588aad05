"""The comparison that decides ``correct`` for cells that serve detections.

What is compared is what the timed path itself handed to the host in the
window: per image, the ragged ``boxes``/``scores``/``refs`` that
``detections_to_numpy`` gave. The plain reference's dense maps for the same
image and exemplar are read *at the places the program chose*, so rounding
that moves a peak by a cell does not fail a run, while a wrong score or box
at the chosen place does:

``score_gap``    widest |served score - reference score at that cell|
``box_gap``      widest |served box - reference box at that cell|, in units
                 of the exemplar's larger side
``nms_overlaps`` pairs of served boxes of one image over the IoU threshold
                 (exact: the NMS guarantee, limit 0)
``missed_clear`` reference detections that no rounding inside the margins
                 could remove and that were not served (limit 0)
``spurious_clear`` served detections that no rounding inside the margins
                 could produce: under the threshold, not a local peak, or
                 under a box that clearly suppresses them (limit 0)
``dets_compared`` how many served detections were read (a floor: a run that
                 serves nothing has compared nothing)

The margins (``score_margin``, ``iou_margin``) say how far a score and an IoU
may move by rounding; they are the cell's, set from readings (PERF.md).
"""

from __future__ import annotations

import numpy as np

from benchmarks import reference as ref


def _cells_of(refs: np.ndarray, hh: int, ww: int) -> np.ndarray:
    ix = np.clip(np.rint(refs[:, 0] * ww).astype(np.int64), 0, ww - 1)
    iy = np.clip(np.rint(refs[:, 1] * hh).astype(np.int64), 0, hh - 1)
    return iy * ww + ix


def compare_image(served: dict, obj: np.ndarray, reg: np.ndarray, exemplar,
                  rules: dict) -> dict:
    """One image: the numbers above, as plain floats and counts."""
    hh, ww = obj.shape
    thr, iou_thr = rules["cls_threshold"], rules["iou_threshold"]
    m, m_iou = rules["score_margin"], rules["iou_margin"]
    k = rules["max_detections"]
    p = (1.0 / (1.0 + np.exp(-obj.astype(np.float64)))).astype(np.float32)
    flat_p = p.reshape(-1)
    ref_boxes = ref.decode_boxes(reg, exemplar).reshape(-1, 4)
    ew, eh = ref.clipped_extent(exemplar)
    nb = ref.neighbour_max(p, ref.peak_kernel(eh, ew, hh, ww)).reshape(-1)

    boxes = np.asarray(served["boxes"], np.float32).reshape(-1, 4)
    scores = np.asarray(served["scores"], np.float32).reshape(-1)
    cells = _cells_of(np.asarray(served["refs"], np.float32).reshape(-1, 2),
                      hh, ww)
    n = len(scores)
    out = {"dets_compared": n, "score_gap": 0.0, "box_gap": 0.0,
           "nms_overlaps": 0, "missed_clear": 0, "spurious_clear": 0}
    if n:
        out["score_gap"] = float(np.abs(scores - flat_p[cells]).max())
        out["box_gap"] = float(np.abs(boxes - ref_boxes[cells]).max()
                               / max(ew, eh, 1e-6))
        for i in range(n):
            later = np.arange(n) > i
            out["nms_overlaps"] += int(
                (later & (ref.iou_one_to_many(boxes[i], boxes)
                          > iou_thr + 1e-4)).sum())

    # what could be a candidate under rounding, and what surely is one
    maybe = np.flatnonzero((flat_p >= thr - m) & (flat_p >= nb - 2 * m))
    sure = np.flatnonzero((flat_p >= thr + m) & (flat_p >= nb + 2 * m))
    rank_ok = len(maybe) <= k - 100
    clear_kept = []
    for c in sure:
        rivals = maybe[(maybe != c) & (flat_p[maybe] >= flat_p[c] - 2 * m)]
        if len(rivals) and (ref.iou_one_to_many(ref_boxes[c],
                                                ref_boxes[rivals])
                            > iou_thr - m_iou).any():
            continue
        clear_kept.append(c)
    clear_kept = np.asarray(clear_kept, np.int64)
    if rank_ok:
        out["missed_clear"] = int(len(set(clear_kept.tolist())
                                      - set(cells.tolist())))
    for i in range(n):
        c = cells[i]
        if flat_p[c] < thr - m or flat_p[c] < nb[c] - 2 * m:
            out["spurious_clear"] += 1
            continue
        above = clear_kept[(clear_kept != c)
                           & (flat_p[clear_kept] >= flat_p[c] + 2 * m)]
        if len(above) and (ref.iou_one_to_many(boxes[i], ref_boxes[above])
                           >= iou_thr + m_iou).any():
            out["spurious_clear"] += 1
    return out


def merge(per_image: list) -> dict:
    """Widest gaps and summed counts over the sampled images."""
    out = {}
    for name in ("score_gap", "box_gap"):
        out[name] = max((r[name] for r in per_image), default=0.0)
    for name in ("nms_overlaps", "missed_clear", "spurious_clear",
                 "dets_compared"):
        out[name] = sum(r[name] for r in per_image)
    return out


def verdict(numbers: dict, limits: dict):
    """``(correct, compared)``: each number beside its limit. A limit is an
    upper one, except ``dets_compared``'s, which is a floor."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name]
        floor = name == "dets_compared"
        good = value >= limit if floor else value <= limit
        ok = ok and bool(good) and bool(np.isfinite(value))
        compared[name] = {"value": value, "limit": limit,
                          "kind": "at_least" if floor else "at_most"}
    return ok, compared
