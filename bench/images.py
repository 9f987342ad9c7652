"""The benchmark's own copy of the synthetic IVS-3cls image generator.

Copied from `repro.data.detection.render_batch` so that a change to the
program cannot move the benchmark's inputs: images with 1-6 objects of
three visually distinct classes (filled rectangles, outlined rectangles,
vertical blobs) on a noise background, plus YOLOv2 grid targets.  The same
`seed` tuple always renders the same batch.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# anchor (w, h) as image fractions, as the detector's head decodes them
ANCHORS = np.array([[0.08, 0.12], [0.18, 0.25], [0.35, 0.45],
                    [0.5, 0.3], [0.75, 0.65]], np.float32)


def _draw_object(img: np.ndarray, cls: int, box, rng) -> None:
    H, W, _ = img.shape
    cx, cy, w, h = box
    x0, x1 = int((cx - w / 2) * W), int((cx + w / 2) * W)
    y0, y1 = int((cy - h / 2) * H), int((cy + h / 2) * H)
    x0, y0 = max(x0, 0), max(y0, 0)
    x1, y1 = min(x1, W), min(y1, H)
    color = rng.random(3) * 0.5 + 0.5
    if cls == 0:      # "vehicle": filled rectangle
        img[y0:y1, x0:x1] = color
    elif cls == 1:    # "bike": outlined rectangle
        t = max(1, (y1 - y0) // 6)
        img[y0:y0 + t, x0:x1] = color
        img[y1 - t:y1, x0:x1] = color
        img[y0:y1, x0:x0 + t] = color
        img[y0:y1, x1 - t:x1] = color
    else:             # "pedestrian": bright vertical blob
        xm = (x0 + x1) // 2
        t = max(1, (x1 - x0) // 3)
        img[y0:y1, max(xm - t, 0):min(xm + t, W)] = color


def render_batch(img_hw: Tuple[int, int], batch: int, n_classes: int,
                 n_anchors: int, stride: int, seed) -> Dict:
    """`batch` images [B, H, W, 3] in [0, 1] with their boxes, classes and
    YOLO targets, all drawn from `np.random.default_rng(seed)`."""
    H, W = img_hw
    rng = np.random.default_rng(seed)
    images = rng.random((batch, H, W, 3)).astype(np.float32) * 0.15
    all_boxes, all_classes = [], []
    for b in range(batch):
        n = rng.integers(1, 7)
        boxes, classes = [], []
        for _ in range(n):
            w = rng.uniform(0.1, 0.5)
            h = rng.uniform(0.1, 0.5)
            cx = rng.uniform(w / 2, 1 - w / 2)
            cy = rng.uniform(h / 2, 1 - h / 2)
            cls = int(rng.integers(0, n_classes))
            _draw_object(images[b], cls, (cx, cy, w, h), rng)
            boxes.append([cx, cy, w, h])
            classes.append(cls)
        all_boxes.append(np.asarray(boxes, np.float32))
        all_classes.append(np.asarray(classes, np.int64))
    targets = yolo_targets(all_boxes, all_classes, (H // stride, W // stride),
                           n_anchors)
    return {"images": images, "boxes": all_boxes, "classes": all_classes,
            "targets": targets}


def _iou_wh(wh1, wh2) -> float:
    inter = min(wh1[0], wh2[0]) * min(wh1[1], wh2[1])
    return inter / (wh1[0] * wh1[1] + wh2[0] * wh2[1] - inter + 1e-9)


def yolo_targets(boxes: List[np.ndarray], classes: List[np.ndarray],
                 grid_hw: Tuple[int, int], n_anchors: int
                 ) -> Dict[str, np.ndarray]:
    """YOLOv2 targets: for each box, the best-IoU anchor of its grid cell is
    responsible."""
    B = len(boxes)
    gh, gw = grid_hw
    obj = np.zeros((B, gh, gw, n_anchors), np.float32)
    txywh = np.zeros((B, gh, gw, n_anchors, 4), np.float32)
    tcls = np.zeros((B, gh, gw, n_anchors), np.int64)
    for b in range(B):
        for box, cls in zip(boxes[b], classes[b]):
            cx, cy, w, h = box
            gx = min(int(cx * gw), gw - 1)
            gy = min(int(cy * gh), gh - 1)
            a = int(np.argmax([_iou_wh((w, h), tuple(A))
                               for A in ANCHORS[:n_anchors]]))
            obj[b, gy, gx, a] = 1.0
            txywh[b, gy, gx, a] = [cx * gw - gx, cy * gh - gy, w, h]
            tcls[b, gy, gx, a] = cls
    return {"obj": obj, "txywh": txywh, "cls": tcls}
