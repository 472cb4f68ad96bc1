"""Object detection — SSD train + mAP evaluation on synthetic shapes
(examples/objectdetection parity)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.models.image import MeanAveragePrecision, ObjectDetector


def main():
    rng = np.random.default_rng(0)
    n, size = (16, 48) if SMOKE else (48, 48)
    images = np.zeros((n, size, size, 3), dtype="float32")
    gt_boxes, gt_labels = [], []
    for i in range(n):
        y0, x0 = rng.integers(4, size - 24, 2)
        images[i, y0:y0 + 20, x0:x0 + 20] = 1.0
        gt_boxes.append([[y0 / size, x0 / size, (y0 + 20) / size,
                          (x0 + 20) / size]])
        gt_labels.append([1])

    det = ObjectDetector(num_classes=2, image_size=size, score_threshold=0.12)
    det.compile(optimizer="adam")
    det.fit(images, gt_boxes, gt_labels, batch_size=8,
            nb_epoch=10 if SMOKE else 60)
    dets = det.predict(images[:8])
    mAP = MeanAveragePrecision(num_classes=2, iou_threshold=0.3)(
        dets, gt_boxes[:8], gt_labels[:8])
    print(f"detections on 8 images: {sum(len(d) for d in dets)}, mAP={mAP:.3f}")


if __name__ == "__main__":
    main()
