"""Streaming object detection — reference
``zoo/.../examples/streaming/objectdetection`` (Spark-Streaming SSD over image
batches): frames flow through the Cluster-Serving stream (broker → pipelined
engine → result hash) with an SSD detector as the served model; detections
stream back per frame."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.models.image.objectdetection import (ObjectDetector,
                                                            decode_predictions,
                                                            nms)
from analytics_zoo_tpu.serving import (ClusterServing, InputQueue, OutputQueue,
                                       ServingConfig, start_broker)


def frame_stream(n, size, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        img = np.full((size, size, 3), 0.1, dtype="float32")
        s = size // 3
        y0 = (i * 7) % (size - s)
        x0 = (i * 11) % (size - s)
        img[y0:y0 + s, x0:x0 + s] = [1.0, 0.2, 0.2]
        yield img


def main():
    size = 48
    n_frames = 6 if SMOKE else 60

    # a briefly-trained detector stands in for a loaded zoo checkpoint
    det = ObjectDetector(num_classes=2, image_size=size, score_threshold=0.05)
    det.compile()
    frames = list(frame_stream(16, size))
    boxes = [[[0.0, 0.0, 0.5, 0.5]]] * 16   # coarse supervision for the demo
    det.fit(frames, boxes, [[1]] * 16, batch_size=8,
            nb_epoch=2 if SMOKE else 30)

    broker = start_broker()
    cfg = ServingConfig(batch_size=4, queue_port=broker.port)
    # serve the RAW head output; decode/NMS happens client-side per frame
    im = InferenceModel().load(det.model)
    job = ClusterServing(im, cfg, group="stream-od").start()
    try:
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        uris = [iq.enqueue(None, image=f) for f in frame_stream(n_frames, size)]
        for t, uri in enumerate(uris):
            raw = oq.query(uri, timeout_s=60)
            bxs, probs = decode_predictions(np.asarray(raw), det.model.anchors)
            scores = probs[:, 1]
            mask = scores > det.score_threshold
            keep = nms(bxs[mask], scores[mask])
            print(f"frame {t}: {len(keep)} detections")
    finally:
        job.stop()
        broker.shutdown()


if __name__ == "__main__":
    main()
