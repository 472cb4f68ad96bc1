"""ONNX ingestion — import a graph, run it, fine-tune it
(pyzoo/zoo/pipeline/api/onnx loader parity; no onnx package needed)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.importers import Net
from analytics_zoo_tpu.importers.onnx_proto import (Attribute, Graph, Node,
                                                    ValueInfo, encode_model)
from analytics_zoo_tpu.nn.optimizers import Adam


def main():
    rng = np.random.default_rng(0)
    g = Graph(name="mlp")
    g.initializers = {
        "w1": (rng.standard_normal((8, 16)) * 0.3).astype("float32"),
        "b1": np.zeros(16, "float32"),
        "w2": (rng.standard_normal((16, 3)) * 0.3).astype("float32"),
        "b2": np.zeros(3, "float32"),
    }
    g.inputs = [ValueInfo("x", (None, 8))]
    g.outputs = [ValueInfo("probs", (None, 3))]
    g.nodes = [
        Node("Gemm", ["x", "w1", "b1"], ["h"]),
        Node("Relu", ["h"], ["hr"]),
        Node("Gemm", ["hr", "w2", "b2"], ["logits"]),
        Node("Softmax", ["logits"], ["probs"],
             attrs={"axis": Attribute(name="axis", i=1)}),
    ]
    import tempfile, os

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.onnx")
        with open(path, "wb") as f:
            f.write(encode_model(g))

        model = Net.load(path)  # auto-detected as ONNX
        model.compile(optimizer=Adam(lr=0.05),
                      loss="sparse_categorical_crossentropy",
                      metrics=["accuracy"])
        x = rng.standard_normal((512, 8)).astype("float32")
        y = (x[:, :3].argmax(axis=1)).astype("int32")
        print("before:", model.evaluate(x, y))
        model.fit(x, y, batch_size=64, nb_epoch=3 if SMOKE else 15)
        print("after fine-tune:", model.evaluate(x, y))


if __name__ == "__main__":
    main()
