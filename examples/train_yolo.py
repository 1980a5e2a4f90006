"""Train + serve the YOLOv3 detector on synthetic data (BASELINE
config 4's workload shape: variable image sizes through the bucketing
policy, static-shape loss/decode/NMS).

Run: python examples/train_yolo.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.flags import apply_compile_cache
from paddle_tpu.models import YOLOv3
from paddle_tpu.static import TrainStep


def synth_batch(rng, n=4, size=128, nb=6):
    imgs = rng.randn(n, 3, size, size).astype(np.float32) * 0.1
    gt_box = np.zeros((n, nb, 4), np.float32)
    gt_label = np.zeros((n, nb), np.int32)
    for i in range(n):
        k = rng.randint(1, nb + 1)
        for j in range(k):
            w, h = rng.uniform(0.1, 0.5, 2)
            cx = rng.uniform(w / 2, 1 - w / 2)
            cy = rng.uniform(h / 2, 1 - h / 2)
            gt_box[i, j] = [cx, cy, w, h]
            gt_label[i, j] = rng.randint(0, 8)
    return (paddle.to_tensor(imgs), paddle.to_tensor(gt_box),
            paddle.to_tensor(gt_label))


def main():
    apply_compile_cache()
    paddle.seed(0)
    model = YOLOv3(num_classes=8, width=8)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    step = TrainStep(model, lambda o, b, l: model.loss(o, b, l), opt,
                     amp_level="O1", amp_dtype="bfloat16")
    rng = np.random.RandomState(0)

    # two size buckets — one compile each, reused across epochs
    for it in range(30):
        size = (96, 128)[it % 2]
        x, box, lbl = synth_batch(rng, size=size)
        loss = step(x, (box, lbl))
        if it % 5 == 0:
            print(f"iter {it:3d} size {size:3d} "
                  f"loss {float(loss.item()):.2f}")
    print(f"compiles: {step._step_fn._cache_size()} "
          "(== 2 buckets, no recompile storm)")

    # serve: the layer is live right after the last step
    model.eval()
    x, _, _ = synth_batch(rng, n=2, size=128)
    im = paddle.to_tensor(np.array([[128, 128]] * 2, np.int32))
    dets, counts = model.predict(model(x), im, conf_thresh=0.3,
                                 keep_top_k=20)
    print("detections per image:", np.asarray(counts._data).tolist())


if __name__ == "__main__":
    main()
