#!/usr/bin/env python
"""Quantization workflows end-to-end: QAT, PTQ, weight-only, serving.

Runs on the CPU (forced below); drop the force and the same code runs
on the chip.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import inference  # noqa: E402
from paddle_tpu.quant import (ImperativeQuantAware,  # noqa: E402
                              PostTrainingQuantization,
                              weight_only_quantize)
from paddle_tpu.vision.models import LeNet  # noqa: E402

rng = np.random.RandomState(0)
X = rng.randn(64, 1, 28, 28).astype(np.float32)
Y = rng.randint(0, 10, (64,)).astype(np.int64)


def train(model, steps=20):
    opt = paddle.optimizer.SGD(learning_rate=0.005,
                               parameters=model.parameters())
    for i in range(steps):
        sl = slice((i * 16) % 64, (i * 16) % 64 + 16)
        loss = paddle.nn.functional.cross_entropy(
            model(paddle.to_tensor(X[sl])), paddle.to_tensor(Y[sl]))
        loss.backward()
        opt.step()
        opt.clear_grad()
    return float(loss._data)


# 1) QAT: wrap, train with fake quant, export int8 through the Predictor
paddle.seed(0)
qat_model = LeNet(num_classes=10)
iqa = ImperativeQuantAware()
iqa.quantize(qat_model)
print("QAT final loss:", round(train(qat_model), 4))
qat_model.eval()
with tempfile.TemporaryDirectory() as td:
    prefix = os.path.join(td, "lenet_int8")
    iqa.save_quantized_model(
        qat_model, prefix,
        input_spec=[paddle.static.InputSpec([1, 1, 28, 28], "float32")])
    cfg = inference.Config(prefix)
    cfg.disable_gpu()
    pred = inference.create_predictor(cfg)
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.copy_from_cpu(X[:1])
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    print("served int8 logits:", np.round(out[0, :4], 3))

# 2) PTQ: train fp32, calibrate over batches, convert
paddle.seed(1)
fp32 = LeNet(num_classes=10)
train(fp32)
fp32.eval()
ptq = PostTrainingQuantization(
    fp32, (paddle.to_tensor(X[i * 16:(i + 1) * 16]) for i in range(4)),
    batch_nums=4)
qmodel = ptq.quantize()
print("PTQ model int8 sublayers:",
      sum(hasattr(s, "weight_int8") for s in qmodel.sublayers()))

# 3) weight-only: one call, no data
paddle.seed(2)
wo = LeNet(num_classes=10)
train(wo)
weight_only_quantize(wo)
print("weight-only int8 sublayers:",
      sum(hasattr(s, "weight_int8") for s in wo.sublayers()))

# 4) TRUE int8 execution: same PTQ flow but the frozen layers run
# int8 x int8 -> int32 on the MXU (double-rate path) with one float
# rescale — not a float simulation
from paddle_tpu.quant import QuantConfig  # noqa: E402

paddle.seed(3)
fp32b = LeNet(num_classes=10)
train(fp32b)
fp32b.eval()
# quantize() converts the model IN PLACE — take the fp32 reference first
ref = np.asarray(fp32b(paddle.to_tensor(X[:32]))._data).argmax(-1)
q8 = PostTrainingQuantization(
    fp32b, (paddle.to_tensor(X[i * 16:(i + 1) * 16]) for i in range(4)),
    batch_nums=4, config=QuantConfig(int8_compute=True)).quantize()
got = np.asarray(q8(paddle.to_tensor(X[:32]))._data).argmax(-1)
print(f"int8-EXECUTING model argmax agreement vs fp32: "
      f"{(ref == got).mean():.2f}")
