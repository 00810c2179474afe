"""Quantization workflows: QAT (train through fake quant), PTQ
(calibrate + convert), and direct weight-only conversion for serving.

Run: JAX_PLATFORMS=cpu python examples/quantize.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.quantization import (PTQ, QAT, FakeQuanterWithAbsMaxObserver,
                                     QuantConfig)


def main():
    paddle.seed(0)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(32, 16).astype(np.float32))

    # --- QAT: straight-through fake quant, weights stay trainable
    model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
    QAT(QuantConfig(activation=FakeQuanterWithAbsMaxObserver)).quantize(model)
    opt = paddle.optimizer.AdamW(5e-3, parameters=model.parameters())
    for i in range(30):
        loss = F.mse_loss(model(x), x)
        loss.backward(); opt.step(); opt.clear_grad()
    print(f"QAT: trained THROUGH int8 fake quant, final loss {float(loss):.4f}")

    # --- PTQ: observe calibration batches, convert to the int8 runtime
    model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
    ref = model(x).numpy()
    ptq = PTQ(QuantConfig())
    ptq.quantize(model)
    for _ in range(4):
        model(paddle.to_tensor(rng.randn(32, 16).astype(np.float32)))
    ptq.convert(model)
    err = np.abs(model(x).numpy() - ref).max() / (np.abs(ref).max() + 1e-9)
    print(f"PTQ: converted to int8 QuantizedLinear, rel err {err:.4f}")

    # --- serving shortcut: direct weight-only conversion (no calibration)
    from paddle_tpu.nn.quant import quantize_linears
    model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
    ref = model(x).numpy()
    quantize_linears(model, algo="weight_only_int8")
    err = np.abs(model(x).numpy() - ref).max() / (np.abs(ref).max() + 1e-9)
    print(f"weight-only int8: rel err {err:.4f} at half the weight bytes")


if __name__ == "__main__":
    main()
