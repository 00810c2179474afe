"""Train → export → serve: every deployment surface over ONE artifact.

Run (CPU): JAX_PLATFORMS=cpu python examples/deploy_inference.py
Run (TPU): python examples/deploy_inference.py

Mirrors the reference deployment story (train dygraph → jit.save /
save_inference_model → Predictor or static Executor):

  1. train a small model eagerly;
  2. export it THREE reference ways — ``paddle.jit.save`` (dygraph
     path), ``paddle.static.save_inference_model`` (static Program
     path), and a weight-only-int8 variant of the serving matmul;
  3. serve the artifact through ``paddle.jit.load``, the
     ``paddle.inference`` Predictor (with and without the ir_optim
     pass), and the classic ``load_inference_model`` + ``Executor.run``
     loop — all agreeing numerically.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import inference as paddle_infer
from paddle_tpu.static import InputSpec


def main():
    rng = np.random.default_rng(0)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 64), nn.GELU(), nn.Linear(64, 8))
    opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
    xs = rng.standard_normal((64, 16)).astype(np.float32)
    ys = rng.standard_normal((64, 8)).astype(np.float32)
    for step in range(30):
        loss = F.mse_loss(net(paddle.to_tensor(xs)), paddle.to_tensor(ys))
        loss.backward()
        opt.step()
        opt.clear_grad()
    print(f"trained: loss={float(loss):.4f}")

    workdir = tempfile.mkdtemp()
    x = rng.standard_normal((5, 16)).astype(np.float32)
    ref = net(paddle.to_tensor(x)).numpy()

    # -- export 1: dygraph jit.save ---------------------------------------
    dy_prefix = os.path.join(workdir, "dygraph_model")
    paddle.jit.save(net, dy_prefix,
                    input_spec=[InputSpec([None, 16], "float32", name="x")])
    loaded = paddle.jit.load(dy_prefix)
    np.testing.assert_allclose(loaded(x).numpy(), ref, rtol=1e-5, atol=1e-6)
    print("jit.save -> jit.load OK")

    # -- export 2: static Program -> save_inference_model ------------------
    st_prefix = os.path.join(workdir, "static_model")
    main_prog = paddle.static.Program()
    startup = paddle.static.Program()
    with paddle.static.program_guard(main_prog, startup):
        xv = paddle.static.data("x", [None, 16], "float32")
        out = net(xv)
    paddle.static.save_inference_model(st_prefix, [xv], [out],
                                       program=main_prog)
    exe = paddle.static.Executor()
    prog, feed_names, fetches = paddle.static.load_inference_model(
        st_prefix, exe)
    (got,) = exe.run(prog, feed={"x": x}, fetch_list=fetches)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    print(f"save_inference_model -> Executor.run OK (feeds={feed_names})")

    # -- serve: the Predictor facade, ir_optim on vs off -------------------
    def serve(prefix, ir_optim):
        config = paddle_infer.Config(prefix)
        config.switch_ir_optim(ir_optim)
        pred = paddle_infer.create_predictor(config)
        pred.run([x])                                # warm / compile
        t0 = time.perf_counter()
        for _ in range(50):
            out = pred.run([x])[0]
        return out, (time.perf_counter() - t0) / 50

    out_opt, t_opt = serve(dy_prefix, True)
    out_raw, t_raw = serve(dy_prefix, False)
    np.testing.assert_allclose(out_opt, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out_raw, ref, rtol=1e-5, atol=1e-6)
    print(f"Predictor OK: ir_optim on {t_opt*1e6:.0f}us/req, "
          f"off {t_raw*1e6:.0f}us/req ({t_raw/t_opt:.1f}x)")

    # -- weight-only int8 serving matmul ----------------------------------
    from paddle_tpu.nn import quant
    w1 = net[2].weight
    qw, scale = quant.weight_quantize(w1)
    hidden = F.gelu(net[0](paddle.to_tensor(x)))
    q_out = quant.weight_only_linear(hidden, qw, bias=net[2].bias,
                                     weight_scale=scale)
    err = np.abs(q_out.numpy() - ref).max() / (np.abs(ref).max() + 1e-9)
    print(f"weight-only int8 serving OK: rel err {err:.4f}")
    assert err < 0.05
    print("ALL DEPLOY PATHS OK")


if __name__ == "__main__":
    main()
