"""API-coverage manifest additions: numerics of the gap-closing batch
(tools/api_coverage.py MANIFEST must fully resolve, and the nontrivial
new ops must be right, not just present)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


class TestManifestResolves:
    def test_full_manifest(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "api_coverage", "tools/api_coverage.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = []
        for m, names in mod.MANIFEST.items():
            obj = paddle
            for part in (m.split(".") if m else []):
                obj = getattr(obj, part, None)
            for n in names:
                if obj is None or getattr(obj, n, None) is None:
                    missing.append(f"{m}.{n}")
        assert not missing, missing


class TestMaxPoolMaskUnpool:
    def test_roundtrip_matches_torch(self):
        import torch
        import torch.nn.functional as TF
        x = np.random.default_rng(0).standard_normal(
            (2, 3, 8, 8)).astype(np.float32)
        v, idx = F.max_pool2d(paddle.to_tensor(x), 2, 2, return_mask=True)
        tv, tidx = TF.max_pool2d(torch.tensor(x), 2, 2, return_indices=True)
        np.testing.assert_allclose(v.numpy(), tv.numpy(), rtol=1e-6)
        np.testing.assert_array_equal(idx.numpy(), tidx.numpy())
        up = F.max_unpool2d(v, idx, 2, 2)
        tup = TF.max_unpool2d(tv, tidx, 2, 2)
        np.testing.assert_allclose(up.numpy(), tup.numpy(), rtol=1e-6)

    def test_1d_3d_with_stride_padding(self):
        import torch
        import torch.nn.functional as TF
        x1 = np.random.default_rng(1).standard_normal(
            (2, 2, 11)).astype(np.float32)
        v, idx = F.max_pool1d(paddle.to_tensor(x1), 3, 2, 1,
                              return_mask=True)
        tv, tidx = TF.max_pool1d(torch.tensor(x1), 3, 2, 1,
                                 return_indices=True)
        np.testing.assert_allclose(v.numpy(), tv.numpy(), rtol=1e-6)
        np.testing.assert_array_equal(idx.numpy(), tidx.numpy())
        x3 = np.random.default_rng(2).standard_normal(
            (1, 2, 6, 6, 6)).astype(np.float32)
        v3, idx3 = F.max_pool3d(paddle.to_tensor(x3), 2, 2,
                                return_mask=True)
        tv3, tidx3 = TF.max_pool3d(torch.tensor(x3), 2, 2,
                                   return_indices=True)
        np.testing.assert_allclose(v3.numpy(), tv3.numpy(), rtol=1e-6)
        np.testing.assert_array_equal(idx3.numpy(), tidx3.numpy())


class TestNewLosses:
    def test_huber_and_multi_margin_match_torch(self):
        import torch
        import torch.nn.functional as TF
        a = np.random.default_rng(1).standard_normal((4, 5)).astype(np.float32)
        b = np.random.default_rng(2).standard_normal((4, 5)).astype(np.float32)
        np.testing.assert_allclose(
            F.huber_loss(paddle.to_tensor(a), paddle.to_tensor(b),
                         delta=0.7).numpy(),
            TF.huber_loss(torch.tensor(a), torch.tensor(b),
                          delta=0.7).numpy(), rtol=1e-5)
        lab = np.array([0, 2, 1, 4], np.int64)
        np.testing.assert_allclose(
            F.multi_margin_loss(paddle.to_tensor(a),
                                paddle.to_tensor(lab)).numpy(),
            TF.multi_margin_loss(torch.tensor(a),
                                 torch.tensor(lab)).numpy(), rtol=1e-5)

    def test_rnnt_matches_reference_dp(self):
        import scipy.special as sp

        def ref(lp, lab, T, U):
            alpha = np.full((T, U + 1), -np.inf)
            alpha[0, 0] = 0
            for t in range(T):
                for u in range(U + 1):
                    if t == 0 and u == 0:
                        continue
                    c = []
                    if t > 0:
                        c.append(alpha[t - 1, u] + lp[t - 1, u, 0])
                    if u > 0:
                        c.append(alpha[t, u - 1] + lp[t, u - 1, lab[u - 1]])
                    alpha[t, u] = sp.logsumexp(c)
            return -(alpha[T - 1, U] + lp[T - 1, U, 0])

        rng = np.random.default_rng(0)
        B, T, U, V = 2, 4, 3, 5
        logits = rng.standard_normal((B, T, U + 1, V)).astype(np.float32)
        labels = rng.integers(1, V, (B, U)).astype(np.int32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        out = F.rnnt_loss(
            paddle.to_tensor(logits), paddle.to_tensor(labels),
            paddle.to_tensor(np.array([3, 4], np.int32)),
            paddle.to_tensor(np.array([2, 3], np.int32)),
            reduction="none").numpy()
        refs = [ref(np.asarray(lp[0]), labels[0], 3, 2),
                ref(np.asarray(lp[1]), labels[1], 4, 3)]
        np.testing.assert_allclose(out, refs, rtol=1e-4)


class TestNewOptimizers:
    @pytest.mark.parametrize("cls", ["NAdam", "RAdam", "ASGD", "Rprop"])
    def test_trains(self, cls):
        import paddle_tpu.nn.functional as F2
        paddle.seed(0)
        net = nn.Linear(4, 4)
        opt = getattr(paddle.optimizer, cls)(
            learning_rate=1e-2, parameters=net.parameters())
        x = paddle.to_tensor(np.random.default_rng(0)
                             .standard_normal((8, 4)).astype(np.float32))
        losses = []
        for _ in range(10):
            loss = F2.mse_loss(net(x), x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0], (cls, losses)

    def test_nadam_radam_match_torch(self):
        import torch
        for cls, tcls in (("NAdam", torch.optim.NAdam),
                          ("RAdam", torch.optim.RAdam)):
            w0 = np.random.default_rng(3).standard_normal(6).astype(np.float32)
            g = np.random.default_rng(4).standard_normal(6).astype(np.float32)
            p = paddle.Parameter(w0.copy())
            p.stop_gradient = False
            opt = getattr(paddle.optimizer, cls)(
                learning_rate=0.1, parameters=[p])
            tp = torch.tensor(w0.copy(), requires_grad=True)
            topt = tcls([tp], lr=0.1)
            for _ in range(5):
                p.grad = paddle.to_tensor(g)
                opt.step()
                tp.grad = torch.tensor(g)
                topt.step()
            np.testing.assert_allclose(p.numpy(), tp.detach().numpy(),
                                       rtol=2e-4, atol=2e-5, err_msg=cls)


class TestVisionOps:
    def test_nms(self):
        from paddle_tpu.vision import ops as vops
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11], [20, 20, 30, 30]],
                         np.float32)
        kept = vops.nms(paddle.to_tensor(boxes), 0.5,
                        paddle.to_tensor(np.array([0.9, 0.8, 0.7],
                                                  np.float32))).numpy()
        np.testing.assert_array_equal(kept, [0, 2])
        kept2 = vops.nms(paddle.to_tensor(boxes), 0.5,
                         paddle.to_tensor(np.array([0.7, 0.9, 0.8],
                                                   np.float32))).numpy()
        np.testing.assert_array_equal(kept2, [1, 2])

    def test_roi_align_whole_image(self):
        from paddle_tpu.vision import ops as vops
        x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
        out = vops.roi_align(
            paddle.to_tensor(x),
            paddle.to_tensor(np.array([[0, 0, 4, 4]], np.float32)),
            paddle.to_tensor(np.array([1], np.int32)), 2, aligned=False)
        assert out.shape == [1, 1, 2, 2]
        # mean of each quadrant's sampled grid is monotone across quadrants
        o = out.numpy()[0, 0]
        assert o[0, 0] < o[0, 1] < o[1, 0] < o[1, 1]


class TestMVNAndTransforms:
    def test_mvn_matches_scipy(self):
        from scipy.stats import multivariate_normal
        import paddle_tpu.distribution as D
        loc = np.array([1.0, -0.5], np.float32)
        cov = np.array([[2.0, 0.3], [0.3, 1.0]], np.float32)
        mvn = D.MultivariateNormal(loc, covariance_matrix=cov)
        x = np.array([0.5, 0.2], np.float32)
        ref = multivariate_normal(loc, cov)
        np.testing.assert_allclose(float(mvn.log_prob(paddle.to_tensor(x))),
                                   ref.logpdf(x), rtol=1e-4)
        np.testing.assert_allclose(float(mvn.entropy()), ref.entropy(),
                                   rtol=1e-5)

    def test_reshape_stack_independent_transforms(self):
        import paddle_tpu.distribution as D
        rt = D.ReshapeTransform((4,), (2, 2))
        x = paddle.to_tensor(np.arange(4.0, dtype=np.float32))
        y = rt.forward(x)
        assert y.shape == [2, 2]
        np.testing.assert_allclose(rt.inverse(y).numpy(), x.numpy())
        it = D.IndependentTransform(D.ExpTransform(), 1)
        z = paddle.to_tensor(np.zeros((3, 4), np.float32))
        assert it.forward_log_det_jacobian(z).shape == [3]


class TestGradientFlowThroughNewSurface:
    """Review-confirmed gradient breaks, pinned fixed."""

    def test_max_pool_mask_backward_reaches_input(self):
        x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
            (1, 2, 4, 4)).astype(np.float32))
        x.stop_gradient = False
        v, idx = F.max_pool2d(x, 2, 2, return_mask=True)
        v.sum().backward()
        assert x.grad is not None
        # each window contributes exactly one 1 at its argmax
        np.testing.assert_allclose(x.grad.numpy().sum(), 8.0)

    def test_max_pool_mask_nhwc(self):
        x = np.random.default_rng(1).standard_normal(
            (1, 4, 4, 3)).astype(np.float32)
        v, idx = F.max_pool2d(paddle.to_tensor(x), 2, 2, return_mask=True,
                              data_format="NHWC")
        ref, ridx = F.max_pool2d(
            paddle.to_tensor(x.transpose(0, 3, 1, 2)), 2, 2,
            return_mask=True)
        np.testing.assert_allclose(v.numpy().transpose(0, 3, 1, 2),
                                   ref.numpy(), rtol=1e-6)
        np.testing.assert_array_equal(idx.numpy().transpose(0, 3, 1, 2),
                                      ridx.numpy())

    def test_weight_norm_trains_v_and_g(self):
        import paddle_tpu.nn.functional as F2
        paddle.seed(0)
        lin = nn.utils.weight_norm(nn.Linear(3, 3))
        x = paddle.to_tensor(np.random.default_rng(0)
                             .standard_normal((4, 3)).astype(np.float32))
        loss = F2.mse_loss(lin(x), x)
        loss.backward()
        assert lin.weight_v.grad is not None
        assert lin.weight_g.grad is not None
        opt = paddle.optimizer.SGD(0.1, parameters=lin.parameters())
        losses = []
        for _ in range(10):
            loss = F2.mse_loss(lin(x), x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_spectral_norm_util_trains_orig(self):
        import paddle_tpu.nn.functional as F2
        paddle.seed(1)
        lin = nn.utils.spectral_norm(nn.Linear(3, 3))
        before = lin.weight_orig.numpy().copy()
        x = paddle.to_tensor(np.random.default_rng(0)
                             .standard_normal((4, 3)).astype(np.float32))
        opt = paddle.optimizer.SGD(0.1, parameters=lin.parameters())
        for _ in range(3):
            loss = F2.mse_loss(lin(x), x)
            loss.backward()
            opt.step()
            opt.clear_grad()
        assert not np.allclose(lin.weight_orig.numpy(), before)
        # normalized weight really has unit top singular value
        w = lin.weight.numpy()
        assert abs(np.linalg.svd(w, compute_uv=False)[0] - 1.0) < 0.05

    def test_spectral_norm_layer_grad_flows(self):
        paddle.seed(2)
        sn = nn.SpectralNorm((4, 3))
        w = paddle.to_tensor(np.random.default_rng(3)
                             .standard_normal((4, 3)).astype(np.float32))
        w.stop_gradient = False
        out = sn(w)
        out.sum().backward()
        assert w.grad is not None


class TestPlaceNamesARealDevice:
    """A Place resolves to the device it names or raises — a tensor asked
    onto the TPU never lands silently on whatever backend there is."""

    def test_cpu_place_resolves_by_id(self):
        t = paddle.to_tensor(np.ones(3, np.float32), place=paddle.CPUPlace(2))
        assert [d.id for d in t._value.devices()] == [2]
        assert t.place == paddle.CPUPlace(0) and t.place.is_cpu_place()

    @pytest.mark.parametrize("place", [paddle.TPUPlace(0),
                                       paddle.CUDAPlace(0)])
    def test_absent_platform_raises(self, place):
        from paddle_tpu.core.enforce import UnavailableError
        with pytest.raises(UnavailableError, match="no 'tpu' backend"):
            paddle.to_tensor(np.ones(3, np.float32), place=place)

    def test_id_past_the_device_count_raises(self):
        from paddle_tpu.core.enforce import OutOfRangeError
        with pytest.raises(OutOfRangeError, match="only 8 cpu"):
            paddle.CPUPlace(8).jax_device()

    def test_traced_tensor_answers_the_current_place(self):
        seen = []

        @jax.jit
        def f(v):
            seen.append(paddle.Tensor(v).place)
            return v
        f(jnp.ones(2))
        assert seen == [paddle.CPUPlace(0)]


def test_tensor_method_aliases():
    t = paddle.to_tensor(np.ones((2, 3), np.float32))
    assert t.dim() == t.ndimension() == t.rank() == 2
    assert t.cuda() is t and t.pin_memory() is t   # device no-ops on TPU
    t.normal_(0.0, 1.0)
    assert float(np.asarray(t.numpy()).std()) > 0
    u = paddle.to_tensor(np.zeros((100,), np.float32))
    u.uniform_(0.0, 1.0)
    un = np.asarray(u.numpy())
    assert un.min() >= 0 and un.max() <= 1 and un.std() > 0
