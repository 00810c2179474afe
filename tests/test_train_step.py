"""M1 end-to-end slice tests: models + jitted TrainStep + metrics
(reference analogue: dygraph-vs-to_static equivalence tests in
test/dygraph_to_static/)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.hapi import TrainStep
from paddle_tpu.models import GPTConfig, GPTForCausalLM, LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils.metrics import (SpeedMeter, peak_flops,
                                      train_flops_per_token)


def make_batch(cfg, b=4, s=32):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (b, s + 1))
    return (paddle.to_tensor(ids[:, :-1].astype(np.int32)),
            paddle.to_tensor(ids[:, 1:].astype(np.int32)))


class TestModels:
    def test_gpt_tiny_forward(self):
        cfg = GPTConfig.tiny()
        m = GPTForCausalLM(cfg)
        x, y = make_batch(cfg)
        logits = m(x)
        assert logits.shape == [4, 32, cfg.vocab_size]
        loss = m(x, labels=y)
        assert loss.size == 1 and np.isfinite(float(loss))

    def test_llama_tiny_forward(self):
        cfg = LlamaConfig.tiny()
        m = LlamaForCausalLM(cfg)
        x, y = make_batch(cfg)
        loss = m(x, labels=y)
        assert np.isfinite(float(loss))
        # GQA: kv heads < q heads exercised
        assert cfg.num_key_value_heads < cfg.num_attention_heads

    def test_param_count_formula(self):
        cfg = GPTConfig.tiny()
        m = GPTForCausalLM(cfg)
        actual = sum(p.size for p in m.parameters())
        est = cfg.num_params()
        assert abs(actual - est) / actual < 0.05

    def test_eager_jit_equivalence(self):
        """Same model, eager loss == jitted loss (the to_static invariant)."""
        cfg = GPTConfig.tiny()
        paddle.seed(3)
        m = GPTForCausalLM(cfg)
        m.eval()
        x, y = make_batch(cfg)
        eager = float(m(x, labels=y))

        from paddle_tpu.jit import functional_call
        import jax
        params, buffers = m.raw_state()
        jitted = jax.jit(lambda p, a, b: functional_call(
            m, p, paddle.Tensor(a), buffers=buffers, labels=paddle.Tensor(b)))
        jl = float(jitted(params, x.value, y.value))
        assert abs(eager - jl) < 1e-4, (eager, jl)


class TestTrainStep:
    def test_loss_decreases(self):
        cfg = GPTConfig.tiny()
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(3e-3, parameters=m.parameters())
        step = TrainStep(m, opt)
        x, y = make_batch(cfg)
        losses = [float(step(x, y)) for _ in range(15)]
        assert losses[-1] < losses[0] * 0.8

    def test_matches_eager_training(self):
        """One jitted step == one eager step (same grads, same update)."""
        cfg = GPTConfig.tiny()
        x, y = make_batch(cfg, b=2, s=16)

        paddle.seed(11)
        m1 = GPTForCausalLM(cfg)
        o1 = paddle.optimizer.SGD(0.1, parameters=m1.parameters())
        loss_e = m1(x, labels=y)
        loss_e.backward()
        o1.step()

        paddle.seed(11)
        m2 = GPTForCausalLM(cfg)
        o2 = paddle.optimizer.SGD(0.1, parameters=m2.parameters())
        step = TrainStep(m2, o2)
        loss_j = step(x, y)
        step.sync_to_model()

        assert abs(float(loss_e) - float(loss_j)) < 1e-5
        sd1, sd2 = m1.state_dict(), m2.state_dict()
        for k in sd1:
            np.testing.assert_allclose(sd1[k].numpy(), sd2[k].numpy(),
                                       rtol=2e-4, atol=1e-5, err_msg=k)

    def test_grad_accum_equivalence(self):
        """grad_accum=2 over batch 8 == single step over batch 8 (mean loss)."""
        cfg = GPTConfig.tiny()
        x, y = make_batch(cfg, b=8, s=16)

        paddle.seed(5)
        m1 = GPTForCausalLM(cfg)
        s1 = TrainStep(m1, paddle.optimizer.SGD(0.05, parameters=m1.parameters()))
        l1 = float(s1(x, y))
        s1.sync_to_model()

        paddle.seed(5)
        m2 = GPTForCausalLM(cfg)
        s2 = TrainStep(m2, paddle.optimizer.SGD(0.05, parameters=m2.parameters()),
                       grad_accum_steps=2)
        l2 = float(s2(x, y))
        s2.sync_to_model()

        assert abs(l1 - l2) < 1e-4
        for k, v in m1.state_dict().items():
            np.testing.assert_allclose(v.numpy(), m2.state_dict()[k].numpy(),
                                       rtol=2e-3, atol=1e-5, err_msg=k)

    def test_donation_guard(self):
        cfg = GPTConfig.tiny()
        m = GPTForCausalLM(cfg)
        s1 = TrainStep(m, paddle.optimizer.SGD(0.1, parameters=m.parameters()))
        x, y = make_batch(cfg)
        s1(x, y)
        with pytest.raises(RuntimeError, match="donated"):
            TrainStep(m, paddle.optimizer.SGD(0.1, parameters=m.parameters()))
        s1.sync_to_model()
        TrainStep(m, paddle.optimizer.SGD(0.1, parameters=m.parameters()))

    def test_remat(self):
        cfg = GPTConfig.tiny()
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        step = TrainStep(m, paddle.optimizer.SGD(0.1, parameters=m.parameters()),
                         remat=True)
        x, y = make_batch(cfg)
        l1 = float(step(x, y))
        assert np.isfinite(l1)


class TestStepClock:
    """A train step is the interval from one call to the next; the
    clock is injected, so a compile's real seconds are no part of it."""

    def _run(self, monkeypatch, slow_call, calls=8, sync_at=None):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import tracing

        now = [50.0]
        monkeypatch.setattr(tracing, "_now", lambda: now[0])
        cfg = GPTConfig.tiny()
        paddle.seed(5)
        m = GPTForCausalLM(cfg)
        step = TrainStep(m, paddle.optimizer.SGD(
            0.1, parameters=m.parameters()))
        jitted, seen = step._jit_step, []

        def dispatch(*args):
            seen.append(1)
            now[0] += 0.5 if len(seen) == slow_call else 0.004
            return jitted(*args)

        step._jit_step = dispatch
        x, y = make_batch(cfg, b=2, s=16)

        def counter(name):
            fam = obs.registry().snapshot()["metrics"][name]
            return sum(s["value"] for s in fam["series"])

        names = ("train_steps", "train_dispatch_seconds",
                 "train_slow_steps", "train_slow_step_seconds")
        before = {n: counter(n) for n in names}
        records = len(obs.tracer().slow_steps())
        for i in range(calls):
            step(x, y)
            now[0] += 0.001             # the caller's own time
            if i + 1 == sync_at:
                step.sync()
                now[0] += 30.0          # an evaluation behind a barrier
        step.sync()
        delta = {n: counter(n) - before[n] for n in names}
        return step, delta, obs.tracer().slow_steps()[records:]

    def test_a_slow_dispatch_is_on_record_once(self, monkeypatch):
        step, delta, slow = self._run(monkeypatch, slow_call=6)
        assert delta["train_steps"] == 8
        assert delta["train_dispatch_seconds"] == pytest.approx(
            7 * 0.004 + 0.5)
        assert delta["train_slow_steps"] == 1
        assert delta["train_slow_step_seconds"] == pytest.approx(
            0.501 - 0.005)
        (rec,) = slow
        assert (rec["kind"], rec["step"]) == ("train", 5)
        assert rec["length_s"] == pytest.approx(0.501)
        assert rec["mean_s"] == pytest.approx(0.005)
        assert rec["phases"]["train.dispatch"] == pytest.approx(0.5)
        assert max(rec["phases"], key=rec["phases"].get) == "train.dispatch"
        # the caller's millisecond lies under no phase
        assert rec["outside_s"] == pytest.approx(0.001)
        assert rec["builds"] == 0

    def test_the_step_that_traces_says_so(self, monkeypatch):
        """The first interval holds the trace of the step function."""
        step, delta, slow = self._run(monkeypatch, slow_call=1)
        (rec,) = slow
        assert rec["step"] == 0 and rec["builds"] == step.trace_count == 1
        assert delta["train_slow_steps"] == 1

    def test_a_steady_run_records_nothing(self, monkeypatch):
        step, delta, slow = self._run(monkeypatch, slow_call=None)
        assert slow == [] and delta["train_slow_steps"] == 0
        assert delta["train_steps"] == 8
        assert delta["train_dispatch_seconds"] == pytest.approx(8 * 0.004)

    def test_a_barrier_ends_the_interval(self, monkeypatch):
        """What follows ``sync()`` is no part of a step."""
        step, delta, slow = self._run(monkeypatch, slow_call=None,
                                      sync_at=4)
        assert slow == [] and delta["train_steps"] == 8


class TestShardedTrainStep:
    def test_dp_sharded_step(self):
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        cfg = GPTConfig.tiny()
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), axis_names=("dp",))
        step = TrainStep(m, paddle.optimizer.AdamW(1e-3, parameters=m.parameters()),
                         mesh=mesh, data_axes=("dp",))
        x, y = make_batch(cfg, b=8)
        losses = [float(step(x, y)) for _ in range(5)]
        assert losses[-1] < losses[0]

    def test_dp_matches_single_device(self):
        """parallel == serial: the core invariant (SURVEY.md §4)."""
        import jax
        from jax.sharding import Mesh
        cfg = GPTConfig.tiny()
        x, y = make_batch(cfg, b=8, s=16)

        paddle.seed(9)
        m1 = GPTForCausalLM(cfg)
        s1 = TrainStep(m1, paddle.optimizer.SGD(0.1, parameters=m1.parameters()))
        l1 = float(s1(x, y))

        paddle.seed(9)
        m2 = GPTForCausalLM(cfg)
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), axis_names=("dp",))
        s2 = TrainStep(m2, paddle.optimizer.SGD(0.1, parameters=m2.parameters()),
                       mesh=mesh)
        l2 = float(s2(x, y))
        assert abs(l1 - l2) < 1e-5

    def test_tp_sharded_matches_replicated(self):
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        cfg = LlamaConfig.tiny()
        x, y = make_batch(cfg, b=4, s=16)

        paddle.seed(21)
        m1 = LlamaForCausalLM(cfg)
        s1 = TrainStep(m1, paddle.optimizer.SGD(0.1, parameters=m1.parameters()))
        l1 = float(s1(x, y))

        paddle.seed(21)
        m2 = LlamaForCausalLM(cfg)
        devs = np.array(jax.devices()[:8]).reshape(4, 2)
        mesh = Mesh(devs, axis_names=("dp", "mp"))

        def spec(name, v):
            if any(s in name for s in ("q_proj.weight", "k_proj.weight",
                                       "v_proj.weight", "gate_proj.weight",
                                       "up_proj.weight")):
                return P(None, "mp")
            if any(s in name for s in ("o_proj.weight", "down_proj.weight")):
                return P("mp", None)
            return P()

        s2 = TrainStep(m2, paddle.optimizer.SGD(0.1, parameters=m2.parameters()),
                       mesh=mesh, param_spec_fn=spec)
        l2 = float(s2(x, y))
        assert abs(l1 - l2) < 1e-4, (l1, l2)


class TestMetrics:
    def test_flops_formula(self):
        f = train_flops_per_token(1000)
        assert f == 6000.0
        f2 = train_flops_per_token(1000, n_layers=2, hidden=8, seq_len=10)
        assert f2 == 6000.0 + 12 * 2 * 8 * 10

    @staticmethod
    def _one_step(**kw):
        import time
        meter = SpeedMeter(n_params=1000, n_chips=2, warmup=0, **kw)
        meter.start()
        time.sleep(0.01)
        meter.step(100)
        return meter

    def test_speed_meter(self):
        s = self._one_step(peak_flops=197e12).summary()
        assert s["tokens_per_sec_per_chip"] > 0
        assert 0 <= s["mfu"] and s["peak_flops"] == 197e12

    def test_speed_meter_on_cpu_has_throughput_and_no_mfu(self):
        meter = self._one_step()
        s = meter.summary()
        assert s["tokens_per_sec_per_chip"] > 0
        assert "mfu" not in s and "peak_flops" not in s
        with pytest.raises(ValueError, match="no peak"):
            meter.mfu()

    def test_peak_table_resolves_the_v5e_kind(self):
        # the exact device_kind string jax reports for a v5e chip
        assert peak_flops("TPU v5 lite") == 197e12

    @pytest.mark.parametrize("kind", ["cpu", "v5e", "TPU v9", ""])
    def test_peak_table_unknown_kind_raises(self, kind):
        with pytest.raises(KeyError, match="no published peak"):
            peak_flops(kind)


class TestHapiModel:
    def test_fit_evaluate(self):
        import paddle_tpu.nn as nn

        x = np.random.randn(32, 4).astype(np.float32)
        y = (x.sum(-1, keepdims=True) > 0).astype(np.float32)
        ds = paddle.io.TensorDataset([x, y])
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
        model = paddle.hapi.Model(net)
        model.prepare(optimizer=paddle.optimizer.Adam(0.01, parameters=net.parameters()),
                      loss=nn.MSELoss())
        model.fit(ds, batch_size=8, epochs=2, verbose=0)
        res = model.evaluate(ds, batch_size=8, verbose=0)
        assert res["loss"] is not None and np.isfinite(res["loss"])


class TestFusedGradAccum:
    """fused_grad_accum puts the microbatch loop inside the differentiated
    scan (the fused_linear_param_grad_add equivalent) — must match the
    materialize-then-add path step for step, and both must match a
    full-batch step (linear loss => averaging microbatch grads is exact).
    """

    def _run(self, fused, accum, steps=3):
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        from paddle_tpu.hapi import TrainStep

        paddle.seed(3)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8))
        opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
        step = TrainStep(net, opt, grad_accum_steps=accum,
                         fused_grad_accum=fused,
                         loss_fn=lambda o, y: F.mse_loss(
                             paddle.Tensor(o), paddle.Tensor(y))._value)
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.standard_normal((8, 8)).astype(np.float32))
        losses = [float(step(x, x)) for _ in range(steps)]
        step.sync_to_model()
        return losses, {k: np.asarray(v._value)
                        for k, v in net.named_parameters()}

    def test_fused_matches_unfused_and_full_batch(self):
        lf, pf = self._run(True, 4)
        lu, pu = self._run(False, 4)
        l1, p1 = self._run(True, 1)
        np.testing.assert_allclose(lf, lu, rtol=1e-5)
        np.testing.assert_allclose(lf, l1, rtol=1e-5)
        for k in pf:
            np.testing.assert_allclose(pf[k], pu[k], rtol=1e-5, atol=1e-6,
                                       err_msg=k)
            np.testing.assert_allclose(pf[k], p1[k], rtol=1e-4, atol=1e-5,
                                       err_msg=k)


class TestGradientMerge:
    """VERDICT r4 item 7: strategy-driven gradient merge — accumulate
    grads across k calls, update on the k-th. Parity: k-step merge with
    avg == one update on the concatenated (big) batch."""

    def _mlp(self, seed=5):
        import paddle_tpu.nn as nn
        paddle.seed(seed)
        return nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 3))

    def _loss(self, out, y):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.core.tensor import Tensor
        return F.mse_loss(Tensor(out), Tensor(y))._value

    def test_merge_equals_big_batch(self):
        rng = np.random.default_rng(0)
        x1, x2 = (rng.standard_normal((4, 6)).astype(np.float32)
                  for _ in range(2))
        y1, y2 = (rng.standard_normal((4, 3)).astype(np.float32)
                  for _ in range(2))

        merged = self._mlp()
        big = self._mlp()
        sm = TrainStep(merged, paddle.optimizer.SGD(
            0.1, parameters=merged.parameters()), loss_fn=self._loss,
            gradient_merge_k=2)
        sb = TrainStep(big, paddle.optimizer.SGD(
            0.1, parameters=big.parameters()), loss_fn=self._loss)

        before = {k: np.asarray(v) for k, v in sm.params.items()}
        sm(paddle.to_tensor(x1), paddle.to_tensor(y1))
        # first call of the pair: NO update happened
        for k in before:
            np.testing.assert_array_equal(np.asarray(sm.params[k]),
                                          before[k], err_msg=k)
        sm(paddle.to_tensor(x2), paddle.to_tensor(y2))

        sb(paddle.to_tensor(np.concatenate([x1, x2])),
           paddle.to_tensor(np.concatenate([y1, y2])))
        for k in sm.params:
            np.testing.assert_allclose(
                np.asarray(sm.params[k]), np.asarray(sb.params[k]),
                rtol=1e-5, atol=1e-6, err_msg=k)

    def test_strategy_wiring(self):
        """DistributedStrategy.gradient_merge on a fleet optimizer flips
        the compiled step (the flag changes the program, not a comment)."""
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers\
            .hybrid_parallel_optimizer import HybridParallelOptimizer

        st = DistributedStrategy()
        st.gradient_merge = True
        st.gradient_merge_configs = {"k_steps": 3, "avg": True}
        net = self._mlp()
        opt = HybridParallelOptimizer(
            paddle.optimizer.SGD(0.1, parameters=net.parameters()),
            hcg=None, strategy=st)
        ts = TrainStep(net, opt, loss_fn=self._loss)
        assert ts.gradient_merge_k == 3
        assert ts._merge is not None


@pytest.mark.slow
class TestLocalSGD:
    """VERDICT r4 item 7: localsgd as a jit transform — per-dp-worker
    local updates (vmap over a stacked param axis, zero per-step comm),
    params averaged across dp every k steps."""

    def _setup(self, k):
        import jax
        from jax.sharding import Mesh
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers\
            .hybrid_parallel_optimizer import HybridParallelOptimizer

        paddle.seed(9)
        net = nn.Linear(4, 2)
        mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("dp",))
        st = DistributedStrategy()
        st.localsgd = True
        st.localsgd_configs = {"k_steps": k}
        opt = HybridParallelOptimizer(
            paddle.optimizer.SGD(0.1, parameters=net.parameters()),
            hcg=None, strategy=st)
        def loss_fn(out, y):
            import paddle_tpu.nn.functional as F
            from paddle_tpu.core.tensor import Tensor
            return F.mse_loss(Tensor(out), Tensor(y))._value

        ts = TrainStep(net, opt, loss_fn=loss_fn, mesh=mesh)
        return ts

    def test_diverge_then_sync(self):
        ts = self._setup(k=2)
        rng = np.random.default_rng(1)
        x = rng.standard_normal((8, 4)).astype(np.float32)
        y = rng.standard_normal((8, 2)).astype(np.float32)
        ts(paddle.to_tensor(x), paddle.to_tensor(y))
        # step 1 (not a sync step): workers hold DIFFERENT params
        w = {k: np.asarray(v) for k, v in ts.params.items()}
        some_diverged = any(
            not np.allclose(v[0], v[1]) for v in w.values())
        assert some_diverged, "local updates did not diverge across dp"
        ts(paddle.to_tensor(x), paddle.to_tensor(y))
        # step 2 (sync): all workers equal
        for k, v in ts.params.items():
            np.testing.assert_allclose(np.asarray(v)[0], np.asarray(v)[1],
                                       rtol=1e-6, err_msg=k)

    def test_sync_is_mean_of_local_sgd_traces(self):
        """Exact math vs a numpy re-implementation of 2-worker local SGD
        with a sync every 2 steps (SGD makes it exactly reproducible)."""
        ts = self._setup(k=2)
        rng = np.random.default_rng(1)
        x = rng.standard_normal((8, 4)).astype(np.float32)
        y = rng.standard_normal((8, 2)).astype(np.float32)
        w0 = {k: np.asarray(v)[0].copy() for k, v in ts.params.items()}

        def np_grads(w, b, xb, yb):
            # Linear: out = x @ W + b; mse mean loss
            out = xb @ w + b
            g = 2.0 * (out - yb) / out.size
            return xb.T @ g, g.sum(0)

        # emulate: worker d sees batch shard d each step, lr 0.1
        names = sorted(w0)
        Wk = [k for k in names if np.asarray(w0[k]).ndim == 2][0]
        bk = [k for k in names if np.asarray(w0[k]).ndim == 1][0]
        W = [w0[Wk].copy(), w0[Wk].copy()]
        b = [w0[bk].copy(), w0[bk].copy()]
        for step in range(2):
            for d in range(2):
                xb, yb = x[d * 4:(d + 1) * 4], y[d * 4:(d + 1) * 4]
                gW, gb = np_grads(W[d], b[d], xb, yb)
                W[d] = W[d] - 0.1 * gW
                b[d] = b[d] - 0.1 * gb
        Wm, bm = (W[0] + W[1]) / 2, (b[0] + b[1]) / 2

        ts(paddle.to_tensor(x), paddle.to_tensor(y))
        ts(paddle.to_tensor(x), paddle.to_tensor(y))
        np.testing.assert_allclose(np.asarray(ts.params[Wk])[0], Wm,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ts.params[bk])[0], bm,
                                   rtol=1e-4, atol=1e-5)

    def test_state_dict_roundtrip_under_localsgd(self):
        """Review r5: state_dict must not leak the (dp, ...) stacking —
        saved shapes are model shapes, and loading restacks."""
        ts = self._setup(k=2)
        rng = np.random.default_rng(2)
        x = rng.standard_normal((8, 4)).astype(np.float32)
        y = rng.standard_normal((8, 2)).astype(np.float32)
        ts(paddle.to_tensor(x), paddle.to_tensor(y))   # workers diverge
        sd = ts.state_dict()
        model_shapes = {k: tuple(v.shape)
                        for k, v in ts.model.named_parameters()}
        for k, shape in model_shapes.items():
            assert tuple(np.shape(sd[k].numpy() if hasattr(sd[k], "numpy")
                                  else sd[k])) == shape, k
        ts.set_state_dict(sd)
        # restacked and synced: compiled step still runs
        ts(paddle.to_tensor(x), paddle.to_tensor(y))
        for k, v in ts.params.items():
            assert np.shape(v)[0] == 2, k


class TestDGC:
    """VERDICT r4 missing #4: DGC as the last static meta_optimizer —
    momentum correction + top-k sparsification with error feedback,
    rampup gating (reference DGCMomentumOptimizer semantics)."""

    def _net(self, seed=13):
        import paddle_tpu.nn as nn
        paddle.seed(seed)
        return nn.Linear(16, 8)

    def _loss(self, out, y):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.core.tensor import Tensor
        return F.mse_loss(Tensor(out), Tensor(y))._value

    def _data(self):
        rng = np.random.default_rng(0)
        return (rng.standard_normal((16, 16)).astype(np.float32),
                rng.standard_normal((16, 8)).astype(np.float32))

    def test_pre_rampup_equals_plain_momentum(self):
        from paddle_tpu.distributed.fleet.meta_optimizers.dgc_optimizer \
            import DGCMomentum

        x, y = self._data()
        a, b = self._net(), self._net()
        sa = TrainStep(a, paddle.optimizer.Momentum(
            0.05, parameters=a.parameters()), loss_fn=self._loss)
        sb = TrainStep(b, DGCMomentum(
            0.05, rampup_begin_step=100, parameters=b.parameters()),
            loss_fn=self._loss)
        for _ in range(4):
            sa(paddle.to_tensor(x), paddle.to_tensor(y))
            sb(paddle.to_tensor(x), paddle.to_tensor(y))
        for k in sa.params:
            np.testing.assert_allclose(np.asarray(sa.params[k]),
                                       np.asarray(sb.params[k]),
                                       rtol=1e-6, err_msg=k)

    def test_sparsified_update_with_error_feedback(self):
        from paddle_tpu.distributed.fleet.meta_optimizers.dgc_optimizer \
            import DGCMomentum

        x, y = self._data()
        net = self._net()
        opt = DGCMomentum(0.05, rampup_begin_step=0, sparsity=[0.75],
                          parameters=net.parameters())
        ts = TrainStep(net, opt, loss_fn=self._loss)
        before = {k: np.asarray(v) for k, v in ts.params.items()}
        loss0 = float(ts(paddle.to_tensor(x), paddle.to_tensor(y)))
        wk = [k for k in ts.params if np.asarray(before[k]).ndim == 2][0]
        changed = (np.asarray(ts.params[wk]) != before[wk]).mean()
        # top-25% sparsified: roughly a quarter of entries move
        assert 0.05 < changed < 0.6, changed
        # unsent residual is banked for error feedback
        err = np.asarray(ts.opt_state["slots"][wk]["error"])
        assert np.abs(err).max() > 0
        # and training still converges (error feedback at work)
        for _ in range(40):
            loss = float(ts(paddle.to_tensor(x), paddle.to_tensor(y)))
        assert loss < loss0

    def test_strategy_wiring(self):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet.meta_optimizers.dgc_optimizer \
            import DGCMomentum

        st = fleet.DistributedStrategy()
        st.dgc = True
        st.dgc_configs = {"rampup_begin_step": 5, "sparsity": [0.9]}
        net = self._net()
        wrapped = fleet.distributed_optimizer(
            paddle.optimizer.Momentum(0.05, parameters=net.parameters()),
            strategy=st)
        assert isinstance(wrapped._inner_opt, DGCMomentum)
        assert wrapped._inner_opt._rampup_begin == 5
        with pytest.raises(TypeError, match="Momentum"):
            fleet.distributed_optimizer(
                paddle.optimizer.AdamW(
                    1e-3, parameters=net.parameters()), strategy=st)

    def test_begin_step_warmup_stays_dense(self):
        """Review r5: localsgd_configs.begin_step must be honored —
        before it, every step syncs (dense DP), after it workers drift."""
        import jax
        from jax.sharding import Mesh
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers\
            .hybrid_parallel_optimizer import HybridParallelOptimizer

        paddle.seed(9)
        net = nn.Linear(4, 2)
        mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("dp",))
        st = DistributedStrategy()
        st.localsgd = True
        st.localsgd_configs = {"k_steps": 10, "begin_step": 3}

        def loss_fn(out, y):
            import paddle_tpu.nn.functional as F
            from paddle_tpu.core.tensor import Tensor
            return F.mse_loss(Tensor(out), Tensor(y))._value

        opt = HybridParallelOptimizer(
            paddle.optimizer.SGD(0.1, parameters=net.parameters()),
            hcg=None, strategy=st)
        ts = TrainStep(net, opt, loss_fn=loss_fn, mesh=mesh)
        rng = np.random.default_rng(1)
        x = rng.standard_normal((8, 4)).astype(np.float32)
        y = rng.standard_normal((8, 2)).astype(np.float32)
        # steps 1, 2 are warmup (< begin_step=3): synced every step
        for _ in range(2):
            ts(paddle.to_tensor(x), paddle.to_tensor(y))
            for k, v in ts.params.items():
                np.testing.assert_allclose(np.asarray(v)[0],
                                           np.asarray(v)[1], rtol=1e-6)
        # step 3: local updates begin — workers drift (k_steps=10 so no
        # sync falls on this step)
        ts(paddle.to_tensor(x), paddle.to_tensor(y))
        w = {k: np.asarray(v) for k, v in ts.params.items()}
        assert any(not np.allclose(v[0], v[1]) for v in w.values())
