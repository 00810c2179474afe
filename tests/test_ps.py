"""Parameter-server runtime (distributed/ps/) — host-side sparse tables,
the authenticated pull/push service, and the fleet PS lifecycle.

Reference behaviors covered: MemorySparseTable pull-creates rows /
push-merges duplicate ids and applies the server-side optimizer
(paddle/fluid/distributed/ps/table/), BrpcPsClient id partitioning,
fleet init_server/run_server/init_worker/stop_worker + the
TRAINING_ROLE env protocol (fleet/base/role_maker.py _ps_env).
"""

import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.ps import (
    DenseTable, DistributedEmbedding, PSClient, PSServer, SparseTable,
    set_client,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ================================================================ tables
class TestSparseTable:
    def test_pull_creates_deterministic_rows(self):
        a = SparseTable(dim=4, seed=7)
        b = SparseTable(dim=4, seed=7)
        ids = np.array([3, 99, 3], np.int64)
        ra, rb = a.pull(ids), b.pull(ids)
        np.testing.assert_array_equal(ra, rb)
        np.testing.assert_array_equal(ra[0], ra[2])   # same id, same row
        assert len(a) == 2                            # dedup in storage
        c = SparseTable(dim=4, seed=8)
        assert not np.array_equal(c.pull(ids), ra)    # seed matters

    def test_sgd_push_merges_duplicates(self):
        t = SparseTable(dim=2, optimizer="sgd", lr=0.5,
                        initializer="zeros")
        ids = np.array([1, 2, 1], np.int64)
        t.pull(ids)
        g = np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 0.0]], np.float32)
        t.push(ids, g)
        # id 1 saw summed grad [2, 0] in ONE optimizer step
        np.testing.assert_allclose(t.pull(np.array([1]))[0], [-1.0, 0.0])
        np.testing.assert_allclose(t.pull(np.array([2]))[0], [0.0, -0.5])

    def test_adagrad_matches_numpy(self):
        t = SparseTable(dim=3, optimizer="adagrad", lr=0.1,
                        initializer="zeros", eps=1e-8)
        w = np.zeros(3, np.float32)
        g2 = np.zeros(3, np.float32)
        rng = np.random.default_rng(0)
        for _ in range(4):
            g = rng.standard_normal(3).astype(np.float32)
            t.push(np.array([5]), g[None])
            g2 += g * g
            w -= 0.1 * g / (np.sqrt(g2) + 1e-8)
        np.testing.assert_allclose(t.pull(np.array([5]))[0], w,
                                   rtol=1e-5)

    def test_adam_matches_numpy(self):
        t = SparseTable(dim=2, optimizer="adam", lr=0.01,
                        initializer="zeros")
        w = np.zeros(2, np.float32)
        m = np.zeros(2, np.float32)
        v = np.zeros(2, np.float32)
        rng = np.random.default_rng(1)
        for step in range(1, 4):
            g = rng.standard_normal(2).astype(np.float32)
            t.push(np.array([0]), g[None])
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            mh, vh = m / (1 - 0.9 ** step), v / (1 - 0.999 ** step)
            w -= 0.01 * mh / (np.sqrt(vh) + 1e-8)
        np.testing.assert_allclose(t.pull(np.array([0]))[0], w,
                                   rtol=1e-5)

    def test_save_load_roundtrip(self):
        t = SparseTable(dim=2, seed=3)
        t.pull(np.array([10, 20], np.int64))
        t.push(np.array([10]), np.ones((1, 2), np.float32))
        t2 = SparseTable(dim=2, seed=3)
        t2.load_state(t.state())
        np.testing.assert_array_equal(t2.pull(np.array([10, 20])),
                                      t.pull(np.array([10, 20])))


class TestDenseTable:
    def test_push_pull(self):
        t = DenseTable((2, 2), lr=1.0)
        t.push(np.ones((2, 2)))
        np.testing.assert_allclose(t.pull(), -np.ones((2, 2)))


# =============================================================== service
@pytest.fixture
def two_servers():
    servers = [PSServer(bind_ip="127.0.0.1", token="t0k"),
               PSServer(bind_ip="127.0.0.1", token="t0k")]
    for s in servers:
        s.start()
    client = PSClient([f"127.0.0.1:{s.port}" for s in servers],
                      token="t0k")
    yield servers, client
    for s in servers:
        s.stop()


class TestService:
    def test_sparse_partition_roundtrip(self, two_servers):
        servers, client = two_servers
        client.create_sparse_table(1, dim=3, initializer="zeros", lr=1.0)
        ids = np.array([0, 1, 2, 3, 4, 1], np.int64)   # both shards + dup
        rows = client.pull_sparse(1, ids)
        assert rows.shape == (6, 3)
        grads = np.arange(18, dtype=np.float32).reshape(6, 3)
        client.push_sparse(1, ids, grads)
        got = client.pull_sparse(1, ids)
        # id 1 (rows 1 and 5) merged: -(g1+g5); order preserved
        np.testing.assert_allclose(got[1], -(grads[1] + grads[5]))
        np.testing.assert_array_equal(got[1], got[5])
        np.testing.assert_allclose(got[2], -grads[2])
        # rows landed on the right shards: each server holds only its ids
        stats = client.stats()
        assert stats[0][1] == 3 and stats[1][1] == 2   # {0,2,4} vs {1,3}

    def test_dense_roundtrip(self, two_servers):
        _, client = two_servers
        client.create_dense_table(2, (2,), lr=1.0)
        client.push_dense(2, np.array([1.0, 2.0]))
        np.testing.assert_allclose(client.pull_dense(2), [-1.0, -2.0])

    def test_bad_token_rejected(self, two_servers):
        servers, _ = two_servers
        bad = PSClient([f"127.0.0.1:{servers[0].port}"], token="wrong")
        with pytest.raises(Exception):
            bad.pull_dense(0)

    def test_save_load(self, two_servers, tmp_path):
        _, client = two_servers
        client.create_sparse_table(1, dim=2, initializer="zeros", lr=1.0)
        ids = np.array([7, 8], np.int64)
        client.push_sparse(1, ids, np.ones((2, 2), np.float32))
        client.save(str(tmp_path))
        client.push_sparse(1, ids, np.ones((2, 2), np.float32))
        client.load(str(tmp_path))                     # rollback
        np.testing.assert_allclose(client.pull_sparse(1, ids),
                                   -np.ones((2, 2)))


# ==================================================== embedding + fleet
class TestDistributedEmbedding:
    def test_train_loop_updates_server_rows(self, two_servers):
        _, client = two_servers
        emb = DistributedEmbedding(100, 8, client=client, lr=0.1,
                                   seed=5)
        lin = paddle.nn.Linear(8, 1)
        opt = paddle.optimizer.SGD(0.1, parameters=lin.parameters())
        ids = paddle.to_tensor(np.array([[1, 2], [3, 1]], np.int64))
        before = client.pull_sparse(emb.table_id,
                                    np.array([1, 2, 3])).copy()
        losses = []
        for _ in range(5):
            e = emb(ids)                   # (2, 2, 8) pulled from servers
            out = lin(e.reshape([2, -1]).matmul(
                paddle.ones([16, 8]) / 16.0))
            loss = ((out - 1.0) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        after = client.pull_sparse(emb.table_id, np.array([1, 2, 3]))
        assert not np.allclose(before, after)          # server applied push
        assert losses[-1] < losses[0]                  # and it helps
        # a second worker's client sees the same updated rows
        other = PSClient(client.endpoints, token="t0k")
        np.testing.assert_array_equal(
            other.pull_sparse(emb.table_id, np.array([1, 2, 3])), after)

    def test_no_grad_skips_push(self, two_servers):
        _, client = two_servers
        emb = DistributedEmbedding(10, 4, client=client,
                                   initializer="zeros")
        ids = paddle.to_tensor(np.array([1, 2], np.int64))
        with paddle.no_grad():
            out = emb(ids)
        assert out.shape == [2, 4]


# ============================================================ env + fleet
SERVER_SCRIPT = """
import paddle_tpu.distributed.fleet as fleet
fleet.init(is_collective=False)
assert fleet.is_server()
fleet.init_server()
print("SERVING", flush=True)
fleet.run_server()
"""


class TestFleetPS:
    def test_role_maker_ps_env(self, monkeypatch):
        from paddle_tpu.distributed.fleet.role_maker import (
            PaddleCloudRoleMaker, Role)
        monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
        monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST",
                           "127.0.0.1:1234,127.0.0.1:1235")
        monkeypatch.setenv("POD_IP", "127.0.0.1")
        monkeypatch.setenv("PADDLE_PORT", "1235")
        rm = PaddleCloudRoleMaker(is_collective=False)
        assert rm.is_server() and not rm.is_worker()
        assert rm.role() == Role.SERVER
        assert rm.server_index() == 1
        assert rm.server_num() == 2

    def test_cross_process_lifecycle(self, monkeypatch, tmp_path):
        """One real PSERVER OS process via the env protocol; this process
        is the trainer: init_worker -> train-ish push/pull ->
        stop_worker shuts the server down."""
        port = _free_port()
        eps = f"127.0.0.1:{port}"
        env = dict(os.environ)
        env.update(TRAINING_ROLE="PSERVER",
                   PADDLE_PSERVERS_IP_PORT_LIST=eps,
                   POD_IP="127.0.0.1", PADDLE_PORT=str(port),
                   PADDLE_JOB_TOKEN="secret", JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH",
                                                          ""))
        proc = subprocess.Popen([sys.executable, "-c", SERVER_SCRIPT],
                                env=env, stdout=subprocess.PIPE,
                                text=True)
        try:
            assert proc.stdout.readline().strip() == "SERVING"
            monkeypatch.setenv("TRAINING_ROLE", "TRAINER")
            monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST", eps)
            monkeypatch.setenv("PADDLE_JOB_TOKEN", "secret")
            import paddle_tpu.distributed.fleet as fleet
            fleet.init(is_collective=False)
            assert fleet.is_worker()
            assert fleet.server_endpoints() == [eps]
            fleet.init_worker()
            from paddle_tpu.distributed import ps
            client = ps.the_client()
            client.create_sparse_table(1, dim=2, initializer="zeros",
                                       lr=1.0)
            client.push_sparse(1, np.array([4]),
                               np.ones((1, 2), np.float32))
            np.testing.assert_allclose(
                client.pull_sparse(1, np.array([4])), [[-1.0, -1.0]])
            fleet.stop_worker()                # first worker: shutdown
            assert proc.wait(timeout=20) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
            set_client(None)
