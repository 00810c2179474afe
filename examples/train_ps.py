"""Parameter-server training: sparse embeddings on host-side table
servers, dense math on the worker.

Run: JAX_PLATFORMS=cpu python examples/train_ps.py

The classic recommendation-model deploy shape (reference: the brpc PS
under paddle/fluid/distributed/ps/ driven by
fleet.init(role)/init_server/run_server/init_worker/stop_worker):

  * this script re-launches itself twice as PSERVER processes via the
    TRAINING_ROLE env protocol, each hosting a shard of the embedding
    table (ids hash-partitioned id % n_servers);
  * the worker (this process) trains a tiny two-tower-ish CTR model:
    DistributedEmbedding rows pulled per batch + a dense MLP, labels
    from a synthetic click rule;
  * embedding grads are PUSHED to the servers (server-side Adagrad,
    fully async a_sync semantics); dense params train locally;
  * the first worker's fleet.stop_worker() shuts the servers down.
"""
import os
import socket
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


SERVER = """
import paddle_tpu.distributed.fleet as fleet
fleet.init(is_collective=False)
fleet.init_server()
print("SERVING", flush=True)
fleet.run_server()
"""


def main():
    ports = [free_port(), free_port()]
    eps = ",".join(f"127.0.0.1:{p}" for p in ports)
    servers = []
    for p in ports:
        env = dict(os.environ)
        env.update(TRAINING_ROLE="PSERVER", PADDLE_PSERVERS_IP_PORT_LIST=eps,
                   POD_IP="127.0.0.1", PADDLE_PORT=str(p),
                   JAX_PLATFORMS="cpu")
        servers.append(subprocess.Popen([sys.executable, "-c", SERVER],
                                        env=env, stdout=subprocess.PIPE,
                                        text=True))
    for s in servers:
        assert s.stdout.readline().strip() == "SERVING"
    print(f"2 table servers up at {eps}")

    os.environ["TRAINING_ROLE"] = "TRAINER"
    os.environ["PADDLE_PSERVERS_IP_PORT_LIST"] = eps
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.distributed.ps import DistributedEmbedding

    fleet.init(is_collective=False)
    fleet.init_worker()

    vocab, dim = 10_000, 16
    emb = DistributedEmbedding(vocab, dim, optimizer="adagrad", lr=0.1,
                               seed=0)
    mlp = paddle.nn.Sequential(
        paddle.nn.Linear(3 * dim, 32), paddle.nn.ReLU(),
        paddle.nn.Linear(32, 1))
    opt = paddle.optimizer.AdamW(1e-2, parameters=mlp.parameters())

    rng = np.random.default_rng(0)
    losses = []
    for step in range(30):
        ids = rng.integers(0, vocab, (64, 3))
        # synthetic click rule: "user likes low ids"
        label = (ids.sum(1) < 1.5 * vocab).astype(np.float32)[:, None]
        feats = emb(paddle.to_tensor(ids))           # pulled from servers
        logits = mlp(feats.reshape([64, -1]))
        loss = paddle.nn.functional.binary_cross_entropy_with_logits(
            logits, paddle.to_tensor(label))
        loss.backward()                              # pushes row grads
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
        if step % 10 == 0:
            print(f"step {step:3d} loss {losses[-1]:.4f}")

    from paddle_tpu.distributed import ps
    stats = ps.the_client().stats()
    rows = sum(s[emb.table_id] for s in stats)
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f}); "
          f"{rows} rows live across {len(stats)} servers "
          f"{[s[emb.table_id] for s in stats]}")
    assert losses[-1] < losses[0]
    fleet.stop_worker()                              # shuts servers down
    for s in servers:
        assert s.wait(timeout=20) == 0
    print("servers shut down cleanly — PS lifecycle complete")


if __name__ == "__main__":
    main()
