"""Multi-process mesh dryrun worker (VERDICT r4 item 5).

Launched as N OS processes by ``__graft_entry__.dryrun_multichip`` (or
the fleet launcher) with the launcher's env protocol
(``PADDLE_TRAINER_ID`` / ``PADDLE_TRAINERS_NUM`` /
``PADDLE_MASTER_ENDPOINT``). Proves the cross-process story end to end:

1. rendezvous through the launcher's HTTP KV master — rank 0 publishes
   the jax coordinator address, everyone fetches it;
2. ``jax.distributed.initialize`` forms the global runtime (2 processes
   x 4 local CPU devices = one 8-device mesh);
3. a jitted computation over a ``Mesh`` spanning BOTH processes runs a
   real cross-process collective (the mean over the dp axis), checked
   numerically against the global batch;
4. the fleet topology (HybridCommunicateGroup) builds over the global
   device list.

Reference analogue: multi-node NCCL ProcessGroup init through TCPStore +
an allreduce smoke (test_collective_* multi-node tests).
"""

import json
import os
import socket
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def launch(n_procs: int = 2, devices_per_proc: int = 4,
           timeout: float = 420.0):
    """Shared launcher (used by __graft_entry__.dryrun_multichip AND
    tests/test_multiprocess_mesh.py — one env protocol, one cleanup
    path): start the KV master, spawn ``n_procs`` workers with the
    launcher env protocol, and return their parsed JSON results. Any
    failure kills EVERY worker before raising — a dead rank otherwise
    leaves its peer orphaned inside jax.distributed.initialize."""
    import subprocess

    from paddle_tpu.distributed.launch.kv_master import KVServer

    srv = KVServer(host="127.0.0.1").start()
    procs = []
    try:
        for r in range(n_procs):
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                f"{devices_per_proc}")
            env["PADDLE_TRAINER_ID"] = str(r)
            env["PADDLE_TRAINERS_NUM"] = str(n_procs)
            env["PADDLE_MASTER_ENDPOINT"] = f"127.0.0.1:{srv.port}"
            env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        outs = []
        for r, p in enumerate(procs):
            so, se = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError(f"mp worker {r} rc={p.returncode}: "
                                   f"{se[-1500:]}")
            outs.append(json.loads(so.strip().splitlines()[-1]))
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        srv.stop()


def main() -> None:
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    nprocs = int(os.environ["PADDLE_TRAINERS_NUM"])
    master = os.environ["PADDLE_MASTER_ENDPOINT"]

    from paddle_tpu.distributed.launch.kv_master import KVClient
    kv = KVClient(master)
    if rank == 0:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        kv.put("jax/coordinator", coord.encode())
    else:
        deadline = time.time() + 60
        coord = None
        while time.time() < deadline:
            try:
                got = kv.prefix("jax/").get("jax/coordinator")
            except Exception:
                got = None
            if got:
                coord = got.decode() if isinstance(got, bytes) else got
                break
            time.sleep(0.2)
        assert coord, "rank0 never published the jax coordinator"

    import jax
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=rank)
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    local = jax.local_device_count()
    assert jax.process_count() == nprocs, jax.process_count()
    n_global = jax.device_count()
    assert n_global == nprocs * local, (n_global, nprocs, local)

    # ---- global mesh spanning both processes + a real collective ---------
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    per = 2                                     # rows per device
    rows = n_global * per

    def row(i):
        return np.full((per, 4), float(i), np.float32)

    global_batch = np.concatenate([row(i) for i in range(n_global)])
    arr = jax.make_array_from_callback(
        (rows, 4), sharding,
        lambda idx: global_batch[idx])

    @jax.jit
    def global_mean(x):                          # cross-process all-reduce
        return jnp.mean(x)

    got = float(global_mean(arr))
    want = float(global_batch.mean())
    assert abs(got - want) < 1e-6, (got, want)

    # ---- fleet topology over the global device list ----------------------
    from paddle_tpu.distributed.fleet.base_topology import (
        create_hybrid_communicate_group)
    hcg = create_hybrid_communicate_group(dp_degree=n_global)
    assert hcg.get_data_parallel_world_size() == n_global

    # ---- FULL train step across both processes ---------------------------
    # dp=8 over the 2-process mesh: params replicated globally (identical
    # seed per process), each process feeds its local half of the global
    # batch (per-rank data, like a DistributedBatchSampler shard); the
    # jitted fwd+bwd+AdamW step runs ONE SPMD program over both
    # processes, with the dp grad-sum riding the cross-process
    # collectives verified above. Losses must agree bit-for-bit across
    # ranks (replicated output).
    import paddle_tpu as paddle
    from paddle_tpu.hapi import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=32,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    ts = TrainStep(model, opt, mesh=mesh, data_axes=("dp",))
    lrng = np.random.default_rng(100 + rank)      # per-rank data
    local_b = n_global // nprocs                  # rows this process feeds
    losses = []
    for _ in range(3):
        ids = lrng.integers(0, cfg.vocab_size, (local_b, 17))
        x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
        yb = paddle.to_tensor(ids[:, 1:].astype(np.int32))
        losses.append(float(ts(x, yb)))
    assert all(np.isfinite(l) for l in losses), losses

    print(json.dumps({
        "rank": rank, "processes": jax.process_count(),
        "global_devices": n_global, "local_devices": local,
        "collective_mean": got, "expected": want,
        "train_losses": [round(l, 6) for l in losses], "ok": True,
    }), flush=True)


if __name__ == "__main__":
    main()
