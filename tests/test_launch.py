"""Launch CLI / elastic supervisor / spawn tests — all on a fake local
cluster (no hardware, no jax in the workers unless noted)."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.distributed.launch import (Controller, ElasticManager,
                                           FileRendezvous, LaunchContext)
from paddle_tpu.distributed.launch.main import build_parser


def _clean_env():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PADDLE_")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _script(tmp_path, body, name="worker.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


class TestEnvProtocol:
    def test_rank_env(self):
        ctx = LaunchContext("x.py", nnodes=2, node_rank=1, nproc_per_node=2,
                            master="10.0.0.1:8070")
        env = ctx.rank_env(1)
        assert env["PADDLE_TRAINER_ID"] == "3"
        assert env["PADDLE_TRAINERS_NUM"] == "4"
        assert env["PADDLE_LOCAL_RANK"] == "1"
        assert env["PADDLE_MASTER"] == "10.0.0.1:8070"
        eps = env["PADDLE_TRAINER_ENDPOINTS"].split(",")
        assert len(eps) == 4 and env["PADDLE_CURRENT_ENDPOINT"] == eps[3]

    def test_parser(self):
        args = build_parser().parse_args(
            ["--nnodes", "2", "--nproc_per_node", "4", "--master",
             "h:1234", "--max_restart", "3", "train.py", "--lr", "0.1"])
        assert args.nnodes == 2 and args.nproc_per_node == 4
        assert args.training_script == "train.py"
        assert args.training_script_args == ["--lr", "0.1"]


class TestController:
    def test_gang_runs_and_logs(self, tmp_path):
        script = _script(tmp_path, """
            import os
            print("rank", os.environ["PADDLE_TRAINER_ID"],
                  "of", os.environ["PADDLE_TRAINERS_NUM"], flush=True)
        """)
        ctx = LaunchContext(script, nproc_per_node=3,
                            log_dir=str(tmp_path / "log"))
        c = Controller(ctx, base_env=_clean_env())
        c.start()
        assert c.watch(timeout=60) == 0
        for r in range(3):
            log = (tmp_path / "log" / f"workerlog.{r}").read_text()
            assert f"rank {r} of 3" in log

    def test_failure_tears_down_gang(self, tmp_path):
        script = _script(tmp_path, """
            import os, sys, time
            if os.environ["PADDLE_TRAINER_ID"] == "1":
                sys.exit(7)
            time.sleep(60)     # must be killed by the controller
        """)
        ctx = LaunchContext(script, nproc_per_node=3,
                            log_dir=str(tmp_path / "log"))
        c = Controller(ctx, base_env=_clean_env())
        t0 = time.time()
        c.start()
        rc = c.watch(timeout=60)
        assert rc == 7
        assert time.time() - t0 < 30, "teardown should not wait for sleepers"
        assert all(p.poll() is not None for p in c.procs)


class TestElastic:
    def test_restart_until_success(self, tmp_path):
        """Worker crashes on the first round (flag file absent), succeeds on
        the second — the supervisor must relaunch exactly once."""
        flag = tmp_path / "came_back"
        script = _script(tmp_path, f"""
            import os, sys
            flag = {str(flag)!r}
            if not os.path.exists(flag):
                open(flag, "w").write("x")
                sys.exit(1)
            sys.exit(0)
        """)
        ctx = LaunchContext(script, nproc_per_node=1, max_restart=2,
                            log_dir=str(tmp_path / "log"))
        mgr = ElasticManager(ctx, rendezvous=FileRendezvous(
            str(tmp_path / "rdzv")), base_env=_clean_env())
        assert mgr.run() == 0
        assert mgr.restarts == 1
        assert mgr.history == [1, 0]

    def test_restart_budget_exhausted(self, tmp_path):
        script = _script(tmp_path, "import sys; sys.exit(3)\n")
        ctx = LaunchContext(script, nproc_per_node=1, max_restart=2,
                            log_dir=str(tmp_path / "log"))
        mgr = ElasticManager(ctx, base_env=_clean_env())
        assert mgr.run() == 3
        assert mgr.restarts == 2
        assert mgr.history == [3, 3, 3]

    def test_killed_worker_triggers_restart(self, tmp_path):
        """SIGKILL a live worker mid-run: the supervisor must notice the
        death and relaunch; second round succeeds via the flag file."""
        import threading
        flag = tmp_path / "second_round"
        script = _script(tmp_path, f"""
            import os, sys, time
            flag = {str(flag)!r}
            if os.path.exists(flag):
                sys.exit(0)
            open(flag, "w").write("x")
            time.sleep(120)        # wait to be killed
        """)
        ctx = LaunchContext(script, nproc_per_node=1, max_restart=1,
                            log_dir=str(tmp_path / "log"))
        mgr = ElasticManager(ctx, base_env=_clean_env())

        def killer():
            deadline = time.time() + 30
            while time.time() < deadline:
                if flag.exists():
                    time.sleep(0.3)   # let it settle into sleep
                    # find the worker via the manager's controller
                    for _ in range(50):
                        procs = getattr(mgr, "_live_procs", None)
                        if procs:
                            break
                        time.sleep(0.1)
                    if procs:
                        os.kill(procs[0].pid, signal.SIGKILL)
                    return
                time.sleep(0.1)

        # expose live procs for the killer thread
        orig_run = Controller.watch

        def patched_watch(self, *a, **k):
            mgr._live_procs = self.procs
            return orig_run(self, *a, **k)

        Controller.watch = patched_watch
        try:
            th = threading.Thread(target=killer)
            th.start()
            rc = mgr.run(round_timeout=60)
            th.join()
        finally:
            Controller.watch = orig_run
        assert rc == 0
        assert mgr.restarts == 1

    def test_rendezvous_membership(self, tmp_path):
        r = FileRendezvous(str(tmp_path / "rdzv"))
        r.register("a", {"rank": 0})
        r.register("b", {"rank": 1})
        assert sorted(r.alive_nodes()) == ["a", "b"]
        assert r.barrier(2, timeout=1.0)
        r.deregister("a")
        assert r.alive_nodes() == ["b"]
        assert not r.barrier(2, timeout=0.3)


class TestLaunchCLI:
    def test_end_to_end_module(self, tmp_path):
        script = _script(tmp_path, """
            import os
            with open(os.path.join(os.environ["OUT_DIR"],
                      f"out.{os.environ['PADDLE_TRAINER_ID']}"), "w") as f:
                f.write(os.environ["PADDLE_TRAINER_ENDPOINTS"])
        """)
        env = _clean_env()
        env["OUT_DIR"] = str(tmp_path)
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", str(tmp_path / "log"),
             script],
            env=env, cwd="/root/repo", capture_output=True, text=True,
            timeout=120)
        assert r.returncode == 0, r.stderr
        for rank in range(2):
            assert (tmp_path / f"out.{rank}").exists()


class TestSpawn:
    def test_spawn_runs_ranks(self, tmp_path):
        import multiprocessing as mp
        from paddle_tpu.distributed import spawn

        def fn(rank, out_dir):
            import os
            with open(os.path.join(out_dir, f"r{rank}"), "w") as f:
                f.write(os.environ["PADDLE_TRAINERS_NUM"])

        spawn(_spawn_target, args=(str(tmp_path),), nprocs=2)
        for rank in range(2):
            assert (tmp_path / f"r{rank}").read_text() == "2"


def _spawn_target(rank, out_dir):
    with open(os.path.join(out_dir, f"r{rank}"), "w") as f:
        f.write(os.environ["PADDLE_TRAINERS_NUM"])


class TestHTTPKVRendezvous:
    """Rank-0 HTTP KV master (no shared filesystem — VERDICT r2 item 6)."""

    def test_kv_roundtrip_and_prefix(self):
        from paddle_tpu.distributed.launch.kv_master import KVClient, KVServer

        srv = KVServer("127.0.0.1", 0).start()
        try:
            c = KVClient(f"127.0.0.1:{srv.port}", retries=3)
            assert c.get("missing") is None
            c.put("a/1", b"one")
            c.put("a/2", b"two")
            c.put("b/1", b"three")
            assert c.get("a/1") == b"one"
            assert c.prefix("a/") == {"a/1": "one", "a/2": "two"}
            c.delete("a/1")
            assert c.get("a/1") is None
            assert c.prefix("a/") == {"a/2": "two"}
        finally:
            srv.stop()

    def test_barrier_across_processes(self, tmp_path):
        """Workers in SEPARATE processes rendezvous over plain TCP: no
        shared directory anywhere."""
        from paddle_tpu.distributed.launch.kv_master import HTTPRendezvous

        rdzv = HTTPRendezvous("127.0.0.1:0", is_master=True)
        try:
            worker = _script(tmp_path, f"""
                import sys
                sys.path.insert(0, {os.getcwd()!r})
                from paddle_tpu.distributed.launch.kv_master import (
                    HTTPRendezvous)
                r = HTTPRendezvous({rdzv.endpoint!r})
                r.register(sys.argv[1], {{"rank": int(sys.argv[2])}})
                ok = r.barrier(3, timeout=90)
                sys.exit(0 if ok else 7)
            """)
            procs = [subprocess.Popen(
                [sys.executable, worker, f"w{i}", str(i)],
                env=_clean_env()) for i in range(2)]
            # the third member registers in-process (the master node).
            # Generous timeouts: each worker pays the full interpreter +
            # package import before registering, which takes tens of
            # seconds on a loaded machine (observed flake in a full-suite
            # run alongside two other pytest sessions).
            rdzv.register("w2", {"rank": 2})
            assert rdzv.barrier(3, timeout=90)
            for p in procs:
                assert p.wait(timeout=120) == 0
            assert rdzv.alive_nodes() == ["w0", "w1", "w2"]
        finally:
            rdzv.shutdown()

    def test_ttl_expires_stale_members(self):
        from paddle_tpu.distributed.launch.kv_master import HTTPRendezvous

        rdzv = HTTPRendezvous("127.0.0.1:0", is_master=True, ttl=0.5)
        try:
            rdzv.register("stale", {"rank": 0})
            assert rdzv.alive_nodes() == ["stale"]
            time.sleep(0.8)
            assert rdzv.alive_nodes() == []
            rdzv.heartbeat("stale", {"rank": 0})
            assert rdzv.alive_nodes() == ["stale"]
        finally:
            rdzv.shutdown()

    def test_elastic_restart_over_http(self, tmp_path):
        """ElasticManager drives a failing-then-succeeding gang with the
        HTTP rendezvous instead of the shared-dir one."""
        from paddle_tpu.distributed.launch.kv_master import HTTPRendezvous

        flag = tmp_path / "second_round"
        script = _script(tmp_path, f"""
            import os, sys
            flag = {str(flag)!r}
            if os.path.exists(flag):
                sys.exit(0)
            open(flag, "w").write("x")
            sys.exit(1)
        """)
        ctx = LaunchContext(script, nproc_per_node=1, max_restart=2,
                            log_dir=str(tmp_path / "log"))
        rdzv = HTTPRendezvous("127.0.0.1:0", is_master=True)
        try:
            mgr = ElasticManager(ctx, rendezvous=rdzv,
                                 base_env=_clean_env())
            assert mgr.run() == 0
            assert mgr.restarts == 1
            assert mgr.history == [1, 0]
            assert rdzv.alive_nodes() == []   # deregistered after the run
        finally:
            rdzv.shutdown()


class TestKVMasterAuth:
    """Advisor r3: a job token gates every route; wrong/missing tokens are
    rejected before touching the store."""

    def test_token_required_when_set(self):
        from paddle_tpu.distributed.launch.kv_master import KVClient, KVServer

        srv = KVServer("127.0.0.1", 0, token="s3cret").start()
        try:
            good = KVClient(f"127.0.0.1:{srv.port}", retries=2,
                            retry_interval=0.05, token="s3cret")
            good.put("k", b"v")
            assert good.get("k") == b"v"

            bad = KVClient(f"127.0.0.1:{srv.port}", retries=2,
                           retry_interval=0.05)
            # 403 is deterministic: fail fast with the auth error, no
            # retry storm masquerading as "master unreachable"
            with pytest.raises(PermissionError, match="job token"):
                bad.put("k", b"evil")
            with pytest.raises(PermissionError, match="job token"):
                bad.get("k")
            assert good.get("k") == b"v"  # store untouched by bad client
        finally:
            srv.stop()

    def test_rendezvous_token_from_env(self, monkeypatch):
        from paddle_tpu.distributed.launch.kv_master import (HTTPRendezvous,
                                                             KVClient)

        monkeypatch.setenv("PADDLE_JOB_TOKEN", "jobtok")
        rdzv = HTTPRendezvous("127.0.0.1:0", is_master=True)
        try:
            rdzv.register("n0", {"rank": 0})
            assert rdzv.alive_nodes() == ["n0"]
            anon = KVClient(rdzv.endpoint, retries=2, retry_interval=0.05)
            with pytest.raises(PermissionError, match="job token"):
                anon.get("nodes/n0")
        finally:
            rdzv.shutdown()
