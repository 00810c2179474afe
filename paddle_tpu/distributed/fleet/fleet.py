"""The Fleet facade: ``fleet.init`` / ``distributed_model`` /
``distributed_optimizer``.

Reference: python/paddle/distributed/fleet/fleet.py — a singleton that (1)
builds the HybridCommunicateGroup from ``strategy.hybrid_configs``, (2)
wraps the user model with the per-strategy meta_parallel class, (3) wraps
the optimizer with HybridParallelOptimizer (or DygraphShardingOptimizer when
sharding is on). The TPU build keeps that exact surface; under the hood the
"groups" are mesh axes and the wrappers mostly declare shardings for the
jitted train step (see meta_parallel/*)."""

from __future__ import annotations

from typing import Optional

from .base_topology import (
    CommunicateTopology, HybridCommunicateGroup, try_get_hybrid_communicate_group,
)
from .distributed_strategy import DistributedStrategy
from .meta_optimizers import DygraphShardingOptimizer, HybridParallelOptimizer
from .meta_parallel import PipelineParallel
from .meta_parallel.meta_parallel_base import (
    DataParallel, ShardingParallel, TensorParallel,
)
from .meta_parallel.pp_layers import PipelineLayer


class Fleet:
    def __init__(self):
        self._is_initialized = False
        self._user_defined_strategy: Optional[DistributedStrategy] = None
        self._hcg: Optional[HybridCommunicateGroup] = None

    # ------------------------------------------------------------------ init
    def init(self, role_maker=None, is_collective: bool = True,
             strategy: Optional[DistributedStrategy] = None):
        if role_maker is None:
            from .role_maker import PaddleCloudRoleMaker
            try:
                role_maker = PaddleCloudRoleMaker(is_collective=is_collective)
            except ValueError as e:
                if not is_collective:
                    # PS mode was explicitly requested: a bad TRAINING_ROLE
                    # or server-endpoint env is a real config error, not
                    # stale launcher residue — downgrading it to a warning
                    # would silently turn a PSERVER into a worker
                    raise
                # stale/inconsistent PADDLE_* env outside a launch-CLI job
                # must not break single-process init (reference behavior)
                import warnings
                warnings.warn(f"ignoring inconsistent PADDLE_* env: {e}")
                from .role_maker import UserDefinedRoleMaker
                role_maker = UserDefinedRoleMaker(current_id=0, worker_num=1)
        self._role_maker = role_maker
        if strategy is None:
            strategy = DistributedStrategy()
        self._user_defined_strategy = strategy
        if role_maker is not None and getattr(role_maker, "is_server",
                                              lambda: False)():
            # a PSERVER process hosts tables only — building the device
            # mesh would take accelerators the server has no use for
            self._hcg = None
            self._is_initialized = True
            return self
        deg = strategy.degrees()
        topo = CommunicateTopology(
            ("data", "pipe", "sharding", "sep", "model"),
            (deg["dp"], deg["pp"], deg["sharding"], deg["sep"], deg["mp"]))
        self._hcg = HybridCommunicateGroup(topo)
        self._is_initialized = True
        return self

    def is_initialized(self) -> bool:
        return self._is_initialized

    def get_hybrid_communicate_group(self) -> HybridCommunicateGroup:
        if self._hcg is None:
            raise RuntimeError("fleet.init() has not been called")
        return self._hcg

    def worker_index(self) -> int:
        # the role maker carries the job-level identity (multi-host rank);
        # the hcg is mesh-local and single-controller
        rm = getattr(self, "_role_maker", None)
        if rm is not None:
            return rm.worker_index()
        return (self._hcg.global_rank if self._hcg else 0)

    def worker_num(self) -> int:
        rm = getattr(self, "_role_maker", None)
        if rm is not None and rm.worker_num() > 1:
            return rm.worker_num()
        return self._hcg.nranks if self._hcg else 1

    def is_first_worker(self) -> bool:
        return self.worker_index() == 0

    def barrier_worker(self):
        pass  # single controller: nothing to synchronize

    # -- PS-era worker/server API. Collective mode: workers are ranks and
    # there are no servers. PS mode (fleet.init(is_collective=False) with
    # the TRAINING_ROLE env protocol): backed by the host-side table
    # runtime in distributed/ps (reference fleet.py init_server/
    # run_server/init_worker/stop_worker over the brpc PS).
    def is_worker(self) -> bool:
        rm = getattr(self, "_role_maker", None)
        return rm.is_worker() if rm is not None else True

    def is_server(self) -> bool:
        rm = getattr(self, "_role_maker", None)
        return rm.is_server() if rm is not None else False

    def worker_endpoints(self, to_string=False):
        rm = getattr(self, "_role_maker", None)
        eps = rm.worker_endpoints() if rm is not None and hasattr(
            rm, "worker_endpoints") else ["127.0.0.1:0"]
        return ",".join(eps) if to_string else eps

    def server_num(self) -> int:
        rm = getattr(self, "_role_maker", None)
        return rm.server_num() if rm is not None and hasattr(
            rm, "server_num") else 0

    def server_index(self) -> int:
        rm = getattr(self, "_role_maker", None)
        return rm.server_index() if rm is not None and hasattr(
            rm, "server_index") else -1

    def server_endpoints(self, to_string=False):
        rm = getattr(self, "_role_maker", None)
        eps = (rm.server_endpoints() if rm is not None and hasattr(
            rm, "server_endpoints") else [])
        return ",".join(eps) if to_string else eps

    def init_worker(self, scopes=None):
        """PS mode: connect this trainer to the table servers."""
        eps = self.server_endpoints()
        if not eps:
            return                       # collective mode: nothing to do
        from ..ps import PSClient, set_client
        set_client(PSClient(eps))

    def init_server(self, dirname=None, **kwargs):
        """PS mode: build this process's table-shard server (reference
        semantics: init_server(dirname) preloads saved tables; actual
        serving starts in run_server)."""
        if not self.is_server():
            raise RuntimeError(
                "init_server: this process is not a PSERVER (set "
                "TRAINING_ROLE/PADDLE_PORT per the PS env protocol and "
                "call fleet.init(is_collective=False))")
        from ..ps import PSServer
        ep = self._role_maker.get_current_endpoint()
        port = int(ep.rsplit(":", 1)[1])
        self._ps_server = PSServer(port=port, load_dir=dirname,
                                   server_index=self.server_index())

    def run_server(self):
        """Blocking serve loop; returns after a worker sends shutdown."""
        srv = getattr(self, "_ps_server", None)
        if srv is None:
            raise RuntimeError("call fleet.init_server() first")
        srv.run()

    def stop_worker(self):
        """PS mode, reference semantics: the FIRST worker's stop_worker
        shuts the servers down; everyone drops their client."""
        from .. import ps
        if ps._client is not None and self.is_first_worker():
            ps._client.shutdown_servers()
        ps.set_client(None)

    @property
    def util(self):
        from .utils.fs import UtilBase
        return UtilBase()

    # ----------------------------------------------------------------- wrap
    def distributed_model(self, model):
        """Wrap per the strategy (reference fleet.py:distributed_model):
        pp>1 → PipelineParallel (requires a PipelineLayer), else mp>1 →
        TensorParallel, else sharding>1 → ShardingParallel, else DataParallel."""
        hcg = self.get_hybrid_communicate_group()
        strategy = self._user_defined_strategy
        if hcg.get_pipe_parallel_world_size() > 1:
            if not isinstance(model, PipelineLayer):
                raise TypeError(
                    "pp_degree > 1 requires the model to be a PipelineLayer")
            return PipelineParallel(model, hcg, strategy)
        if hcg.get_model_parallel_world_size() > 1:
            return TensorParallel(model, hcg, strategy)
        if hcg.get_sharding_parallel_world_size() > 1:
            return ShardingParallel(model, hcg, strategy)
        return DataParallel(model, hcg, strategy)

    def distributed_optimizer(self, optimizer, strategy=None):
        if strategy is not None:
            self._user_defined_strategy = strategy
        st = self._user_defined_strategy or DistributedStrategy()
        hcg = self._hcg
        if getattr(st, "dgc", False):
            # reference dgc_optimizer.py: DGC applies to Momentum only,
            # silently skipping others — here we fail loudly instead
            from ...optimizer.optimizer import Momentum
            from .meta_optimizers.dgc_optimizer import DGCMomentum
            if type(optimizer) is not Momentum:
                raise TypeError(
                    "strategy.dgc requires a Momentum optimizer "
                    f"(got {type(optimizer).__name__})")
            cfg = st.dgc_configs
            optimizer = DGCMomentum(
                learning_rate=optimizer._lr,
                momentum=optimizer._momentum,
                rampup_begin_step=cfg.get("rampup_begin_step", 0),
                rampup_step=cfg.get("rampup_step", 1),
                sparsity=cfg.get("sparsity", [0.999]),
                parameters=optimizer._parameter_list,
                use_nesterov=optimizer._nesterov,
                weight_decay=optimizer._weight_decay,
                grad_clip=optimizer._grad_clip,
                multi_precision=optimizer._multi_precision)
        if hcg is not None and hcg.get_sharding_parallel_world_size() > 1:
            stage = int(st.sharding_configs.get("stage", 1))
            if stage == 1:
                optimizer = DygraphShardingOptimizer(optimizer, hcg)
        return HybridParallelOptimizer(optimizer, hcg, st)

    # ----------------------------------------------------- minimize (static)
    def minimize(self, optimizer, loss, startup_program=None,
                 parameter_list=None, no_grad_set=None):
        raise NotImplementedError(
            "static-graph fleet.minimize is out of scope; use "
            "distributed_model + the jitted TrainStep")


_fleet_singleton = Fleet()


def _get_fleet() -> Fleet:
    return _fleet_singleton


def init(role_maker=None, is_collective: bool = True, strategy=None):
    return _fleet_singleton.init(role_maker, is_collective, strategy)


def is_initialized() -> bool:
    return _fleet_singleton.is_initialized()


def distributed_model(model):
    return _fleet_singleton.distributed_model(model)


def distributed_optimizer(optimizer, strategy=None):
    return _fleet_singleton.distributed_optimizer(optimizer, strategy)


def get_hybrid_communicate_group_from_fleet():
    return _fleet_singleton.get_hybrid_communicate_group()
