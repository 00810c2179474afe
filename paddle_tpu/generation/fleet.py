"""Fleet serving: a prefix-affinity router over N serving-engine
replicas.

One :class:`~paddle_tpu.generation.serving.ServingEngine` is
production-shaped (continuous batching, replay recovery, SLO
preemption, KV tiering) but caps the "millions of users" axis at a
single page pool and one decode stream. :class:`FleetRouter` is the
layer above: it owns N engine replicas over ONE model and places every
submitted request with

  **prefix affinity** — route to the replica whose
  :class:`~paddle_tpu.generation.serving.PrefixCache` already holds the
  longest page-aligned prefix of the prompt (probed via
  ``PrefixCache.peek(include_spilled=True)``: a host-tier hit still
  beats re-running prefill on a cold replica). System prompts and
  few-shot preambles therefore concentrate per replica, each replica's
  cache deepens on ITS tenants, and the fleet's effective prefix
  working set is the SUM of the replicas' — the r09 hit/miss counters
  (now per-``replica`` series) make the policy measurable;

  **deadline-aware load balance** as the tiebreak — among equally-hit
  replicas, place on the one with the least deadline-bearing work,
  then the least total work (a tight-deadline arrival avoids queueing
  behind other tight work it would preempt or be slack-ordered with);

  **round-robin** as the fallback — a prompt no replica has seen
  spreads uniformly (``policy="round_robin"`` forces this for every
  request: the A/B baseline arm of ``tools/serving_load.py --fleet``).

The replicas share one decode program cache (same model, same pool
geometry => same :class:`~paddle_tpu.generation.program_cache.DecodeKey`),
so N replicas compile ONCE per program kind/rung — replica fan-out adds
pools and host scheduling, never retraces.

Replica loss is a first-class event, not an exception path: the
``router_dispatch`` fault site drills it. A replica that dies
mid-drive is harvested — every completed result it still held is
banked, every live request is exported as pure host state
(``ServingEngine.export_requests``: prompt + emitted tokens) — then
rebuilt with identical geometry (cached programs re-serve, zero
retrace) while the harvested requests re-route through normal
placement across the fleet. Greedy decoding makes every re-routed
continuation bit-identical, exactly the r10 replay argument one level
up.

All router state is host-side Python; nothing here is trace-reachable.
Telemetry rides the r09 registry through ``_observe_*`` helpers, with
the fleet's own families (``fleet_requests_routed{replica,reason}``,
``fleet_replica_losses``, ``fleet_rerouted_requests``).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import observability as obs
from ..core.tensor import _val
from ..testing import faults
from .program_cache import ProgramBuildError
from .serving import OK, Request, ServingEngine

__all__ = ["FleetRouter"]


class _FleetTelemetry:
    def __init__(self):
        r = obs.registry()
        self.routed = r.counter(
            "fleet_requests_routed",
            "requests placed by the fleet router, by replica and "
            "placement reason (affinity = longest cached prefix won; "
            "balance = affinity tie broken by deadline-aware load; "
            "round_robin = no replica had the prefix)",
            labels=("replica", "reason"))
        self.losses = r.counter(
            "fleet_replica_losses",
            "replica-loss events absorbed by the router (harvest + "
            "rebuild + re-route)", labels=("replica",))
        self.rerouted = r.counter(
            "fleet_rerouted_requests",
            "in-flight/queued requests re-routed from a lost replica "
            "out of its host-side state")
        self.replicas = r.gauge(
            "fleet_replicas", "engine replicas the router is driving")


class FleetRouter:
    """Drive ``model`` behind N :class:`ServingEngine` replicas with
    prefix-affinity placement. The surface mirrors the engine's:
    ``submit`` returns a fleet-global rid; ``run_step`` pumps every
    replica one scheduler round; ``poll``/``results``/``take_results``/
    ``status`` pass through with rid translation; ``run`` steps until
    drained. Engine keyword arguments (page budget, ladder, chunk,
    ``host_tier_pages``, ``tp_degree``, ...) apply to every replica;
    ``prefix_cache`` defaults ON here — affinity is pointless without
    it. ``tp_degree > 1`` makes every replica a tensor-parallel decode
    group over the SAME mp device set (r19) — the fleet axis stays a
    routing construct, so replica loss/rebuild and re-route replay are
    untouched by tp; the per-engine ``tp`` metric label keeps a mixed
    fleet's series apart."""

    POLICIES = ("prefix_affinity", "round_robin")

    def __init__(self, model, replicas: int = 2,
                 policy: str = "prefix_affinity", **engine_kw):
        from .. import flags as _flags

        if replicas < 1:
            raise ValueError(f"need >= 1 replica, got {replicas}")
        if policy not in self.POLICIES:
            raise ValueError(
                f"unknown policy {policy!r} (have {self.POLICIES})")
        self.model = model
        self.policy = policy
        engine_kw.setdefault("prefix_cache", True)
        self._engine_kw = dict(engine_kw)
        self.engines: List[ServingEngine] = [
            self._make_engine(i) for i in range(replicas)]
        self._rr = 0                    # round-robin cursor
        self._next_rid = 0              # fleet-global rids
        # fleet rid -> (replica index, local rid), and the per-replica
        # inverse (rebuilt entries on re-route)
        self._where: Dict[int, Tuple[int, int]] = {}
        self._local2g: List[Dict[int, int]] = [
            {} for _ in range(replicas)]
        # results/statuses banked ABOVE the engines: a lost replica's
        # completed work survives its rebuild here
        self._results: Dict[int, List[int]] = {}
        self._status: Dict[int, str] = {}
        # replica-loss budget: consecutive losses with zero completed
        # work in between bound a crash-looping fleet the same way the
        # engine's no-progress retry budget bounds a wedged backend
        self.max_losses = (int(_flags.get_flag("serving_max_retries"))
                           * max(2, replicas))
        self._consec_losses = 0
        self._completed_at_loss = 0
        self.losses = 0                 # host probes (tests/benches)
        self.rerouted = 0
        self.placements: List[Tuple[int, int, str]] = []  # (rid, ri, why)
        self._f_router = faults.site("router_dispatch")
        self._m = _FleetTelemetry()
        self._observe_fleet()

    def _make_engine(self, idx: int) -> ServingEngine:
        return ServingEngine(self.model, replica=str(idx),
                             **self._engine_kw)

    # ------------------------------------------------------------ frontend
    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               deadline: Optional[float] = None,
               on_token: Optional[Callable] = None,
               replica: Optional[int] = None) -> int:
        """Place one request and return its fleet-global rid. Streaming
        callbacks fire with the FLEET rid (they survive re-routing: the
        wrapper closes over it, not over any replica-local id).
        ``replica`` pins placement explicitly (tests, drains)."""
        prompt = np.asarray(_val(prompt), np.int32).reshape(-1)
        rid = self._next_rid
        self._next_rid += 1
        if replica is not None:
            ri, why = int(replica), "pinned"
        else:
            ri, why = self._place(prompt, deadline)
        cb = None
        if on_token is not None:
            def cb(_lrid, tok, done, _cb=on_token, _g=rid):
                try:
                    _cb(_g, tok, done)
                except Exception as exc:
                    # a raising USER callback must surface to the fleet
                    # caller (the engine contract) — tag it so run_step
                    # never mistakes a client bug for a replica loss
                    exc._fleet_callback = True
                    raise
        lrid = self.engines[ri].submit(
            prompt, max_new_tokens, eos_token_id=eos_token_id,
            deadline=deadline, on_token=cb)
        self._where[rid] = (ri, lrid)
        self._local2g[ri][lrid] = rid
        self.placements.append((rid, ri, why))
        self._observe_placement(ri, why)
        return rid

    def has_work(self) -> bool:
        return any(e.has_work() for e in self.engines)

    def run_step(self) -> bool:
        """One scheduler round on every replica that has work. A
        replica that raises — the ``router_dispatch`` site, or an
        engine failure its own replay recovery could not absorb — is
        treated as LOST: its finished results bank, its live requests
        re-route across the fleet from host state, and it rebuilds
        fresh (cached programs re-serve)."""
        for ri in range(len(self.engines)):
            eng = self.engines[ri]
            if not eng.has_work():
                continue
            try:
                self._f_router.check(replica=ri)
                eng.step()
            except Exception as exc:
                if getattr(exc, "_fleet_callback", False):
                    raise       # a client callback bug, not a loss
                if isinstance(exc, ProgramBuildError):
                    raise       # deterministic: a rebuilt replica
                                # would hit the same compiler refusal
                if self._fleet_completed() > self._completed_at_loss:
                    self._consec_losses = 0     # real progress since
                if self._consec_losses >= self.max_losses:
                    raise
                self._lose_replica(ri, exc)
        return self.has_work()

    def run(self, max_wall: Optional[float] = None
            ) -> Dict[int, List[int]]:
        """Step the fleet until drained; returns ``{rid: tokens}`` and
        retains statuses for exactly the drained rids until the next
        drain (the engine's ``run`` contract, fleet-wide)."""
        t0 = time.perf_counter()
        while self.has_work():
            if max_wall is not None and \
                    time.perf_counter() - t0 > max_wall:
                why = "fleet run(max_wall=%.3f) watchdog" % max_wall
                for eng in self.engines:
                    if eng.has_work():
                        eng._expire_all(why)
                        eng._drain_events()
                break
            self.run_step()
        out = self._drain()
        self._status = {rid: self._status[rid] for rid in out
                        if rid in self._status}
        return out

    def results(self) -> Dict[int, List[int]]:
        """Completed results so far WITHOUT draining (the exception-
        safety accessor, fleet-wide): banked loss-survivor results plus
        whatever each live replica holds."""
        out = dict(self._results)
        for ri, eng in enumerate(self.engines):
            for lrid, toks in eng.results().items():
                rid = self._local2g[ri].get(lrid)
                if rid is not None:
                    out[rid] = toks
        return out

    def take_results(self) -> Dict[int, List[int]]:
        """Drain completed results and their statuses — the
        ``run_step`` loop's collection surface (same leak contract as
        the engine's)."""
        out = self._drain()
        for rid in out:
            self._status.pop(rid, None)
        return out

    def poll(self, rid: int) -> Dict[str, object]:
        if rid in self._results:
            return {"status": self._status.get(rid, OK),
                    "tokens": list(self._results[rid]), "done": True}
        ri, lrid = self._where[rid]
        return self.engines[ri].poll(lrid)

    def status(self, rid: int) -> str:
        st = self._status.get(rid)
        if st is not None:
            return st
        loc = self._where.get(rid)
        if loc is None:
            return "PENDING"
        ri, lrid = loc
        return self.engines[ri].status(lrid)

    def statuses(self) -> Dict[int, str]:
        out = dict(self._status)
        for rid, (ri, lrid) in self._where.items():
            out[rid] = self.engines[ri].status(lrid)
        return out

    # ----------------------------------------------------------- placement
    def _place(self, prompt: np.ndarray,
               deadline: Optional[float]) -> Tuple[int, str]:
        """Prefix affinity -> deadline-aware load tiebreak ->
        round-robin fallback (or pure round-robin under that policy)."""
        if self.policy == "round_robin" or len(self.engines) == 1:
            return self._rr_next(), "round_robin"
        best, cands = 0, []
        for ri, eng in enumerate(self.engines):
            if eng._prefix is None:
                continue
            hit = eng._prefix.peek(prompt, include_spilled=True)
            if hit > best:
                best, cands = hit, [ri]
            elif hit == best and best > 0:
                cands.append(ri)
        if not cands:
            return self._rr_next(), "round_robin"
        if len(cands) == 1:
            return cands[0], "affinity"
        return (min(cands, key=lambda ri: self._load_key(ri, deadline)),
                "balance")

    def _load_key(self, ri: int, deadline: Optional[float]):
        """Deadline-aware load: a deadline-bearing arrival avoids the
        replica with the most deadline-bearing work first (that is the
        work it would be slack-ordered against or have to preempt),
        then total work; replica index breaks exact ties."""
        tight, total = self.engines[ri].load()
        return ((tight, total, ri) if deadline is not None
                else (total, tight, ri))

    def _rr_next(self) -> int:
        ri = self._rr % len(self.engines)
        self._rr += 1
        return ri

    # ------------------------------------------------------- replica loss
    def _fleet_completed(self) -> int:
        """Completed requests visible fleet-wide right now: banked
        loss survivors plus every live replica's undrained results —
        the progress signal the loss budget keys on."""
        return (len(self._results)
                + sum(len(e._results) for e in self.engines))

    def _lose_replica(self, ri: int, exc: Exception) -> None:
        """Absorb one replica loss: bank its completed work, export its
        live requests as host state, rebuild it with identical geometry
        (the process program cache re-serves every compiled step), and
        re-route the exports through normal placement. The loss budget
        counts CONSECUTIVE losses with no completed work anywhere in
        the fleet in between — a healthy replica merely surviving its
        own step must not reset the bound, or a persistent crash loop
        beside one live replica would never trip it (``run_step``
        applies the progress reset BEFORE its budget check)."""
        eng = self.engines[ri]
        st = eng.statuses()
        for lrid, toks in eng.take_results().items():
            rid = self._local2g[ri].pop(lrid, None)
            if rid is not None:
                self._where.pop(rid, None)
                self._results[rid] = toks
                self._status[rid] = st.get(lrid, OK)
        # strip-at-export / re-bind-on-adopt: streaming callbacks are
        # engine-local, never part of the exported host bundles — pull
        # the registry off the dying engine, re-bind per request below
        callbacks = eng.take_callbacks()
        harvested = eng.export_requests()
        lost_map = self._local2g[ri]
        self._local2g[ri] = {}
        self.engines[ri] = self._make_engine(ri)
        self.losses += 1
        self._consec_losses += 1
        self._completed_at_loss = self._fleet_completed()
        self._observe_loss(ri)
        for req in harvested:
            cb = callbacks.get(req.rid)
            rid = lost_map.pop(req.rid, None)
            if rid is None:
                continue
            self._route_existing(rid, req, cb)
            self.rerouted += 1
        self._observe_reroutes(len(harvested))

    def _route_existing(self, rid: int, req: Request,
                        on_token: Optional[Callable] = None) -> None:
        """Re-route one harvested request through normal placement.
        ``inject_request`` keeps its tokens/deadline (and re-binds the
        stripped streaming callback under the fresh local rid), so the
        receiving replica replays the continuation bit-identically."""
        ri, why = self._place(req.prompt, req.deadline)
        lrid = self.engines[ri].inject_request(req, on_token=on_token)
        self._where[rid] = (ri, lrid)
        self._local2g[ri][lrid] = rid
        self.placements.append((rid, ri, why))
        self._observe_placement(ri, why)

    # ------------------------------------------------------------ internals
    def _drain(self) -> Dict[int, List[int]]:
        out, self._results = self._results, {}
        for ri, eng in enumerate(self.engines):
            st = eng.statuses()
            for lrid, toks in eng.take_results().items():
                rid = self._local2g[ri].pop(lrid, None)
                if rid is None:
                    continue
                self._where.pop(rid, None)
                out[rid] = toks
                self._status.setdefault(rid, st.get(lrid, OK))
        if out:
            # drained completions are fleet progress; the undrained
            # census just reset, so re-baseline the loss budget's mark
            self._consec_losses = 0
            self._completed_at_loss = self._fleet_completed()
        return out

    # ------------------------------------------------- telemetry helpers
    def _observe_fleet(self) -> None:
        self._m.replicas.set(len(self.engines))

    def _observe_placement(self, ri: int, why: str) -> None:
        self._m.routed.labels(replica=str(ri), reason=why).inc()

    def _observe_loss(self, ri: int) -> None:
        self._m.losses.labels(replica=str(ri)).inc()

    def _observe_reroutes(self, n: int) -> None:
        if n:
            self._m.rerouted.inc(n)
