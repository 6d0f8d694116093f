"""SchedTwin orchestrator — the simulation-in-the-loop digital twin.

Wires together the paper's workflow (Figure 2):

  ① physical event --> ② produced onto the event bus -->
  ③ twin consumes --> ④ synchronization (sync.py) -->
  ⑤ parallel what-if DES (whatif.py) --> ⑥ policy selection
  (scoring.py) --> ⑥A extract next job-run events -->
  ⑦ decision feedback: ``qrun`` the selected jobs.

The twin never sees true runtimes — only user estimates and actual
completion events as they occur, exactly the information a production
PBS deployment exposes.

Resilience layer (DESIGN.md §12): ingestion is HARDENED by default —
malformed events are quarantined into ``dead_letters`` instead of
raising mid-cycle, duplicate/out-of-order ``seq`` deliveries are
absorbed idempotently (``events.SeqTracker`` + state-guarded
``sync.apply_event``), sequence gaps trigger probe resyncs, and bus
reads retry transient failures with bounded backoff.  On a clean
in-order stream every hardened path reduces to the original handlers
bit-for-bit.  Decision cycles can run under a wall-clock budget
(``guard.DeadlineGuard``) that degrades the decision down a ladder
rather than letting it arrive late, and ``snapshot()``/``restore()``
make the whole twin crash-safe through ``checkpoint.manager``.

The job table is a window over the scheduler's stream
(``sync.JobSlots``): ``max_jobs`` slots for the live jobs, external ids
unbounded, a slot recycled when its job ends.  ``attach()`` starts a
twin beside a scheduler that already has a queue, from its job view.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sync, telemetry
from repro.core.engine import DrainEngine
from repro.core.events import (BusReadError, DeadLetter, Event, EventBus,
                               EventKind, SeqTracker, read_with_retry,
                               validate_event)
from repro.core.fan import FanSpec, normalize_fan
from repro.core.guard import DeadlineGuard, GuardSpec
from repro.core.objective import ObjectiveLike, resolve_goal
from repro.core.policies import (PAPER_POOL, PolicyPool, PolicySpec,
                                 PoolLike, normalize_pool)
from repro.core.race import RaceSpec, normalize_race
from repro.core.scoring import ScoreWeights
from repro.core.state import DONE, INVALID, QUEUED, SimState, empty_state
from repro.core.telemetry import fetch


def _jsonable(x):
    """Recursively strip numpy/JAX scalar types out of snapshot extras
    (CycleRecord cost dicts hold device scalars; ``json.dump`` chokes
    on them bitlessly — ``.item()`` round-trips f32 exactly)."""
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (bool, int, float, str)) or x is None:
        return x
    arr = fetch(x)
    return arr.item() if arr.ndim == 0 else arr.tolist()


def _fork_pool(pool: PolicyPool, p: int) -> PolicyPool:
    """Pool member p as a k=1 pool (one schedule pass, no comparison)."""
    return PolicyPool(
        spec=PolicySpec(pool.spec.family[p:p + 1],
                        pool.spec.theta[p:p + 1]),
        names=(pool.names[p],))


def _metered(entry):
    """An ingestion entry point run under the twin's ``StageMeter``:
    stage totals start at zero, and the cycle it records carries them."""
    @functools.wraps(entry)
    def run(self, *args):
        with self._meter.pump(self.telemetry.cycles):
            return entry(self, *args)
    return run


class SchedTwin:
    """Real-time digital twin for adaptive scheduling.

    Parameters
    ----------
    bus : EventBus
        Stream carrying scheduler hook events (②→③).
    qrun : callable(list[int], float) -> None
        Decision feedback into the physical system (⑦) — the PBS
        ``qrun <jobid>`` equivalent, supplied by the cluster emulator
        (or by a real PBS adapter).
    free_nodes_probe : callable() -> int, optional
        Authoritative node-availability probe (§3.2's "command-line
        tools"); when given, the mirror's free count is resynced before
        every decision.
    pool : candidate pool in tie-break order (default: paper's WFP,
        FCFS, SJF).  Any ``policies.normalize_pool`` input works: a
        ``PolicyPool``, a stacked ``PolicySpec``, a sweep-grammar
        string (``"paper,wfp:a=1..5x5"``), or a sequence of legacy
        policy ids — ids are lifted to their parametric fixed points,
        which produce bit-identical decisions (tests/test_policyspec).
    objective : the administrator-configured optimization goal (§3.4;
        DESIGN.md §8) policy selection minimizes — an
        ``objective.Objective``, a grammar string (``"score"``,
        ``"avg_wait"``, ``"min:avg_wait@util>=0.85"``), or None for
        the paper's 4-term score.
    weights : DEPRECATED legacy goal spelling; a ``ScoreWeights`` here
        lifts to the bit-identical paper-score objective (with a
        ``DeprecationWarning``).
    ensemble : if > 1, use uncertainty-ensemble decisions (beyond paper).
    fan : optional ``fan.FanSpec`` (or bare int F) — decide over an
        on-device Monte-Carlo fan of F perturbed futures per policy
        (DESIGN.md §10) instead of the single nominal future; pairs
        naturally with a distributional ``objective``
        (``"p95:avg_wait"``, ``"cvar:0.9:score"``).  Decisions then
        carry device-computed per-policy confidence intervals, recorded
        in telemetry with no host recompute.  Mutually exclusive with
        ``ensemble > 1``.
    race : optional ``race.RaceSpec`` (or bare ``FanSpec``/int) — decide
        via the successive-halving fan race (DESIGN.md §11): every
        policy starts at a small fan F₀, per-rung CIs eliminate
        statistically-dominated policies, survivors double F, and CRN
        prefix-stability means each rung replays only the new member
        suffix.  Same winner as ``fan=`` at the race's F_max, at a
        fraction of the member budget; per-cycle rungs/members/
        separation land in ``CycleRecord``.  Mutually exclusive with
        ``fan=`` and ``ensemble > 1``.
    engine : the policy-batched what-if engine (``core.engine``); pick
        the scheduling-pass backend here (``DrainEngine("pallas")`` for
        the TPU kernel, ``DrainEngine("auto")`` to pick per platform).
        Default: the pure-JAX reference backend.
    guard : optional ``guard.GuardSpec`` (or a bare float budget in
        seconds, or a prebuilt ``DeadlineGuard``) — run every decision
        cycle under a wall-clock budget, walking the degradation ladder
        (shrunk race/fan → static fallback pool → hold incumbent) on
        budget pressure so ``qrun`` is always fed on time (DESIGN.md
        §12).  Ladder level / margin / misses land in ``CycleRecord``.
    max_jobs : the job table's slots: how many jobs may be live (queued
        or running) at once.  Job ids are the scheduler's own and
        unbounded; a job's slot is recycled when it ends.
    jobs_probe : callable() -> dict, optional
        Authoritative full job-table probe (the qstat analogue of
        ``free_nodes_probe``; ``ClusterEmulator.jobs_view``).  When the
        stream declares events LOST (a sequence hole aged past the
        reorder window), the mirror is rebuilt from this probe — the
        only heal for a dropped QUEUEJOB — and ``attach()`` builds the
        first mirror from it.
    fallback_pool : the static pool the ladder's level 2 decides over
        (default: the paper's §4.1 pool).
    clock / sleep : injectable time sources (ladder determinism under a
        fake clock in tests; instant backoff in the chaos benchmark).
    reorder_window : how many seqs behind the high-water mark a missing
        event may lag before it is declared lost (``SeqTracker``).
    read_retries / read_backoff_s : bounded-backoff policy for
        transient ``BusReadError`` on bus reads.
    """

    CONSUMER = "schedtwin"

    def __init__(self,
                 bus: EventBus,
                 qrun: Callable[[List[int], float], None],
                 total_nodes: int,
                 max_jobs: int = 256,
                 pool: PoolLike = PAPER_POOL,
                 objective: ObjectiveLike = None,
                 weights: Optional[ScoreWeights] = None,
                 free_nodes_probe: Optional[Callable[[], int]] = None,
                 ensemble: int = 1,
                 ensemble_noise: float = 0.3,
                 fan: Optional[FanSpec] = None,
                 race: Optional[RaceSpec] = None,
                 engine: Optional[DrainEngine] = None,
                 seed: int = 0,
                 guard=None,
                 jobs_probe: Optional[Callable[[], dict]] = None,
                 fallback_pool: PoolLike = PAPER_POOL,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 reorder_window: int = 64,
                 read_retries: int = 3,
                 read_backoff_s: float = 0.01) -> None:
        if fan is not None and ensemble > 1:
            raise ValueError("fan= and ensemble>1 are mutually exclusive")
        if race is not None and (fan is not None or ensemble > 1):
            raise ValueError(
                "race= is mutually exclusive with fan= and ensemble>1")
        self.bus = bus
        self.qrun = qrun
        self.pool = normalize_pool(pool)
        self.objective = resolve_goal(objective, weights)
        self.max_jobs = max_jobs
        # the mirror of record, on the host (``core/sync.py``): ingest
        # never touches the device; each decision uploads it once.
        self.state: SimState = empty_state(max_jobs, total_nodes, xp=np)
        self.slots = sync.JobSlots(max_jobs)
        self.telemetry = telemetry.Telemetry()
        self._meter = telemetry.StageMeter()
        self.free_nodes_probe = free_nodes_probe
        self.ensemble = ensemble
        self.ensemble_noise = ensemble_noise
        self.fan = normalize_fan(fan) if fan is not None else None
        self.race = normalize_race(race) if race is not None else None
        self.engine = engine if engine is not None else DrainEngine()
        self._key = jax.random.PRNGKey(seed)
        # -- resilience layer (DESIGN.md §12) --------------------------
        if isinstance(guard, DeadlineGuard):
            self.guard: Optional[DeadlineGuard] = guard
        elif isinstance(guard, GuardSpec):
            self.guard = DeadlineGuard(guard)
        elif guard is not None:
            self.guard = DeadlineGuard(GuardSpec(budget_s=float(guard)))
        else:
            self.guard = None
        self.jobs_probe = jobs_probe
        self.fallback_pool = normalize_pool(fallback_pool)
        self.dead_letters: List[DeadLetter] = []
        self._tracker = SeqTracker(reorder_window)
        self._clock = clock
        self._sleep = sleep
        self.read_retries = read_retries
        self.read_backoff_s = read_backoff_s
        # last winner as (source pool, index) — the ladder's level-3
        # incumbent; JSON-serializable for snapshots.
        self._incumbent: Optional[Tuple[str, int]] = None

    # ------------------------------------------------------------------
    @_metered
    def pump(self) -> int:
        """③ consume pending events; run a decision cycle if any event
        opened a scheduling opportunity.  Returns #events consumed.

        Hardened: transient read failures retry with bounded backoff
        (exhaustion skips this pump rather than crashing — the events
        stay in the log for the next one); each event then passes
        through ``_ingest`` (quarantine / dedup / reorder / gap
        classification) and losses trigger a probe resync."""
        ing = self.telemetry.ingest
        span = self._meter.span

        def _count_retry(attempt: int, exc: Exception) -> None:
            ing.read_retries += 1

        try:
            with span("twin.read"):
                events = read_with_retry(
                    self.bus, self.CONSUMER, retries=self.read_retries,
                    backoff_s=self.read_backoff_s, sleep=self._sleep,
                    on_retry=_count_retry)
        except BusReadError:
            ing.read_failures += 1
            return 0
        self._meter.tally["events"] += len(events)
        needs_cycle = False
        lost_any = False
        with span("twin.ingest"):
            t_latest = float(self.state.now)
            for ev in events:
                applied, cycle, gap, lost = self._ingest(ev)
                needs_cycle |= cycle
                lost_any |= lost
                if gap or lost:
                    needs_cycle = True   # something is missing — resync+look
                if applied:
                    t_latest = max(t_latest, float(ev.time))
        if lost_any and self.jobs_probe is not None:
            # events are gone for good (aged past the reorder window):
            # rebuild the job table from the authoritative probe — the
            # only heal for a dropped QUEUEJOB.
            with span("twin.resync"):
                self._resync_jobs(self.jobs_probe())
                ing.resyncs += 1
                t_latest = max(t_latest, float(self.state.now))
        if needs_cycle:
            self._decision_cycle(t_latest)
        return len(events)

    @_metered
    def on_event(self, ev: Event) -> None:
        """Push-mode entry point (bus.subscribe)."""
        span = self._meter.span
        with span("twin.read"):
            self.bus.read(self.CONSUMER)  # keep offset in step with pushes
        self._meter.tally["events"] += 1
        with span("twin.ingest"):
            applied, needs_cycle, gap, lost = self._ingest(ev)
        if lost and self.jobs_probe is not None:
            with span("twin.resync"):
                self._resync_jobs(self.jobs_probe())
                self.telemetry.ingest.resyncs += 1
        if needs_cycle or gap or lost:
            self._decision_cycle(float(ev.time) if applied
                                 else float(self.state.now))

    def _ingest(self, ev: Event) -> Tuple[bool, bool, bool, bool]:
        """Sanitize + apply ONE event.  Returns ``(applied, needs_cycle,
        gap_detected, losses_declared)``.  Never raises: malformed
        events and handler failures land in ``dead_letters``."""
        ing = self.telemetry.ingest
        reason = validate_event(ev)
        if reason is not None:
            self.dead_letters.append(DeadLetter(ev, reason))
            ing.quarantined += 1
            return False, False, False, False
        gap = lost = False
        if ev.seq >= 0:
            obs = self._tracker.observe(ev.seq)
            if obs.new_gaps:
                ing.gaps += obs.new_gaps
                gap = True
            if obs.newly_lost:
                ing.lost += obs.newly_lost
                lost = True
            if obs.status == "duplicate":
                ing.duplicates += 1
                return False, False, gap, lost
            if obs.status == "reordered":
                ing.reordered += 1
        try:
            cycle = self._apply(ev, idempotent=True)
        except sync.WindowFull as exc:
            self.dead_letters.append(DeadLetter(ev, f"window full: {exc}"))
            ing.quarantined += 1
            ing.window_full += 1
            self._meter.tally["window_full"] += 1
            return False, False, gap, lost
        except Exception as exc:  # noqa: BLE001 — quarantine boundary
            self.dead_letters.append(
                DeadLetter(ev, f"apply failed: {type(exc).__name__}: "
                               f"{exc}"))
            ing.quarantined += 1
            return False, False, gap, lost
        return True, cycle, gap, lost

    def _apply(self, ev: Event, idempotent: bool) -> bool:
        """Apply one event to the mirror through the job table
        (``twin.slots``): a QUEUEJOB takes a slot (``sync.WindowFull``
        when none is free), a job event the window does not hold (never
        queued here, or already ended) changes nothing, a NODEFAIL's
        victim is named by its slot, and a job that ends gives its slot
        back, cleared.  Returns whether a decision cycle is due."""
        span = self._meter.span
        with span("twin.slots"):
            if ev.kind == EventKind.QUEUEJOB:
                slot = self.slots.assign(ev.job_id)
            elif ev.kind in (EventKind.RUNJOB, EventKind.JOBOBIT):
                slot = self.slots.slot(ev.job_id)
                if slot < 0:
                    return False
            else:
                slot = -1
                victim = int(ev.payload.get("victim_job", -1))
                if victim >= 0:
                    ev = dataclasses.replace(ev, payload={
                        **ev.payload,
                        "victim_job": float(self.slots.slot(victim))})
            job = ev.job_id
            if slot >= 0:
                ev = dataclasses.replace(ev, job_id=slot)
        self._capture_residual(ev)
        try:
            self.state, cycle = sync.apply_event(self.state, ev,
                                                 idempotent=idempotent)
        except Exception:
            if (ev.kind == EventKind.QUEUEJOB
                    and int(self.state.jobs.state[slot]) == INVALID):
                self.slots.release(job)
            raise
        if (ev.kind == EventKind.JOBOBIT
                and int(self.state.jobs.state[slot]) == DONE):
            with span("twin.slots"):
                self.state = sync.release(self.state, slot)
                self.slots.release(job)
                self._meter.tally["recycled"] += 1
        return cycle

    def _resync_jobs(self, view: dict) -> None:
        """The mirror and its job table rebuilt from ``view``; live jobs
        the window cannot hold are counted as refused."""
        self.state, self.slots, refused = sync.resync_jobs(self.state, view)
        self.telemetry.ingest.window_full += refused
        self._meter.tally["window_full"] += refused

    def _capture_residual(self, ev: Event) -> None:
        """§3.2 estimate-vs-true runtime residual: a JOBOBIT reveals the
        actual walltime (obit time − recorded start) of a job the twin
        only ever knew by its user estimate.  Recorded host-side into
        telemetry before the mirror forgets the start time;
        ``FanSpec.from_history`` fits its lognormal σ to these pairs.
        ``ev`` names its job by slot."""
        if ev.kind != EventKind.JOBOBIT or ev.job_id < 0:
            return
        start = float(self.state.jobs.start_t[ev.job_id])
        if start < 0.0:  # never started in the mirror — no ground truth
            return
        est = float(self.state.jobs.est_runtime[ev.job_id])
        self.telemetry.record_residual(est, ev.time - start)

    # ------------------------------------------------------------------
    def _decide_at_level(self, level: int, state: SimState):
        """One decision on ``state`` (the mirror, on the device) at the
        given ladder level (DESIGN.md §12).  Returns ``(decision,
        race_out, names, source)`` where ``names`` label the decision's
        forks and ``source`` ∈ {'pool', 'fallback'} says which pool the
        winning index refers to (the incumbent bookkeeping).  Level 0
        is the configured decision mode verbatim; a mode with nothing
        to shrink falls through level 1 to the static pool."""
        if level >= 3 and self._incumbent is not None:
            # hold the incumbent: one k=1 schedule pass, no comparison
            src, idx = self._incumbent
            base = self.pool if src == "pool" else self.fallback_pool
            pool1 = _fork_pool(base, idx)
            decision = self.engine.decide(state, pool1.spec,
                                          self.objective)
            return decision, None, pool1.names, self._incumbent
        if level >= 2 or (level == 1 and self.race is None
                          and self.fan is None and self.ensemble <= 1):
            # static fallback pool, single nominal future — the paper's
            # own baseline twin (also level 3 before any incumbent)
            decision = self.engine.decide(
                state, self.fallback_pool.spec, self.objective)
            return (decision, None, self.fallback_pool.names,
                    ("fallback", None))
        if level == 1:
            shrink = self.guard.spec.shrink
            if self.race is not None:
                r = self.race
                fan1 = dataclasses.replace(
                    r.fan, n=max(r.f0, int(np.ceil(r.fan.n * shrink))))
                bm = (r.budget_ms * shrink
                      if getattr(r, "budget_ms", None) else r.budget_ms)
                shrunk = dataclasses.replace(r, fan=fan1, budget_ms=bm)
                decision, race_out = self.engine.decide_race(
                    state, self.pool.spec, shrunk,
                    objective=self.objective)
                return decision, race_out, self.pool.names, ("pool", None)
            if self.fan is not None:
                fan1 = dataclasses.replace(
                    self.fan, n=max(1, int(np.ceil(self.fan.n * shrink))))
                decision = self.engine.decide_fan(
                    state, self.pool.spec, fan1,
                    objective=self.objective)
                return decision, None, self.pool.names, ("pool", None)
            # ensemble: shrink member count (key consumption below is
            # identical at levels 0 and 1 — snapshot determinism)
            self._key, sub = jax.random.split(self._key)
            n1 = max(2, int(np.ceil(self.ensemble * shrink)))
            decision = self.engine.decide_ensemble(
                state, self.pool.spec, sub, n_ens=n1,
                noise=self.ensemble_noise, objective=self.objective)
            return decision, None, self.pool.names, ("pool", None)
        # level 0 — the configured decision mode
        if self.race is not None:
            decision, race_out = self.engine.decide_race(
                state, self.pool.spec, self.race,
                objective=self.objective)
            return decision, race_out, self.pool.names, ("pool", None)
        if self.fan is not None:
            decision = self.engine.decide_fan(
                state, self.pool.spec, self.fan,
                objective=self.objective)
            return decision, None, self.pool.names, ("pool", None)
        if self.ensemble > 1:
            self._key, sub = jax.random.split(self._key)
            decision = self.engine.decide_ensemble(
                state, self.pool.spec, sub,
                n_ens=self.ensemble, noise=self.ensemble_noise,
                objective=self.objective)
            return decision, None, self.pool.names, ("pool", None)
        decision = self.engine.decide(state, self.pool.spec,
                                      self.objective)
        return decision, None, self.pool.names, ("pool", None)

    def _decision_cycle(self, t: float) -> None:
        """④→⑦ : sync, simulate, select, feed back — under the deadline
        guard's ladder when one is configured."""
        span = self._meter.span
        if self.free_nodes_probe is not None:
            with span("twin.resync"):
                self.state = sync.resync_free_nodes(
                    self.state, self.free_nodes_probe())

        with span("twin.slots"):
            packed, ids = sync.pack(self.state, self.slots)
        with span("twin.upload"):
            state = telemetry.upload(packed)

        level = self.guard.plan() if self.guard is not None else 0
        with telemetry.StopWatch(self._clock) as sw:
            with span("twin.decide"):
                decision, race_out, names, source = \
                    self._decide_at_level(level, state)
            with span("twin.fetch"):
                # every result in one read, which waits for the device
                words = fetch(decision.words)
        with span("twin.unpack"):
            job_ids = self._record_cycle(t, level, sw.seconds, decision,
                                         race_out, names, source, words,
                                         ids)
        if job_ids:
            # ⑦ qrun — the physical system will emit RUNJOB events that
            # flow back through the bus and insert predicted-end events.
            with span("twin.qrun"):
                self.qrun(job_ids, t)

    def _record_cycle(self, t: float, level: int, seconds: float, decision,
                      race_out, names, source, words: np.ndarray,
                      ids: np.ndarray) -> List[int]:
        """The decision's results, read to the host as ``words``
        (``Decision.packed``), recorded as one ``CycleRecord``; returns
        the jobs to ``qrun``, by their ids (``ids[i]`` is the job in row
        ``i`` of the packed mirror)."""
        guard_fields = {}
        if self.guard is not None:
            missed, margin = self.guard.observe(level, seconds)
            guard_fields = dict(
                guard_level=level,
                deadline_s=self.guard.spec.budget_s,
                margin_s=margin, deadline_miss=missed)

        view = decision.unpack(words)
        with self._meter.span("twin.slots"):
            job_ids = ids[np.nonzero(view.run_mask)[0]].tolist()
        # decisions are reported by family name + θ ("WFP",
        # "wfp[a=2,tau=600]", ...); pool position stays the tie-break.
        win_idx = view.policy_index
        winner = names[win_idx]
        src, idx = source
        self._incumbent = (src, win_idx) if idx is None else (src, idx)
        costs = {name: float(c) for name, c in zip(names, view.costs)}
        # the goal's per-term device-computed breakdown for ALL k forks
        # (policy -> term -> cost): downstream reports (radar areas,
        # summarize-style tables) consume this instead of recomputing
        # costs on the host from raw metrics.
        term_costs = {name: {term: float(v[i])
                             for term, v in view.cost_terms.items()}
                      for i, name in enumerate(names)}
        # fan/ensemble decisions carry device-computed per-policy
        # uncertainty (DESIGN.md §10); record it as-is, no host math.
        cost_ci = {}
        fan_width = {}
        if view.cost_ci is not None:
            cost_ci = {name: float(c) for name, c in zip(names, view.cost_ci)}
        if view.fan_width is not None:
            fan_width = {name: float(w)
                         for name, w in zip(names, view.fan_width)}
        race_fields = {}
        if race_out is not None:
            race_fields = dict(
                race_rungs=len(race_out.rungs),
                race_members=int(race_out.members),
                race_separation=float(np.min(race_out.separation)),
                race_stopped=race_out.stopped)
        self.telemetry.record(telemetry.CycleRecord(
            time=t, wall_seconds=seconds, policy=winner,
            costs=costs, n_started=len(job_ids), started_jobs=job_ids,
            objective=str(self.objective), term_costs=term_costs,
            cost_ci=cost_ci, fan_width=fan_width,
            fan_size=decision.fan_size, live_jobs=len(ids),
            **race_fields, **guard_fields))
        return job_ids

    @_metered
    def flush(self) -> bool:
        """End-of-stream reconcile (the emulator's ``on_quiesce`` hook):
        when the producer has quiesced but jobs look unfinished, any
        still-pending sequence holes can never heal — declare them lost,
        rebuild from the authoritative probe, and run one more decision
        cycle if the reconciled mirror still holds queued work.  Returns
        True iff a cycle ran (progress was possible).  A clean stream
        never reaches here with pending holes or queued jobs, so the
        happy path is untouched."""
        ing = self.telemetry.ingest
        newly = self._tracker.flush()
        if newly:
            ing.lost += newly
        with self._meter.span("twin.resync"):
            if self.jobs_probe is not None:
                self._resync_jobs(self.jobs_probe())
                ing.resyncs += 1
            queued = bool((self.state.jobs.state == QUEUED).any())
            now = float(self.state.now) if queued else 0.0
        if queued:
            self._decision_cycle(now)
            return True
        return False

    @_metered
    def attach(self) -> bool:
        """Start beside a scheduler that is already running: build the
        mirror from its job view (``jobs_probe``, the qstat analogue),
        keyed by external id, and its clock; read the bus from its end
        on (the view holds what came before); and decide at once if
        anything is queued.  Returns True iff a cycle ran."""
        if self.jobs_probe is None:
            raise ValueError("attach() needs a jobs_probe")
        with self._meter.span("twin.attach"):
            view = self.jobs_probe()
            self._resync_jobs(view)
            self.state = self.state._replace(now=np.float32(
                max(float(self.state.now), float(view["now"]))))
            head = len(self.bus)
            self.bus.restore_offsets({self.CONSUMER: head})
            self._tracker.max_seen = max(self._tracker.max_seen, head - 1)
            queued = bool((self.state.jobs.state == QUEUED).any())
        if queued:
            self._decision_cycle(float(self.state.now))
        return queued

    # ------------------------------------------------------------------
    def recover(self) -> None:
        """Rebuild the mirror from a full bus replay (twin restart)."""
        self.state = empty_state(self.state.jobs.capacity,
                                 int(self.state.total_nodes), xp=np)
        self.slots = sync.JobSlots(self.state.jobs.capacity)
        for ev in self.bus.replay():
            self._apply(ev, idempotent=False)

    # -- crash-safe snapshots (DESIGN.md §12) ---------------------------
    def snapshot(self, manager, step: Optional[int] = None,
                 app_extra: Optional[Dict] = None) -> str:
        """Serialize the ENTIRE decision-relevant twin runtime through
        ``checkpoint.CheckpointManager``: the SimState mirror, its job
        table's id of each slot and the RNG key ride the array tree (bitwise npz round-trip); the consumer
        offset, SeqTracker, guard ladder state, incumbent, dead letters,
        and telemetry ride the JSON ``extra``.  A twin built with the
        same configuration and ``restore()``d from this snapshot
        produces the uninterrupted run's remaining decision sequence
        bitwise (benchmarks/chaos.py gates this end to end).  ``step``
        defaults to the number of recorded cycles.  ``app_extra`` lets
        the caller (e.g. ``twin_loop`` persisting the emulator + bus for
        cross-process resume) ride JSON payload in the same manifest."""
        step = len(self.telemetry.cycles) if step is None else int(step)
        tm = self.telemetry
        extra = {
            "consumer_offset": int(
                self.bus.snapshot_offsets().get(self.CONSUMER, 0)),
            "tracker": self._tracker.to_dict(),
            "guard": (self.guard.to_dict()
                      if self.guard is not None else None),
            "incumbent": (list(self._incumbent)
                          if self._incumbent is not None else None),
            "dead_letters": [[dl.event.to_dict(), dl.reason]
                             for dl in self.dead_letters],
            "telemetry": {
                "cycles": [dataclasses.asdict(c) for c in tm.cycles],
                "job_start_policy": {str(k): v for k, v in
                                     tm.job_start_policy.items()},
                "runtime_residuals": [[e, a] for e, a
                                      in tm.runtime_residuals],
                "ingest": tm.ingest.as_dict(),
            },
        }
        if app_extra is not None:
            extra["app"] = app_extra
        return manager.save(step, {"state": self.state, "key": self._key,
                                   "slots": self.slots.ids},
                            _jsonable(extra))

    def restore(self, manager,
                step: Optional[int] = None) -> Tuple[int, Optional[Dict]]:
        """Inverse of ``snapshot`` — call on a twin built with the SAME
        configuration (pool/objective/fan/race/guard/engine are code,
        not checkpoint payload).  Also rewinds the bus consumer offset,
        so the next ``pump()`` resumes exactly where the snapshot cut.
        Returns ``(step_restored, app_extra_or_None)``."""
        step = manager.latest_step() if step is None else int(step)
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint to restore under {manager.root!r}")
        target = {"state": self.state, "key": self._key,
                  "slots": self.slots.ids}
        tree, extra = manager.restore(step, target)
        self.state = jax.tree.map(np.asarray, tree["state"])
        self.slots = sync.JobSlots.from_ids(tree["slots"])
        self._key = jnp.asarray(tree["key"])
        self.bus.restore_offsets(
            {self.CONSUMER: int(extra["consumer_offset"])})
        self._tracker = SeqTracker.from_dict(extra["tracker"])
        if self.guard is not None:
            self.guard.restore(extra.get("guard"))
        inc = extra.get("incumbent")
        self._incumbent = (inc[0], int(inc[1])) if inc else None
        self.dead_letters = [
            DeadLetter(Event.from_dict(e), r)
            for e, r in extra.get("dead_letters", [])]
        tmd = extra.get("telemetry", {})
        tm = telemetry.Telemetry()
        tm.cycles = [telemetry.CycleRecord(**c)
                     for c in tmd.get("cycles", [])]
        tm.job_start_policy = {int(k): v for k, v in
                               tmd.get("job_start_policy", {}).items()}
        tm.runtime_residuals = [(float(e), float(a)) for e, a in
                                tmd.get("runtime_residuals", [])]
        tm.ingest = telemetry.IngestStats(**tmd.get("ingest", {}))
        self.telemetry = tm
        return step, extra.get("app")
