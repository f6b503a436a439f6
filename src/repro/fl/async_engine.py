"""Event-driven ASYNCHRONOUS federation: staleness-aware FedBuff over the
packed wire (Nguyen et al. '22 buffered async aggregation composed with
FLoCoRA's quantized low-rank messages).

The paper's loop is synchronous; production fleets are not. This engine
replaces round lockstep with a VIRTUAL-CLOCK discrete-event simulation:

  * DISPATCH — the server samples an idle client, broadcasts the current
    global adapters truncated to the client's rank (shared codec path:
    ``flocora.server_downlink`` / ``broadcast``), and schedules the
    update's arrival with a pluggable :class:`~repro.fl.traces.FleetTrace`
    (lognormal compute+network latency per rank tier, periodic
    availability windows, deterministic replay from a seed);
  * ARRIVAL — the client's PACKED wire message (uint32 payloads + fp32
    sidecars, rank-tagged header; ``flocora.client_uplink``) enters a
    staleness-aware FedBuff buffer: its weight is discounted by
    ``2^(-staleness / half_life)`` where staleness is the number of
    global versions the server advanced since the client's dispatch;
  * FLUSH — every ``buffer_size`` arrivals the buffer aggregates into a
    new global version in ONE rank-bucketed pass on the fused
    ``dequant_agg`` kernel (:meth:`FedBuffAggregator.flush`); with
    ``FLoCoRAConfig.flat_wire`` (default) the buffered messages are
    FLAT-TREE wire leaves (core/flat.py), so a whole buffer's unpack +
    dequantize + staleness-weighted reduce is ONE fused kernel launch
    per rank bucket, not one per adapter leaf. FedBuff
    applies averaged client DELTAS, not averaged models: the new global
    is ``g + server_lr * (mean_u - mean_start)`` where ``mean_u`` is the
    fused buffered packed sum and ``mean_start`` the same
    discounted-weight mean over the broadcasts those clients trained
    from (both zero-padded to the server rank). A stale update therefore
    contributes exactly its LOCAL progress — its outdated base model
    cancels instead of dragging the global backward — and a buffer of
    all-fresh updates at ``server_lr=1`` reproduces the sync FedAvg of
    that buffer (exactly when quantization is off; with it, deltas are
    measured against the dequantized broadcast each client actually
    received, per the wire). The history records the
    (virtual time, client loss, TCC bytes) trajectory — plus bytes AND
    virtual seconds to a target metric via :func:`time_to_target`.

MICRO-BATCHED EXECUTION. Simulating one jitted program per arrival would
be dispatch-bound; instead, pending arrivals within a virtual-time
window (``microbatch_window`` after the earliest pending event) are
grouped BY RANK and each group trains as one vmapped program through
``make_staggered_cohort_trainer`` (per-client start trees — arrivals in
a group may have been dispatched from different global versions). Group
client dims pad to a pow2, so total recompiles are bounded by
#distinct-ranks x log2(max micro-batch) — never by #arrivals.

DETERMINISM AND RESUME. Every stochastic choice (client sampling, batch
shuffling, trace latency) is drawn from a generator keyed by
``(seed, domain, ids)`` — a pure function of the simulation state, with
no mutable RNG stream. Checkpoints (``repro.checkpoint``, atomic npz +
JSON manifest) therefore round-trip the FULL engine state — virtual
clock, global version, event queue, in-flight broadcasts and computed
uplinks, cumulative byte accounting, history — and a killed-then-resumed
run replays the remaining events BIT-EXACTLY (checkpoints align to flush
boundaries, so the FedBuff buffer is empty by construction; this is
asserted). ``try_resume`` restores everything.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step, restore
from repro.core import flocora, lora
from repro.core.aggregation import FedBuffAggregator
from repro.core.flocora import FLoCoRAConfig
from repro.core.quant import gaussian_epsilon
from repro.fl.client import ClientConfig, cohort_steps, natural_steps, \
    make_staggered_cohort_trainer, pad_cohort_batches, pow2_pad, \
    stack_local_batches, unstack_cohort
from repro.fl.server import WireAccounting
from repro.fl.traces import FleetTrace
from repro.obs import metrics as obsm
from repro.obs import trace as obst
from repro.utils.tree import tree_bytes

Array = jax.Array

# rng key domains (traces.py owns TAG_LATENCY = 0xA1)
TAG_SAMPLE = 0xB1     # which idle client to dispatch
TAG_BATCH = 0xB2      # a dispatched client's local batch shuffle


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Engine knobs for the asynchronous FedBuff loop."""
    total_arrivals: int = 200    # stop after this many buffered arrivals
    concurrency: int = 8         # clients kept in flight
    buffer_size: int = 10        # FedBuff K: flush every K arrivals
    streaming_agg: bool = False  # fold flat arrivals at add time (O(1)
    #                              flush cost/memory in buffer_size)
    half_life: float = 4.0       # staleness discount half-life (versions)
    server_lr: float = 1.0       # scale on the applied mean flush delta
    microbatch_window: float = 0.0  # virtual-seconds arrival grouping
    strict_compiles: bool = False  # raise if a steady-state streaming
    #                                fold recompiles (obs.CompileWatchdog)
    seed: int = 0
    eval_every: int = 5          # eval_fn every N flushes
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 10   # checkpoint every N flushes

    def __post_init__(self):
        if min(self.total_arrivals, self.concurrency, self.buffer_size,
               self.eval_every, self.checkpoint_every) < 1:
            raise ValueError("total_arrivals/concurrency/buffer_size/"
                             "eval_every/checkpoint_every must be >= 1")
        if self.half_life <= 0:
            raise ValueError("half_life must be > 0")
        if self.server_lr <= 0:
            raise ValueError("server_lr must be > 0")
        if self.microbatch_window < 0:
            raise ValueError("microbatch_window must be >= 0")


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-not-yet-buffered client update."""
    cid: int
    rank: int
    version: int          # global version the client trained from
    dispatch_idx: int     # global dispatch counter (rng/trace key)
    t_dispatch: float
    t_arrival: float
    n_k: int              # client sample count (aggregation weight)
    start: Any            # broadcast fp tree at `rank` (None if dropped)
    msg: Any = None       # computed packed uplink (micro-batch cache)
    loss: float = float("nan")
    # CHURN: decided at dispatch from the trace (keyed (seed, cid,
    # dispatch_idx), so it replays on resume). A dropped dispatch never
    # trains and never buffers — its downlink bytes were wasted, and the
    # server notices at t_arrival (the deadline a live client would
    # have hit), dispatching a replacement
    dropped: bool = False
    down: int = 0         # downlink bytes spent at dispatch


def time_to_target(history: list[dict], key: str, target: float,
                   mode: str = "min") -> Optional[dict]:
    """Bytes AND virtual seconds to a target metric: the first history
    record whose ``key`` reaches ``target`` (``mode='min'``: <=, for
    losses; ``'max'``: >=, for accuracies). Returns {'version',
    't_virtual', 'tcc_bytes'} or None if never reached."""
    for h in history:
        if key not in h:
            continue
        hit = h[key] <= target if mode == "min" else h[key] >= target
        if hit:
            return {"version": h["version"], "t_virtual": h["t_virtual"],
                    "tcc_bytes": h["tcc_bytes"]}
    return None


class AsyncFLServer:
    """Virtual-clock asynchronous FL server (see module docstring).

    Same model/loss/data/eval contract as the sync :class:`FLServer`;
    ``trace`` supplies the fleet timing model and ``aggregator`` (a
    :class:`FedBuffAggregator`, default-constructed when omitted) the
    buffered staleness-discounted rule. ``trainer`` may be passed to
    share a compiled staggered-cohort trainer across engine instances
    (same loss_fn/ccfg), e.g. for steady-state benchmarking.
    """

    def __init__(self, model: dict, loss_fn: Callable,
                 client_data: list[dict], acfg: AsyncConfig,
                 ccfg: ClientConfig, fcfg: FLoCoRAConfig,
                 trace: Optional[FleetTrace] = None,
                 eval_fn: Optional[Callable] = None,
                 aggregator: Optional[FedBuffAggregator] = None,
                 trainer: Optional[Callable] = None,
                 registry: Optional[obsm.MetricsRegistry] = None,
                 tracer: Optional[obst.Tracer] = None):
        self.frozen = model["frozen"]
        self.global_train = model["train"]
        self.loss_fn = loss_fn
        self.client_data = client_data
        self.acfg, self.ccfg, self.fcfg = acfg, ccfg, fcfg
        self.trace = trace if trace is not None \
            else FleetTrace(seed=acfg.seed)
        self.eval_fn = eval_fn
        # telemetry: spans land on the VIRTUAL clock (with_clock view),
        # so exported timelines read in simulated fleet seconds
        self.registry = obsm.get_registry(registry)
        self.tracer = obst.get_tracer(tracer).with_clock(
            lambda: self.clock)
        if fcfg.error_feedback:
            # an EF residual assumes the NEXT encode of the same client
            # compensates the previous one; async staleness breaks that
            # invariant, so fail loudly instead of silently degrading
            # (this also bars SparsityConfig(require_ef=True) profiles:
            # async sparse uplinks need require_ef=False, accepting the
            # top-k bias FLASC's EF would otherwise absorb)
            raise ValueError("error feedback is not supported by the "
                             "async engine")
        sched = fcfg.rank_schedule
        if sched is not None and sched.n_clients != len(client_data):
            raise ValueError(
                f"rank_schedule covers {sched.n_clients} clients, fleet "
                f"has {len(client_data)}")
        # lazy Population fleets (duck-typed: rank_for/sample_cid/
        # schedule_steps/shard_size) carry their own rank tiers; an
        # explicit RankSchedule overrides
        self._pop = client_data \
            if hasattr(client_data, "sample_cid") else None
        if self._pop is not None and sched is None \
                and self._pop.max_rank > fcfg.rank:
            raise ValueError(
                f"population max tier rank {self._pop.max_rank} "
                f"exceeds the server rank {fcfg.rank}")
        if aggregator is None:
            aggregator = FedBuffAggregator()
        if not isinstance(aggregator, FedBuffAggregator):
            raise ValueError(
                f"async engine requires a FedBuffAggregator, got "
                f"{type(aggregator).__name__}")
        if aggregator.r_target is not None \
                and aggregator.r_target != fcfg.rank:
            # the delta flush applies at the global tree's rank: any
            # other target would shape-error mid-run, so fail at config
            # time like the sync server does
            raise ValueError(
                f"async aggregator r_target={aggregator.r_target} must "
                f"match the server rank {fcfg.rank}")
        fields: dict[str, Any] = {"pending": list(aggregator.pending),
                                  "streams": dict(aggregator.streams)}
        if acfg.streaming_agg:
            fields["streaming"] = True
        if acfg.strict_compiles:
            # zero-steady-state-compile invariant, enforced at runtime:
            # every streaming fold after an accumulator's first raises
            # CompileBudgetExceeded if the backend compiled
            fields["strict_compiles"] = True
        if aggregator.half_life is None:
            fields["half_life"] = acfg.half_life    # config-threaded
        if aggregator.r_target is None:
            fields["r_target"] = fcfg.rank
        self.aggregator = dataclasses.replace(aggregator, **fields)
        self.trainer = trainer if trainer is not None \
            else make_staggered_cohort_trainer(loss_fn, ccfg)
        # fixed schedule length across the fleet: the staggered cohort
        # program's (steps, B) never changes, only (rank, pow2 K)
        # retrace. A Population knows its schedule in O(1); the eager
        # path scans the materialized shards.
        self.schedule_steps = client_data.schedule_steps(ccfg) \
            if self._pop is not None else cohort_steps(client_data, ccfg)
        hetero = self._pop is not None and sched is None \
            and self._pop.mixed_ranks
        self.wire = WireAccounting(fcfg, registry=self.registry,
                                   hetero=hetero)
        # -- simulation state (everything below round-trips checkpoints)
        self.clock = 0.0
        self.version = 0
        self.n_dispatched = 0
        self.n_arrived = 0
        self.n_churned = 0
        self._wasted_cum = 0
        self.n_flushes = 0
        self.inflight: dict[int, _InFlight] = {}   # dispatch_idx -> rec
        self.heap: list[tuple[float, int]] = []    # (t_arrival, idx)
        self._bcast_memo: dict[int, Any] = {}      # rank -> start tree
        self.history: list[dict] = []
        self._down_cum = 0
        self._up_cum = 0
        self._flush_stats: list[tuple[float, int, int]] = []
        self._flush_starts: list[Any] = []   # broadcast refs, || pending
        # streaming mode: running discounted-weight sum of the resized
        # start trees (mean_start's numerator), O(1) in buffer_size —
        # the streaming twin of _flush_starts
        self._start_sum: Any = None
        self._start_weight: float = 0.0
        self.initial_model_bytes = tree_bytes(self.frozen)
        self.program_keys: set[tuple[int, int]] = set()  # (rank, padK)
        self.ckpt = CheckpointManager(acfg.checkpoint_dir) \
            if acfg.checkpoint_dir else None

    # -- deterministic keyed randomness -------------------------------------
    def _rng(self, *key: int) -> np.random.Generator:
        """A fresh generator keyed by (seed, *key): every draw is a pure
        function of simulation ids, so resumed runs replay identically
        without serializing any RNG stream."""
        return np.random.default_rng([self.acfg.seed, *key])

    def _rank_for(self, cid: int) -> int:
        sched = self.fcfg.rank_schedule
        if sched is not None:
            return sched.rank_for(cid, self.version)   # versions anneal
        if self._pop is not None:
            return self._pop.rank_for(cid)             # device tier
        return self.fcfg.rank

    @property
    def tcc_bytes(self) -> int:
        """Shared-once initial model + every measured down/uplink."""
        return self.initial_model_bytes + self._down_cum + self._up_cum

    # -- dispatch -----------------------------------------------------------
    def _sample_cid(self, idx: int, busy: set) -> Optional[int]:
        """One dispatch candidate. A lazy Population rejection-samples
        against the (O(concurrency)) busy set — never enumerating the
        fleet; eager list fleets keep the explicit free-list draw."""
        if self._pop is not None:
            return self._pop.sample_cid(self._rng(TAG_SAMPLE, idx), busy)
        free = [c for c in range(len(self.client_data)) if c not in busy]
        if not free:
            return None
        return int(free[self._rng(TAG_SAMPLE, idx).integers(len(free))])

    def _dispatch_one(self) -> bool:
        """Sample an idle client, broadcast, schedule its arrival (or,
        for a churned dispatch, schedule the deadline at which the
        server will notice the update never came)."""
        busy = {f.cid for f in self.inflight.values()}
        idx = self.n_dispatched
        cid = self._sample_cid(idx, busy)
        if cid is None:
            return False
        rank = self._rank_for(cid)
        # churn is a trace draw keyed (seed, cid, dispatch_idx): known
        # at dispatch, replayed identically on resume
        dropped = self.trace.churned(cid, idx)
        start = None
        if not dropped:
            start = self._bcast_memo.get(rank)
            if start is None:
                # one pack+unpack per (version, rank): the memo is
                # cleared at every flush, and start trees are never
                # mutated, so in-flight records may share them
                start = flocora.broadcast(self.global_train, self.fcfg,
                                          rank=self.wire.bcast_rank(rank))
                self._bcast_memo[rank] = start
        down = self.wire.downlink_bytes(self.global_train, rank)
        self._down_cum += down
        self.wire.record_down(rank, down)
        # message sizes are symmetric, so the round trip on the trace's
        # wire is 2x the measured downlink
        t_arr = self.trace.arrival(cid, idx, rank, 2 * down, self.clock)
        if dropped or self._pop is None:
            # dropped dispatches never train, so their shard is never
            # materialized (n_k unused)
            n_k = 0 if dropped else \
                len(next(iter(self.client_data[cid].values())))
        else:
            n_k = self._pop.shard_size
        self.inflight[idx] = _InFlight(cid, rank, self.version, idx,
                                       self.clock, t_arr, n_k, start,
                                       dropped=dropped, down=down)
        heapq.heappush(self.heap, (t_arr, idx))
        self.n_dispatched += 1
        self.registry.set("fl.inflight", len(self.inflight))
        return True

    def _expected_arrivals(self) -> int:
        """Arrivals already buffered plus live (non-churned) dispatches
        still in flight — the dispatch guard, so churn pulls in extra
        dispatches instead of starving ``total_arrivals``."""
        return self.n_arrived + sum(1 for r in self.inflight.values()
                                    if not r.dropped)

    def _fill_pipeline(self) -> None:
        while (len(self.inflight) < self.acfg.concurrency
               and self._expected_arrivals() < self.acfg.total_arrivals):
            if not self._dispatch_one():
                break

    # -- micro-batched local training ---------------------------------------
    def _compute_microbatch(self) -> None:
        """Train every not-yet-computed in-flight update whose arrival
        falls within ``microbatch_window`` of the earliest pending
        event, grouped by rank — one staggered-cohort program per
        (rank, pow2 group)."""
        t0 = self.heap[0][0]
        horizon = t0 + self.acfg.microbatch_window
        by_rank: dict[int, list[int]] = {}
        for t, idx in self.heap:
            rec = self.inflight[idx]
            if t <= horizon and rec.msg is None and not rec.dropped:
                by_rank.setdefault(rec.rank, []).append(idx)
        for rank in sorted(by_rank):
            idxs = sorted(by_rank[rank],
                          key=lambda i: (self.inflight[i].t_arrival, i))
            self._train_group(rank, idxs)

    def _train_group(self, rank: int, idxs: list[int]) -> None:
        recs = [self.inflight[i] for i in idxs]
        datas = [self.client_data[r.cid] for r in recs]
        per = [stack_local_batches(self._rng(TAG_BATCH, r.cid,
                                             r.dispatch_idx),
                                   d, self.ccfg,
                                   steps=self.schedule_steps)
               for r, d in zip(recs, datas)]
        batches = {k: np.stack([p[k] for p in per]) for k in per[0]}
        n_steps = np.asarray(
            [min(natural_steps(d, self.ccfg), self.schedule_steps)
             for d in datas], np.int32)
        k_pad = pow2_pad(len(recs))
        batches, n_steps = pad_cohort_batches(batches, n_steps, k_pad)
        starts = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0),
            *([r.start for r in recs]
              + [recs[0].start] * (k_pad - len(recs))))
        self.program_keys.add((rank, k_pad))
        trained, losses = self.trainer(self.frozen, starts,
                                       jax.tree.map(jnp.asarray, batches),
                                       jnp.asarray(n_steps))
        losses = np.asarray(losses)
        for k, (rec, t_k) in enumerate(zip(recs, unstack_cohort(trained))):
            # density keys off the DISPATCH version (rec.version), a
            # pure function of checkpointed state — resumed runs emit
            # byte-identical uplinks. DP (when configured) privatizes
            # the delta vs rec.start with noise keyed by the dispatch
            # ids, so concurrent dispatches of one client never share a
            # noise draw and resume replays it bit-exactly
            rec.msg, _ = flocora.client_uplink(
                t_k, self.fcfg, rnd=rec.version, start=rec.start,
                dp_key=(rec.version, rec.cid, rec.dispatch_idx),
                dp_seed=self.acfg.seed)
            rec.loss = float(losses[k])

    # -- the event loop -----------------------------------------------------
    def step(self) -> Optional[dict]:
        """Process ONE event — an arrival, or a churned dispatch's
        deadline; returns the flush record when an arrival filled the
        buffer, else None."""
        if not self.heap:
            self._fill_pipeline()
            if not self.heap:
                raise RuntimeError("no events left "
                                   f"({self.n_arrived} arrivals done)")
        head = self.inflight[self.heap[0][1]]
        if head.msg is None and not head.dropped:
            self._compute_microbatch()
        t_arr, idx = heapq.heappop(self.heap)
        rec = self.inflight.pop(idx)
        self.clock = max(self.clock, t_arr)
        if rec.dropped:
            # CHURN: the update never arrives — the spent downlink was
            # wasted, the client slot frees, a replacement dispatches
            self.n_churned += 1
            self._wasted_cum += rec.down
            self.wire.record_wasted(rec.rank, rec.down, reason="churned")
            self.registry.inc("fl.clients_churned")
            self.registry.set("fl.inflight", len(self.inflight))
            self._fill_pipeline()
            return None
        staleness = self.version - rec.version
        density = self.fcfg.uplink_density(rec.version)
        up = self.wire.uplink_bytes(rec.rank, rec.msg, density) or 0
        self._up_cum += up
        self.wire.record_up(rec.rank, up, density)
        self.n_arrived += 1
        # one dispatch->arrival span per update, on VIRTUAL time
        self.tracer.event("fl/inflight", ts=rec.t_dispatch,
                          dur=t_arr - rec.t_dispatch, track="fl/async",
                          cid=rec.cid, rank=rec.rank,
                          version=rec.version, staleness=staleness)
        self.registry.observe("fl.staleness", staleness)
        self.registry.set("fl.inflight", len(self.inflight))
        self.aggregator.add(rec.msg, rec.n_k, staleness)
        self.registry.observe("fl.buffer_occupancy",
                              self.aggregator.buffered)
        if self.acfg.streaming_agg:
            self._fold_start(
                rec.start,
                self.aggregator.discounted_weight(rec.n_k, staleness))
        else:
            self._flush_starts.append(rec.start)
        self._flush_stats.append((rec.loss, staleness, rec.rank))
        out = None
        if self.aggregator.buffered >= self.acfg.buffer_size:
            out = self._flush()
        if self._expected_arrivals() < self.acfg.total_arrivals:
            self._dispatch_one()       # keep the pipeline full
        return out

    def _fold_start(self, start: Any, w: float) -> None:
        """Streaming twin of ``_flush_starts``: fold one arrival's
        broadcast into the running discounted-weight start sum, so
        mean_start at flush is an O(1) normalize like the uplink side."""
        target = self.aggregator.r_target or self.fcfg.rank
        s = lora.resize_tree_rank(start, target)
        if self._start_sum is None:
            self._start_sum = jax.tree.map(
                lambda x: w * x.astype(jnp.float32), s)
        else:
            self._start_sum = jax.tree.map(
                lambda a, x: a + w * x.astype(jnp.float32),
                self._start_sum, s)
        self._start_weight += float(w)

    def _apply_mean(self, mean_u: Any, mean_start: Any) -> None:
        """g <- g + server_lr * (mean_u - mean_start): the buffered
        updates contribute their LOCAL training progress relative to the
        broadcasts they each started from (see module docstring)."""
        lr = self.acfg.server_lr
        self.global_train = jax.tree.map(
            lambda g, mu, ms: (g.astype(jnp.float32)
                               + lr * (mu.astype(jnp.float32) - ms)
                               ).astype(g.dtype),
            self.global_train, mean_u, mean_start)

    def _apply_delta(self, mean_u: Any, weights: list[float]) -> None:
        w = np.asarray(weights, np.float32)
        wn = w / max(float(w.sum()), 1e-8)
        target = self.aggregator.r_target or self.fcfg.rank
        starts = [lora.resize_tree_rank(s, target)
                  for s in self._flush_starts]
        mean_start = jax.tree.map(
            lambda *xs: sum(float(a) * x.astype(jnp.float32)
                            for a, x in zip(wn, xs)), *starts)
        self._apply_mean(mean_u, mean_start)

    def _apply_delta_streaming(self, mean_u: Any) -> None:
        """O(1) flush apply: mean_start = start_sum / start_weight
        (mirrors the aggregator's zero-weight raise)."""
        if self._start_weight <= 0.0:
            raise ValueError("streaming flush with zero accumulated "
                             "start weight")
        inv = 1.0 / self._start_weight
        mean_start = jax.tree.map(lambda a: a * inv, self._start_sum)
        self._start_sum, self._start_weight = None, 0.0
        self._apply_mean(mean_u, mean_start)

    def _flush(self) -> dict:
        losses = [l for l, _, _ in self._flush_stats]
        stales = [s for _, s, _ in self._flush_stats]
        ranks: dict[str, int] = {}
        for _, _, r in self._flush_stats:
            ranks[str(r)] = ranks.get(str(r), 0) + 1
        n_buf = self.aggregator.buffered
        weights = [wt for _, wt in self.aggregator.pending]
        with self.tracer.span("fl/flush", track="fl/async",
                              version=self.version, n_flushed=n_buf):
            mean_u = self.aggregator.flush()  # fused buffered packed sum
            if self.acfg.streaming_agg:
                self._apply_delta_streaming(mean_u)
            else:
                self._apply_delta(mean_u, weights)
        self._flush_starts = []
        self._bcast_memo = {}          # broadcasts of the old version
        density = self.fcfg.uplink_density(self.version)
        self.version += 1
        self.n_flushes += 1
        self.registry.inc("fl.flushes")
        rec = {"version": self.version, "t_virtual": self.clock,
               "n_arrived": self.n_arrived, "n_flushed": n_buf,
               "n_churned": self.n_churned,
               "client_loss": float(np.mean(losses)),
               "staleness_mean": float(np.mean(stales)),
               "staleness_max": int(max(stales)),
               "flush_ranks": ranks,
               "down_bytes": self._down_cum, "up_bytes": self._up_cum,
               "tcc_bytes": self.tcc_bytes,
               # downlinks spent on dispatches that churned mid-round
               "wasted_bytes": self._wasted_cum,
               # schema-uniform with the sync history (None = dense);
               # the density of the version this flush advanced FROM
               "uplink_density": density}
        if self.fcfg.dp is not None:
            # each flush is one Gaussian release of the aggregate;
            # conservative RDP composition over versions so far
            eps = gaussian_epsilon(self.fcfg.dp.noise_multiplier,
                                   self.version, self.fcfg.dp.delta)
            rec["dp_epsilon"] = eps
            self.registry.set("fl.dp_epsilon", eps)
        self._flush_stats = []
        if self.eval_fn and self.n_flushes % self.acfg.eval_every == 0:
            rec.update({k: float(v) for k, v in
                        self.eval_fn(self.frozen,
                                     self.global_train).items()})
        self.history.append(rec)
        if self.ckpt and self.n_flushes % self.acfg.checkpoint_every == 0:
            self.save()
        return rec

    def run(self) -> list[dict]:
        """Drive the event loop to ``total_arrivals`` buffered arrivals
        (continuing from restored state after ``try_resume``), with a
        final partial flush so the history covers every update."""
        self._fill_pipeline()
        while self.n_arrived < self.acfg.total_arrivals:
            self.step()
        if self.aggregator.buffered:
            self._flush()
        return self.history

    # -- checkpoint/resume (full simulator state) ---------------------------
    def _start_template(self, rank: int) -> Any:
        """Shape/dtype template of a rank-``rank`` broadcast tree."""
        if self.wire.bcast_rank(rank) is None:
            return self.global_train
        return lora.resize_tree_rank(self.global_train, rank,
                                     method="slice")

    def _msg_template(self, rank: int, version: int = 0) -> Any:
        """Shape/dtype template of a rank-``rank`` packed/sparse uplink
        dispatched at global ``version`` (density annealing changes the
        sparse payload shapes between versions)."""
        zeros = jax.tree.map(jnp.zeros_like, self._start_template(rank))
        return flocora.client_uplink(zeros, self.fcfg, rnd=version)[0]

    def save(self) -> None:
        if self.ckpt is None:
            return
        # checkpoints align to flush boundaries: the FedBuff buffer is
        # empty by construction, so the buffered messages never need to
        # serialize — everything else does. The same alignment empties
        # the streaming accumulators (flush resets them) and the start
        # sum, so the streaming state checkpoints as its empty value;
        # mid-buffer accumulator round-trip is covered at unit level by
        # StreamingFlatAccumulator.state()/from_state.
        assert (not self.aggregator.pending and not self._flush_starts
                and self.aggregator.buffered == 0
                and self._start_sum is None), \
            "async checkpoint must align to a flush boundary"
        trees: dict[str, Any] = {"train": self.global_train}
        meta_if: dict[str, dict] = {}
        for idx, rec in self.inflight.items():
            if rec.start is not None:
                # churned dispatches carry no start tree (never train)
                trees[f"inflight_{idx}"] = rec.start
            if rec.msg is not None:
                # computed uplinks ride along so a resumed run never
                # recomputes them under a different micro-batch grouping
                trees[f"msg_{idx}"] = rec.msg
            meta_if[str(idx)] = {
                "cid": rec.cid, "rank": rec.rank, "version": rec.version,
                "t_dispatch": rec.t_dispatch, "t_arrival": rec.t_arrival,
                "n_k": rec.n_k, "has_msg": rec.msg is not None,
                "loss": rec.loss, "dropped": rec.dropped,
                "down": rec.down}
        self.ckpt.save(self.n_flushes, trees, metadata={
            "clock": self.clock, "version": self.version,
            "n_dispatched": self.n_dispatched,
            "n_arrived": self.n_arrived, "n_flushes": self.n_flushes,
            "n_churned": self.n_churned, "wasted_cum": self._wasted_cum,
            "down_cum": self._down_cum, "up_cum": self._up_cum,
            "heap": sorted(self.heap), "inflight": meta_if,
            "history": self.history})

    def try_resume(self) -> bool:
        if self.ckpt is None:
            return False
        step = latest_step(self.ckpt.directory)
        if step is None:
            return False
        # pass 1: the manifest metadata describes the in-flight trees'
        # ranks, from which the like-templates are rebuilt for pass 2
        _, man = restore(self.ckpt.directory, step,
                         {"train": self.global_train})
        meta = man["metadata"]
        like: dict[str, Any] = {"train": self.global_train}
        for s, m in meta["inflight"].items():
            if not m.get("dropped", False):
                like[f"inflight_{s}"] = self._start_template(m["rank"])
            if m["has_msg"]:
                like[f"msg_{s}"] = self._msg_template(m["rank"],
                                                      m["version"])
        trees, _ = restore(self.ckpt.directory, step, like)
        self.global_train = trees["train"]
        self.clock = meta["clock"]
        self.version = meta["version"]
        self.n_dispatched = meta["n_dispatched"]
        self.n_arrived = meta["n_arrived"]
        self.n_flushes = meta["n_flushes"]
        self.n_churned = meta.get("n_churned", 0)
        self._wasted_cum = meta.get("wasted_cum", 0)
        self._down_cum = meta["down_cum"]
        self._up_cum = meta["up_cum"]
        self.history = list(meta["history"])
        self._flush_stats = []
        self._start_sum, self._start_weight = None, 0.0
        for st in self.aggregator.streams.values():
            st.reset()      # checkpoint boundary == empty accumulators
        self.inflight = {}
        for s, m in meta["inflight"].items():
            idx = int(s)
            self.inflight[idx] = _InFlight(
                m["cid"], m["rank"], m["version"], idx, m["t_dispatch"],
                m["t_arrival"], m["n_k"], trees.get(f"inflight_{s}"),
                msg=trees.get(f"msg_{s}"), loss=m["loss"],
                dropped=m.get("dropped", False), down=m.get("down", 0))
        self.heap = [tuple(e) for e in meta["heap"]]
        heapq.heapify(self.heap)
        return True
