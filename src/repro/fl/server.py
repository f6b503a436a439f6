"""FL server orchestration: FLoCoRA rounds with fault tolerance.

Production-shaped features:
  * client sampling (uniform over C clients, K' = oversample*K sampled);
  * STRAGGLER MITIGATION: K' > K clients are dispatched, the aggregation
    takes the first K arrivals (simulated latency ordering) — the paper's
    synchronous FedAvg becomes deadline-robust;
  * CLIENT DROPOUT: a failed client (prob p_fail) contributes nothing;
    aggregation weights renormalize over survivors — a round never blocks;
  * RANK-BUCKETED COHORT ENGINE: with a heterogeneous rank profile
    (``FLoCoRAConfig.rank_schedule``) the surviving clients are grouped
    by adapter rank and each bucket runs as ONE jitted vmapped program
    (bucket sizes pad to pow2, so the compile count is bounded by
    #distinct-ranks x log2(max cohort)); uniform fleets keep the single
    vmapped cohort program (see fl/client.py);
  * WIRE-TRUE quantized exchange per the paper: broadcast truncates the
    global adapters to each client's rank, messages travel PACKED (uint32
    payloads + fp32 sidecars + rank-tagged header, core/messages.py) and
    the server aggregates the packed payloads on the fused dequant_agg
    kernel — per rank bucket when mixed — via a pluggable Aggregator
    strategy (zero-pad FedAvg, FLoRIST-style SVD recombination, FedBuff,
    optional error feedback). With ``FLoCoRAConfig.flat_wire`` (default)
    the dense quantized exchange rides the FLAT-TREE codec
    (core/flat.py): each uplink packs and each cohort aggregates in ONE
    fused kernel launch regardless of the adapter tree's leaf count,
    with byte-identical wire payloads;
  * atomic checkpoint/resume of (round, global adapters, sampler RNG) —
    a restarted server continues the exact run; the RNG bit-generator
    state rides the JSON manifest directly;
  * TCC accounting derived from MEASURED emitted message sizes (cached
    per rank): heterogeneous fleets sum per-client uplinks/downlinks
    instead of Eq. 2's uniform ``2 * one_way * rounds``, and the
    shared-once initial model is included.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flocora, messages
from repro.core.aggregation import Aggregator, ErrorFeedbackFedAvg, \
    FedAvgAggregator, FedBuffAggregator, ef_fold_dropped
from repro.core.flocora import FLoCoRAConfig
from repro.core.quant import gaussian_epsilon
from repro.checkpoint import CheckpointManager
from repro.fl.client import ClientConfig, cohort_steps, \
    make_cohort_trainer, pad_cohort_batches, pow2_pad, \
    stack_cohort_batches, unstack_cohort
from repro.fl.traces import FleetTrace
from repro.obs import metrics as obsm
from repro.obs import trace as obst
from repro.utils.tree import tree_bytes

Array = jax.Array

# rng key domain for client dropout draws: keyed by (seed, round, cid)
# like the trace latency draws, so a killed-and-resumed run reproduces
# every failure outcome (the draws never touch the mutable sampler
# stream). traces.py owns 0xA1/0xA2.
TAG_FAILURE = 0xA3


@dataclasses.dataclass
class ServerConfig:
    rounds: int = 100
    n_clients: int = 100
    clients_per_round: int = 10
    oversample: float = 1.0        # straggler mitigation: dispatch K'=o*K
    p_client_failure: float = 0.0  # simulated client dropout
    seed: int = 0
    eval_every: int = 5
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 25
    # FedBuff staleness discount half-life (in staleness units: straggler
    # arrival rank for sync rounds, global-version lag for async);
    # threaded into a FedBuffAggregator whose half_life is unset
    fedbuff_half_life: float = 4.0


class WireAccounting:
    """Measured wire-byte cache, shared by the sync (:class:`FLServer`)
    and async (``fl/async_engine.AsyncFLServer``) engines. Message size
    is determined by (rank, uplink density), so ONE measured emission
    per key is exact for the whole run; the uplink re-measure
    cross-checks that EF/quant/rank/sparsity changes never desynchronize
    the accounting. Downlinks always travel dense, so their cache keys
    stay per-rank.

    ``record_down``/``record_up`` additionally emit each ACTUAL
    transfer as labeled obs counters (``wire.down_bytes`` /
    ``wire.up_bytes`` by rank and uplink density) — the engines call
    them once per dispatched/surviving client, so the registry's view
    matches the cumulative TCC accounting."""

    def __init__(self, fcfg: FLoCoRAConfig,
                 registry: Optional[obsm.MetricsRegistry] = None,
                 hetero: bool = False):
        self.fcfg = fcfg
        self.registry = obsm.get_registry(registry)
        # hetero=True forces per-rank broadcast truncation even without a
        # RankSchedule — a lazy Population carries its rank tiers itself
        self.hetero = hetero
        self.down: dict[int, int] = {}
        self.up: dict[tuple[int, Optional[float]], int] = {}
        self.wasted = 0          # bytes spent on transfers that never
        #                          contributed (churned or straggled)

    def bcast_rank(self, rank: int) -> Optional[int]:
        """None keeps the uniform fleet's broadcast byte-identical to the
        classic path (no resize walk)."""
        if self.hetero or self.fcfg.rank_schedule is not None:
            return rank
        return None

    def downlink_bytes(self, global_train: Any, rank: int) -> int:
        got = self.down.get(rank)
        if got is None:
            msg = flocora.server_downlink(global_train, self.fcfg,
                                          self.bcast_rank(rank))
            got = messages.packed_wire_bytes(msg)
            self.down[rank] = got
        return got

    def uplink_bytes(self, rank: int, msg: Any = None,
                     density: Optional[float] = None) -> Optional[int]:
        """None when no uplink was emitted at this (rank, density) yet
        (callers fall back to the symmetric downlink size)."""
        got = self.up.get((rank, density))
        if got is None and msg is not None:
            got = messages.packed_wire_bytes(msg)
            self.up[(rank, density)] = got
        return got

    # -- labeled transfer counters (one call per actual transfer) -----------
    def record_down(self, rank: int, nbytes: int) -> None:
        self.registry.inc("wire.down_bytes", nbytes, rank=rank)
        self.registry.inc("wire.downlinks", rank=rank)

    def record_up(self, rank: int, nbytes: int,
                  density: Optional[float] = None) -> None:
        self.registry.inc("wire.up_bytes", nbytes, rank=rank,
                          density=density)
        self.registry.inc("wire.uplinks", rank=rank, density=density)

    def record_wasted(self, rank: int, nbytes: int,
                      reason: str = "straggled") -> None:
        """Bytes that were genuinely transferred but never contributed
        to the global model: a straggler's discarded round trip, a
        churned client's spent downlink. Already counted in
        down/up_bytes — this is the waste-attribution view."""
        self.wasted += nbytes
        self.registry.inc("wire.wasted_bytes", nbytes, rank=rank,
                          reason=reason)


class FLServer:
    """Simulates the paper's FL loop (Fig. 1) over arbitrary models.

    model: dict with 'frozen'/'train' trees (train = FLoCoRA adapters);
    loss_fn(frozen, train, batch); client_data: list of per-client dict
    datasets (numpy); eval_fn(frozen, train) -> metrics dict;
    aggregator: Aggregator strategy (defaults to FedAvg, or its
    EF-compensated variant when fcfg.error_feedback is set).
    """

    def __init__(self, model: dict, loss_fn: Callable,
                 client_data: list[dict], scfg: ServerConfig,
                 ccfg: ClientConfig, fcfg: FLoCoRAConfig,
                 eval_fn: Optional[Callable] = None,
                 aggregator: Optional[Aggregator] = None,
                 trace: Optional[FleetTrace] = None,
                 registry: Optional[obsm.MetricsRegistry] = None,
                 tracer: Optional[obst.Tracer] = None):
        self.frozen = model["frozen"]
        self.global_train = model["train"]
        self.loss_fn = loss_fn
        self.client_data = client_data
        self.scfg, self.ccfg, self.fcfg = scfg, ccfg, fcfg
        self.eval_fn = eval_fn
        # deadline cohorts: when a FleetTrace is given, straggler
        # ordering uses TRACE arrival times (keyed by (seed, cid, round),
        # resume-deterministic) instead of the mutable sampler stream
        self.trace = trace
        # telemetry: None means the process defaults (disabled unless
        # obs.enable() ran) — both are injectable per server
        self.registry = obsm.get_registry(registry)
        self.tracer = obst.get_tracer(tracer)
        self.rng = np.random.default_rng(scfg.seed)
        self.round = 0
        self.history: list[dict] = []
        self.trainer = make_cohort_trainer(loss_fn, ccfg)
        # fixed schedule length across ALL clients: the cohort program's
        # shape never changes between rounds (only distinct cohort sizes
        # K retrace), and small clients are masked, not over-trained.
        # A lazy Population knows its own (O(1)) schedule; the eager path
        # scans the materialized shards.
        self.cohort_schedule_steps = client_data.schedule_steps(ccfg) \
            if hasattr(client_data, "schedule_steps") \
            else cohort_steps(client_data, ccfg)
        self.rank_schedule = fcfg.rank_schedule
        # lazy Population fleets carry their own rank tiers (per device
        # tier); a RankSchedule overrides when both are present
        self._pop_ranks = None
        if self.rank_schedule is None \
                and hasattr(client_data, "rank_for"):
            if client_data.max_rank > fcfg.rank:
                raise ValueError(
                    f"population max tier rank {client_data.max_rank} "
                    f"exceeds the server rank {fcfg.rank}")
            self._pop_ranks = client_data
        if self.rank_schedule is not None \
                and self.rank_schedule.n_clients != scfg.n_clients:
            raise ValueError(
                f"rank_schedule covers {self.rank_schedule.n_clients} "
                f"clients, server has {scfg.n_clients}")
        # EF engages when the uplink is actually lossy: quantized and/or
        # sparse (a sparse-only fp wire still drops mass to compensate)
        ef_wanted = fcfg.error_feedback and (fcfg.qcfg.enabled
                                             or fcfg.sparsity_active)
        if aggregator is None:
            aggregator = ErrorFeedbackFedAvg(fcfg.qcfg, fcfg.rank) \
                if ef_wanted else FedAvgAggregator(fcfg.qcfg, fcfg.rank)
        elif ef_wanted != isinstance(aggregator, ErrorFeedbackFedAvg):
            # the uplink encode (fcfg.error_feedback) and the residual
            # store (aggregator type) must agree, or EF silently degrades
            # to plain RTN / maintains dead residuals
            raise ValueError(
                "error_feedback={} (quant {}) requires {} aggregator, got "
                "{}".format(fcfg.error_feedback,
                            "on" if fcfg.qcfg.enabled else "off",
                            "an ErrorFeedbackFedAvg" if ef_wanted
                            else "a non-EF",
                            type(aggregator).__name__))
        if isinstance(aggregator, FedBuffAggregator) \
                and aggregator.half_life is None:
            # half_life is a config field, not a hard-coded default:
            # thread it from ServerConfig (copy, so the caller's instance
            # stays reusable; the pending buffer must not alias)
            aggregator = dataclasses.replace(
                aggregator, half_life=scfg.fedbuff_half_life,
                pending=list(aggregator.pending))
        sched = fcfg.rank_schedule
        if sched is not None:
            mixed = (len(set(sched.client_ranks)) > 1
                     or sched.max_rank != fcfg.rank
                     or sched.anneal_every > 0)
            if mixed and not isinstance(
                    aggregator, (FedAvgAggregator, FedBuffAggregator)):
                # only aggregators with a rank-bucketed path may see a
                # mixed-rank cohort: fail at config time, not with a
                # shape error mid-round
                raise ValueError(
                    f"{type(aggregator).__name__} has no rank-bucketed "
                    "aggregation path for mixed-rank cohorts; use "
                    "FedAvgAggregator (or a subclass such as "
                    "SVDRecombinationAggregator) or FedBuffAggregator")
            explicit = getattr(aggregator, "r_target", None)
            if explicit is not None and explicit < sched.max_rank:
                # a target below a scheduled client rank would let the
                # global tree's shape float with each round's cohort
                raise ValueError(
                    f"aggregator r_target={explicit} is below the rank "
                    f"schedule's max rank {sched.max_rank}")
        if getattr(aggregator, "r_target", 0) is None:
            # pin the global tree's rank on a copy so the caller's
            # instance stays reusable across servers — mutable stores
            # (EF residuals, served ranks) must not alias the copy
            fields: dict[str, Any] = {"r_target": fcfg.rank}
            if hasattr(aggregator, "residuals"):
                fields["residuals"] = dict(aggregator.residuals)
            if hasattr(aggregator, "served_ranks"):
                fields["served_ranks"] = dict(aggregator.served_ranks)
            if hasattr(aggregator, "pending"):
                fields["pending"] = list(aggregator.pending)
            aggregator = dataclasses.replace(aggregator, **fields)
        self.aggregator = aggregator
        self.ckpt = CheckpointManager(scfg.checkpoint_dir) \
            if scfg.checkpoint_dir else None
        # TCC is derived from MEASURED emitted message sizes, cached per
        # client rank by the shared WireAccounting (also used by the
        # async engine)
        hetero = self._pop_ranks is not None \
            and self._pop_ranks.mixed_ranks
        self.wire = WireAccounting(fcfg, registry=self.registry,
                                   hetero=hetero)
        self.initial_model_bytes = tree_bytes(self.frozen)
        self._tcc_cum = self.initial_model_bytes

    @property
    def round_bytes_per_client(self) -> int:
        """2x the MEASURED one-way message size at the server rank
        (lazy: the first access emits and measures a downlink)."""
        return 2 * self._downlink_bytes(self.fcfg.rank)

    # -- per-rank wire accounting (measured, not shape math) ----------------
    def _rank_for(self, cid: int, rnd: int) -> int:
        if self.rank_schedule is not None:
            return self.rank_schedule.rank_for(cid, rnd)
        if self._pop_ranks is not None:
            return self._pop_ranks.rank_for(cid)
        return self.fcfg.rank

    def _client_failed(self, rnd: int, cid: int) -> bool:
        """Keyed dropout draw — a pure function of (seed, round, cid),
        independent of the sampler stream and of checkpoint boundaries
        (i.i.d. draws from ``self.rng`` made resumed runs diverge)."""
        p = self.scfg.p_client_failure
        if p <= 0.0:
            return False
        rng = np.random.default_rng(
            [self.scfg.seed, TAG_FAILURE, rnd, cid])
        return bool(rng.random() < p)

    def _bcast_rank(self, rank: int) -> Optional[int]:
        return self.wire.bcast_rank(rank)

    def _downlink_bytes(self, rank: int) -> int:
        return self.wire.downlink_bytes(self.global_train, rank)

    def _uplink_bytes(self, rank: int, msg: Any = None,
                      density: Optional[float] = None) -> int:
        got = self.wire.uplink_bytes(rank, msg, density)
        if got is None:               # no uplink emitted yet at this rank
            return self._downlink_bytes(rank)
        return got

    # -- fault tolerance ----------------------------------------------------
    def save(self):
        if self.ckpt is None:
            return
        # bit-generator state is a plain dict of ints/strings — it rides
        # the JSON manifest as-is (no repr/eval round-trip)
        self.ckpt.save(self.round, {"train": self.global_train},
                       metadata={"round": self.round,
                                 "tcc_bytes": self._tcc_cum,
                                 "rng_state": self.rng.bit_generator.state})

    def try_resume(self) -> bool:
        if self.ckpt is None:
            return False
        got = self.ckpt.restore_latest({"train": self.global_train})
        if got is None:
            return False
        step, trees, man = got
        self.global_train = trees["train"]
        self.round = man["metadata"]["round"]
        # legacy manifests predate measured TCC: rebuild per Eq. 2
        self._tcc_cum = man["metadata"].get(
            "tcc_bytes",
            self.initial_model_bytes
            + self.round * self.scfg.clients_per_round
            * self.round_bytes_per_client)
        st = man["metadata"].get("rng_state")
        if isinstance(st, str):
            # legacy manifests stored repr(state); literal_eval migrates
            # them safely (plain dict of ints, never code)
            st = ast.literal_eval(st)
        if st:
            self.rng.bit_generator.state = st
        return True

    # -- tracing -------------------------------------------------------------
    def _span(self, name: str, **args):
        return self.tracer.span(name, track="fl/round", **args)

    def _settle(self, tree: Any) -> None:
        """With tracing on, wait for ``tree`` so that the open span holds
        its device time; with tracing off the round does not block."""
        if self.tracer.enabled:
            jax.block_until_ready(tree)

    # -- one round (paper Fig. 1) --------------------------------------------
    def run_round(self) -> dict:
        with self._span("fl/round", round=self.round):
            return self._run_round()

    def _run_round(self) -> dict:
        scfg, fcfg = self.scfg, self.fcfg
        rnd = self.round                      # schedules are 0-based
        k_target = scfg.clients_per_round
        k_dispatch = max(k_target, int(round(scfg.oversample * k_target)))
        sampled = self.rng.choice(scfg.n_clients, size=k_dispatch,
                                  replace=False)
        rank_of = {int(cid): self._rank_for(int(cid), rnd)
                   for cid in sampled}
        density = fcfg.uplink_density(rnd)
        # (1) broadcast precedes failure: downlink bytes are spent for
        # every dispatched client, at that client's rank
        down_bytes = 0
        for r in rank_of.values():
            b = self._downlink_bytes(r)
            down_bytes += b
            self.wire.record_down(r, b)

        survivors = [cid for cid in (int(c) for c in sampled)
                     if not self._client_failed(rnd, cid)]
        # a dropped client's downlink was spent for nothing
        wasted_bytes = 0
        for cid in sampled:
            cid = int(cid)
            if cid not in survivors:
                b = self._downlink_bytes(rank_of[cid])
                wasted_bytes += b
                self.wire.record_wasted(rank_of[cid], b,
                                        reason="dropped")
        if not survivors:
            # an all-dropout round still consumed its downlinks; record
            # it so history (and TCC curves) never have gaps — with the
            # SAME key set as an aggregating round (schema-asserted in
            # tests/test_obs.py)
            self.round += 1
            self._tcc_cum += down_bytes
            rec = {"round": self.round, "n_agg": 0,
                   "n_dropped": k_dispatch, "n_straggled": 0,
                   "client_loss": float("nan"), "cohort_ranks": {},
                   "down_bytes": down_bytes, "up_bytes": 0,
                   "round_bytes": down_bytes, "tcc_bytes": self._tcc_cum,
                   "wasted_bytes": wasted_bytes,
                   "uplink_density": density}
            if fcfg.dp is not None:
                rec["dp_epsilon"] = gaussian_epsilon(
                    fcfg.dp.noise_multiplier, self.round, fcfg.dp.delta)
            self.history.append(rec)
            if self.ckpt and self.round % self.scfg.checkpoint_every == 0:
                self.save()
            return rec

        # (2)+(3) RANK-BUCKETED ENGINE: survivors group by adapter rank;
        # each bucket's local runs execute as ONE jitted vmapped program
        # (pow2-padded client dim, per-client n_steps mask), then every
        # client emits its PACKED wire message at its own rank
        buckets: dict[int, list[int]] = {}
        for cid in survivors:
            buckets.setdefault(rank_of[cid], []).append(cid)
        if self.trace is not None:
            # DEADLINE COHORTS: arrival order comes from the fleet trace
            # (availability wait + compute + transfer at the client's
            # rank and measured message size), keyed (seed, cid, round) —
            # a pure function of simulation ids, so straggler outcomes
            # survive kill/resume bit-exactly
            latency = {cid: self.trace.arrival(
                cid, rnd, rank_of[cid],
                2 * self._downlink_bytes(rank_of[cid]), 0.0)
                for cid in survivors}
        else:
            latency = {cid: self.rng.exponential(1.0)
                       for cid in survivors}
        ef = isinstance(self.aggregator, ErrorFeedbackFedAvg)
        results = []
        for r in sorted(buckets):
            cids = buckets[r]
            with self._span("fl/broadcast", round=rnd, rank=r,
                            clients=len(cids)):
                g_bcast = flocora.broadcast(self.global_train, fcfg,
                                            rank=self._bcast_rank(r))
                datas = [self.client_data[cid] for cid in cids]
                with self._span("fl/stage_batches", round=rnd, rank=r):
                    batches, n_steps = stack_cohort_batches(
                        self.rng, datas, self.ccfg,
                        steps=self.cohort_schedule_steps)
                    if self.rank_schedule is not None:
                        # pow2-padded buckets bound compile count for
                        # mixed fleets; uniform fleets keep the exact-K
                        # classic shape
                        batches, n_steps = pad_cohort_batches(
                            batches, n_steps, pow2_pad(len(cids)))
                with self._span("fl/h2d", round=rnd, rank=r):
                    batches = jax.tree.map(jnp.asarray, batches)
                    self._settle(batches)
            with self._span("fl/client_train", round=rnd, rank=r,
                            clients=len(cids)):
                trained, losses = self.trainer(self.frozen, g_bcast,
                                               batches,
                                               jnp.asarray(n_steps))
                with self._span("fl/train_wait", round=rnd, rank=r):
                    losses = np.asarray(losses)
                    # no trainer time may leak into fl/pack
                    self._settle(trained)
            with self._span("fl/pack", round=rnd, rank=r,
                            clients=len(cids)):
                with self._span("fl/slice", round=rnd, rank=r,
                                clients=len(cids)):
                    per_client = unstack_cohort(trained)
                for k, (cid, t_k) in enumerate(zip(cids, per_client)):
                    res = self.aggregator.residual(cid, t_k) \
                        if ef else None
                    # start/dp_key engage only when fcfg.dp is set: the
                    # client's DELTA vs its broadcast is clipped+noised
                    # (keyed (round, cid)) before quantization
                    with self._span("fl/encode", client=cid):
                        msg, res = flocora.client_uplink(
                            t_k, fcfg, res, rnd=rnd, start=g_bcast,
                            dp_key=(rnd, cid), dp_seed=self.scfg.seed)
                    n_i = len(next(iter(datas[k].values())))
                    results.append((latency[cid], n_i, msg,
                                    float(losses[k]), r, cid, res))

        # every survivor transmitted its uplink (stragglers included)
        with self._span("fl/uplink", round=rnd, clients=len(results)):
            up_bytes = 0
            for r_i in results:
                b = self._uplink_bytes(r_i[4], r_i[2], density)
                up_bytes += b
                self.wire.record_up(r_i[4], b, density)

        # straggler policy: first K arrivals win; a straggler's whole
        # round trip (downlink + discarded uplink) was wasted
        results.sort(key=lambda r: r[0])
        kept = results[:k_target]
        for r_i in results[k_target:]:
            b = self._downlink_bytes(r_i[4]) \
                + self._uplink_bytes(r_i[4], density=density)
            wasted_bytes += b
            self.wire.record_wasted(r_i[4], b, reason="straggled")
        if ef:
            # residuals commit AFTER the straggler cut: a kept client's
            # residual assumes delivery (e' = comp - deq(msg)); a
            # straggled client's message was DISCARDED, so its whole
            # reconstruction folds back into the residual and the next
            # uplink re-ships the lost mass (unbiased-in-time)
            for rec_i in kept:
                self.aggregator.store_residual(rec_i[5], rec_i[6])
            for rec_i in results[k_target:]:
                self.aggregator.store_residual(
                    rec_i[5], ef_fold_dropped(rec_i[6], rec_i[2]))
        weights = jnp.asarray([r[1] for r in kept], jnp.float32)
        # (4) aggregation strategy; packed inputs lower onto the fused
        # dequant+reduce kernel, per rank bucket when the cohort is mixed
        with self._span("fl/aggregate", round=rnd, n_agg=len(kept)):
            self.global_train = self.aggregator.aggregate(
                [r[2] for r in kept], weights)
            self._settle(self.global_train)
        self.round += 1

        self._tcc_cum += down_bytes + up_bytes
        kept_ranks: dict[int, int] = {}
        for r in kept:
            kept_ranks[r[4]] = kept_ranks.get(r[4], 0) + 1
        rec = {"round": self.round, "n_agg": len(kept),
               "n_dropped": k_dispatch - len(results),
               "n_straggled": len(results) - len(kept),
               "client_loss": float(np.mean([r[3] for r in kept])),
               "cohort_ranks": kept_ranks,
               "down_bytes": down_bytes, "up_bytes": up_bytes,
               "round_bytes": down_bytes + up_bytes,
               # measured heterogeneous sums, incl. the shared-once
               # initial model (replaces Eq. 2's 2 * one_way * rounds)
               "tcc_bytes": self._tcc_cum,
               # dropout downlinks + straggler round trips this round
               "wasted_bytes": wasted_bytes,
               # always present (None = dense uplink) so the history
               # schema is uniform across sparse/dense/all-dropout rounds
               "uplink_density": density}
        if fcfg.dp is not None:
            # conservative RDP composition over the rounds so far (one
            # Gaussian release per participating client per round)
            eps = gaussian_epsilon(fcfg.dp.noise_multiplier, self.round,
                                   fcfg.dp.delta)
            rec["dp_epsilon"] = eps
            self.registry.set("fl.dp_epsilon", eps)
        if fcfg.qcfg.enabled or density is not None:
            rec["up_bytes_measured"] = self._uplink_bytes(
                max(kept_ranks, key=kept_ranks.get), density=density)
            rec["up_bytes_by_rank"] = {
                r: b for (r, d), b in self.wire.up.items() if d == density}
        if self.eval_fn and self.round % self.scfg.eval_every == 0:
            rec.update(self.eval_fn(self.frozen, self.global_train))
        self.history.append(rec)
        if self.ckpt and self.round % self.scfg.checkpoint_every == 0:
            self.save()
        return rec

    def run(self, rounds: Optional[int] = None) -> list[dict]:
        for _ in range(rounds or self.scfg.rounds):
            self.run_round()
        return self.history
