"""Unified telemetry layer (src/repro/obs/).

The acceptance contract:
  * the metrics registry records labeled counters/gauges/histograms,
    no-ops (and allocates nothing) when disabled, and is injectable —
    two instances never see each other's counts;
  * the tracer spans wall time OR an engine's virtual clock, and both
    exports (Chrome trace JSON, JSONL) round-trip through json.load;
  * repro.obs.compile is the ONE backend-compile listener: the
    ``count_compiles`` fixture measures, ``CompileWatchdog`` enforces
    (raises on a fresh compile inside a zero-budget block), and the
    serving engine / streaming accumulator runtime invariants ride it;
  * HISTORY SCHEMA: every sync ``run_round`` record — including an
    all-dropout round — and every async flush record carries the full
    key set (bytes, density, rank breakdown, staleness);
  * END TO END: one FL round + one async run + one serve simulation
    with obs enabled produce a loadable Chrome trace and a metrics dump
    covering wire bytes, staleness, cache hit rate and compile counts.
"""
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, serve
from repro.core.flocora import FLoCoRAConfig, RankSchedule
from repro.core.lora import LoRAConfig, linear_apply, linear_init
from repro.core.aggregation import FedBuffAggregator, \
    StreamingFlatAccumulator
from repro.core import messages
from repro.core.quant import QuantConfig
from repro.fl import AsyncConfig, AsyncFLServer, ClientConfig, FLServer, \
    FleetTrace, LognormalLatency, ServerConfig
from repro.obs import metrics as obsm
from repro.obs import trace as obst
from repro.obs.compile import CompileBudgetExceeded, CompileWatchdog

# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_labeled_counters_gauges_histograms():
    reg = obsm.MetricsRegistry()
    reg.inc("wire.up_bytes", 100, rank=8, density=0.1)
    reg.inc("wire.up_bytes", 50, rank=8, density=0.1)
    reg.inc("wire.up_bytes", 7, rank=4, density=None)
    assert reg.counter_value("wire.up_bytes") == 157
    assert reg.counter_value("wire.up_bytes", rank=8, density=0.1) == 150
    # label order does not matter: one canonical key
    assert reg.counter_value("wire.up_bytes", density=0.1, rank=8) == 150
    reg.set("fl.inflight", 3)
    reg.set("fl.inflight", 5)
    assert reg.gauge("fl.inflight").get() == 5
    for v in (0, 1, 1, 3, 100):
        reg.observe("fl.staleness", v)
    st = reg.histogram("fl.staleness").get()
    assert st.count == 5 and st.min == 0 and st.max == 100
    assert reg.histogram("fl.staleness").mean() == pytest.approx(21.0)
    d = reg.dump()
    assert d["counters"]["wire.up_bytes"]["density=0.1,rank=8"] == 150
    assert "fl.staleness" in d["histograms"]
    json.dumps(d)                      # the dump is plain JSON


def test_registry_disabled_is_a_noop_and_instances_are_isolated():
    off = obsm.MetricsRegistry(enabled=False)
    off.inc("x", 5)
    off.observe("h", 1.0)
    off.set("g", 2.0)
    assert off.dump() == {"counters": {}, "gauges": {}, "histograms": {}}
    a, b = obsm.MetricsRegistry(), obsm.MetricsRegistry()
    a.inc("x", 1)
    assert b.counter_value("x") == 0
    # get_registry: explicit instance wins, None -> process default
    assert obsm.get_registry(a) is a
    assert obsm.get_registry(None) is obsm.default_registry()
    assert not obsm.default_registry().enabled  # off unless opted in


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_wall_and_virtual_clocks(tmp_path):
    tr = obst.Tracer()
    with tr.span("work", track="t0", k=1):
        pass
    vclock = [12.5]
    view = tr.with_clock(lambda: vclock[0])
    with view.span("virtual_work", track="t1"):
        vclock[0] = 14.0               # the span reads the fake clock
    tr.event("explicit", ts=3.0, dur=2.0, track="t1", cid=7)
    tr.instant("flush", track="t1", ts=20.0)
    names = [e["name"] for e in tr.events]
    assert names == ["work", "virtual_work", "explicit", "flush"]
    vw = tr.events[1]
    assert vw["ts"] == pytest.approx(12.5e6)
    assert vw["dur"] == pytest.approx(1.5e6)

    chrome = tmp_path / "trace.json"
    tr.export_chrome(str(chrome))
    doc = json.load(open(chrome))
    assert doc["traceEvents"]
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["name"] == "thread_name"}
    assert {"t0", "t1"} <= tracks
    jl = tmp_path / "trace.jsonl"
    tr.export_jsonl(str(jl))
    lines = [json.loads(ln) for ln in open(jl)]
    assert {ln["track"] for ln in lines} == {"t0", "t1"}


def test_tracer_view_tracks_parent_enable_live():
    tr = obst.Tracer(enabled=False)
    view = tr.with_clock(lambda: 1.0)
    view.event("dropped", ts=0.0)
    assert tr.events == []
    tr.enabled = True                  # enabling the parent enables views
    view.event("kept", ts=0.0)
    assert [e["name"] for e in tr.events] == ["kept"]


# ---------------------------------------------------------------------------
# compile counting + watchdog
# ---------------------------------------------------------------------------


def test_count_compiles_fixture_and_watchdog(count_compiles):
    @jax.jit
    def f(x):
        return x * 2 + 1

    x = jnp.arange(17.0)               # odd length: a fresh shape
    with count_compiles() as c:
        jax.block_until_ready(f(x))
    assert c.count >= 1
    with count_compiles() as c:        # steady state: cached program
        jax.block_until_ready(f(x))
    assert c.count == 0
    with CompileWatchdog(0, label="steady"):   # budget met: no raise
        jax.block_until_ready(f(x))
    with pytest.raises(CompileBudgetExceeded, match="fresh"):
        with CompileWatchdog(0, label="fresh"):
            jax.block_until_ready(f(jnp.arange(19.0)))
    # a user exception propagates un-masked even over budget
    with pytest.raises(ZeroDivisionError):
        with CompileWatchdog(0):
            jax.block_until_ready(f(jnp.arange(23.0)))
            1 / 0


def test_compiles_feed_enabled_default_registry():
    reg = obsm.MetricsRegistry()
    prev = obsm.set_default_registry(reg)
    try:
        jax.block_until_ready(
            jax.jit(lambda x: x - 3)(jnp.arange(29.0)))
    finally:
        obsm.set_default_registry(prev)
    assert reg.counter_value("jax.backend_compiles") >= 1
    assert reg.counter_value("jax.backend_compile_secs") > 0


# ---------------------------------------------------------------------------
# tiny LoRA workload (mirrors test_async_engine: fast compiles)
# ---------------------------------------------------------------------------


def _lora_model(seed=0, rank=8):
    k = jax.random.PRNGKey(seed)
    fz, tr = linear_init(k, 16, 10, "lora",
                         LoRAConfig(rank=rank, alpha=float(rank)),
                         base_dtype=jnp.float32)
    return {"frozen": {"lin": fz},
            "train": {"lin": tr, "bias": jnp.zeros((10,))}}


def _lora_loss(frozen, train, batch):
    logits = linear_apply(frozen["lin"], train["lin"], batch["x"], 1.0,
                          jnp.float32) + train["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None],
                                         axis=1)), {}


def _lin_data(n=120, n_clients=6, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(16, 10)).astype(np.float32)
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1).astype(np.int32)
    parts = np.array_split(rng.permutation(n), n_clients)
    return [{"x": x[p], "y": y[p]} for p in parts]


def _sync_server(data, p_fail=0.0, **fkw):
    scfg = ServerConfig(rounds=2, n_clients=len(data),
                        clients_per_round=3, p_client_failure=p_fail,
                        seed=0)
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1)
    fcfg = FLoCoRAConfig(rank=8, alpha=8.0, quant_bits=8, **fkw)
    return FLServer(_lora_model(rank=8), _lora_loss, data, scfg, ccfg,
                    fcfg)


SYNC_KEYS = {"round", "n_agg", "n_dropped", "n_straggled", "client_loss",
             "cohort_ranks", "down_bytes", "up_bytes", "round_bytes",
             "tcc_bytes", "uplink_density"}
ASYNC_KEYS = {"version", "t_virtual", "n_arrived", "n_flushed",
              "client_loss", "staleness_mean", "staleness_max",
              "flush_ranks", "down_bytes", "up_bytes", "tcc_bytes",
              "uplink_density"}


# ---------------------------------------------------------------------------
# history record schema completeness
# ---------------------------------------------------------------------------


def test_sync_history_schema_complete_even_on_all_dropout():
    data = _lin_data()
    srv = _sync_server(data)
    rec = srv.run_round()
    assert SYNC_KEYS <= rec.keys(), SYNC_KEYS - rec.keys()
    assert rec["uplink_density"] is None     # dense uplink, key present
    assert rec["down_bytes"] > 0 and rec["up_bytes"] > 0

    srv_dead = _sync_server(data, p_fail=1.0)
    rec0 = srv_dead.run_round()
    assert rec0["n_agg"] == 0                # every client dropped
    assert SYNC_KEYS <= rec0.keys(), SYNC_KEYS - rec0.keys()
    assert rec0["down_bytes"] > 0 and rec0["up_bytes"] == 0


def test_async_flush_schema_complete():
    data = _lin_data()
    acfg = AsyncConfig(total_arrivals=8, concurrency=3, buffer_size=4,
                       seed=0)
    srv = AsyncFLServer(_lora_model(rank=8), _lora_loss, data, acfg,
                        ClientConfig(local_epochs=1, batch_size=8,
                                     lr=0.1),
                        FLoCoRAConfig(rank=8, alpha=8.0, quant_bits=8),
                        trace=FleetTrace(seed=0, latency=LognormalLatency(
                            compute_median_s=5.0, network_mbps=20.0)))
    hist = srv.run()
    assert hist
    for rec in hist:
        assert ASYNC_KEYS <= rec.keys(), ASYNC_KEYS - rec.keys()


# ---------------------------------------------------------------------------
# runtime zero-steady-state-compile enforcement
# ---------------------------------------------------------------------------


def _flat_msgs(n, bits=4, rank=8):
    qcfg = QuantConfig(bits=bits)
    out = []
    for i in range(n):
        k = jax.random.PRNGKey(i)
        ks = jax.random.split(k, 2)
        tree = {"a": jax.random.normal(ks[0], (13, rank)),
                "b": jax.random.normal(ks[1], (rank, 21))}
        out.append(messages.pack_message(tree, qcfg, flat=True))
    return out


def test_streaming_accumulator_strict_compiles():
    msgs = _flat_msgs(4)
    st = StreamingFlatAccumulator.for_layout(msgs[0].layout,
                                             strict_compiles=True)
    for m in msgs:                     # first fold may compile; rest not
        st.fold(m, 1.0)
    jax.block_until_ready(st.acc)
    # a cleared compile cache makes the next steady-state fold retrace,
    # which the watchdog must surface instead of silently recompiling
    jax.clear_caches()
    with pytest.raises(CompileBudgetExceeded, match="streaming"):
        st.fold(msgs[0], 1.0)
    # threaded through the aggregator config field
    agg = FedBuffAggregator(streaming=True, strict_compiles=True)
    agg.add(msgs[0], 1.0, 0.0)
    assert next(iter(agg.streams.values())).strict_compiles


def test_serve_engine_strict_compiles_steady_state():
    weights, store = serve.make_store(n_clients=8, d_model=32,
                                      n_layers=2, ranks=(4, 8), bits=4,
                                      seed=0)
    cache = serve.AdapterCache(capacity_bytes=1 << 20, qcfg=store.qcfg)
    eng = serve.AdapterServingEngine(weights, scale=0.5, qcfg=store.qcfg,
                                     cache=cache, fetch=store.fetch,
                                     strict_compiles=True)
    cids = [0, 1, 2, 3]                # both rank buckets
    eng.admit(cids)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    for _ in range(2):                 # warm (first sight of the shape)
        jax.block_until_ready(eng.step(x, cids))
    for _ in range(3):                 # steady state: watchdogged, clean
        jax.block_until_ready(eng.step(x, cids))
    jax.clear_caches()                 # force a retrace on a warm shape
    with pytest.raises(CompileBudgetExceeded, match="steady-state"):
        eng.step(x, cids)


# ---------------------------------------------------------------------------
# end to end: one round + one async run + one serve sim, obs enabled
# ---------------------------------------------------------------------------


def test_end_to_end_trace_and_metrics_dump(tmp_path):
    reg = obsm.MetricsRegistry(enabled=False)
    tracer = obst.Tracer(enabled=False)
    prev_r = obsm.set_default_registry(reg)
    prev_t = obst.set_default_tracer(tracer)
    try:
        obs.enable()
        data = _lin_data()
        # sync: one round (mixed ranks so wire counters get labels)
        srv = FLServer(
            _lora_model(rank=8), _lora_loss, data,
            ServerConfig(rounds=1, n_clients=len(data),
                         clients_per_round=3, seed=0),
            ClientConfig(local_epochs=1, batch_size=8, lr=0.1),
            FLoCoRAConfig(rank=8, alpha=8.0, quant_bits=8,
                          rank_schedule=RankSchedule.tiered(
                              (4, 8), len(data))))
        srv.run_round()
        # async: a short run (staleness + virtual-clock spans)
        asrv = AsyncFLServer(
            _lora_model(rank=8), _lora_loss, data,
            AsyncConfig(total_arrivals=6, concurrency=3, buffer_size=3,
                        seed=0),
            ClientConfig(local_epochs=1, batch_size=8, lr=0.1),
            FLoCoRAConfig(rank=8, alpha=8.0, quant_bits=8),
            trace=FleetTrace(seed=0, latency=LognormalLatency(
                compute_median_s=5.0, network_mbps=20.0)))
        asrv.run()
        # serve: a small simulated workload (cache hit rate)
        weights, store = serve.make_store(n_clients=8, d_model=32,
                                          ranks=(4, 8), bits=4, seed=0)
        eng = serve.AdapterServingEngine(
            weights, scale=0.5, qcfg=store.qcfg,
            cache=serve.AdapterCache(capacity_bytes=1 << 20,
                                     qcfg=store.qcfg),
            fetch=store.fetch)
        serve.simulate(eng, store,
                       serve.WorkloadConfig(n_requests=12, rate_rps=500.0,
                                            gen_tokens=2, max_batch=4,
                                            seed=0))
    finally:
        obs.disable()
        obsm.set_default_registry(prev_r)
        obst.set_default_tracer(prev_t)

    # the metrics dump covers bytes, staleness, hit rate, compiles
    d = reg.dump()
    assert sum(reg.counter("wire.down_bytes").values.values()) > 0
    assert sum(reg.counter("wire.up_bytes").values.values()) > 0
    # per-rank labels from the tiered sync fleet
    assert any("rank=" in k for k in
               reg.counter("wire.up_bytes").values)
    assert reg.histogram("fl.staleness").get() is not None
    hits = reg.counter_value("serve.cache.hits")
    misses = reg.counter_value("serve.cache.misses")
    assert hits + misses > 0 and misses > 0   # cold cache missed first
    assert reg.counter_value("jax.backend_compiles") > 0
    assert [r["round"] for r in srv.history] == [1]
    assert reg.counter_value("fl.flushes") >= 1
    dump_path = tmp_path / "metrics.json"
    reg.dump_json(str(dump_path))
    json.load(open(dump_path))

    # the trace covers all three engines and loads as Chrome JSON
    path = tmp_path / "trace.json"
    tracer.export_chrome(str(path))
    doc = json.load(open(path))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fl/broadcast", "fl/client_train", "fl/pack", "fl/uplink",
            "fl/aggregate"} <= names, names
    assert {"fl/inflight", "fl/flush"} <= names
    assert {"serve/decode_step", "serve/request"} <= names
    # async spans sit on VIRTUAL time: dispatch->arrival durations are
    # fleet-scale seconds, far beyond the wall time this test ran for
    inflight = [e for e in doc["traceEvents"]
                if e["name"] == "fl/inflight"]
    assert inflight and all(e["dur"] >= 1e6 for e in inflight)
    assert all("staleness" in e["args"] for e in inflight)


def test_disabled_obs_records_nothing_through_engines():
    """Engines built with the (disabled) process defaults must leave no
    telemetry behind — the <2% overhead contract starts with zero
    recording."""
    data = _lin_data()
    srv = _sync_server(data)
    srv.run_round()
    assert obsm.default_registry().dump() == {
        "counters": {}, "gauges": {}, "histograms": {}}
    assert obst.default_tracer().events == []


# ---------------------------------------------------------------------------
# the sync round's spans, and their profiler annotations
# ---------------------------------------------------------------------------

ROUND_PHASES = ("fl/broadcast", "fl/client_train", "fl/pack", "fl/uplink",
                "fl/aggregate")
# span -> the span it lies in
ROUND_PARENT = {**{p: "fl/round" for p in ROUND_PHASES},
                "fl/stage_batches": "fl/broadcast",
                "fl/h2d": "fl/broadcast",
                "fl/train_wait": "fl/client_train",
                "fl/slice": "fl/pack", "fl/encode": "fl/pack"}


def _traced_sync_server(tracer, clients_per_round=3, **fkw):
    data = _lin_data()
    return FLServer(
        _lora_model(rank=8), _lora_loss, data,
        ServerConfig(rounds=1, n_clients=len(data),
                     clients_per_round=clients_per_round, seed=0),
        ClientConfig(local_epochs=1, batch_size=8, lr=0.1),
        FLoCoRAConfig(rank=8, alpha=8.0, quant_bits=8, **fkw),
        tracer=tracer)


def _assert_one_slice_per_bucket(by_name: dict) -> None:
    """Each ``fl/pack`` (one per rank bucket) holds exactly one
    ``fl/slice``, whose args name the pack's round, rank and clients."""
    packs, slices = by_name["fl/pack"], by_name["fl/slice"]
    assert len(slices) == len(packs)
    for p in packs:
        inside = [s for s in slices if p["ts"] <= s["ts"]
                  and s["ts"] + s["dur"] <= p["ts"] + p["dur"]]
        assert len(inside) == 1
        assert inside[0]["args"] == {"round": 0, "rank": p["args"]["rank"],
                                     "clients": p["args"]["clients"]}


def _host_span_names(logdir) -> set:
    from jax.profiler import ProfileData
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_sync_round_spans_nest_inside_the_round():
    tracer = obst.Tracer()
    srv = _traced_sync_server(tracer)
    rec = srv.run_round()
    evs = [e for e in tracer.events if e["ph"] == "X"]
    by_name: dict = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert set(by_name) == {"fl/round", *ROUND_PARENT}, set(by_name)
    rnd, = by_name["fl/round"]
    assert rnd["args"] == {"round": 0}
    # one slice per rank bucket, in its pack and for its clients; one
    # encode per cohort client
    _assert_one_slice_per_bucket(by_name)
    encoded = [e["args"]["client"] for e in by_name["fl/encode"]]
    assert len(set(encoded)) == len(encoded) == rec["n_agg"] == 3
    for e in evs:
        if e["name"] == "fl/round":
            continue
        parents = by_name[ROUND_PARENT[e["name"]]]
        assert any(p["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= p["ts"] + p["dur"]
                   for p in parents), e["name"]
    # the five phases follow one another inside the round
    phases = sorted((by_name[p][0] for p in ROUND_PHASES),
                    key=lambda e: e["ts"])
    assert [e["name"] for e in phases] == list(ROUND_PHASES)
    for a, b in zip(phases, phases[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]


def test_mixed_rank_round_slices_once_per_rank_bucket():
    """Two rank buckets, one padded to a power of two: one slice each,
    for the bucket's live clients; one encode per cohort client."""
    tracer = obst.Tracer()
    srv = _traced_sync_server(
        tracer, clients_per_round=5,
        rank_schedule=RankSchedule.tiered((4, 8), 6))
    rec = srv.run_round()
    by_name: dict = {}
    for e in tracer.events:
        if e["ph"] == "X":
            by_name.setdefault(e["name"], []).append(e)
    assert sorted(p["args"]["rank"] for p in by_name["fl/pack"]) == [4, 8]
    assert sorted(p["args"]["clients"] for p in by_name["fl/pack"]) \
        == [2, 3]
    _assert_one_slice_per_bucket(by_name)
    encoded = [e["args"]["client"] for e in by_name["fl/encode"]]
    assert len(set(encoded)) == len(encoded) == rec["n_agg"] == 5


def test_enabled_tracer_spans_land_in_a_profiler_trace(tmp_path):
    """No benchmark code: a plain enabled tracer's spans are on the
    profiler's host plane; a virtual-clock view's are not."""
    tracer = obst.Tracer()
    srv = _traced_sync_server(tracer)
    srv.run_round()                    # compile outside the profile
    view = tracer.with_clock(lambda: 5.0)
    with jax.profiler.trace(str(tmp_path)):
        srv.run_round()
        with view.span("fl/virtual_only"):
            pass
    names = _host_span_names(tmp_path)
    assert {"fl/round", "fl/pack", "fl/h2d"} <= names, names
    assert "fl/virtual_only" not in names
    assert "fl/virtual_only" in {e["name"] for e in tracer.events}


def test_disabled_tracer_leaves_no_events_nor_annotations(tmp_path):
    tracer = obst.Tracer(enabled=False)
    srv = _traced_sync_server(tracer)
    srv.run_round()
    with jax.profiler.trace(str(tmp_path)):
        srv.run_round()
    assert tracer.events == []
    assert not {n for n in _host_span_names(tmp_path)
                if n.startswith("fl/")}
