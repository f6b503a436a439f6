"""Quickstart: FLoCoRA (paper Fig. 1) in ~40 lines.

Federates a ResNet-8 over 20 clients on a synthetic CIFAR-like task,
exchanging int8-quantized LoRA adapters, and prints the communication
saving vs FedAvg (paper Tables I/III).

``--hetero`` runs the heterogeneous fleet instead: 10 clients in three
rank tiers (r in {4, 8, 16} — phones, laptops, workstations), trained
end-to-end by the rank-bucketed engine with per-client truncated
broadcasts and measured mixed-rank TCC.

``--async`` drops round lockstep entirely: the same three-tier fleet
runs through the EVENT-DRIVEN FedBuff engine (fl/async_engine.py) — a
virtual clock schedules each client's dispatch/arrival from a lognormal
latency trace, arrivals buffer with staleness-discounted weights, and
every ``--buffer`` arrivals flush into a new global version. Prints the
per-version (virtual time, loss, staleness, TCC) trajectory.

``--sparse`` runs the FLASC-style sparse-delta uplink (core/sparse.py):
clients top-k sparsify their adapter deltas to 10% density, survivors
quantize to 4 bits, and error feedback re-ships each round's dropped
mass — prints fp32 vs int4 vs int4+10% message sizes and the asymmetric
down/up byte trajectory.

``--dp [NOISE]`` privatizes the uniform quickstart's uplinks: each
client's adapter delta is clipped to L2 norm 1 and Gaussian-noised at
``NOISE`` x clip (default 0.3) BEFORE int8 quantization
(core/quant.DPConfig — quantization is post-processing, so the wire is
already private), and every round's history row carries the cumulative
``dp_epsilon`` spent.

    PYTHONPATH=src python examples/quickstart.py [--rounds 10] \
        [--hetero | --async [--arrivals 90] | --sparse [--density 0.1] \
         | --dp [0.3]]
"""
import argparse
import sys

import jax
import numpy as np

sys.path.insert(0, "src")

from repro.core import messages
from repro.core.flocora import FLoCoRAConfig, RankSchedule
from repro.core.lora import LoRAConfig
from repro.core.quant import QuantConfig
from repro.data import SyntheticVision, lda_partition
from repro.fl import ClientConfig, FLServer, ServerConfig
from repro.models.resnet import ResNetConfig, init as resnet_init, loss_fn
from repro.utils.compile_cache import enable_compile_cache


def run_uniform(rounds: int, dp_noise=None):
    # data: 20 clients worth of non-IID (LDA 0.5) synthetic images
    rng = np.random.default_rng(0)
    sv = SyntheticVision(seed=0)
    y = rng.integers(0, 10, 2000)
    x = sv.sample(rng, y)
    parts = lda_partition(y, 20, alpha=0.5)
    data = [{"x": x[p], "y": y[p].astype(np.int32)} for p in parts]

    # model: frozen random ResNet-8 + rank-32 adapters (alpha = 16r)
    cfg = ResNetConfig(arch="resnet8", lora=LoRAConfig(rank=32, alpha=512.0))
    model = resnet_init(jax.random.PRNGKey(0), cfg)

    fedavg_bytes = messages.message_wire_bytes(
        resnet_init(jax.random.PRNGKey(0),
                    ResNetConfig(arch="resnet8", mode="fedavg"))["train"],
        QuantConfig())
    flocora_bytes = messages.message_wire_bytes(model["train"],
                                                QuantConfig(bits=8))
    print(f"message: FedAvg {fedavg_bytes/1e6:.2f} MB -> FLoCoRA+int8 "
          f"{flocora_bytes/1e6:.3f} MB "
          f"({fedavg_bytes/flocora_bytes:.1f}x smaller)")

    dp = None
    if dp_noise is not None:
        from repro.core.quant import DPConfig
        dp = DPConfig(clip_norm=1.0, noise_multiplier=dp_noise)
        print(f"dp: clip L2 to {dp.clip_norm}, noise {dp.noise_multiplier}"
              f" x clip before int8 quantization (delta={dp.delta:g})")
    server = FLServer(
        model, lambda f, t, b: loss_fn(f, t, cfg, b), data,
        ServerConfig(rounds=rounds, n_clients=20, clients_per_round=5),
        ClientConfig(local_epochs=1, batch_size=32, lr=0.01),
        FLoCoRAConfig(rank=32, alpha=512.0, quant_bits=8, dp=dp))
    for h in server.run():
        print(h)


def run_hetero(rounds: int):
    """Mixed-rank fleet: 10 clients in three rank tiers, end-to-end."""
    from repro.core import flocora

    rng = np.random.default_rng(0)
    sv = SyntheticVision(seed=0)
    y = rng.integers(0, 10, 1000)
    x = sv.sample(rng, y)
    parts = lda_partition(y, 10, alpha=0.5)
    data = [{"x": x[p], "y": y[p].astype(np.int32)} for p in parts]

    # three device classes: phones r=4, laptops r=8, workstations r=16;
    # the server holds rank-16 globals and truncates each broadcast
    sched = RankSchedule.tiered((4, 8, 16), n_clients=10)
    cfg = ResNetConfig(arch="resnet8", lora=LoRAConfig(rank=16, alpha=256.0))
    model = resnet_init(jax.random.PRNGKey(0), cfg)
    fcfg = FLoCoRAConfig(rank=16, alpha=256.0, quant_bits=8,
                         rank_schedule=sched)

    for r in (4, 8, 16):
        kb = flocora.client_wire_bytes(model["train"], fcfg, r) / 1e3
        n = sum(1 for cr in sched.client_ranks if cr == r)
        print(f"tier r={r:2d}: {n} clients, {kb:7.1f} kB one-way")

    server = FLServer(
        model, lambda f, t, b: loss_fn(f, t, cfg, b), data,
        ServerConfig(rounds=rounds, n_clients=10, clients_per_round=6),
        ClientConfig(local_epochs=1, batch_size=32, lr=0.01),
        fcfg)
    for h in server.run():
        print({k: h[k] for k in ("round", "n_agg", "client_loss",
                                 "cohort_ranks", "round_bytes",
                                 "tcc_bytes") if k in h})


def run_async(arrivals: int, buffer_size: int):
    """Three-tier fleet, no rounds: event-driven staleness-aware FedBuff
    over the packed wire, on a virtual clock."""
    from repro.core import flocora
    from repro.fl import AsyncConfig, AsyncFLServer, AvailabilityWindows, \
        FleetTrace, LognormalLatency, time_to_target

    rng = np.random.default_rng(0)
    sv = SyntheticVision(seed=0)
    y = rng.integers(0, 10, 1000)
    x = sv.sample(rng, y)
    parts = lda_partition(y, 12, alpha=0.5)
    data = [{"x": x[p], "y": y[p].astype(np.int32)} for p in parts]

    sched = RankSchedule.tiered((4, 8, 16), n_clients=12)
    cfg = ResNetConfig(arch="resnet8", lora=LoRAConfig(rank=16, alpha=256.0))
    model = resnet_init(jax.random.PRNGKey(0), cfg)
    fcfg = FLoCoRAConfig(rank=16, alpha=256.0, quant_bits=8,
                         rank_schedule=sched)
    # phones train ~45 s (median, heavier tiers longer), uplink over a
    # jittery 20 Mb/s link, and each client is only available 80% of a
    # 10-minute duty cycle
    trace = FleetTrace(seed=0,
                       latency=LognormalLatency(compute_median_s=45.0,
                                                network_mbps=20.0),
                       availability=AvailabilityWindows(period_s=600.0,
                                                       duty=0.8))
    for r in (4, 8, 16):
        kb = flocora.client_wire_bytes(model["train"], fcfg, r) / 1e3
        print(f"tier r={r:2d}: {kb:7.1f} kB one-way")

    srv = AsyncFLServer(
        model, lambda f, t, b: loss_fn(f, t, cfg, b), data,
        AsyncConfig(total_arrivals=arrivals, concurrency=6,
                    buffer_size=buffer_size, half_life=4.0,
                    microbatch_window=60.0, seed=0),
        ClientConfig(local_epochs=1, batch_size=32, lr=0.01),
        fcfg, trace=trace)
    for h in srv.run():
        print({k: (round(v, 4) if isinstance(v, float) else v)
               for k, v in h.items()
               if k in ("version", "t_virtual", "n_arrived", "client_loss",
                        "staleness_mean", "flush_ranks", "tcc_bytes")})
    last = srv.history[-1]
    print(f"virtual {last['t_virtual'] / 60:.1f} min, "
          f"{last['tcc_bytes'] / 1e6:.2f} MB total")
    hit = time_to_target(srv.history, "client_loss",
                         1.5 * last["client_loss"])
    if hit:
        print(f"reached 1.5x final loss at {hit['t_virtual'] / 60:.1f} "
              f"min / {hit['tcc_bytes'] / 1e6:.2f} MB")


def run_sparse(rounds: int, density: float):
    """Sparse-delta uplink: top-k 10%-density 4-bit adapters with error
    feedback, over the same 20-client fleet as the uniform quickstart."""
    from repro.core.sparse import SparsityConfig

    rng = np.random.default_rng(0)
    sv = SyntheticVision(seed=0)
    y = rng.integers(0, 10, 2000)
    x = sv.sample(rng, y)
    parts = lda_partition(y, 20, alpha=0.5)
    data = [{"x": x[p], "y": y[p].astype(np.int32)} for p in parts]

    cfg = ResNetConfig(arch="resnet8", lora=LoRAConfig(rank=32, alpha=512.0))
    model = resnet_init(jax.random.PRNGKey(0), cfg)
    fcfg = FLoCoRAConfig(rank=32, alpha=512.0, quant_bits=4,
                         error_feedback=True,
                         sparsity=SparsityConfig(density=density))

    fp = messages.message_wire_bytes(model["train"], QuantConfig())
    q4 = messages.message_wire_bytes(model["train"], QuantConfig(bits=4))
    sp = messages.message_wire_bytes(model["train"], QuantConfig(bits=4),
                                     density)
    print(f"uplink: fp32 {fp / 1e3:.1f} kB -> int4 {q4 / 1e3:.1f} kB "
          f"-> int4+top-k({density:.0%}) {sp / 1e3:.1f} kB "
          f"({fp / sp:.1f}x smaller; EF re-ships the dropped mass)")

    server = FLServer(
        model, lambda f, t, b: loss_fn(f, t, cfg, b), data,
        ServerConfig(rounds=rounds, n_clients=20, clients_per_round=5),
        ClientConfig(local_epochs=1, batch_size=32, lr=0.01),
        fcfg)
    for h in server.run():
        print({k: h[k] for k in ("round", "n_agg", "client_loss",
                                 "uplink_density", "down_bytes",
                                 "up_bytes", "tcc_bytes") if k in h})
    hist = server.history
    print(f"round bytes down/up: {hist[-1]['down_bytes']} / "
          f"{hist[-1]['up_bytes']} "
          f"(dense wire would up {hist[-1]['down_bytes']})")
    # sanity: measured uplink == static sparse accounting
    assert hist[-1]["up_bytes_measured"] == sp


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--hetero", action="store_true",
                    help="mixed-rank cohort (10 clients, 3 rank tiers)")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="event-driven FedBuff fleet (virtual clock)")
    ap.add_argument("--sparse", action="store_true",
                    help="FLASC-style top-k sparse uplink with EF")
    ap.add_argument("--density", type=float, default=0.1,
                    help="sparse: fraction of adapter entries uplinked")
    ap.add_argument("--arrivals", type=int, default=90,
                    help="async: total virtual arrivals")
    ap.add_argument("--buffer", type=int, default=6,
                    help="async: FedBuff buffer size")
    ap.add_argument("--dp", type=float, nargs="?", const=0.3,
                    default=None, metavar="NOISE",
                    help="uniform quickstart with DP uplinks: clip + "
                         "Gaussian noise at NOISE x clip (default 0.3)")
    args = ap.parse_args()
    if args.sparse and not 0.0 < args.density <= 1.0:
        ap.error("--density must be in (0, 1]")
    if args.sparse:
        run_sparse(args.rounds, args.density)
    elif args.async_:
        run_async(args.arrivals, args.buffer)
    elif args.hetero:
        run_hetero(args.rounds)
    else:
        run_uniform(args.rounds, dp_noise=args.dp)


if __name__ == "__main__":
    main()
