"""The benchmark harness: one cell of ``BENCHMARK.json``, one run.

A cell names a configuration and a traffic mix; each is a data file,
found by name:

- the configuration is the JSON file that ``BENCHMARK.json`` names, and
  its ``family`` names ``bench/models/<family>.py`` (the plain reference,
  the weights and the FLOP formula) and ``bench/models/<family>_program.py``
  (how the program builds that model's loss);
- the traffic mix is ``bench/traffic/<traffic>.json``;
- the limits of the numbers that decide ``correct`` are
  ``bench/limits/<workload>.json``;
- each per-layer metric is read by ``bench/metrics/<metric>.py``.

A run makes the cell's data and weights from the seed, builds the
program's ``FLServer`` through its public constructor and runs its first
rounds (set-up: the first round compiles), then runs whole rounds for the
window, each ending when ``global_train`` is ready. With ``trace`` the
window is a few rounds under the profiler instead, and the per-layer
metrics are read from that trace, the server's spans and the arguments
the cohort trainer was given. After the window the reference follows the
set-up's rounds from the same weights and batches, and the uplinks of the
last round are checked against the reference quantizer.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from bench import data, reference, trace_reduce
from bench.peaks import peaks

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# the faults a test or a calibration can plant under the timed path
FAULTS = ("unchanged", "half_cohort", "altered_uplink")
# the numbers of ``correct`` that compare training with the reference;
# the control (the reference in bfloat16) is judged on these
TRAINING = ("update_gap", "change_gap", "change_worst")
# what a kept trace's readers were given, beside the trace and the cell
KEPT_CTX = ("spans", "rounds", "wall_s", "schedule_steps", "step_flops",
            "codec_rows", "bits", "chips")


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything a run of workload ``name`` needs, from the data files
    under ``root`` (a checkout)."""
    root = Path(root)
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    cell = dict(cells[name], root=root)
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_spec"] = _json(root / conf["file"])
    cell["traffic_spec"] = _json(
        root / "bench" / "traffic" / f"{cell['traffic']}.json")
    cell["limits"] = _json(root / "bench" / "limits" / f"{name}.json")
    cell["end_to_end"] = [m for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])]
    reported = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in reported)]
    return cell


def family(cell: dict):
    """The configuration's plain reference, weights and FLOP formula."""
    name = cell["config_spec"]["family"]
    return _module(cell["root"] / "bench" / "models" / f"{name}.py")


def program_model(cell: dict):
    """How the program builds the configuration's model."""
    name = cell["config_spec"]["family"]
    return _module(cell["root"] / "bench" / "models" / f"{name}_program.py")


def read_metrics(cell: dict, ctx: dict, strict: bool = False) -> dict:
    """The cell's per-layer metrics, each from its reader; a reader that
    finds nothing to read returns None and its metric is left out. With
    ``strict`` (a traced run on the chip) that is an error instead: every
    metric the cell lists must find something to read there."""
    out, missing = {}, []
    for m in cell["per_layer"]:
        reader = _module(cell["root"] / "bench" / "metrics"
                         / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is None:
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    if strict and missing:
        raise RuntimeError(f"{cell['name']}: the readers of {missing} found "
                           "nothing to read in this run's trace")
    return out


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program
    kept, so that only a cell's first run in a checkout compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs compiled (or loaded from the persistent cache) so far."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1


# one listener per process: JAX keeps every listener it is given
_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


class Recorder:
    """Wraps the server's cohort trainer and its aggregator's ``aggregate``
    to keep what the checks and the per-layer metrics need: for the first
    ``follow`` rounds each slot's batches (a fingerprint of each image and
    the labels) and active step counts; for the latest round the trained
    trees and the uplinks; with ``count`` on, every round's step counts.
    ``faults`` plants faults under the timed path (tests, calibration)."""

    def __init__(self, server, follow: int, faults=()):
        unknown = set(faults) - set(FAULTS)
        if unknown:
            raise ValueError(f"unknown faults {sorted(unknown)}")
        self.server, self.follow, self.faults = server, follow, set(faults)
        self.feeds: list = []
        self.steps: list = []
        self.count = False
        self.last_trained = self.last_msgs = None
        self.schedule = None
        self._train = server.trainer
        self._aggregate = server.aggregator.aggregate
        server.trainer = self.trainer
        server.aggregator.aggregate = self.aggregate

    def trainer(self, frozen, train0, batches, n_steps):
        out = self._train(frozen, train0, batches, n_steps)
        x = batches["x"]
        self.schedule = int(x.shape[1])
        if len(self.feeds) < self.follow:
            fp = x.reshape(*x.shape[:3], -1)[..., :reference.FINGERPRINT]
            self.feeds.append((np.asarray(fp), np.asarray(batches["y"]),
                               np.asarray(n_steps)))
        if self.count:
            self.steps.append(n_steps)
        self.last_trained = out[0]
        return out

    def aggregate(self, msgs, weights):
        if "altered_uplink" in self.faults:
            msgs = [jax.tree.map(_flip_first_word, msgs[0])] + list(msgs[1:])
        self.last_msgs = list(msgs)
        if "half_cohort" in self.faults:
            half = max(1, len(msgs) // 2)
            msgs, weights = msgs[:half], weights[:half]
        if "unchanged" in self.faults:
            return self.server.global_train
        return self._aggregate(msgs, weights)


def _flip_first_word(a):
    """The top bit of each byte of an uplink's first payload word."""
    if a.dtype != np.uint32:
        return a
    return a.at[(0,) * a.ndim].set(a[(0,) * a.ndim] ^ np.uint32(0x80808080))


def annotated_tracer():
    """The server's span tracer, enabled, with each span also entered as
    a profiler ``TraceAnnotation`` so that it lies on the device trace's
    clock."""
    from repro.obs import trace as obst

    class Tracer(obst.Tracer):
        @contextlib.contextmanager
        def span(self, name, track="main", **args):
            with jax.profiler.TraceAnnotation(name), \
                    super().span(name, track, **args):
                yield

    return Tracer(enabled=True)


def build_server(cell: dict, weights: dict, clients: list, seed: int,
                 tracer=None):
    """The program's synchronous FLoCoRA server for this cell."""
    from repro.core.flocora import FLoCoRAConfig
    from repro.fl import ClientConfig, FLServer, ServerConfig
    cfg, traffic = cell["config_spec"], cell["traffic_spec"]
    loss_fn = program_model(cell).loss_fn(cfg)
    scfg = ServerConfig(
        rounds=1, n_clients=traffic["n_clients"],
        clients_per_round=traffic["clients_per_round"],
        oversample=traffic["oversample"],
        p_client_failure=traffic["p_client_failure"], seed=seed,
        eval_every=1 << 30)
    ccfg = ClientConfig(local_epochs=traffic["local_epochs"],
                        batch_size=traffic["batch_size"], lr=cfg["lr"],
                        momentum=cfg["momentum"])
    fcfg = FLoCoRAConfig(rank=cfg["lora_rank"], alpha=cfg["lora_alpha"],
                         quant_bits=cfg["quant_bits"],
                         flat_wire=cfg["flat_wire"])
    return FLServer(weights, loss_fn, clients, scfg, ccfg, fcfg,
                    tracer=tracer)


def _round(server) -> dict:
    rec = server.run_round()
    jax.block_until_ready(server.global_train)
    return rec


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32),
                        jax.device_get(tree))


def _device_block(chips: int, traced: dict | None) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if traced is not None:
        out["busy_s"] = traced["busy_s"]
        out["window_s"] = traced["window_s"]
    return out


def _traced_window(server, rounds: int,
                   keep: str | None = None) -> tuple[float, dict]:
    """``rounds`` rounds under the profiler: (their wall seconds, the
    trace's reduction). ``keep`` is a path to copy the trace to."""
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        with jax.profiler.trace(logdir, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                t0 = time.perf_counter()
                for _ in range(rounds):
                    with jax.profiler.TraceAnnotation("bench/round"):
                        _round(server)
                wall = time.perf_counter() - t0
        path = trace_reduce.find_xplane(logdir)
        red = trace_reduce.reduce(path)
        if keep:
            shutil.copyfile(path, keep)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return wall, red


def _codec_rows(tree) -> list[tuple[int, int]]:
    """(channels, values per channel) of every quantized tensor."""
    return [(x.shape[-1], int(np.prod(x.shape)) // x.shape[-1])
            for x in jax.tree.leaves(tree) if len(x.shape) >= 2]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             faults=(), control: bool = False,
             keep_trace: str | None = None) -> dict:
    """One run of ``cell``: the result line, as a dict. ``control`` also
    puts the reference computed in bfloat16 in the program's place and
    adds its numbers under ``control`` (calibration, tests).
    ``keep_trace`` is a path to copy a traced run's trace to, with what
    its readers were given beside it in ``<keep_trace>.ctx.json``."""
    from repro.core import messages
    t_start = time.perf_counter()
    counter = compile_counter()
    cfg, traffic = cell["config_spec"], cell["traffic_spec"]
    fam = family(cell)
    s_data, s_weights, s_server = data.substreams(seed, 3)
    clients = data.make_clients(s_data, traffic, tuple(cfg["image"]),
                                cfg["n_classes"])
    weights = jax.jit(functools.partial(fam.init, cfg=cfg))(
        jax.random.PRNGKey(s_weights))
    frozen_host = _host(weights["frozen"])
    g0 = _host(weights["train"])
    tracer = annotated_tracer() if trace else None
    server = build_server(cell, weights, clients, s_server, tracer)
    del weights
    rec = Recorder(server, traffic["follow_rounds"], faults)
    prog_trees, prog_losses = [], []
    for _ in range(traffic["follow_rounds"]):
        prog_losses.append(_round(server)["client_loss"])
        prog_trees.append(_host(server.global_train))
    setup_s = time.perf_counter() - t_start

    n0 = counter.count
    traced = None
    if trace:
        tracer.reset()
        rec.count = True
        rounds = traffic["trace_rounds"]
        wall, traced = _traced_window(server, rounds, keep_trace)
    else:
        rounds, t0 = 0, time.perf_counter()
        while True:
            _round(server)
            rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
    window_compiles = counter.count - n0
    device = _device_block(cell["chips"], traced)

    # the last round's uplinks, then free the program's state
    bits = cfg["quant_bits"]
    measured = [messages.packed_wire_bytes(m) for m in rec.last_msgs]
    wires = [[b for name, b in messages.message_to_wire(m)
              if name != messages.HEADER_KEY] for m in rec.last_msgs]
    stacked = _host(rec.last_trained)
    k = len(jax.tree.leaves(stacked)[0])
    trained = [jax.tree.map(lambda a: a[i], stacked) for i in range(k)]
    spans = _span_seconds(tracer) if trace else {}
    steps = [np.asarray(s) for s in rec.steps]
    schedule, feeds_rec = rec.schedule, rec.feeds
    del server, rec, stacked
    gc.collect()

    # the reference follows the set-up's rounds
    index = reference.fingerprint_index(clients)
    feeds, feed_faults = [], []
    for fp, labels, n_steps in feeds_rec:
        feed, bad = reference.recover_feed(
            index, clients, fp, labels, n_steps, traffic["batch_size"],
            traffic["local_epochs"])
        feeds.append(feed)
        feed_faults += bad
    del index
    ref_trees, ref_losses = reference.follow(fam, cfg, frozen_host, g0,
                                             feeds, clients, bits)
    checks = reference.training_checks(g0, prog_trees, prog_losses,
                                       ref_trees, ref_losses)
    ctrl = None
    if control:
        low = reference.follow(fam, cfg, frozen_host, g0, feeds, clients,
                               bits, dtype=jax.numpy.bfloat16,
                               precision=jax.lax.Precision.DEFAULT)
        ctrl = reference.training_checks(g0, *low, ref_trees, ref_losses)
    numbers = {
        **{n: checks[n] for n in TRAINING},
        "uplink_level_gap": reference.uplink_level_gap(trained, wires, bits),
        "uplink_bytes_gap": float(max(abs(b - reference.wire_bytes(g0, bits))
                                      for b in measured)),
        "feed_faults": float(len(feed_faults)),
        "window_compiles": float(window_compiles),
    }
    limits = cell["limits"]
    correct = all(numbers[n] <= limits[n] for n in numbers)
    if ctrl is not None:
        ctrl["correct"] = all(ctrl[n] <= limits[n] for n in TRAINING)

    if trace:
        ctx = {"cell": cell, "config": cfg, "traffic": traffic,
               "trace": traced, "spans": spans, "rounds": rounds,
               "wall_s": wall, "n_steps": steps, "schedule_steps": schedule,
               "step_flops": fam.step_flops(cfg, traffic["batch_size"]),
               "codec_rows": _codec_rows(g0), "bits": bits,
               "peaks": peaks(device["kind"]), "chips": cell["chips"]}
        if keep_trace:
            Path(keep_trace + ".ctx.json").write_text(json.dumps(
                {k: ctx[k] for k in KEPT_CTX}
                | {"n_steps": [s.tolist() for s in steps],
                   "device_kind": device["kind"]}))
        metrics = read_metrics(cell, ctx,
                               strict=device["platform"] == "tpu")
    else:
        metrics = {
            "round_s": {"value": wall / rounds, "unit": "s/round"},
            "uplink_bytes": {"value": float(np.mean(measured)),
                             "unit": "B/client"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {m["name"]: metrics[m["name"]] for m in cell["end_to_end"]}
    out = {"correct": correct, "attempted": rounds, "failed": 0,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = trace_reduce.breakdown(traced)
    out["diagnostics"] = {"loss_gap": checks["loss_gap"],
                          "change_worst_rounds":
                              checks["change_worst_rounds"],
                          "feed_faults": feed_faults[:5],
                          "update_leaf": checks["update_leaf"],
                          "change_leaf": checks["change_leaf"],
                          "leaves_left_out": checks["leaves_left_out"],
                          "prog_losses": prog_losses,
                          "ref_losses": ref_losses}
    if ctrl is not None:
        out["control"] = ctrl
    out["checks"] = {n: {"value": v, "limit": limits[n]}
                     for n, v in numbers.items()}
    return out


def _span_seconds(tracer) -> dict:
    """{span name: [seconds of each span]} of the server's tracer."""
    out: dict = {}
    for e in tracer.events:
        if e.get("ph") == "X":
            out.setdefault(e["name"], []).append(e["dur"] / 1e6)
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the accelerator.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
