"""Chip smoke test: the FLoCoRA round and the adapter server, once each,
on a TPU, through the repository's own entry points.

    python chip_smoke.py                # one chip: phases fl_round, serve
    python chip_smoke.py --four-chips   # four chips: sharded cohort
                                        # reduction vs one chip, only

Phase ``fl_round`` runs the quickstart's uniform configuration: a frozen
random ResNet-8 (widths 64/128/256) with rank-32 adapters (alpha 512)
over 20 LDA-0.5 clients of synthetic 32x32x3 CIFAR-shaped images, 5 per
round, batch 32, the int8 flat wire, ``FLServer.run`` for 3 rounds. It
checks that every loss is finite and the measured uplink bytes equal
the static accounting; that one round's uplinks, packed by the Pallas
kernel, hold bit for bit the words of the jnp twin codec; and that the
``dequant_agg_rows`` kernel aggregate of those uplinks matches a plain
jnp unpack + weighted sum.

Phase ``serve`` runs one fused decode step of ``AdapterServingEngine``
at 1024 int4 adapters in rank buckets {4, 8}, d = 256, 64 rows, and
checks it against the engine's merged-dense ``oracle_step``.

Phase ``sharded_reduction`` (``--four-chips``) reduces 512 synthetic
packed ResNet-8 int8 uplinks on a 4-chip ``clients`` mesh and checks
the result against the single-chip ``dequant_agg_rows``.

Each phase prints its measurements on ``[phase]`` lines and raises on a
failed check. Without a TPU the script exits non-zero before any phase.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.core import flocora, messages  # noqa: E402
from repro.core.flocora import FLoCoRAConfig  # noqa: E402
from repro.core.lora import LoRAConfig  # noqa: E402
from repro.core.quant import QuantConfig  # noqa: E402
from repro.data import SyntheticVision, lda_partition  # noqa: E402
from repro.fl import ClientConfig, FLServer, ServerConfig  # noqa: E402
from repro.kernels import ops as kops, ref as kref  # noqa: E402
from repro.launch.mesh import make_client_mesh  # noqa: E402
from repro.models.resnet import ResNetConfig, init as resnet_init, \
    loss_fn  # noqa: E402
from repro.obs.compile import compile_count  # noqa: E402
from repro.serve import AdapterCache, AdapterServingEngine, \
    make_store  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

# fp32 tolerance of a reordered weighted sum (the repo's kernel tests)
RTOL, ATOL = 1e-5, 1e-6


def _log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _check(ok: bool, phase: str, what: str) -> None:
    if not ok:
        raise RuntimeError(f"[{phase}] check failed: {what}")


def _mosaic(fn, *args) -> bool:
    """Does ``fn``'s compiled program hold a Mosaic (Pallas) kernel?"""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _plain_agg(packed, scale, zp, w, n_valid, bits):
    """The cohort aggregate in plain jnp: unpack, dequantize, weighted
    sum, tails past each row's length zeroed."""
    lv = kref.unpack_words(packed, bits).astype(jnp.float32)
    zpz = jnp.where(scale > 0, zp, 0.0)
    deq = (lv - zpz[..., None]) * scale[..., None]
    out = jnp.sum(w[:, None, None] * deq, axis=0)
    col = jnp.arange(out.shape[1])[None, :]
    return jnp.where(col < n_valid[:, None], out, 0.0)


def _max_rel_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def fl_round(rounds: int = 3, n_samples: int = 2000, n_clients: int = 20,
             clients_per_round: int = 5, rank: int = 32, batch: int = 32,
             bits: int = 8) -> dict:
    """The quickstart's sync round (``examples/quickstart.py``)."""
    phase = "fl_round"
    rng = np.random.default_rng(0)
    sv = SyntheticVision(seed=0)
    y = rng.integers(0, 10, n_samples)
    x = sv.sample(rng, y)
    parts = lda_partition(y, n_clients, alpha=0.5)
    data = [{"x": x[p], "y": y[p].astype(np.int32)} for p in parts]
    alpha = 16.0 * rank
    cfg = ResNetConfig(arch="resnet8", lora=LoRAConfig(rank=rank,
                                                       alpha=alpha))
    model = resnet_init(jax.random.PRNGKey(0), cfg)
    fcfg = FLoCoRAConfig(rank=rank, alpha=alpha, quant_bits=bits)
    server = FLServer(
        model, lambda f, t, b: loss_fn(f, t, cfg, b), data,
        ServerConfig(rounds=rounds, n_clients=n_clients,
                     clients_per_round=clients_per_round),
        ClientConfig(local_epochs=1, batch_size=batch, lr=0.01), fcfg)
    static_bytes = messages.message_wire_bytes(model["train"], fcfg.qcfg)
    _log(phase, devices=jax.device_count(), clients=n_clients,
         per_round=clients_per_round, rank=rank, bits=bits,
         static_uplink_bytes=static_bytes)

    # the cohort trainer's output is every client's trained adapter tree:
    # keep the last round's, to rebuild its uplinks below
    trained = []
    train = server.trainer

    def recording_trainer(*args):
        out = train(*args)
        trained[:] = [out[0]]
        return out

    server.trainer = recording_trainer
    for _ in range(rounds):
        n0, t0 = compile_count(), time.perf_counter()
        rec = server.run(1)[-1]
        jax.block_until_ready(server.global_train)
        wall = time.perf_counter() - t0
        _log(phase, round=rec["round"], wall_s=wall,
             compiles=compile_count() - n0, loss=rec["client_loss"],
             up_bytes_measured=rec["up_bytes_measured"],
             round_bytes=rec["round_bytes"])
        _check(bool(np.isfinite(rec["client_loss"])), phase,
               f"finite loss in round {rec['round']}")
        _check(rec["up_bytes_measured"] == static_bytes, phase,
               f"measured uplink {rec['up_bytes_measured']} B == static "
               f"accounting {static_bytes} B")

    # the last round's uplinks: kernel-packed words vs the jnp twin
    stacked = trained[0]
    k = jax.tree.leaves(stacked)[0].shape[0]
    msgs, n_words = [], 0
    for i in range(k):
        t_i = jax.tree.map(lambda a: a[i], stacked)
        msg, _ = flocora.client_uplink(t_i, fcfg)
        twin = messages.pack_message(t_i, fcfg.qcfg, use_kernel=False)
        for got, want in zip(jax.tree.leaves(msg.as_tree(),
                                             is_leaf=messages.is_wire_leaf),
                             jax.tree.leaves(twin,
                                             is_leaf=messages.is_wire_leaf)):
            if not messages.is_packed_leaf(want):
                continue
            gw, ww = np.asarray(got.payload), np.asarray(want.payload)
            nw = ww.shape[1]
            _check(np.array_equal(gw[:, :nw], ww)
                   and not gw[:, nw:].any()
                   and np.array_equal(np.asarray(got.scale),
                                      np.asarray(want.scale))
                   and np.array_equal(np.asarray(got.zp),
                                      np.asarray(want.zp)),
                   phase, "kernel-packed words == jnp twin words")
            n_words += ww.size
        msgs.append(msg)
    lo = msgs[0].layout
    packed = jnp.stack([m.payload for m in msgs])
    scale = jnp.stack([m.scale for m in msgs])
    zp = jnp.stack([m.zp for m in msgs])
    w = jnp.asarray(np.random.default_rng(1).uniform(0.5, 2.0, k),
                    jnp.float32)
    nv = jnp.asarray(lo.n_valid_vec())
    agg = kops.dequant_agg_rows(packed, scale, zp, w, nv, bits)
    want = _plain_agg(packed, scale, zp, w, nv, bits)
    agg_ok = np.allclose(np.asarray(agg), np.asarray(want), rtol=RTOL,
                         atol=ATOL)
    x2d = jnp.zeros((lo.c_total, lo.n_max), jnp.float32)
    mosaic = {
        "quant_pack_rows": _mosaic(
            lambda a, b: kops.quant_pack_rows(a, b, bits), x2d, nv),
        "dequant_agg_rows": _mosaic(
            lambda *a: kops.dequant_agg_rows(*a, bits),
            packed, scale, zp, w, nv)}
    _log(phase, uplinks=k, words_bit_identical=n_words,
         agg_max_rel_err=_max_rel_err(agg, want), agg_within_tol=agg_ok,
         layout=f"{lo.c_total}x{lo.n_max}",
         mosaic_kernels=",".join(n for n, v in mosaic.items() if v))
    _check(agg_ok, phase, f"kernel aggregate within rtol {RTOL} atol "
           f"{ATOL} of the plain jnp aggregate")
    return {"history": server.history, "mosaic": mosaic}


def serve(n_adapters: int = 1024, d_model: int = 256,
          ranks: tuple = (4, 8), bits: int = 4, rows: int = 64,
          slab_slots: int = 512) -> dict:
    """One fused decode step of the multi-tenant adapter server."""
    phase = "serve"
    weights, store = make_store(n_clients=n_adapters, d_model=d_model,
                                n_layers=2, ranks=ranks, bits=bits, seed=0)
    total = sum(store.bytes_of(c) for c in store.cids)
    cache = AdapterCache(capacity_bytes=2 * total, qcfg=store.qcfg)
    engine = AdapterServingEngine(weights, 0.5, store.qcfg, cache,
                                  fetch=store.fetch, slab_slots=slab_slots)
    engine.admit(list(range(n_adapters)))
    rng = np.random.default_rng(0)
    cids = [int(c) for c in rng.integers(0, n_adapters, rows)]
    x = jnp.asarray(rng.standard_normal((rows, d_model)) * 0.5, jnp.float32)
    n0, t0 = compile_count(), time.perf_counter()
    y = jax.block_until_ready(engine.step(x, cids))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(engine.step(x, cids))
    warm = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        want = engine.oracle_step(x, cids)
    err = _max_rel_err(y, want)
    r_max = max(ranks)
    per = 32 // bits
    mosaic = _mosaic(
        lambda *a: kops.multi_lora_matmul_packed(*a, 0.5, bits),
        x, weights[0],
        jnp.zeros((slab_slots, r_max, -(-d_model // per)), jnp.uint32),
        jnp.zeros((slab_slots, r_max)), jnp.zeros((slab_slots, r_max)),
        jnp.zeros((slab_slots, d_model, -(-r_max // per)), jnp.uint32),
        jnp.zeros((slab_slots, d_model)), jnp.zeros((slab_slots, d_model)),
        jnp.zeros((rows,), jnp.int32))
    _log(phase, adapters=n_adapters, store_bytes=total, rows=rows,
         d=d_model, step_cold_s=cold, step_warm_s=warm,
         compiles=compile_count() - n0, max_rel_err_vs_oracle=err,
         mosaic_kernels="multi_lora_matmul_packed" if mosaic else "")
    _check(y.shape == (rows, d_model) and bool(np.isfinite(y).all()),
           phase, "finite (rows, d) output")
    _check(err < 1e-4, phase, f"fused step within 1e-4 (relative to the "
           f"largest output) of oracle_step: {err}")
    return {"max_rel_err": err, "mosaic": {"multi_lora_matmul_packed":
                                           mosaic}}


def sharded_reduction(k: int = 512, rank: int = 32, bits: int = 8,
                      n_devices: int = 4, iters: int = 3) -> dict:
    """The mesh-sharded cohort reduction against one device: ``k``
    synthetic packed uplinks of the quickstart ResNet-8 layout (random
    levels, real row lengths), pre-sharded on the ``clients`` mesh."""
    phase = "sharded_reduction"
    model = resnet_init(jax.random.PRNGKey(0), ResNetConfig(
        arch="resnet8", lora=LoRAConfig(rank=rank, alpha=16.0 * rank)))
    lo = messages.pack_message(model["train"], QuantConfig(bits=bits),
                               flat=True).layout
    nv = jnp.asarray(lo.n_valid_vec())
    nww = -(-nv // (32 // bits))          # words holding each row's levels

    @jax.jit
    def uplinks(key):
        ks = jax.random.split(key, 4)
        words = jax.random.bits(ks[0], (k, lo.c_total, lo.nw_max),
                                jnp.uint32)
        col = jnp.arange(lo.nw_max)[None, None, :]
        packed = jnp.where(col < nww[None, :, None], words, 0)
        scale = jax.random.uniform(ks[1], (k, lo.c_total), minval=1e-3,
                                   maxval=1e-2)
        zp = jnp.floor(jax.random.uniform(ks[2], (k, lo.c_total),
                                          maxval=(1 << bits) - 1))
        w = jax.random.uniform(ks[3], (k,), minval=0.5, maxval=2.0)
        return packed, scale, zp, w / jnp.sum(w)

    packed, scale, zp, w = uplinks(jax.random.PRNGKey(1))

    mesh = make_client_mesh(n_devices)
    on_mesh = NamedSharding(mesh, PartitionSpec(kops.CLIENT_AXIS))
    sh = [jax.device_put(a, on_mesh) for a in (packed, scale, zp, w)]

    def single():
        return kops.dequant_agg_rows(packed, scale, zp, w, nv, bits)

    def sharded():
        return kops.dequant_agg_rows_sharded(*sh, nv, bits, mesh)

    times = {}
    for name, fn in (("single", single), ("sharded", sharded)):
        out = jax.block_until_ready(fn())          # compile + warm-up
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        times[name] = (time.perf_counter() - t0) / iters
    one, many = single(), sharded()
    ok = np.allclose(np.asarray(many), np.asarray(one), rtol=RTOL,
                     atol=ATOL)
    _log(phase, devices=int(np.prod(mesh.devices.shape)), clients=k,
         packed_bytes=int(packed.size) * 4,
         layout=f"{lo.c_total}x{lo.n_max}", single_s=times["single"],
         sharded_s=times["sharded"], max_rel_err=_max_rel_err(many, one),
         within_tol=ok)
    _check(ok, phase, f"sharded within rtol {RTOL} atol {ATOL} of the "
           "single-device reduction")
    return {"times": times}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded cohort reduction on a "
                         "4-chip mesh, against one chip")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    enable_compile_cache()
    if args.four_chips:
        if jax.device_count() < 4:
            sys.exit(f"--four-chips needs 4 TPU chips; JAX found "
                     f"{jax.device_count()}")
        sharded_reduction()
    else:
        kernels = {**fl_round()["mosaic"], **serve()["mosaic"]}
        missing = [n for n, v in kernels.items() if not v]
        _check(not missing, "main", f"a Mosaic kernel in each of "
               f"{missing}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
