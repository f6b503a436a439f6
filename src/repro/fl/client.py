"""Client-side local training (paper §IV setup).

Defaults match the paper: SGD momentum 0.9, lr 0.01, batch 32, 5 local
epochs. Two execution engines over the same local-run body:

  * ``make_local_trainer`` — one client per call; jits ONCE per
    (model, batch-shape) and is reused by every simulated client;
  * ``make_cohort_trainer`` — the VMAPPED COHORT ENGINE: K clients'
    local runs batch into ONE jitted program over stacked
    (K, steps, B, ...) batches. The K local scans execute as a single
    vectorized program — on accelerators every matmul carries the extra
    K dim instead of K sequential dispatches (see
    benchmarks/round_throughput.py for the clients/sec win).

Batches are pre-gathered host-side (``stack_local_batches`` /
``stack_cohort_batches``) and each local run is a lax.scan.

``fedprox_mu`` adds the FedProx proximal term — demonstrating the paper's
aggregation-agnostic claim (FLoCoRA composes with any FL optimizer
unchanged, §III).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import sgd

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ClientConfig:
    local_epochs: int = 5
    batch_size: int = 32
    lr: float = 0.01
    momentum: float = 0.9
    fedprox_mu: float = 0.0


def _local_run(loss_fn: Callable, cfg: ClientConfig):
    """Un-jitted single-client local run, shared by both engines.

    ``run(frozen, train0, batches) -> (train, mean_loss)`` where batches
    is a pytree with leading (steps, B) dims."""
    opt = sgd(momentum=cfg.momentum)

    def run(frozen, train0, batches):
        opt_state = opt.init(train0)

        def grad_loss(train, batch):
            loss, _ = loss_fn(frozen, train, batch)
            if cfg.fedprox_mu > 0.0:
                prox = sum(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))
                    for a, b in zip(jax.tree.leaves(train),
                                    jax.tree.leaves(train0)))
                loss = loss + 0.5 * cfg.fedprox_mu * prox
            return loss

        def step(carry, batch):
            train, opt_state = carry
            loss, grads = jax.value_and_grad(grad_loss)(train, batch)
            train, opt_state = opt.update(grads, opt_state, train, cfg.lr)
            return (train, opt_state), loss

        (train, _), losses = jax.lax.scan(step, (train0, opt_state), batches)
        return train, jnp.mean(losses)

    return run


def make_local_trainer(loss_fn: Callable, cfg: ClientConfig):
    """loss_fn(frozen, train, batch) -> (loss, metrics).

    Returns ``run(frozen, train0, batches) -> (train, mean_loss)``.
    Jitted once; sequential-baseline engine (one client per call)."""
    return jax.jit(_local_run(loss_fn, cfg))


def _masked_local_run(loss_fn: Callable, cfg: ClientConfig):
    """Single-client local run over a FIXED-length schedule with a
    per-client active step count: steps past ``n_steps`` are no-ops
    (params, momentum and loss untouched), so heterogeneous clients
    batch into one program without training small clients past their
    own local_epochs."""
    opt = sgd(momentum=cfg.momentum)

    def run(frozen, train0, batches, n_steps):
        opt_state = opt.init(train0)

        def grad_loss(train, batch):
            loss, _ = loss_fn(frozen, train, batch)
            if cfg.fedprox_mu > 0.0:
                prox = sum(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))
                    for a, b in zip(jax.tree.leaves(train),
                                    jax.tree.leaves(train0)))
                loss = loss + 0.5 * cfg.fedprox_mu * prox
            return loss

        def step(carry, inp):
            t, batch = inp
            train, opt_state = carry
            loss, grads = jax.value_and_grad(grad_loss)(train, batch)
            train2, opt2 = opt.update(grads, opt_state, train, cfg.lr)
            active = t < n_steps
            keep = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(active, a, b), new, old)
            return ((keep(train2, train), keep(opt2, opt_state)),
                    jnp.where(active, loss, 0.0))

        ts = jnp.arange(jax.tree.leaves(batches)[0].shape[0])
        (train, _), losses = jax.lax.scan(step, (train0, opt_state),
                                          (ts, batches))
        return train, jnp.sum(losses) / jnp.maximum(n_steps, 1)

    return run


def make_cohort_trainer(loss_fn: Callable, cfg: ClientConfig):
    """Vmapped cohort engine: K clients in one jitted program.

    Returns ``run(frozen, train0, batches, n_steps) -> (trained, losses)``
    where batches has leading (K, steps, B) dims, ``n_steps`` is the (K,)
    per-client active step count (masked no-ops beyond it), ``trained``
    leaves carry a leading K dim and ``losses`` is (K,).
    ``frozen``/``train0`` are shared (broadcast state) across the cohort.
    Compilation caches on (K, steps, B, ...): keep the schedule length
    fixed across rounds (see FLServer) so only distinct cohort sizes K
    retrace."""
    return jax.jit(jax.vmap(_masked_local_run(loss_fn, cfg),
                            in_axes=(None, None, 0, 0)))


@jax.jit
def unstack_cohort(trained: Any) -> list:
    """A cohort trainer's stacked output -> one tree per client row, in
    ONE dispatch: tree ``k`` equals ``jax.tree.map(lambda x: x[k],
    trained)`` bit for bit, which costs three eager dispatches per leaf.

    Every row comes back, the masked rows of a pow2-padded bucket too:
    callers use the first ``len(cohort)``. The program is keyed by the
    tree's structure and leaf shapes (leading K included), which the
    cohort trainer already fixes, so it compiles once per bucket shape."""
    k = jax.tree.leaves(trained)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], trained) for i in range(k)]


def make_staggered_cohort_trainer(loss_fn: Callable, cfg: ClientConfig):
    """Async cohort engine: like ``make_cohort_trainer`` but ``train0``
    carries a leading K dim — each client starts from its OWN adapter
    tree (asynchronous arrivals trained from different global versions
    batch into one program; see fl/async_engine.py).

    Compilation caches on (adapter shapes, K, steps, B): the async
    engine groups arrivals by rank and pads each group's client dim to a
    pow2, so the compiled-program count stays bounded by
    #distinct-ranks x log2(max micro-batch)."""
    return jax.jit(jax.vmap(_masked_local_run(loss_fn, cfg),
                            in_axes=(None, 0, 0, 0)))


def stack_local_batches(rng: np.random.Generator, data: dict,
                        cfg: ClientConfig,
                        steps: Optional[int] = None) -> dict:
    """Host-side: pack a client's dataset into (steps, B, ...) batches,
    reshuffling each local epoch (with wraparound padding).

    ``steps`` overrides the natural step count (epochs are repeated /
    truncated to exactly that many batches) — the cohort engine equalizes
    step counts across clients this way."""
    n = len(next(iter(data.values())))
    per_epoch = max(1, n // cfg.batch_size)
    total = per_epoch * cfg.local_epochs if steps is None else steps
    idx_all = []
    got = 0
    while got < total:
        idx = rng.permutation(n)
        take = per_epoch * cfg.batch_size
        if take > n:
            idx = np.concatenate([idx, rng.integers(0, n, take - n)])
        idx_all.append(idx[:take].reshape(per_epoch, cfg.batch_size))
        got += per_epoch
    idx_all = np.concatenate(idx_all, axis=0)[:total]
    return {k: v[idx_all] for k, v in data.items()}


def natural_steps(data: dict, cfg: ClientConfig) -> int:
    """One client's paper-faithful local schedule length."""
    n = len(next(iter(data.values())))
    return max(1, n // cfg.batch_size) * cfg.local_epochs


def cohort_steps(datas: list[dict], cfg: ClientConfig) -> int:
    """Fixed schedule length for a cohort engine program: the largest
    client's natural schedule. Clients with fewer steps are MASKED past
    their own count (see make_cohort_trainer), not over-trained."""
    return max(natural_steps(d, cfg) for d in datas)


def pow2_pad(k: int) -> int:
    """Next power of two >= k. The rank-bucketed engine pads each
    bucket's client dim to a pow2 so the per-bucket compiled-program
    count is bounded by #distinct-ranks x log2(max cohort) instead of
    #ranks x #bucket-sizes."""
    p = 1
    while p < k:
        p *= 2
    return p


def pad_cohort_batches(batches: dict, n_steps: np.ndarray, k_pad: int
                       ) -> tuple[dict, np.ndarray]:
    """Pad the leading client dim of a stacked cohort to ``k_pad`` by
    repeating client 0's batches with ``n_steps = 0``: padded rows run
    fully masked (no parameter updates) and their outputs are
    discarded."""
    k = int(n_steps.shape[0])
    if k_pad <= k:
        return batches, n_steps
    reps = k_pad - k
    out = {key: np.concatenate([v, np.repeat(v[:1], reps, axis=0)],
                               axis=0)
           for key, v in batches.items()}
    return out, np.concatenate([n_steps,
                                np.zeros(reps, np.int32)]).astype(np.int32)


def stack_cohort_batches(rng: np.random.Generator, datas: list[dict],
                         cfg: ClientConfig,
                         steps: Optional[int] = None
                         ) -> tuple[dict, np.ndarray]:
    """Host-side: gather K clients' local schedules into one
    (K, steps, B, ...) stack for the cohort engine.

    Returns (stacked batches, (K,) int32 per-client active step counts).
    Pass a server-wide ``steps`` (>= every client's natural count) to pin
    the compiled program shape across rounds."""
    if steps is None:
        steps = cohort_steps(datas, cfg)
    n_steps = np.asarray([min(natural_steps(d, cfg), steps)
                          for d in datas], np.int32)
    per = [stack_local_batches(rng, d, cfg, steps=steps) for d in datas]
    return ({k: np.stack([p[k] for p in per], axis=0) for k in per[0]},
            n_steps)
