"""The plain reference of a synchronous FLoCoRA round, and the comparisons
that decide a run's ``correct``.

One round (arXiv:2406.14082, Fig. 1): the server quantizes the global
adapter tree and each sampled client trains from its dequantized copy
with SGD and momentum over its own batches; each client quantizes its
trained tree for the uplink; the server's new global tree is the mean of
the dequantized uplinks weighted by the clients' dataset sizes.

The quantizer is the paper's per-channel asymmetric min-max quantizer:
a tensor's channels are its last axis; each channel's range is widened to
hold 0; ``scale = (max - min) / (2^bits - 1)`` (1 where the range is 0),
``zp = round(-min / scale)`` clipped to the level range, and a value's
level is ``round(x / scale) + zp`` clipped likewise. Tensors of fewer
than two axes travel as float32. On the wire each tensor of two or more
axes takes ``ceil(n * bits / 8)`` bytes of levels, packed row after row
with the first level in the low bits, and a float32 scale and zero point
per channel; the others take 4 bytes a value.

Nothing here comes from the program; the model is a family module's
``loss`` (``bench/models/<family>.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


# -- the codec ---------------------------------------------------------------

def channel_rows(x: np.ndarray) -> np.ndarray:
    """(..., C) -> (C, n): one row per channel."""
    return np.moveaxis(x, -1, 0).reshape(x.shape[-1], -1)


def quantize_rows(rows: np.ndarray, bits: int):
    """(C, n) float32 -> (levels (C, n) int64, scale (C,), zp (C,))."""
    qmax = np.float32((1 << bits) - 1)
    rows = rows.astype(np.float32)
    lo = np.minimum(rows.min(axis=1), np.float32(0))
    hi = np.maximum(rows.max(axis=1), np.float32(0))
    span = hi - lo
    scale = np.where(span > 0, span / qmax, np.float32(1)).astype(np.float32)
    zp = np.clip(np.round(-lo / scale), 0, qmax).astype(np.float32)
    q = np.clip(np.round(rows / scale[:, None]) + zp[:, None], 0, qmax)
    return q.astype(np.int64), scale, zp


def roundtrip(tree, bits: int):
    """What the receiver reconstructs from the quantized message."""
    def one(x):
        x = np.asarray(x, np.float32)
        if x.ndim < 2:
            return x
        q, scale, zp = quantize_rows(channel_rows(x), bits)
        deq = ((q - zp[:, None]) * scale[:, None]).astype(np.float32)
        c = x.shape[-1]
        return np.moveaxis(deq.reshape((c,) + x.shape[:-1]), 0, -1)
    return jax.tree.map(one, tree)


def wire_bytes(tree, bits: int) -> int:
    """Bytes of one message of ``tree`` on the wire, without its header."""
    total = 0
    for x in jax.tree.leaves(tree):
        n = int(np.prod(x.shape))
        if len(x.shape) < 2:
            total += 4 * n
        else:
            total += math.ceil(n * bits / 8) + 8 * x.shape[-1]
    return total


def wire_levels(payload: np.ndarray, bits: int, shape: tuple) -> np.ndarray:
    """A tensor's level bytes from the wire -> levels (C, n)."""
    c = shape[-1]
    n = int(np.prod(shape)) // c
    raw = np.asarray(payload, np.uint8).reshape(-1)
    if bits == 8:
        return raw[: c * n].astype(np.int64).reshape(c, n)
    b = np.unpackbits(raw, bitorder="little")[: c * n * bits]
    weights = (1 << np.arange(bits)).astype(np.int64)
    return (b.reshape(c * n, bits).astype(np.int64) @ weights).reshape(c, n)


# -- local training and the round --------------------------------------------

def make_step(family, cfg: dict, dtype, precision):
    """One jitted SGD-with-momentum step of the plain model:
    ``(frozen, train, mu, x, y) -> (train, mu, loss)``."""
    lr, mom = cfg["lr"], cfg["momentum"]

    @jax.jit
    def step(frozen, train, mu, x, y):
        loss, g = jax.value_and_grad(
            lambda t: family.loss(frozen, t, cfg, x.astype(dtype), y,
                                  precision))(train)
        mu = jax.tree.map(lambda m, d: mom * m + d.astype(m.dtype), mu, g)
        train = jax.tree.map(lambda p, m: p - lr * m.astype(p.dtype),
                             train, mu)
        return train, mu, loss

    return step


def to_device(tree, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)


def reference_round(step, frozen, global_tree, feed, clients, bits: int,
                    dtype):
    """One round from ``global_tree`` (host float32): ``feed`` lists, per
    client slot, ``(cid, idx)`` with ``idx`` (active steps, batch) its
    batches' rows. Returns (new global tree, mean client loss)."""
    start = to_device(roundtrip(global_tree, bits), dtype)
    trained, losses, sizes = [], [], []
    for cid, idx in feed:
        data = clients[cid]
        train = start
        mu = jax.tree.map(jnp.zeros_like, start)
        run = []
        for rows in idx:
            train, mu, loss = step(frozen, train, mu,
                                   jnp.asarray(data["x"][rows]),
                                   jnp.asarray(data["y"][rows]))
            run.append(loss)
        losses.append(float(np.mean(np.asarray(jax.device_get(run),
                                               np.float64))))
        trained.append(jax.tree.map(
            lambda a: np.asarray(a, np.float32), jax.device_get(train)))
        sizes.append(len(data["y"]))
    w = np.asarray(sizes, np.float64) / np.sum(sizes)
    deq = [roundtrip(t, bits) for t in trained]
    new = jax.tree.map(
        lambda *xs: sum(np.float64(wk) * x.astype(np.float64)
                        for wk, x in zip(w, xs)).astype(np.float32), *deq)
    return new, float(np.mean(losses))


def follow(family, cfg: dict, frozen, g0, feeds, clients, bits: int,
           dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST):
    """The reference over the followed rounds: (global trees after each
    round, each round's mean client loss)."""
    step = make_step(family, cfg, dtype, precision)
    frozen = to_device(frozen, dtype)
    trees, losses, g = [], [], g0
    for feed in feeds:
        g, loss = reference_round(step, frozen, g, feed, clients, bits,
                                  dtype)
        trees.append(g)
        losses.append(loss)
    return trees, losses


# -- the feed: which rows the program trained each client on -----------------

FINGERPRINT = 8           # leading values of an image that identify it


def fingerprint_index(clients: list[dict]) -> dict:
    """{fingerprint bytes: (cid, row)} over every client's images."""
    out = {}
    for cid, d in enumerate(clients):
        fp = np.ascontiguousarray(
            d["x"].reshape(len(d["x"]), -1)[:, :FINGERPRINT])
        for row, key in enumerate(fp.view(f"V{fp.shape[1] * 4}")[:, 0]):
            out[key.tobytes()] = (cid, row)
    return out


def recover_feed(index: dict, clients: list[dict], fingerprints, labels,
                 n_steps, batch: int, epochs: int) -> tuple[list, list]:
    """Map a round's staged batches back to the clients' rows.

    ``fingerprints`` (K, steps, batch, FINGERPRINT), ``labels`` (K,
    steps, batch) and ``n_steps`` (K,) are what the trainer was given.
    Returns ([(cid, idx (active steps, batch))], [faults]): a fault is a
    row that is no client's, a slot that mixes clients or trains a client
    twice, a label that is not the row's, an active step count that is
    not the client's schedule, or an epoch that skips or repeats a row."""
    feed, faults = [], []
    seen = set()
    for k in range(len(n_steps)):
        ns = int(n_steps[k])
        fp = np.ascontiguousarray(fingerprints[k, :ns], np.float32)
        keys = fp.reshape(-1, FINGERPRINT).view(f"V{FINGERPRINT * 4}")[:, 0]
        hits = [index.get(key.tobytes()) for key in keys]
        if any(h is None for h in hits):
            faults.append(f"slot {k}: a row is no client's")
            continue
        cids = {c for c, _ in hits}
        if len(cids) != 1:
            faults.append(f"slot {k}: rows of clients {sorted(cids)}")
            continue
        cid = cids.pop()
        if cid in seen:
            faults.append(f"client {cid} trained twice in one round")
        seen.add(cid)
        rows = np.asarray([r for _, r in hits]).reshape(ns, batch)
        data = clients[cid]
        n = len(data["y"])
        per_epoch = max(1, n // batch)
        if ns != per_epoch * epochs:
            faults.append(f"client {cid}: {ns} steps, schedule "
                          f"{per_epoch * epochs}")
        if not np.array_equal(data["y"][rows],
                              np.asarray(labels[k, :ns])):
            faults.append(f"client {cid}: labels are not the rows'")
        for e in range(0, ns, per_epoch):
            ep = rows[e: e + per_epoch].reshape(-1)
            ok = (len(set(ep.tolist())) == len(ep)) if n >= batch \
                else set(ep.tolist()) == set(range(n))
            if not ok:
                faults.append(f"client {cid}: epoch {e // per_epoch} "
                              "skips or repeats a row")
        feed.append((cid, rows))
    return feed, faults


# -- the comparisons ----------------------------------------------------------

def leaf_norms(a, b) -> dict:
    """{path: ||a - b||} over the leaves of two host trees."""
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree.leaves(b)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64) - np.asarray(y, np.float64)))
        for (p, x), y in zip(flat_a, flat_b)}


def moving_leaves(ref_update: dict, floor: float = 1e-3) -> set:
    """The leaves the reference's first update moves: those whose change
    is at least ``floor`` of the median leaf's. The others move by
    rounding alone and are left out of the comparisons."""
    med = float(np.median(list(ref_update.values())))
    return {k for k, v in ref_update.items() if v >= floor * med}


def norm_gaps(prog: dict, ref: dict, keep: set) -> dict:
    """{leaf: |prog norm - ref norm| / max(ref norm, median ref norm)}."""
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def training_checks(g0, prog_trees, prog_losses, ref_trees,
                    ref_losses) -> dict:
    """The numbers that compare a followed run with the reference, each
    a gap of norms, leaf by leaf (``norm_gaps``):

    - ``update_gap``: the worst leaf's, of the first round's update;
    - ``change_gap``: the median leaf's, of the change over all followed
      rounds;
    - ``change_worst``: the worst leaf's, of that change;
    - ``loss_gap``: the largest relative gap of a round's mean client
      loss, reported and not compared: the bfloat16 control reads no
      higher than sound runs.

    ``change_worst_rounds`` follows the leaf of ``change_worst`` round by
    round: its gap of the change after each followed round (the first is
    its gap of the first update)."""
    ref_u = leaf_norms(ref_trees[0], g0)
    keep = moving_leaves(ref_u)
    upd = norm_gaps(leaf_norms(prog_trees[0], g0), ref_u, keep)
    per_round = [norm_gaps(leaf_norms(p, g0), leaf_norms(r, g0), keep)
                 for p, r in zip(prog_trees, ref_trees)]
    chg = per_round[-1]
    worst = max(chg, key=chg.get)
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog_losses, ref_losses))
    return {"update_gap": max(upd.values()),
            "change_gap": float(np.median(list(chg.values()))),
            "change_worst": chg[worst], "loss_gap": loss,
            "update_leaf": max(upd, key=upd.get), "change_leaf": worst,
            "change_worst_rounds": [g[worst] for g in per_round],
            "leaves_left_out": sorted(set(ref_u) - keep)}


def uplink_level_gap(trained: list, wires: list, bits: int) -> float:
    """Largest |level| gap between each uplink's wire levels and the
    reference quantizer applied to the trained tree it came from, or
    infinity where a float32 tensor differs. An uplink is matched to its
    trained tree by its scales."""
    refs = []
    for t in trained:
        leaves = [np.asarray(x, np.float32) for x in jax.tree.leaves(t)]
        q = [quantize_rows(channel_rows(x), bits) if x.ndim >= 2 else None
             for x in leaves]
        refs.append((leaves, q))
    worst = 0.0
    for entries in wires:
        scales = np.concatenate([np.asarray(b["scale"], np.float32)
                                 for b in entries if "scale" in b])
        dist = [np.abs(np.concatenate([qi[1] for qi in q if qi is not None])
                       - scales).sum() for _, q in refs]
        leaves, q = refs[int(np.argmin(dist))]
        if len(entries) != len(leaves):
            return float("inf")
        for x, qi, b in zip(leaves, q, entries):
            if qi is None:          # float32 on the wire: exact
                if not np.array_equal(
                        np.asarray(b["payload"], np.float32).reshape(-1),
                        x.reshape(-1)):
                    return float("inf")
                continue
            got = wire_levels(b["payload"], bits, x.shape)
            worst = max(worst, float(np.abs(got - qi[0]).max()))
    return worst
