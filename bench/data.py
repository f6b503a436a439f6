"""A cell's inputs, made from its seed: CIFAR-shaped class-conditional
images and the LDA split of their labels over the clients.

This is a copy of the repository's synthetic-vision generator and LDA
partition, kept with the benchmark so that the yardstick cannot move
with the program. The generator is unchanged in what it draws from: each
class has a template of twice box-blurred Gaussian noise, normalised to
unit variance; each image is its class template shifted by up to two
pixels each way, mirrored with probability one half, plus Gaussian noise.
Only how it is made differs: the 50 shifted and mirrored variants of each
template are made once, and the images are gathered from them and noised
on the device in one jitted call, which is 614 MB for 50,000 images.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SHIFT = 2          # pixels, each way


def substreams(seed: int, n: int) -> list[int]:
    """``n`` independent 32-bit seeds from one benchmark seed of any size."""
    return [int(s) for s in np.random.SeedSequence(seed).generate_state(n)]


def templates(seed: int, n_classes: int, shape: tuple) -> np.ndarray:
    rng = np.random.default_rng(seed)
    raw = rng.normal(size=(n_classes, *shape))
    for _ in range(2):
        raw = (raw + np.roll(raw, 1, 1) + np.roll(raw, -1, 1)
               + np.roll(raw, 1, 2) + np.roll(raw, -1, 2)) / 5.0
    return (raw / raw.std()).astype(np.float32)


def variants(tmpl: np.ndarray) -> np.ndarray:
    """(classes, shifts * 2, H, W, C): every shift of every template,
    then the same mirrored left to right."""
    shifts = [(dy, dx) for dy in range(-SHIFT, SHIFT + 1)
              for dx in range(-SHIFT, SHIFT + 1)]
    out = []
    for t in tmpl:
        rolled = [np.roll(t, s, axis=(0, 1)) for s in shifts]
        out.append(np.stack(rolled + [r[:, ::-1] for r in rolled]))
    return np.stack(out)


@jax.jit
def _noised(key, bank, index, noise):
    x = bank[index]
    return x + noise * jax.random.normal(key, x.shape, x.dtype)


def make_images(seed: int, n: int, n_classes: int, shape: tuple,
                noise: float) -> tuple[np.ndarray, np.ndarray]:
    """``n`` images in ``n_classes`` balanced classes: (x (n, *shape) f32,
    y (n,) int32), both on the host."""
    if n % n_classes:
        raise ValueError(f"{n} images do not split into {n_classes} "
                         "balanced classes")
    s_tmpl, s_lab, s_var, s_noise = substreams(seed, 4)
    rng = np.random.default_rng(s_lab)
    y = rng.permutation(np.repeat(np.arange(n_classes, dtype=np.int32),
                                  n // n_classes))
    bank = variants(templates(s_tmpl, n_classes, shape))
    per_class = bank.shape[1]
    pick = np.random.default_rng(s_var).integers(0, per_class, n)
    index = y.astype(np.int64) * per_class + pick
    x = _noised(jax.random.PRNGKey(s_noise),
                jnp.asarray(bank.reshape(-1, *shape)),
                jnp.asarray(index, jnp.int32), jnp.float32(noise))
    host = np.array(x)
    x.delete()
    return host, y


def lda_partition(labels: np.ndarray, n_clients: int, alpha: float,
                  seed: int = 0, min_size: int = 2,
                  max_retries: int = 1000) -> list[np.ndarray]:
    """Per-client index arrays: each class's examples are split across
    the clients by a Dirichlet(alpha) draw (Hsu et al. 2019). A draw
    that leaves a client under ``min_size`` examples is redrawn; after
    ``max_retries`` the last draw is repaired by moving examples from the
    fullest clients to the starved ones."""
    if n_clients * min_size > len(labels):
        raise ValueError(
            f"min_size={min_size} infeasible: {n_clients} clients need "
            f"{n_clients * min_size} samples, have {len(labels)}")
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    for _ in range(max(1, max_retries)):
        buckets: list[list[int]] = [[] for _ in range(n_clients)]
        for c in range(n_classes):
            idx = np.where(labels == c)[0]
            rng.shuffle(idx)
            props = rng.dirichlet(np.full(n_clients, alpha))
            cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
            for b, part in zip(buckets, np.split(idx, cuts)):
                b.extend(part.tolist())
        if min(len(b) for b in buckets) >= min_size:
            break
    else:
        for i in sorted(range(n_clients), key=lambda j: len(buckets[j])):
            while len(buckets[i]) < min_size:
                donor = max(range(n_clients), key=lambda j: len(buckets[j]))
                buckets[i].append(buckets[donor].pop())
    out = []
    for b in buckets:
        arr = np.asarray(b, np.int64)
        rng.shuffle(arr)
        out.append(arr)
    return out


def make_clients(seed: int, traffic: dict, shape: tuple,
                 n_classes: int) -> list[dict]:
    """The traffic mix's client datasets, one {"x", "y"} dict each.

    The LDA split is drawn from the mix's fixed ``partition_seed``, so
    every seed gives the same clients' sizes and label mixes, and with
    them the same cohort schedule and programs; the seed draws the
    images and the order of the clients."""
    s_img, s_order = substreams(seed, 2)
    x, y = make_images(s_img, traffic["n_images"], n_classes, shape,
                       traffic["noise"])
    parts = lda_partition(y, traffic["n_clients"], traffic["lda_alpha"],
                          seed=traffic["partition_seed"])
    order = np.random.default_rng(s_order).permutation(len(parts))
    return [{"x": x[parts[i]], "y": y[parts[i]]} for i in order]
