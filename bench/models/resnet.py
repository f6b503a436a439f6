"""The plain reference of the CIFAR ResNets with conv-LoRA adapters
(FLoCoRA, arXiv:2406.14082, section III), their weights from the seed,
and the FLOPs of one local training step.

Straightforward ``jax.numpy``; nothing here comes from the program.

Architecture, as the paper describes it (He et al. 2016, CIFAR variant,
with GroupNorm after Hsu et al.): a 3x3 stem convolution 3 -> 64 and a
GroupNorm; basic blocks of two 3x3 convolutions, each followed by a
GroupNorm, ReLU after the first and after the residual sum; a 1x1
projection with its own GroupNorm where a block changes stride or width;
global average pooling; a dense classifier with bias. FLoCoRA freezes
every convolution but the stem and trains a low-rank side chain beside
each: ``(alpha / r) * conv1x1(conv(x, B), A)``, where ``B`` is ``(kh, kw,
c_in, r)`` and carries the convolution's stride and padding, and ``A`` is
``(1, 1, r, c_out)``. The stem, every GroupNorm and the classifier are
trained densely. Departure: none known.

Weights are laid out as the program takes them: ``{"frozen": ...,
"train": ...}`` with the same keys and shapes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GN_EPS = 1e-5


def stages(cfg: dict) -> list[tuple[int, int, int]]:
    return list(zip(cfg["stage_widths"], cfg["blocks_per_stage"],
                    cfg["stage_strides"]))


def blocks(cfg: dict):
    """(c_in, width, stride, has_projection) of every basic block."""
    cin = cfg["stem_width"]
    for width, n, stride in stages(cfg):
        for b in range(n):
            s = stride if b == 0 else 1
            yield cin, width, s, (s != 1 or cin != width)
            cin = width


def init(key, cfg: dict) -> dict:
    """Random weights: He-normal convolutions, B He-normal over its fan-in
    and A zero (the LoRA convention), GroupNorm scale 1 and bias 0, the
    classifier normal over sqrt(fan-in) with zero bias."""
    r = cfg["lora_rank"]
    keys = iter(jax.random.split(key, 256))

    def he(shape):
        fan = shape[0] * shape[1] * shape[2]
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * math.sqrt(2.0 / fan)

    def norm(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    def lora_conv(kh, cin, cout):
        frozen = {"w": he((kh, kh, cin, cout))}
        return frozen, {"b": he((kh, kh, cin, r)),
                        "a": jnp.zeros((1, 1, r, cout), jnp.float32)}

    c0 = cfg["stem_width"]
    frozen = {"stem": {"conv": {}, "norm": {}}, "blocks": [], "fc": {}}
    train = {"stem": {"conv": {"w": he((3, 3, cfg["image"][2], c0))},
                      "norm": norm(c0)}, "blocks": []}
    for cin, width, _, proj in blocks(cfg):
        bf, bt = {}, {}
        bf["conv1"], bt["conv1"] = lora_conv(3, cin, width)
        bf["norm1"], bt["norm1"] = {}, norm(width)
        bf["conv2"], bt["conv2"] = lora_conv(3, width, width)
        bf["norm2"], bt["norm2"] = {}, norm(width)
        if proj:
            bf["ds"], bt["ds"] = lora_conv(1, cin, width)
            bf["ds_norm"], bt["ds_norm"] = {}, norm(width)
        frozen["blocks"].append(bf)
        train["blocks"].append(bt)
    d = cfg["stage_widths"][-1]
    train["fc"] = {"w": jax.random.normal(next(keys), (d, cfg["n_classes"]),
                                          jnp.float32) * d ** -0.5,
                   "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}
    return {"frozen": frozen, "train": train}


def _conv(x, w, stride, padding, precision):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _group_norm(p, x, groups):
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + GN_EPS)
    return xg.reshape(n, h, w, c) * p["scale"].astype(x.dtype) \
        + p["bias"].astype(x.dtype)


def forward(frozen, train, cfg: dict, x, precision):
    """Logits (N, classes) of images x (N, H, W, C)."""
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    groups = cfg["gn_groups"]

    def conv(fz, tr, h, stride, padding="SAME"):
        if "w" in tr:
            return _conv(h, tr["w"], stride, padding, precision)
        y = _conv(h, fz["w"], stride, padding, precision)
        side = _conv(_conv(h, tr["b"], stride, padding, precision),
                     tr["a"], 1, "VALID", precision)
        return y + scale * side

    h = conv(frozen["stem"]["conv"], train["stem"]["conv"], x, 1)
    h = jax.nn.relu(_group_norm(train["stem"]["norm"], h, groups))
    for (_, _, s, proj), fz, tr in zip(blocks(cfg), frozen["blocks"],
                                       train["blocks"]):
        y = conv(fz["conv1"], tr["conv1"], h, s)
        y = jax.nn.relu(_group_norm(tr["norm1"], y, groups))
        y = _group_norm(tr["norm2"], conv(fz["conv2"], tr["conv2"], y, 1),
                        groups)
        if proj:
            h = _group_norm(tr["ds_norm"], conv(fz["ds"], tr["ds"], h, s),
                            groups)
        h = jax.nn.relu(y + h)
    h = jnp.mean(h, axis=(1, 2))
    return jnp.dot(h, train["fc"]["w"].astype(h.dtype),
                   precision=precision) + train["fc"]["b"].astype(h.dtype)


def loss(frozen, train, cfg: dict, x, y, precision):
    """Mean cross-entropy of the batch."""
    logits = forward(frozen, train, cfg, x, precision)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _taps(n: int, k: int, stride: int) -> int:
    """(output, kernel tap) pairs along one axis of a ``SAME`` convolution
    that read the input and not its zero padding."""
    out = math.ceil(n / stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride + t - lo < n)


def step_flops(cfg: dict, batch: int) -> int:
    """Model FLOPs of one local SGD step on ``batch`` images: the forward
    pass, the activation gradients and the weight gradients of the trained
    parameters, counted as 2 per multiply-add of every convolution and of
    the classifier. The frozen convolutions have no weight gradient, the
    stem's input (the image) needs no gradient, a kernel tap that falls on
    the zero padding is no work, and the element-wise work (GroupNorm,
    ReLU, pooling, softmax, the SGD update) is not counted."""
    h, w, c_img = cfg["image"]
    r, c0, classes = cfg["lora_rank"], cfg["stem_width"], cfg["n_classes"]

    def macs(hh, ww, k, cin, cout, stride):
        return _taps(hh, k, stride) * _taps(ww, k, stride) * cin * cout

    total = 2 * macs(h, w, 3, c_img, c0, 1)          # stem: fwd + dW

    def adapted(hh, ww, k, cin, cout, stride):
        base = 2 * macs(hh, ww, k, cin, cout, stride)      # fwd + dx
        side_b = 3 * macs(hh, ww, k, cin, r, stride)       # fwd + dx + dW
        ho, wo = math.ceil(hh / stride), math.ceil(ww / stride)
        side_a = 3 * macs(ho, wo, 1, r, cout, 1)
        return base + side_b + side_a

    for cin, width, s, proj in blocks(cfg):
        total += adapted(h, w, 3, cin, width, s)
        if proj:
            total += adapted(h, w, 1, cin, width, s)
        h, w = math.ceil(h / s), math.ceil(w / s)
        total += adapted(h, w, 3, width, width, 1)
    total += 3 * cfg["stage_widths"][-1] * classes       # fwd + dx + dW
    return 2 * total * batch
