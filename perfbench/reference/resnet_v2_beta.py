"""Plain reference of the two benchmark configurations.

Straightforward ``jax.numpy`` in float32 at highest matmul precision, written
from the architectures' descriptions: the ResNet-v2 "beta" trunk (three 3x3
root convs, pre-activation bottleneck units with the stride on a stage's LAST
unit, atrous stages once the output stride is reached), the DeepLabV3+ head
(ASPP with split-separable atrous branches and a pooled branch, a decoder
with the first unit's residual as skip), the classification head, the Lovasz
hinge and the label-smoothed cross-entropy, Adam under continuous exponential
decay and Nesterov SGD with coupled kernel decay under warm-up + cosine, and
the two on-device input programs (the affine augmentation with its Laplacian
channel; flip + reflect-padded crop).

It imports nothing of the program and takes nothing the program made: sizes
come from the configuration file, weights and rows from the harness's seed.
Parameters live in one flat dict keyed by '/'-joined names. Every module is
train-mode (BatchNorm on batch statistics), which is what the timed step runs.

Departures from the published descriptions, all the program's own and stated
in the configuration files: v2 pre-activation units and a 3-conv root where He
et al. draw v1 units and a 7x7 root; stride on the last unit of a stage; the
space-to-depth stem is the plain 3x3 stride-2 conv it equals.

``quant`` is the control's hook: a function applied to both operands of every
convolution and matrix product in the forward pass (identity in the
reference).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Params = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# parameters: one walk serves both the spec (shapes) and the forward (values)
# ---------------------------------------------------------------------------


class _Scope:
    """Hands out parameters by name. With ``values=None`` it records
    (shape, kind) and returns zeros — the spec walk."""

    def __init__(self, values: Optional[Params]):
        self.values = values
        self.spec: Dict[str, Tuple[Tuple[int, ...], str]] = {}
        # batch statistics of the BatchNorms this scope itself walks (the
        # residual units walk scopes of their own): name -> (mean, var)
        self.stats: Dict[str, Tuple[jax.Array, jax.Array]] = {}

    def get(self, name: str, shape: Sequence[int], kind: str) -> jax.Array:
        shape = tuple(int(s) for s in shape)
        if self.values is None:
            self.spec[name] = (shape, kind)
            return jnp.zeros(shape, jnp.float32)
        value = self.values[name]
        if tuple(value.shape) != shape:
            raise ValueError(f"{name}: expected {shape}, got {tuple(value.shape)}")
        return value.astype(jnp.float32)


def _conv(x, kernel, stride=1, rate=1, groups=1, padding="SAME", quant=None):
    if quant is not None:
        x, kernel = quant(x), quant(kernel)
    return lax.conv_general_dilated(
        x,
        kernel,
        window_strides=(stride, stride),
        padding=padding,
        rhs_dilation=(rate, rate),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        precision=HIGHEST,
    )


def _batch_norm(sc: _Scope, name: str, x, eps: float):
    c = x.shape[-1]
    scale = sc.get(f"{name}/scale", (c,), "bn_scale")
    bias = sc.get(f"{name}/bias", (c,), "bn_bias")
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    sc.stats[name] = (mean, var)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _conv_bn(sc, name, x, features, k, stride=1, rate=1, *, eps, quant):
    kernel = sc.get(f"{name}/conv/kernel", (k, k, x.shape[-1], features), "conv")
    x = _conv(x, kernel, stride, rate, quant=quant)
    return jax.nn.relu(_batch_norm(sc, f"{name}/bn", x, eps))


def _conv_bias(sc, name, x, features, k, stride=1, *, quant):
    kernel = sc.get(f"{name}/kernel", (k, k, x.shape[-1], features), "conv")
    bias = sc.get(f"{name}/bias", (features,), "bias")
    return _conv(x, kernel, stride, quant=quant) + bias


def _bottleneck(sc, name, x, depth, bottleneck, stride, rate, *, eps, quant):
    preact = jax.nn.relu(_batch_norm(sc, f"{name}/preact", x, eps))
    if depth == x.shape[-1]:
        shortcut = x[:, ::stride, ::stride, :]
    else:
        shortcut = _conv_bias(sc, f"{name}/shortcut", preact, depth, 1, stride, quant=quant)
    r = _conv_bn(sc, f"{name}/conv1", preact, bottleneck, 1, eps=eps, quant=quant)
    r = _conv_bn(sc, f"{name}/conv2", r, bottleneck, 3, stride, rate, eps=eps, quant=quant)
    r = _conv_bias(sc, f"{name}/conv3", r, depth, 1, quant=quant)
    return jax.nn.relu(shortcut + r), r


def stage_table(cfg: dict) -> List[Tuple[str, int, int, int, int]]:
    """(unit name, depth, bottleneck, stride, unit rate) for every residual
    unit, in order — the layer table the FLOP count walks too."""
    units = []
    if cfg["block_layout"] == "classic":
        bases, last_strides = (64, 128, 256, 512), (2, 2, 2, 1)
        for b, (base, n, last) in enumerate(zip(bases, cfg["n_blocks"], last_strides)):
            for i in range(n):
                units.append(
                    (f"block{b + 1}_unit{i + 1}", base * 4, base, last if i == n - 1 else 1, 1)
                )
        return units
    for b, (base, n) in enumerate(zip((128, 256, 512), cfg["n_blocks"])):
        for i in range(n):
            units.append(
                (f"block{b + 1}_unit{i + 1}", base * 4, base, 2 if i == n - 1 else 1, 1)
            )
    for i, r in enumerate(cfg["multi_grid"]):
        units.append((f"block4_unit{i + 1}", 1024, 256, 1, r))
    return units


def _backbone(sc, cfg, x, *, quant, remat):
    eps = cfg["batch_norm_epsilon"]
    x = _conv_bn(sc, "backbone/conv1_1", x, 64, 3, 2, eps=eps, quant=quant)
    x = _conv_bn(sc, "backbone/conv1_2", x, 64, 3, eps=eps, quant=quant)
    x = _conv_bn(sc, "backbone/conv1_3", x, 128, 3, eps=eps, quant=quant)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    x = jax.nn.relu(_batch_norm(sc, "backbone/postnorm", x, eps))
    # the root strides by 4; from the target stride on, strides turn into rates
    target = None if cfg["output_stride"] is None else cfg["output_stride"] // 4
    current, rate, skip = 1, 1, None
    for name, depth, bottleneck, stride, unit_rate in stage_table(cfg):
        if target is not None and current == target:
            applied_stride, applied_rate = 1, rate * unit_rate
            rate *= stride
        else:
            applied_stride, applied_rate = stride, unit_rate
            current *= stride

        def unit(values, x, name=name, depth=depth, bottleneck=bottleneck,
                 s=applied_stride, r=applied_rate):
            inner = _Scope(values)
            out = _bottleneck(inner, f"backbone/{name}", x, depth, bottleneck, s, r,
                              eps=eps, quant=quant)
            return out, inner.spec

        if sc.values is None:
            (x, residual), spec = unit(None, x)
            sc.spec.update(spec)
        else:
            fn = (lambda v, x, unit=unit: unit(v, x)[0])
            if remat:
                fn = jax.checkpoint(fn)
            x, residual = fn(sc.values, x)
        if name == "block1_unit1":
            skip = residual
    return x, skip


def _resize_bilinear_sym(x, out_hw):
    """Bilinear resize that never reads a zero halo: 1 px symmetric pad,
    resize to (h+4, w+4), trim 2 px a side."""
    h, w = int(out_hw[0]), int(out_hw[1])
    x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="symmetric")
    n, _, _, c = x.shape
    x = jax.image.resize(x, (n, h + 4, w + 4, c), method="bilinear", precision=HIGHEST)
    return x[:, 2:-2, 2:-2, :]


def _split_separable(sc, name, x, features, rate, *, eps, quant):
    c = x.shape[-1]
    dw = sc.get(f"{name}/depthwise/kernel", (3, 3, 1, c), "depthwise")
    db = sc.get(f"{name}/depthwise/bias", (c,), "bias")
    x = _conv(x, dw, rate=rate, groups=c, padding=[(rate, rate), (rate, rate)], quant=quant) + db
    x = jax.nn.relu(x)
    pw = sc.get(f"{name}/pointwise/kernel", (1, 1, c, features), "pointwise")
    x = _conv(x, pw, quant=quant)
    return jax.nn.relu(_batch_norm(sc, f"{name}/pointwise_bn", x, eps))


def _deeplab_head(sc, cfg, features, skip, *, quant):
    eps, depth = cfg["batch_norm_epsilon"], cfg["base_depth"]
    a1 = _conv_bn(sc, "aspp/conv_1x1", features, depth, 1, eps=eps, quant=quant)
    a2 = _split_separable(sc, "aspp/conv_3x3_1", features, depth, 2, eps=eps, quant=quant)
    a3 = _split_separable(sc, "aspp/conv_3x3_2", features, depth, 4, eps=eps, quant=quant)
    a4 = _split_separable(sc, "aspp/conv_3x3_3", features, depth, 8, eps=eps, quant=quant)
    pooled = jnp.mean(features, axis=(1, 2), keepdims=True)
    pooled = _conv_bn(sc, "aspp/pool_conv_1x1", pooled, depth, 1, eps=eps, quant=quant)
    a5 = _resize_bilinear_sym(pooled, features.shape[1:3])
    aspp = jnp.concatenate([a1, a2, a3, a4, a5], axis=-1)
    aspp = _conv_bn(sc, "aspp/project", aspp, depth, 1, eps=eps, quant=quant)
    aspp_up = _resize_bilinear_sym(aspp, skip.shape[1:3])
    dec = _conv_bn(sc, "decoder_conv_1x1", skip, depth, 1, eps=eps, quant=quant)
    dec = jnp.concatenate([dec, aspp_up], axis=-1)
    dec = _conv_bias(sc, "decoder_conv_3x3", dec, 1, 3, quant=quant)
    return _resize_bilinear_sym(dec, cfg["input_shape"])


def _forward(sc: _Scope, cfg: dict, images, *, quant=None, remat=False):
    features, skip = _backbone(sc, cfg, images.astype(jnp.float32), quant=quant, remat=remat)
    if cfg["num_classes"] is None:
        return _deeplab_head(sc, cfg, features, skip, quant=quant)
    pooled = jnp.mean(features, axis=(1, 2))
    kernel = sc.get("logits/kernel", (pooled.shape[-1], cfg["num_classes"]), "dense")
    bias = sc.get("logits/bias", (cfg["num_classes"],), "bias")
    if quant is not None:
        pooled, kernel = quant(pooled), quant(kernel)
    return jnp.dot(pooled, kernel, precision=HIGHEST) + bias


def param_spec(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of every trainable leaf, by an abstract walk."""
    sc = _Scope(None)
    h, w = cfg["input_shape"]
    x = jax.ShapeDtypeStruct((2, h, w, cfg["input_channels"]), jnp.float32)
    jax.eval_shape(lambda x: _forward(sc, cfg, x), x)
    return dict(sc.spec)


def head_leaves(cfg: dict) -> List[str]:
    """The output layer's leaves: the last convolution of the decoder, or the
    classifier."""
    layer = "decoder_conv_3x3" if cfg["num_classes"] is None else "logits"
    return [f"{layer}/kernel", f"{layer}/bias"]


# the root's BatchNorms: the layers whose batch statistics rounding has not
# yet had a deep network to be amplified by (PERF.md, PR 23)
ROOT_NORMS = ("backbone/conv1_1/bn", "backbone/conv1_2/bn", "backbone/conv1_3/bn")


def forward(cfg: dict, params: Params, images, *, quant=None, remat=False, stats=False):
    """Train-mode logits: [B, H, W, 1] (segmentation) or [B, classes]; with
    ``stats`` also the root BatchNorms' batch mean and variance."""
    sc = _Scope(params)
    logits = _forward(sc, cfg, images, quant=quant, remat=remat)
    if not stats:
        return logits
    flat = {}
    for name in ROOT_NORMS:
        flat[f"{name}/mean"], flat[f"{name}/var"] = sc.stats[name]
    return logits, flat


# ---------------------------------------------------------------------------
# the control's precision: one step below the bfloat16 the configurations state
# ---------------------------------------------------------------------------


def quant_int8(x):
    """Per-tensor symmetric int8 and back, straight through for gradients:
    what an int8 forward pass would feed its convolutions."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + lax.stop_gradient(q - x)


def quant_e4m3(x):
    """Per-tensor scaled fp8 (e4m3) and back, straight through for gradients."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def quant_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def lovasz_hinge(logits, labels):
    """Mean over images of the binary Lovasz hinge (Berman et al. 2018,
    Alg. 1): sort the hinge errors descending, weight them by the discrete
    gradient of the Jaccard loss along that order."""

    def one(lg, lb):
        signs = 2.0 * lb - 1.0
        errors = 1.0 - lg * signs
        order = jnp.argsort(-errors)
        errors_sorted = errors[order]
        gt = lb[order]
        total = jnp.sum(gt)
        inter = total - jnp.cumsum(gt)
        union = total + jnp.cumsum(1.0 - gt)
        jaccard = 1.0 - inter / jnp.maximum(union, 1e-12)
        grad = jnp.concatenate([jaccard[:1], jaccard[1:] - jaccard[:-1]])
        return jnp.dot(jax.nn.relu(errors_sorted), lax.stop_gradient(grad), precision=HIGHEST)

    b = logits.shape[0]
    return jnp.mean(
        jax.vmap(one)(logits.reshape(b, -1).astype(jnp.float32),
                      labels.reshape(b, -1).astype(jnp.float32))
    )


def smoothed_cross_entropy(logits, labels, smoothing: float):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    true = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    k = logits.shape[-1]
    return jnp.mean(-(1.0 - smoothing) * true - (smoothing / k) * jnp.sum(logp, axis=-1))


def loss_fn(cfg: dict, params: Params, batch, *, quant=None, remat=False):
    """(loss, the root BatchNorms' batch statistics)."""
    logits, stats = forward(cfg, params, batch["images"], quant=quant, remat=remat, stats=True)
    if cfg["num_classes"] is None:
        return lovasz_hinge(logits, batch["labels"]), stats
    return smoothed_cross_entropy(logits, batch["labels"], cfg["label_smoothing"]), stats


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


def learning_rate(cfg: dict, count):
    """The rate of update number ``count`` (0 for the first)."""
    count = jnp.asarray(count, jnp.float32)
    if cfg["lr_schedule"] == "exponential":
        return cfg["lr"] * cfg["lr_decay_rate"] ** (count / cfg["lr_decay_steps"])
    warm, total = cfg["lr_warmup_steps"], cfg["lr_decay_steps"]
    if warm == 0:
        frac = jnp.minimum(count, total) / total
        return cfg["lr"] * 0.5 * (1.0 + jnp.cos(math.pi * frac))
    frac = jnp.clip((count - warm) / (total - warm), 0.0, 1.0)
    cosine = cfg["lr"] * 0.5 * (1.0 + jnp.cos(math.pi * frac))
    return jnp.where(count < warm, cfg["lr"] * count / warm, cosine)


def opt_init(cfg: dict, params: Params) -> dict:
    zeros = {k: jnp.zeros_like(v, jnp.float32) for k, v in params.items()}
    if cfg["optimizer"] == "adam":
        return {"mu": zeros, "nu": dict(zeros)}
    return {"trace": zeros}


def opt_update(cfg: dict, grads: Params, opt: dict, params: Params, count):
    """One update: returns (new params, new optimizer state)."""
    lr = learning_rate(cfg, count)
    if cfg["optimizer"] == "adam":
        b1, b2, eps = 0.9, 0.999, 1e-8
        t = jnp.asarray(count, jnp.float32) + 1.0
        mu = {k: b1 * opt["mu"][k] + (1 - b1) * g for k, g in grads.items()}
        nu = {k: b2 * opt["nu"][k] + (1 - b2) * g * g for k, g in grads.items()}
        new = {
            k: params[k]
            - lr * (mu[k] / (1 - b1**t)) / (jnp.sqrt(nu[k] / (1 - b2**t)) + eps)
            for k in params
        }
        return new, {"mu": mu, "nu": nu}
    # Nesterov SGD; l2 decay joins the gradient of weight matrices first
    m, wd = cfg["sgd_momentum"], cfg["weight_decay"]
    decayed = {
        k: g + (wd * params[k] if k.endswith("/kernel") else 0.0) for k, g in grads.items()
    }
    trace = {k: g + m * opt["trace"][k] for k, g in decayed.items()}
    new = {k: params[k] - lr * (decayed[k] + m * trace[k]) for k in params}
    return new, {"trace": trace}


def first_gradient(cfg: dict, opt_after_one: dict, params0: Params) -> Params:
    """The first gradient as the optimizer got it, worked out from the state
    after one update (both sides go through this same arithmetic)."""
    if cfg["optimizer"] == "adam":
        return {k: v / (1 - 0.9) for k, v in opt_after_one["mu"].items()}
    wd = cfg["weight_decay"]
    return {
        k: v - (wd * params0[k] if k.endswith("/kernel") else 0.0)
        for k, v in opt_after_one["trace"].items()
    }


def train_steps(
    cfg: dict,
    params: Params,
    batches: Sequence[dict],
    *,
    shards: int = 1,
    quant: Optional[Callable] = None,
    drop_half: bool = False,
    skip_exchange: bool = False,
):
    """Follow ``len(batches)`` updates from ``params``. Each batch is split
    into ``shards`` equal row blocks with BatchNorm statistics of their own
    (one per chip), and the gradient is the mean over blocks. Returns the
    per-step losses, the first gradient, the root BatchNorms' batch statistics
    of the first step and the parameters after the last step. ``drop_half`` (mean over the first half of each block) and
    ``skip_exchange`` (block 0's gradient alone) plant two faults."""

    used = 1 if skip_exchange else shards

    @jax.jit
    def step(p, opt, batch, count):
        n = batch["images"].shape[0] // shards
        total = None
        for s in range(used):
            shard = jax.tree.map(lambda x: x[s * n : (s + 1) * n], batch)
            if drop_half:
                shard = jax.tree.map(lambda x: x[: x.shape[0] // 2], shard)
            part = jax.value_and_grad(
                lambda q: loss_fn(cfg, q, shard, quant=quant, remat=True), has_aux=True
            )(p)
            total = part if total is None else jax.tree.map(jnp.add, total, part)
        # loss, statistics and gradient as the program has them: the mean
        # over the row blocks
        (loss, stats), grads = jax.tree.map(lambda x: x / used, total)
        new_p, new_opt = opt_update(cfg, grads, opt, p, count)
        return new_p, new_opt, loss, stats

    # one program per step: nothing runs op by op, so a run loads a handful of
    # executables from the compile cache and not one per leaf shape
    first = jax.jit(lambda opt, p0: first_gradient(cfg, opt, p0))
    opt = jax.jit(lambda p: opt_init(cfg, p))(params)
    params0, losses, grad1, stats1 = params, [], None, None
    for count, batch in enumerate(batches):
        params, opt, loss, stats = step(params, opt, batch, jnp.asarray(count, jnp.int32))
        losses.append(loss)
        if count == 0:
            grad1, stats1 = first(opt, params0), stats
    return {"losses": [float(x) for x in losses], "grad1": grad1, "stats1": stats1,
            "params": params}


# ---------------------------------------------------------------------------
# the input programs
# ---------------------------------------------------------------------------

_LAPLACE = ((0.5, 1.0, 0.5), (1.0, -6.0, 1.0), (0.5, 1.0, 0.5))


def laplace_channel(images):
    """[B, H, W, 1] -> [B, H, W, 2]: the image and its 3x3 isotropic
    Laplacian (zero padding at the border)."""
    k = jnp.asarray(_LAPLACE, jnp.float32)[:, :, None, None]
    lap = _conv(images.astype(jnp.float32), k)
    return jnp.concatenate([images, lap], axis=-1)


def _sample(img, ys, xs, order):
    """Sample [H, W] ``img`` at float coordinates; outside reads 0."""
    h, w = img.shape

    def at(yi, xi):
        inside = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        v = img[jnp.clip(yi, 0, h - 1), jnp.clip(xi, 0, w - 1)]
        return jnp.where(inside, v, 0.0)

    if order == 0:
        # nearest, halves away from zero
        near = lambda v: (jnp.sign(v) * jnp.floor(jnp.abs(v) + 0.5)).astype(jnp.int32)
        return at(near(ys), near(xs))
    y0, x0 = jnp.floor(ys), jnp.floor(xs)
    fy, fx = ys - y0, xs - x0
    y0, x0 = y0.astype(jnp.int32), x0.astype(jnp.int32)
    return (
        at(y0, x0) * (1 - fy) * (1 - fx)
        + at(y0, x0 + 1) * (1 - fy) * fx
        + at(y0 + 1, x0) * fy * (1 - fx)
        + at(y0 + 1, x0 + 1) * fy * fx
    )


def _mat(rows):
    return jnp.stack([jnp.stack([jnp.asarray(v, jnp.float32) for v in r]) for r in rows])


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def augment_seg(aug: dict, key, images, masks, matrix_quant=None):
    """The segmentation input program: per image, reflect-pad, a coin-flip
    transpose, one inverse affine warp composed of coin-flip mirrors, a
    rotation about the centre and a shift (bilinear for the image, nearest
    for the mask, zeros outside), a central crop back to the input size; then
    the Laplacian channel. Draws: one key per image split 3 ways (transpose,
    brightness, affine), the affine key split 9 ways in the order h-flip,
    v-flip, angle, x-shift, y-shift, then the crop draws."""
    pad, out_hw = aug["pad"], images.shape[1:3]

    def one(k, image, mask):
        image = jnp.pad(image[..., 0], pad, mode="reflect")
        mask = jnp.pad(mask[..., 0], pad, mode="reflect")
        k_t, _, k_a = jax.random.split(k, 3)
        do_t = jax.random.uniform(k_t) < aug["transpose_probability"]
        image = jnp.where(do_t, image.T, image)
        mask = jnp.where(do_t, mask.T, mask)
        h, w = image.shape
        k_h, k_v, k_rot, k_tx, k_ty = jax.random.split(k_a, 9)[:5]
        eye = jnp.eye(3, dtype=jnp.float32)
        m = eye
        m = _mm(m, jnp.where(jax.random.uniform(k_h) < 0.5,
                             _mat([[-1, 0, w - 1.0], [0, 1, 0], [0, 0, 1]]), eye))
        m = _mm(m, jnp.where(jax.random.uniform(k_v) < 0.5,
                             _mat([[1, 0, 0], [0, -1, h - 1.0], [0, 0, 1]]), eye))
        rad = aug["rotate_range"] / 180.0 * math.pi
        a = jax.random.uniform(k_rot, minval=-rad, maxval=rad)
        cos, sin = jnp.cos(a), jnp.sin(a)
        cx, cy = (w - 1.0) / 2.0, (h - 1.0) / 2.0
        m = _mm(m, _mat([[cos, -sin, cx - cos * cx + sin * cy],
                         [sin, cos, cy - sin * cx - cos * cy], [0, 0, 1]]))
        tx = jax.random.uniform(k_tx, minval=-aug["width_shift_range"],
                                maxval=aug["width_shift_range"]) * h
        ty = jax.random.uniform(k_ty, minval=-aug["height_shift_range"],
                                maxval=aug["height_shift_range"]) * h
        m = _mm(m, _mat([[1, 0, tx], [0, 1, ty], [0, 0, 1]]))
        if matrix_quant is not None:  # the control's hook
            m = matrix_quant(m)
        ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                              jnp.arange(w, dtype=jnp.float32), indexing="ij")
        in_x = m[0, 0] * xs + m[0, 1] * ys + m[0, 2]
        in_y = m[1, 0] * xs + m[1, 1] * ys + m[1, 2]
        top, left = (h - out_hw[0]) // 2, (w - out_hw[1]) // 2
        crop = (slice(top, top + out_hw[0]), slice(left, left + out_hw[1]))
        return (_sample(image, in_y, in_x, 1)[crop][..., None],
                _sample(mask, in_y, in_x, 0)[crop][..., None])

    keys = jax.random.split(key, images.shape[0])
    aug_images, aug_masks = jax.vmap(one)(keys, images.astype(jnp.float32),
                                          masks.astype(jnp.float32))
    return {"images": laplace_channel(aug_images), "labels": aug_masks}


def flip_crop_offsets(raw, out, pad: int):
    """For every row of ``out``, the (mirror, y, x) under which it IS the
    crop of the reflect-padded, possibly mirrored ``raw`` row — found by
    trying all of them, so no draw has to be replayed. Returns the count of
    exact matches per row ([B] int; a sound row has at least one) and the
    first matching index."""
    b, h, w, _ = raw.shape
    padded = jnp.pad(raw, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
    both = jnp.stack([padded, padded[:, :, ::-1, :]], axis=1)  # [B, 2, H+2p, W+2p, C]
    n = 2 * pad + 1

    def row(cands, target):
        def try_one(idx):
            f, y, x = idx // (n * n), (idx // n) % n, idx % n
            crop = lax.dynamic_slice(cands, (f, y, x, 0), (1, h, w, target.shape[-1]))[0]
            return jnp.all(crop == target)

        hits = lax.map(try_one, jnp.arange(2 * n * n))
        return jnp.sum(hits), jnp.argmax(hits)

    return jax.vmap(row)(both, out)
