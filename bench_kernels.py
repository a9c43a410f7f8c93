"""Microbenchmark: Pallas depthwise conv vs XLA grouped conv at ASPP shapes.

The Pallas VMEM shift-accumulate kernel (ops/pallas_kernels.py) exists on the
claim that XLA's grouped-convolution lowering of the depthwise stage is
VPU-suboptimal. This benchmark decides that claim on real hardware at exactly the
shapes the flagship runs: the ASPP head's atrous depthwise convs (rates 2/4/8 on
the [B, 13, 13, 1024] output-stride-8 feature map of a 101x101 input) and the
decoder's rate-1 conv. ``use_pallas_depthwise`` in the flagship preset should be
flipped on if and only if the Pallas column wins here.

Run: ``python bench_kernels.py [--platform=cpu]`` — prints one JSON line.
bench.py embeds the same measurement in its TPU child ("depthwise_kernels").
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict


def _chained(fn, repeats: int):
    """``fn`` applied ``repeats`` times inside ONE jitted program, output fed
    back as the first argument (every kernel here maps arg0's shape to
    itself). A single kernel call pays a host dispatch and a sync for
    sub-millisecond device work, so an unchained microbench measures the
    dispatch (ratios compressed toward 1). Chaining makes device work
    dominate the window; per-kernel time = call time / repeats. An rsqrt
    renorm keeps the iterates bounded. The renorm is an ADDITIVE shared cost
    c on both sides, which compresses ratios toward 1 by c/(kernel time); at
    these shapes c is a single elementwise pass (~20-100 MB at 819 GB/s,
    25-120us) against per-kernel times of 4,600-26,000us (chip run,
    2026-08-01) — a <1% bias, far below the decision margins quoted from
    this file."""
    import jax
    import jax.numpy as jnp

    def run(x, *rest):
        def body(_, acc):
            y = fn(acc, *rest)
            scale = jax.lax.rsqrt(jnp.mean(jnp.square(y).astype(jnp.float32)) + 1e-6)
            return (y.astype(jnp.float32) * scale).astype(y.dtype)

        return jax.lax.fori_loop(0, repeats, body, x)

    return jax.jit(run)


def _paired_us(fn_a, fn_b, args, iters: int, warmup: int, trials: int = 5,
               repeats: int = 1):
    """A/B comparison robust to drift between windows: the two sides run in
    short INTERLEAVED trials (A,B,A,B,...) and the decision column is the
    MEDIAN of per-trial ratios — drift hits adjacent trials equally and
    cancels in the ratio; the median rejects stragglers. ``repeats`` chains
    the kernel inside each call (see ``_chained``) so device work dominates
    the per-dispatch cost.
    Returns (a_us, b_us, b_over_a) as medians of PER-KERNEL microseconds."""
    from tensorflowdistributedlearning_tpu.utils.profiling import sync

    if repeats > 1:
        fn_a = _chained(fn_a, repeats)
        fn_b = _chained(fn_b, repeats)
    else:
        # repeats=1 must still time a compiled executable, not eager tracing
        import jax

        fn_a, fn_b = jax.jit(fn_a), jax.jit(fn_b)

    for fn in (fn_a, fn_b):  # compile + warm both before any timing
        out = fn(*args)
        sync(out)
        for _ in range(warmup):
            out = fn(*args)
        sync(out)

    def window(fn) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        sync(out)
        return (time.perf_counter() - t0) / (iters * repeats) * 1e6

    a_times, b_times, ratios = [], [], []
    for _ in range(trials):
        a = window(fn_a)
        b = window(fn_b)
        a_times.append(a)
        b_times.append(b)
        ratios.append(b / a)

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    return med(a_times), med(b_times), med(ratios)


def bench_depthwise(
    batch: int = 32,
    hw: int = 13,
    channels: int = 1024,
    rates=(1, 2, 4, 8),
    iters: int = 30,
    warmup: int = 5,
    repeats: int = 64,
) -> Dict:
    import jax
    import numpy as np

    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        depthwise_conv2d,
        depthwise_conv2d_reference,
    )

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (batch, hw, hw, channels)).astype(np.float32)
    w = rng.normal(0, 0.3, (3, 3, channels)).astype(np.float32)
    x, w = jax.device_put(x), jax.device_put(w)

    results: Dict = {}
    wins = 0
    for rate in rates:
        pallas_us, xla_us, speedup = _paired_us(
            lambda a, b, r=rate: depthwise_conv2d(a, b, r),
            lambda a, b, r=rate: depthwise_conv2d_reference(a, b, r),
            (x, w), max(2, iters // 10), warmup, repeats=repeats,
        )
        results[f"rate{rate}"] = {
            "pallas_us": round(pallas_us, 1),
            "xla_us": round(xla_us, 1),
            "speedup": round(speedup, 3),
        }
        wins += speedup > 1.0
    results["pallas_wins"] = bool(wins > len(rates) / 2)
    results["shape"] = [batch, hw, hw, channels]
    return results


def bench_fused_bn_act(
    batch: int = 32,
    hw: int = 13,
    channels: int = 1024,
    iters: int = 30,
    warmup: int = 5,
    repeats: int = 64,
) -> Dict:
    """Fused inference BN+act(+residual) Pallas pass vs XLA's fusion at the
    serving-relevant shape: the ASPP feature map the step profile's dominant
    elementwise/BN bucket (PROFILE_SEG_r05.json: 53.2%) runs over. Both
    columns are HBM-roofline candidates — the question this answers is
    whether Mosaic's single VMEM pass beats XLA's elementwise fusion on real
    hardware, per variant (plain BN+relu, +residual)."""
    import jax
    import numpy as np

    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        fused_bn_act,
        fused_bn_act_reference,
    )

    rng = np.random.default_rng(2)
    x = jax.device_put(
        rng.normal(0, 1, (batch, hw, hw, channels)).astype(np.float32)
    )
    r = jax.device_put(
        rng.normal(0, 1, (batch, hw, hw, channels)).astype(np.float32)
    )
    vecs = tuple(
        jax.device_put(v.astype(np.float32))
        for v in (
            rng.normal(1, 0.1, channels),
            rng.normal(0, 0.1, channels),
            rng.normal(0, 0.1, channels),
            rng.uniform(0.5, 1.5, channels),
        )
    )

    results: Dict = {}
    wins = 0
    for name, resid in (("bn_relu", False), ("bn_relu_residual", True)):
        pallas_us, xla_us, speedup = _paired_us(
            lambda a, rr: fused_bn_act(
                a, *vecs, residual=rr if resid else None
            ),
            lambda a, rr: fused_bn_act_reference(
                a, *vecs, residual=rr if resid else None
            ),
            (x, r), max(2, iters // 10), warmup, repeats=repeats,
        )
        results[name] = {
            "pallas_us": round(pallas_us, 1),
            "xla_us": round(xla_us, 1),
            "speedup": round(speedup, 3),
        }
        wins += speedup > 1.0
    results["pallas_wins"] = bool(wins == 2)
    results["shape"] = [batch, hw, hw, channels]
    return results


def bench_quant(
    batch: int = 64,
    features: int = 1024,
    hw: int = 13,
    conv_channels: int = 128,
    iters: int = 30,
    warmup: int = 5,
    repeats: int = 64,
) -> Dict:
    """int8-compute kernels vs their dequantize-f32 XLA twins at the serving
    shapes (the quant model's dense width and a mid-network conv). On TPU the
    Pallas column is the real int8 x int8 -> int32 MXU kernel and the gate is
    a speedup floor; off-TPU ``int8_matmul``/``int8_conv2d`` auto-dispatch TO
    the reference, so the honest CPU column is a dispatch-overhead tripwire
    (ratio pinned ~1.0) — never the minutes-per-call interpreter. Weights are
    square / channel-preserving so the chained harness can feed outputs back
    as inputs."""
    import jax
    import numpy as np

    from tensorflowdistributedlearning_tpu.ops.quant_kernels import (
        int8_conv2d,
        int8_conv2d_reference,
        int8_matmul,
        int8_matmul_reference,
    )
    from tensorflowdistributedlearning_tpu.train.quantize import quantize_pytree

    rng = np.random.default_rng(3)

    def qweight(shape):
        qtree, _ = quantize_pytree(
            {"m": {"kernel": rng.normal(0, 0.5, shape).astype(np.float32)}},
            "int8",
        )
        rec = qtree["m"]["kernel"]
        return jax.device_put(rec["q"]), jax.device_put(rec["scale"])

    results: Dict = {}
    wins = 0

    x = jax.device_put(
        rng.normal(0, 1, (batch, features)).astype(np.float32)
    )
    wq, ws = qweight((features, features))
    mm_pallas, mm_xla, mm_speedup = _paired_us(
        lambda a: int8_matmul(a, wq, ws, act="relu"),
        lambda a: int8_matmul_reference(a, wq, ws, act="relu"),
        (x,), max(2, iters // 10), warmup, repeats=repeats,
    )
    results["matmul"] = {
        "pallas_us": round(mm_pallas, 1),
        "xla_us": round(mm_xla, 1),
        "speedup": round(mm_speedup, 3),
        "shape": [batch, features, features],
    }
    wins += mm_speedup > 1.0

    xc = jax.device_put(
        rng.normal(0, 1, (8, hw, hw, conv_channels)).astype(np.float32)
    )
    cq, cs = qweight((3, 3, conv_channels, conv_channels))
    cv_pallas, cv_xla, cv_speedup = _paired_us(
        lambda a: int8_conv2d(a, cq, cs, padding="SAME", act="relu"),
        lambda a: int8_conv2d_reference(a, cq, cs, padding="SAME", act="relu"),
        (xc,), max(2, iters // 10), warmup, repeats=repeats,
    )
    results["conv"] = {
        "pallas_us": round(cv_pallas, 1),
        "xla_us": round(cv_xla, 1),
        "speedup": round(cv_speedup, 3),
        "shape": [8, hw, hw, conv_channels],
    }
    wins += cv_speedup > 1.0

    results["pallas_wins"] = bool(wins >= 2)
    return results


def bench_attention(
    batch: int = 32,
    heads: int = 6,
    head_dim: int = 64,
    seq_lens=(196, 1024),
    iters: int = 30,
    warmup: int = 5,
    train_cols: bool = True,
    repeats: int = 16,
) -> Dict:
    """Fused Pallas block attention vs the XLA einsum path at ViT-S shapes
    (T=196 is ViT-S/16 at 224x224; T=1024 is the long-block regime the ring
    hands each device). bf16 inputs, float32 softmax both ways.

    Phase 1 measures the forward for EVERY seq_len; phase 2 adds the
    TRAINING value+grad columns — use_fused_attention rides the train step,
    so the flip decision must price the custom-vjp backward (which REBUILDS
    the score tile) against XLA's autodiff; a forward-only win that loses
    the backward is a net training loss. ``use_fused_attention`` should be
    flipped on iff ``pallas_wins`` (both phases won at most seq_lens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowdistributedlearning_tpu.ops.flash_attention import flash_attention
    from tensorflowdistributedlearning_tpu.parallel.ring_attention import (
        attention_reference,
    )

    rng = np.random.default_rng(1)
    results: Dict = {}
    qkv = {}
    fwd_wins = {}
    for t in seq_lens:
        qkv[t] = tuple(
            jax.device_put(
                rng.normal(0, 1, (batch, t, heads, head_dim)).astype(np.float32)
            ).astype(jnp.bfloat16)
            for _ in range(3)
        )
        pallas_us, xla_us, speedup = _paired_us(
            lambda a, b, c: flash_attention(a, b, c),
            lambda a, b, c: attention_reference(a, b, c),
            qkv[t], max(2, iters // 10), warmup, repeats=repeats,
        )
        results[f"seq{t}"] = {
            "pallas_us": round(pallas_us, 1),
            "xla_us": round(xla_us, 1),
            "speedup": round(speedup, 3),
        }
        fwd_wins[t] = speedup > 1.0

    results["shape"] = [batch, "T", heads, head_dim]
    results["pallas_wins_fwd"] = bool(sum(fwd_wins.values()) > len(seq_lens) / 2)

    wins = 0
    if train_cols:
        def train_readout(fn):
            """fwd+bwd per chained iteration: the grad tuple is not shape-
            preserving, so the chain carries q through a tiny SGD-like update
            (one forward + one backward per repeat — the quantity the train
            step pays; same chain on both comparison sides)."""
            grad_fn = jax.grad(
                lambda a, b, c: jnp.sum(fn(a, b, c).astype(jnp.float32)),
                argnums=(0, 1, 2),  # full backward — all of dq/dk/dv, as the
                # train step pays; q/k/v share one shape so the sum chains
            )

            def one(a, b, c):
                gq, gk, gv = grad_fn(a, b, c)
                upd = (gq.astype(jnp.float32) + gk.astype(jnp.float32)
                       + gv.astype(jnp.float32))
                return (a.astype(jnp.float32) - 1e-3 * upd).astype(a.dtype)

            return one

        for t in seq_lens:
            pallas_train_us, xla_train_us, speedup_train = _paired_us(
                train_readout(flash_attention),
                train_readout(attention_reference),
                qkv[t], max(2, iters // 10), warmup,
                repeats=max(repeats // 2, 1),
            )
            results[f"seq{t}"].update(
                {
                    "pallas_train_us": round(pallas_train_us, 1),
                    "xla_train_us": round(xla_train_us, 1),
                    "speedup_train": round(speedup_train, 3),
                }
            )
            wins += fwd_wins[t] and speedup_train > 1.0
    else:
        wins = sum(fwd_wins.values())
    results["pallas_wins"] = bool(wins > len(seq_lens) / 2)
    return results


def main() -> None:
    import jax

    if "--platform=cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    from tensorflowdistributedlearning_tpu.utils import compile_cache

    compile_cache.configure()
    if jax.default_backend() == "tpu":
        out = bench_depthwise()
    else:
        # chained repeats through the Pallas interpreter are minutes-per-call;
        # tiny everything keeps the CPU smoke bounded
        out = bench_depthwise(batch=2, hw=5, channels=8, iters=2, warmup=1,
                              repeats=2)
    out["platform"] = jax.default_backend()
    print(json.dumps(out), flush=True)
    if jax.default_backend() == "tpu":
        bn = bench_fused_bn_act()
    else:
        bn = bench_fused_bn_act(batch=2, hw=5, channels=8, iters=2, warmup=1,
                                repeats=2)
    bn["platform"] = jax.default_backend()
    print(json.dumps({"fused_bn_act": bn}), flush=True)
    if jax.default_backend() == "tpu":
        qk = bench_quant()
    else:
        qk = bench_quant(batch=4, features=32, hw=5, conv_channels=8,
                         iters=2, warmup=1, repeats=2)
    qk["platform"] = jax.default_backend()
    print(json.dumps({"quant_kernels": qk}), flush=True)
    if jax.default_backend() == "tpu":
        attn = bench_attention()
    else:
        # off-TPU the kernel runs in the (slow) Pallas interpreter; tiny shapes
        # keep the smoke run bounded — the decision data only means anything on
        # real hardware anyway
        attn = bench_attention(batch=2, seq_lens=(64,), iters=2, warmup=1,
                               repeats=2)
    attn["platform"] = jax.default_backend()
    print(json.dumps({"attention": attn}), flush=True)


if __name__ == "__main__":
    main()
