"""The decoder's expert layer at the cell's widths, compiled for a described
TPU v5e (no chip attached): the compiler takes the grouped products and the
row sum at those shapes, and a step that fits the first buffer size moves no
tensor of all ``tokens x experts per token`` rows by the hidden or the
experts' width, in one segment's program. One file, the topology inside a fixture: only the worker
that is given this file loads the TPU's library."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tensorflowdistributedlearning_tpu.ops import pallas_kernels
from tensorflowdistributedlearning_tpu.parallel import expert as expert_lib

T, K, D, F, HELD, TOTAL = 16384, 8, 2304, 896, 16, 64  # mellum2_12b_a2p5b_share4, 2 x 8,192 tokens


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    monkeypatch.setattr(pallas_kernels, "pallas_platform_ok", lambda: True)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a TPU entry cannot be read back here
    yield
    jax.config.update("jax_enable_compilation_cache", cache)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


def test_the_row_sum_is_one_grouped_product(one_chip, as_on_a_tpu):
    rows = expert_lib._segment_rows(T * K, HELD, TOTAL)
    assert rows == 40960
    text = _compiled_text(
        lambda r, tok: expert_lib._sum_rows_into_tokens(r, tok, T), one_chip,
        ((rows, D), jnp.bfloat16), ((rows,), jnp.int32))
    # the one kernel, and no float32 copy of the rows as a scatter-add would take
    assert _kernels(text) == 1 and f"f32[{rows},{D}]" not in text


def test_a_segment_moves_no_full_size_rows(one_chip, as_on_a_tpu):
    rows = expert_lib._segment_rows(T * K, HELD, TOTAL)

    def loss(x, weights, w_gate, w_up, w_down, order, counts):
        out, _ = expert_lib._over_segment(
            rows, K, 0, x, weights, w_gate, w_up, w_down, order, counts)
        return jnp.sum(out)

    bf16, f32, s32 = jnp.bfloat16, jnp.float32, jnp.int32
    text = _compiled_text(
        jax.value_and_grad(loss, argnums=range(5)), one_chip,
        ((T, D), bf16), ((T, K), f32), ((HELD, D, F), bf16), ((HELD, D, F), bf16),
        ((HELD, F, D), bf16), ((T * K,), s32), ((HELD,), s32))
    # 3 products forward, 3 + 3 backward, the row sum forward and backward
    assert _kernels(text) == 11
    for full in (f"[{T * K},{D}]", f"[{T * K},{F}]"):
        assert full not in text, full


def test_the_sparse_layer_is_eight_kernels_and_no_product_over_heads(one_chip, as_on_a_tpu):
    """``ops/sparse_attention.py`` at keye_vl2_30b_a3b_share8's shapes (one
    16,384-token sequence, 4 heads of 128 on one key-value head, an indexer of
    16 x 64, topk 2,048): the compiler takes every kernel, forward and backward
    (scores, threshold, attend, align; the scores' two and the attention's two
    gradients), nothing of ``[heads, T, T]`` stands in memory, nothing but the
    one kernel searches the selection, and a recomputed layer runs it once."""
    from tensorflowdistributedlearning_tpu.models import decoder as decoder_lib
    from tensorflowdistributedlearning_tpu.ops import sparse_attention as sparse_lib

    t, hq, hd, heads, dim, topk = 16384, 4, 128, 16, 64, 2048
    assert sparse_lib.kernels_serve(t, hd, dim)

    def loss(q, k, v, qi, ki, wi, seg):
        out, align, reads, searched = sparse_lib.sparse_attention(
            q, k, v, qi, ki, wi, seg, topk=topk)
        return jnp.sum(out.astype(jnp.float32)) + align, (reads, searched)

    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = (((1, t, hq, hd), bf16), ((1, t, 1, hd), bf16), ((1, t, 1, hd), bf16),
              ((1, t, heads, dim), bf16), ((1, t, dim), bf16), ((1, t, heads), f32),
              ((1, t), jnp.int32))
    text = _compiled_text(
        jax.value_and_grad(loss, argnums=range(6), has_aux=True), one_chip, *shapes)
    assert _kernels(text) == 8
    for product in (f"[{heads},{t},{t}]", f"[{t},{heads},{t}]", f"[{hq},{t},{t}]"):
        assert product not in text, product
    # the selection is its kernel alone: no integers or flags a (query, key) pair beside it
    for searched in (f"pred[{t},{t}]", f"s32[{t},{t}]"):
        assert searched not in text, searched
    # a recomputed layer (the decoder's own policy) keeps the selection's two
    # numbers a query and the attention kernel's output and log-sum-exps, so
    # its backward pass runs scores and loss again, not the search and not
    # the forward attention
    kept = jax.checkpoint(loss, policy=decoder_lib.REMAT_POLICY)
    text = _compiled_text(jax.value_and_grad(kept, argnums=range(6), has_aux=True), one_chip, *shapes)
    assert _kernels(text) == 10
    for once in ("sparse_select", "sparse_attend"):
        assert len(re.findall(rf"%{once}[.\d]* = ", text)) == 1, once


@pytest.mark.parametrize("heads,window", [(8, 512), (6, None)])
def test_attention_takes_the_mixed_layers_head_groups(one_chip, as_on_a_tpu, heads, window):
    """``ops/blocked_attention.py`` at laguna_xs2_33b_a3b_share8's shapes (one
    16,384-token sequence, heads of 128 on one key-value head): the compiler
    takes the splash kernels — forward, dq, dkv — at a window layer's group of
    8 query heads under a window of 512 (a band two 512-blocks wide) and at a
    full layer's group of 6, and no ``[heads, T, T]`` scores stand in memory."""
    from tensorflowdistributedlearning_tpu.models import decoder as decoder_lib
    from tensorflowdistributedlearning_tpu.ops import blocked_attention as attn_lib

    t, hd = 16384, 128
    assert attn_lib.kernel_serves(t, hd)

    def loss(q, k, v, seg):
        out = attn_lib.blocked_attention(q, k, v, seg, window=window)
        return jnp.sum(out.astype(jnp.float32))

    bf16 = jnp.bfloat16
    shapes = (((1, t, heads, hd), bf16), ((1, t, 1, hd), bf16), ((1, t, 1, hd), bf16),
              ((1, t), jnp.int32))
    text = _compiled_text(jax.value_and_grad(loss, argnums=range(3)), one_chip, *shapes)
    assert _kernels(text) == 3
    assert f"[{heads},{t},{hd}]" in text and f"{t},{t}]" not in text
    # recomputed under the decoder's own policy the forward kernel's output and
    # log-sum-exp are kept: still three kernels, where a layer that keeps its
    # input alone runs the forward kernel a second time
    for policy, kernels in ((decoder_lib.REMAT_POLICY, 3), (None, 4)):
        text = _compiled_text(
            jax.value_and_grad(jax.checkpoint(loss, policy=policy), argnums=range(3)), one_chip,
            *shapes)
        assert _kernels(text) == kernels, policy


def test_a_segment_of_small_experts_is_eleven_kernels(one_chip, as_on_a_tpu):
    """``dropless_experts``' segment at laguna_xs2_33b_a3b_share8's shapes: 32
    held experts of width 512 of 256, 16,384 tokens, 8 experts a token — a
    held expert's group is about one 512-row tile. The compiler takes the nine
    grouped products and the two row sums at those tiles, and moves no tensor
    of all 131,072 pairs by the hidden or the experts' width."""
    t, k, d, f, held, total = 16384, 8, 2048, 512, 32, 256
    rows = expert_lib._segment_rows(t * k, held, total)
    assert rows == 20480 and expert_lib._gmm_tiling(rows, d, f) == (512, 1024, 512)
    assert expert_lib._gmm_tiling(rows, f, d) == (512, 512, 1024)

    def loss(x, weights, w_gate, w_up, w_down, order, counts):
        out, _ = expert_lib._over_segment(
            rows, k, 0, x, weights, w_gate, w_up, w_down, order, counts)
        return jnp.sum(out)

    bf16, f32, s32 = jnp.bfloat16, jnp.float32, jnp.int32
    text = _compiled_text(
        jax.value_and_grad(loss, argnums=range(5)), one_chip,
        ((t, d), bf16), ((t, k), f32), ((held, d, f), bf16), ((held, d, f), bf16),
        ((held, f, d), bf16), ((t * k,), s32), ((held,), s32))
    assert _kernels(text) == 11
    for full in (f"[{t * k},{d}]", f"[{t * k},{f}]"):
        assert full not in text, full


@pytest.mark.parametrize("d,v", [(2304, 24576), (2048, 18992), (2048, 12544)])
def test_the_head_is_one_loop_of_three_products_and_no_kept_logits(one_chip, as_on_a_tpu, d, v):
    """``models/decoder.py:chunked_head_loss`` differentiated, at the three
    decoder cells' ``[16384, d] x [d, V]``: one loop over the chunks whose body
    holds the three products with the vocabulary, a chunk's logits written
    once and read by one pass of reductions (the row maximum rides the
    product), and no ``[tokens, V]`` array outside a chunk."""
    from tensorflowdistributedlearning_tpu.models import decoder as decoder_lib

    def loss(h, kernel, targets):
        total, _ = decoder_lib.chunked_head_loss(jnp.bfloat16, h, kernel, targets)
        return total / jnp.sum(targets >= 0)

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
        ((T, d), jnp.float32), ((d, v), jnp.float32), ((T,), jnp.int32))
    assert text.count(" while(") == 1
    body = re.search(r" while\(.*?body=(%[\w.\-]+)", text).group(1)
    start = text.index("\n" + body + " ")
    ops = [line for line in text[start:text.index("\n}\n", start)].splitlines() if " fusion(" in line]
    chunk = decoder_lib.LOSS_CHUNK_TOKENS
    logits = rf"f32\[{chunk},{v}\]"
    # each fusion's result shapes: what stands between "=" and "fusion("
    results = [line.split(" = ", 1)[1].split(" fusion(", 1)[0] for line in ops]
    # written once: by the product, with the row maximum beside it
    written = [r for r in results if re.search(logits, r)]
    assert len(written) == 1 and re.match(rf"\(f32\[{chunk}\]\S*, {logits}", written[0])
    # the products: logits; dlogits @ W^T into the chunk's rows of dh; h^T @ dlogits into dW
    outputs = [r for line, r in zip(ops, results) if "kind=kOutput" in line]
    assert len(outputs) == 3
    assert any(re.match(rf"f32\[{T // chunk},{chunk},{d}\]", r) for r in outputs)
    assert any(re.match(rf"f32\[{d},{v}\]", r) for r in outputs)
    assert f"[{T},{v}]" not in text and f"[{T // chunk},{chunk},{v}]" not in text


def test_the_kda_kernels_compile_at_the_cells_widths(one_chip, as_on_a_tpu):
    """``ops/kda.py`` differentiated at the Kimi-Linear cell's layer: 16,384
    tokens, 32 heads of 128 in bfloat16. Mosaic takes the forward and the
    backward kernel (a refused slice or too much VMEM raises here), and the
    custom call writes the output and q, k, v's gradients in bfloat16."""
    from tensorflowdistributedlearning_tpu.ops import kda as kda_lib

    t, h, d = 16384, 32, 128

    def loss(q, k, v, g, beta, positions, w):
        return jnp.sum(kda_lib.kda(q, k, v, g, beta, positions).astype(jnp.float32) * w)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = _compiled_text(
        jax.grad(loss, argnums=range(5)), one_chip, ((1, t, h, d), bf16), ((1, t, h, d), bf16),
        ((1, t, h, d), bf16), ((1, t, h, d), f32), ((1, t, h), f32), ((1, t), jnp.int32),
        ((1, t, h, d), f32))
    assert _kernels(text) == 2
    assert text.count("kda_fwd") >= 1 and text.count("kda_bwd") >= 1


def test_latent_attention_takes_the_splash_kernel_at_192_and_128(one_chip, as_on_a_tpu):
    """The Kimi-Linear cell's latent layer: 32 heads, queries and keys of 192,
    values of 128, over 16,384 packed tokens — the kernel path, no ``[T, T]``
    scores in HBM."""
    from tensorflowdistributedlearning_tpu.ops import blocked_attention

    t, h = 16384, 32
    assert blocked_attention.kernel_serves(t, 192, 128)

    def loss(q, k, v, seg):
        return jnp.sum(blocked_attention.blocked_attention(q, k, v, seg).astype(jnp.float32))

    bf16 = jnp.bfloat16
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip, ((1, t, h, 192), bf16),
                          ((1, t, h, 192), bf16), ((1, t, h, 128), bf16), ((1, t), jnp.int32))
    assert _kernels(text) == 3  # forward, dq, dkv
    assert f"[{t},{t}]" not in text


def test_a_segment_of_relu2_experts_of_1856_takes_the_kernel(one_chip, as_on_a_tpu):
    """``dropless_experts``' segment at the NemotronH cell's shapes: 8 held
    ungated experts of 1,856 (14.5 x 128) of 128, hidden 2,688, 16,384 tokens,
    6 experts a token. The grouped products run padded to 1,920, so megablox
    serves them — two products forward, two and two backward, the row sums —
    and no ``ragged_dot`` with zero matrices for the absent experts is built."""
    t, k, d, f, held, total = 16384, 6, 2688, 1856, 8, 128
    rows = expert_lib._segment_rows(t * k, held, total)
    assert rows == 7680 and expert_lib.gmm_kernel_serves(rows, d, f + 64)

    def loss(x, weights, w_up, w_down, order, counts):
        out, _ = expert_lib._over_segment(
            rows, k, 0, x, weights, None, w_up, w_down, order, counts)
        return jnp.sum(out)

    bf16, f32, s32 = jnp.bfloat16, jnp.float32, jnp.int32
    text = _compiled_text(
        jax.value_and_grad(loss, argnums=range(4)), one_chip,
        ((t, d), bf16), ((t, k), f32), ((held, d, f), bf16), ((held, f, d), bf16),
        ((t * k,), s32), ((held,), s32))
    assert _kernels(text) == 8
    assert "ragged-dot" not in text and f"[{total},{d},{f}]" not in text


def test_the_ssd_scan_compiles_at_the_cells_widths(one_chip, as_on_a_tpu):
    """``ops/ssd.py`` differentiated at the NemotronH cell's Mamba layer:
    16,384 tokens, 64 heads of 64, 8 groups of B and C of 128, in bfloat16.
    Mosaic takes the forward and the backward kernel (a refused slice, shape
    cast or too much VMEM raises here), and they are the program's only
    kernels; no ``[T, T]`` matrix of a head appears."""
    from tensorflowdistributedlearning_tpu.ops import ssd as ssd_lib

    t, h, p, g, n = 16384, 64, 64, 8, 128

    def loss(x, dt, a, b, c, d, positions, w):
        return jnp.sum(ssd_lib.ssd(x, dt, a, b, c, d, positions) * w)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = _compiled_text(
        jax.grad(loss, argnums=range(6)), one_chip, ((1, t, h, p), bf16), ((1, t, h), f32),
        ((h,), f32), ((1, t, g, n), bf16), ((1, t, g, n), bf16), ((h,), f32),
        ((1, t), jnp.int32), ((1, t, h, p), f32))
    assert f"[{t},{t}]" not in text and f",{t},{t}]" not in text
    assert _kernels(text) == 2
    assert text.count("ssd_fwd") >= 1 and text.count("ssd_bwd") >= 1
