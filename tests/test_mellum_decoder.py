"""The decoder family (``backbone="decoder"``) at a tiny size on the CPU:
program against the plain reference for every share and the uncut model, the
share test, the attention kernel in interpret mode against the masked
reference, no token dropped under any imbalance, the head's one pass over the
token chunks against a plain cross-entropy and by the products in its jaxpr,
the rotary constants against hand-computed values, the packer, and ``fit`` end
to end."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import weights
from perfbench.reference import mellum_decoder as reference
from perfbench.tests import tiny_lm
from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib
from tensorflowdistributedlearning_tpu.models import build_model, decoder as decoder_lib
from tensorflowdistributedlearning_tpu.ops import blocked_attention as attn_lib
from tensorflowdistributedlearning_tpu.parallel import expert as expert_lib

STREAM = tokens_lib.TokenStreamConfig(**tiny_lm.TINY_STREAM)
# the uncut tiny model: 8 heads on 4 key-value heads, 8 experts, 256 ids
FULL = dict(num_attention_heads=8, num_key_value_heads=4, num_experts=8, vocab_size=256)


def _cfg(n: int, s: int) -> dict:
    return tiny_lm.tiny_config(n, s, **{k: v // n for k, v in FULL.items()})


def _model_config(cfg: dict) -> ModelConfig:
    decoder = DecoderConfig.from_published(
        cfg, share_count=cfg["share"]["n"], share_index=cfg["share"]["s"],
        sequence_length=cfg["sequence_length"])
    return ModelConfig(backbone="decoder", dtype="float32", decoder=decoder)


@pytest.fixture(scope="module")
def full_weights():
    cfg = _cfg(1, 0)
    spec = reference.param_spec(cfg)
    key = jax.random.key(0)
    return {
        name: (1.0 if len(shape) == 1 else 0.0) + 0.2 * jax.random.normal(
            jax.random.fold_in(key, i), shape)
        for i, (name, (shape, _)) in enumerate(sorted(spec.items()))
    }


def _batch(vocab: int, seed: int = 3):
    return {k: jnp.asarray(v) for k, v in next(tokens_lib.packed_token_batches(
        2, 64, vocab, STREAM, seed=seed)).items()}


@pytest.mark.parametrize("n,s", [(4, 0), (4, 1), (4, 2), (4, 3), (1, 0)])
def test_program_matches_reference(full_weights, n, s):
    """Loss, routed counts and every gradient leaf, for the four shares and
    the uncut model, on seeded weights."""
    cfg = _cfg(n, s)
    flat = reference.share_of(full_weights, _cfg(1, 0), n, s)
    model = build_model(_model_config(cfg))
    batch = _batch(cfg["vocab_size"])
    template = model.init(jax.random.key(1), np.zeros((1, 8), np.int32))["params"]
    params = weights.unflatten_like(template, flat)

    def loss(p):
        out = model.apply({"params": p}, batch, train=True)
        return out["loss_sum"] / out["n_targets"], out

    (got, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    want, want_grads, want_counts = jax.jit(
        lambda p: reference.batch_loss_and_grad(cfg, p, batch))(flat)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_array_equal(np.asarray(out["expert_tokens"]), np.asarray(want_counts))
    assert float(out["pairs_dropped"]) == 0.0
    flat_grads = weights.flatten(grads)
    assert set(flat_grads) == set(want_grads)
    for name, g in want_grads.items():
        gap = float(jnp.linalg.norm(flat_grads[name] - g) / (jnp.linalg.norm(g) + 1e-30))
        assert gap < 1e-4, (name, gap)
    logits, _ = reference.sequence_logits(cfg, flat, batch["tokens"][0], batch["segment_ids"][0],
                                          batch["positions"][0])
    hidden = model.apply({"params": params}, {k: v[:1] for k, v in batch.items() if k != "targets"})
    got_logits = hidden["hidden"][0] @ flat["head/kernel"]
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(logits), atol=2e-4)


def test_shares_add_up_to_the_uncut_layer(full_weights):
    """The share test: the four shares' attention and expert partial sums add
    up to what the uncut reference gives for the whole layer, for a sliding
    and for a full layer."""
    cfg1 = _cfg(1, 0)
    batch = _batch(64)
    seg, pos = batch["segment_ids"][0], batch["positions"][0]
    u = jax.random.normal(jax.random.key(5), (64, cfg1["hidden_size"]))
    for layer in (0, 3):
        whole_attn = reference.attention_part(cfg1, full_weights, layer, u, seg, pos)
        whole_moe = reference.moe_part(cfg1, full_weights, layer, u)
        attn = moe = 0.0
        for s in range(4):
            cfg = _cfg(4, s)
            flat = reference.share_of(full_weights, cfg1, 4, s)
            attn = attn + reference.attention_part(cfg, flat, layer, u, seg, pos)
            moe = moe + reference.moe_part(cfg, flat, layer, u)
            # the program's layer gives the same parts
            dcfg = _model_config(cfg).decoder
            got = decoder_lib.DecoderMoE(dcfg, jnp.float32).apply(
                {"params": {k.split("/")[-1]: v for k, v in flat.items()
                            if k.startswith(f"layers_{layer}/moe/")}}, u[None])[0][0]
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(reference.moe_part(cfg, flat, layer, u)), atol=1e-4)
        np.testing.assert_allclose(np.asarray(attn), np.asarray(whole_attn), atol=1e-4)
        np.testing.assert_allclose(np.asarray(moe), np.asarray(whole_moe), atol=1e-4)


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("documents", [False, True])
@pytest.mark.parametrize("group", [1, 4])
def test_attention_kernel_against_masked_reference(window, documents, group):
    """The Pallas kernel (interpret mode) against the XLA path, forward and
    gradient, over window x documents x grouped heads (causal throughout)."""
    key = jax.random.key(2)
    b, t, hkv, hd = 2, 256, 2, 128
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, t, hkv * group, hd))
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, t, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, t, hkv, hd))
    seg = np.zeros((b, t), np.int32)
    if documents:
        seg[0, 100:] = 1
        seg[1, 30:200] = 1
        seg[1, 200:] = 2
    seg = jnp.asarray(seg)

    def through(fn, **kw):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, seg, window=window, **kw)))

    want = attn_lib.masked_attention_reference(q, k, v, seg, window=window)
    got = attn_lib.splash_attention(q, k, v, seg, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    g_want = jax.grad(through(attn_lib.masked_attention_reference), (0, 1, 2))(q, k, v)
    g_got = jax.grad(through(attn_lib.splash_attention, interpret=True), (0, 1, 2))(q, k, v)
    for a, w in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-4)


def test_masked_reference_is_the_explicit_mask():
    """The XLA path against scores written out whole with the mask spelt out."""
    key = jax.random.key(4)
    b, t, hq, hkv, hd, window = 1, 32, 4, 2, 16, 8
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, t, hq, hd))
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, t, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, t, hkv, hd))
    seg = jnp.asarray(np.repeat([[0, 1]], 16, axis=1).reshape(1, 32))
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (i - j < window) & (np.asarray(seg[0])[:, None] == np.asarray(seg[0])[None, :])
    kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    got = attn_lib.masked_attention_reference(q, k, v, seg, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def _dense_experts(x, weights_, experts, w_gate, w_up, w_down, first):
    """Every held expert over every token, weighted by the routing."""
    want = jnp.zeros(x.shape)
    for e in range(w_gate.shape[0]):
        y = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        want = want + y * jnp.sum(jnp.where(experts == first + e, weights_, 0.0), 1)[:, None]
    return want


# (tokens, experts held, of, the buffer's rows expected, the routing). The
# first three have 96 pairs, under a tile: one segment of them all. The last
# three route 8,192 pairs in segments of 2,560 rows and take one, two and four
# of them: the expected share (2,048 held), 1.5 times it, everything to one
# held expert.
_IMBALANCE = {
    "all_to_one_held": (48, 4, 8, 96, lambda t: jnp.tile(jnp.asarray([[5, 0]]), (t, 1))),
    "none_held": (48, 4, 8, 0, lambda t: jnp.tile(jnp.asarray([[0, 3]]), (t, 1))),
    "even": (48, 4, 8, 96,
             lambda t: jnp.stack([jnp.arange(t) % 8, (jnp.arange(t) + 3) % 8], 1)),
    "segments_1_expected_share": (4096, 2, 8, 2560, lambda t: jnp.where(
        (jnp.arange(t) < 2048)[:, None], jnp.stack([4 + jnp.arange(t) % 2, jnp.arange(t) % 4], 1),
        jnp.asarray([[0, 1]]))),
    "segments_2_half_over": (4096, 2, 8, 5120, lambda t: jnp.where(
        (jnp.arange(t) < 3072)[:, None], jnp.stack([jnp.arange(t) % 4, 5 - jnp.arange(t) % 2], 1),
        jnp.asarray([[7, 2]]))),
    "segments_4_all_to_one_held": (4096, 2, 8, 10240,
                                   lambda t: jnp.tile(jnp.asarray([[5, 5]]), (t, 1))),
}


@pytest.mark.parametrize("case", sorted(_IMBALANCE))
def test_no_token_is_dropped_under_imbalance(case):
    """Output, counts, the drop counter and every gradient against the dense
    per-expert reference, over one and over several segments of the sorted
    pair buffer."""
    t, held, total, buffer_rows, routing = _IMBALANCE[case]
    d, f, k, first = 16, 8, 2, 4  # the second share holds experts 4..
    key = jax.random.key(7)
    x = jax.random.normal(key, (t, d))
    mats = [jax.random.normal(jax.random.fold_in(key, i), shape) / math.sqrt(shape[1])
            for i, shape in enumerate([(held, d, f), (held, d, f), (held, f, d)], 1)]
    weights_ = jax.random.uniform(jax.random.fold_in(key, 4), (t, k), minval=0.2, maxval=0.8)
    probe = jax.random.normal(jax.random.fold_in(key, 5), (t, d))
    experts = routing(t).astype(jnp.int32)

    def program(x, weights_, *mats):
        out, counts, dropped = expert_lib.dropless_experts(
            x, weights_, experts, *mats, num_experts_total=total, first_expert=first)
        return jnp.sum(out * probe), (out, counts, dropped)

    def dense(x, weights_, *mats):
        return jnp.sum(_dense_experts(x, weights_, experts, *mats, first) * probe)

    (_, (out, counts, dropped)), grads = jax.jit(
        jax.value_and_grad(program, argnums=range(5), has_aux=True))(x, weights_, *mats)
    want = _dense_experts(x, weights_, experts, *mats, first)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)
    assert int(dropped) == 0
    mine = (experts >= first) & (experts < first + held)
    assert np.asarray(counts).tolist() == [
        int((experts == first + e).sum()) for e in range(held)]
    assert int(expert_lib.pair_buffer_rows(counts, t * k, total)) == buffer_rows
    assert int(mine.sum()) <= buffer_rows
    want_grads = jax.grad(dense, argnums=range(5))(x, weights_, *mats)
    for name, got, ref in zip(["x", "weights", "w_gate", "w_up", "w_down"], grads, want_grads):
        scale = max(float(jnp.max(jnp.abs(ref))), 1e-6)
        np.testing.assert_allclose(
            np.asarray(got) / scale, np.asarray(ref) / scale, atol=2e-5, err_msg=name)


def test_rows_are_summed_into_tokens_as_a_grouped_product():
    """The transposed grouped product that sums a buffer's rows into their
    tokens on a TPU (interpret mode here) against the scatter-add: a crowded
    token, tokens with no row, an empty last block of tokens."""
    t, b, d, block = 1024, 1536, 256, 256
    key = jax.random.key(5)
    rows = jax.random.normal(key, (b, d), jnp.bfloat16)
    tok = jax.random.randint(jax.random.fold_in(key, 1), (b,), 0, t)
    tok = jnp.where(tok % 7 == 0, 5, tok)
    tok = jnp.where(tok >= 768, tok - 768 + 100, tok)
    got = expert_lib._sum_rows_on_mxu(rows, tok, t, block, interpret=True)
    want = jax.ops.segment_sum(rows.astype(jnp.float32), tok, num_segments=t)
    assert got.dtype == jnp.float32 and float(jnp.max(jnp.abs(want[768:]))) == 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5)


def test_the_segment_is_the_expected_share_and_a_quarter():
    """1.25 times the pairs expected here in whole tiles, at most all of them
    (where every expert is held, or the pairs are under a tile), and as many
    segments as hold the held pairs."""
    assert expert_lib._segment_rows(16384 * 8, 16, 64) == 40960
    assert expert_lib._segment_rows(16384 * 8, 64, 64) == 131072
    assert expert_lib._segment_rows(8192, 2, 8) == 2560
    assert expert_lib._segment_rows(96, 4, 8) == 96
    rows = [int(expert_lib.pair_buffer_rows(jnp.asarray([n, 0]), 8192, 8))
            for n in (0, 1, 2560, 2561, 5120, 5121, 8192)]
    assert rows == [0, 2560, 2560, 5120, 5120, 7680, 10240]


def test_a_buffer_too_small_counts_what_it_left_out(monkeypatch):
    """Held pairs past the last segment's end are counted as dropped, and the
    pairs the segments do hold are computed."""
    t, d, f, held = 32, 16, 8, 4
    key = jax.random.key(13)
    x = jax.random.normal(key, (t, d))
    mats = [jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, shape in enumerate([(held, d, f), (held, d, f), (held, f, d)])]
    experts = jnp.tile(jnp.asarray([[5, 7]], jnp.int32), (t, 1))  # every pair is held here
    weights_ = jnp.full((t, 2), 0.5)
    # one segment of 40 rows where 64 pairs are held
    monkeypatch.setattr(expert_lib, "_segment_rows", lambda pairs, held, total: 40)
    monkeypatch.setattr(expert_lib, "_segments", lambda n_held, rows: jnp.minimum(n_held, 1))
    out, counts, dropped = expert_lib.dropless_experts(
        x, weights_, experts, *mats, num_experts_total=8, first_expert=4)
    assert np.asarray(counts).tolist() == [0, t, 0, t] and int(dropped) == 2 * t - 40
    # expert 5's 32 pairs and expert 7's first 8 fit
    fit = jnp.where((jnp.arange(t) < 8)[:, None], experts, jnp.asarray([[5, -1]]))
    want = _dense_experts(x, weights_, fit, *mats, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_the_drop_counter_reads_what_the_products_wrote(monkeypatch):
    """The count is not the routing's algebra: rows of a held expert that the
    grouped product passes over are counted."""
    t, d, f, held = 32, 16, 8, 4
    key = jax.random.key(11)
    x = jax.random.normal(key, (t, d))
    mats = [jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, shape in enumerate([(held, d, f), (held, d, f), (held, f, d)])]
    experts = jnp.tile(jnp.asarray([[5, 7]], jnp.int32), (t, 1))  # every pair is held here
    real = expert_lib.grouped_matmul
    # a product that leaves the sorted buffer's last 7 rows (expert 7's) undone
    monkeypatch.setattr(expert_lib, "grouped_matmul",
                        lambda *a, **k: real(*a, **k).at[-7:].set(0.0))
    _, counts, dropped = expert_lib.dropless_experts(
        x, jnp.full((t, 2), 0.5), experts, *mats, num_experts_total=8, first_expert=4)
    assert np.asarray(counts).tolist() == [0, t, 0, t] and int(dropped) == 7


def test_the_windows_say_how_full_the_pair_buffer_was():
    """``moe_buffer_rows`` is a step's mean of the rows worked over and
    ``moe_buffer_fill`` the held pairs over those rows, layer by layer: two
    steps of two layers, the second step taking a longer buffer on layer 1."""
    from tensorflowdistributedlearning_tpu.train import step as step_lib

    task = step_lib.SequenceTask(_model_config(_cfg(2, 1)).decoder, STREAM)
    acc = None
    for tokens_, rows_ in [([[900, 1100], [500, 1500]], [2560, 2560]),
                           ([[1000, 1000], [2000, 1000]], [2560, 4096])]:
        outputs = {k: jnp.asarray(1.0) for k in (
            "loss_sum", "n_targets", "n_correct", "pairs_dropped", "attn_keys_full",
            "attn_keys_sliding", "n_positions", "tile_visits")}
        outputs.update(n_sequences=jnp.asarray(2.0),
                       expert_tokens=jnp.asarray(tokens_, jnp.float32),
                       buffer_rows=jnp.asarray(rows_, jnp.float32))
        deltas = task.metric_deltas(outputs, None)
        acc = deltas if acc is None else {k: acc[k].merge(v) for k, v in deltas.items()}
    scalars, vectors = step_lib.split_scalars(step_lib.compute_metrics(acc))
    fields = task.window_fields(4, scalars, vectors, None)
    assert fields["moe_buffer_rows"] == [2560.0, 3328.0]
    assert fields["moe_buffer_fill"] == [round(4000 / 5120, 4), round(5000 / 6656, 4)]
    assert all(0 < f <= 1 for f in fields["moe_buffer_fill"])
    assert fields["moe_pairs"] == 9000
    assert fields["moe_tile_visits"] == 1.0 and "attn_gate_mean" not in fields


def test_window_means_are_worked_out_on_the_host(monkeypatch):
    """compute_metrics touches no jax.numpy: a window's emission dispatches
    and compiles nothing, whatever the first window of a run is."""
    from tensorflowdistributedlearning_tpu.ops.metrics import Mean
    from tensorflowdistributedlearning_tpu.train import step as step_lib

    acc = {"loss": Mean(jnp.asarray(6.0), jnp.asarray(4.0)),
           "empty": Mean(np.float32(0.0), np.float32(0.0)),
           "moe/expert_tokens": Mean(jnp.asarray([[2.0, 4.0]]), jnp.asarray(2.0))}
    monkeypatch.setattr(step_lib, "jnp", None)
    got = step_lib.compute_metrics(acc)
    assert got == {"loss": 1.5, "empty": 0.0, "moe/expert_tokens": [[1.0, 2.0]]}
    assert step_lib.split_scalars(got) == (
        {"loss": 1.5, "empty": 0.0}, {"moe/expert_tokens": [[1.0, 2.0]]})


# the head's cases: (tokens as [B, T], what the targets lack). 4,096 divides
# neither token count, so the chunk is gcd(tokens, 4096): 32 of 96, 16 of 80
_HEAD_CASES = {
    "some_without_target": ((2, 48), lambda t: t.at[::5].set(-1)),
    "a_chunk_without_any": ((2, 48), lambda t: t.at[32:64].set(-1).at[7].set(-1)),
    "chunk_by_gcd": ((1, 80), lambda t: t.at[-1].set(-1)),
}
_HEAD_D, _HEAD_V = 16, 40


def _head_inputs(case: str):
    shape, lack = _HEAD_CASES[case]
    tokens = shape[0] * shape[1]
    h = jax.random.normal(jax.random.key(1), shape + (_HEAD_D,), jnp.float32)
    kernel = 0.3 * jax.random.normal(jax.random.key(2), (_HEAD_D, _HEAD_V), jnp.float32)
    targets = lack(jax.random.randint(jax.random.key(3), (tokens,), 0, _HEAD_V)).reshape(shape)
    return h, kernel, targets


def _head(dtype, h, kernel, targets):
    return decoder_lib.HeadLoss(_HEAD_V, dtype).apply({"params": {"kernel": kernel}}, h, targets)


def _one_shot_cross_entropy(dtype, h, kernel, targets):
    """All logits at once, float32 from operands rounded to ``dtype`` (the
    rounding passed straight through: a cast's own gradient rounds again)."""
    rounded = lambda x: x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)
    logits = jnp.dot(rounded(h).reshape(-1, _HEAD_D), rounded(kernel), precision="highest")
    t, has = targets.reshape(-1), targets.reshape(-1) >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.sum(jnp.where(has, logp[jnp.arange(t.size), jnp.maximum(t, 0)], 0.0))
    return loss, jnp.sum(has & (jnp.argmax(logits, axis=-1) == t))


@pytest.mark.parametrize("scale", ["mean", 3.0])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_HEAD_CASES))
def test_the_heads_one_pass_against_a_one_shot_cross_entropy(case, dtype, scale):
    """Loss sum and hits equal, the gradients of the hidden states and of the
    kernel to float32 tolerance, under a cotangent that is not 1 — also where
    a chunk holds no target and where the chunk follows from a gcd."""
    dtype = jnp.dtype(dtype)
    h, kernel, targets = _head_inputs(case)
    chunk = math.gcd(targets.size, decoder_lib.LOSS_CHUNK_TOKENS)
    assert 1 < targets.size // chunk and chunk < decoder_lib.LOSS_CHUNK_TOKENS
    if case == "a_chunk_without_any":
        assert (np.asarray(targets).reshape(-1, chunk) < 0).all(axis=1).tolist() == [False, True, False]
    factor = (lambda n: 1.0 / n) if scale == "mean" else (lambda n: scale)

    def program(h, kernel):
        out = _head(dtype, h, kernel, targets)
        return out["loss_sum"] * factor(out["n_targets"]), out

    def plain(h, kernel):
        loss, hits = _one_shot_cross_entropy(dtype, h, kernel, targets)
        return loss * factor(jnp.sum(targets >= 0)), (loss, hits)

    (_, out), (dh, dw) = jax.jit(jax.value_and_grad(program, (0, 1), has_aux=True))(h, kernel)
    (_, (loss, hits)), (want_dh, want_dw) = jax.jit(
        jax.value_and_grad(plain, (0, 1), has_aux=True))(h, kernel)
    assert float(out["loss_sum"]) == pytest.approx(float(loss), rel=2e-6)
    assert float(out["n_correct"]) == float(hits) and float(hits) > 0
    assert float(out["n_targets"]) == float(jnp.sum(targets >= 0))
    assert dh.dtype == h.dtype and dw.dtype == kernel.dtype and dh.shape == h.shape
    scale_of = lambda x: float(jnp.max(jnp.abs(x)))
    np.testing.assert_allclose(dh, want_dh, rtol=0, atol=1e-5 * scale_of(want_dh))
    np.testing.assert_allclose(dw, want_dw, rtol=0, atol=1e-5 * scale_of(want_dw))
    # a position without a target moves nothing
    assert float(jnp.max(jnp.abs(dh.reshape(-1, _HEAD_D)[np.asarray(targets).reshape(-1) < 0]))) == 0.0
    # and the undifferentiated call reads the same sums
    alone = jax.jit(lambda: _head(dtype, h, kernel, targets))()
    assert float(alone["loss_sum"]) == pytest.approx(float(out["loss_sum"]), rel=1e-6)
    assert float(alone["n_correct"]) == float(hits)


def _vocabulary_products(jaxpr, vocab: int):
    """(the ``dot_general``s with ``vocab`` in a shape, the loops that hold
    one) in a jaxpr and every jaxpr inside it."""
    products = loops = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            products += any(vocab in v.aval.shape for v in list(eqn.invars) + list(eqn.outvars))
        inner = 0
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    p, n = _vocabulary_products(sub, vocab)
                    inner, loops = inner + p, loops + n
        products += inner
        loops += bool(inner) and eqn.primitive.name in ("scan", "while")
    return products, loops


@pytest.mark.parametrize("differentiated,want", [(True, (3, 1)), (False, (1, 1))])
def test_the_head_computes_each_chunks_logits_once(differentiated, want):
    """What says the one pass engages: the gradient's jaxpr holds three
    products with the vocabulary a chunk — logits, ``dlogits @ W^T``,
    ``h^T @ dlogits`` — in one loop over the chunks (a recomputed chunk would
    give four in two), and the undifferentiated call holds the logits' alone."""
    h, kernel, targets = _head_inputs("some_without_target")
    loss = lambda h, kernel: _head(jnp.bfloat16, h, kernel, targets)["loss_sum"]
    fn = jax.grad(loss, (0, 1)) if differentiated else loss
    assert _vocabulary_products(jax.make_jaxpr(fn)(h, kernel).jaxpr, _HEAD_V) == want


def test_top_k_routing_renormalises_over_the_chosen():
    logits = jax.random.normal(jax.random.key(1), (10, 8))
    w, e = expert_lib.top_k_routing(logits, 3)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, rtol=1e-6)
    for row in range(10):
        top = np.argsort(-probs[row])[:3]
        assert set(top) == set(np.asarray(e[row]).tolist())
        np.testing.assert_allclose(
            np.sort(np.asarray(w[row])), np.sort(probs[row, top] / probs[row, top].sum()), rtol=1e-5)
    w_raw, _ = expert_lib.top_k_routing(logits, 3, renormalise=False)
    assert np.all(np.asarray(w_raw).sum(1) < 1.0)


def test_yarn_constants_against_hand_values():
    """head_dim 128, theta 500,000, factor 16, original 8,192, beta 32 / 1."""
    cfg = DecoderConfig()
    plain, one = decoder_lib.rope_constants(cfg, "sliding_attention")
    assert one == 1.0
    np.testing.assert_allclose(plain, 500000.0 ** (-np.arange(64) / 64.0), rtol=1e-6)
    yarn, factor = decoder_lib.rope_constants(cfg, "full_attention")
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    # low = floor(64 ln(8192 / (32 * 2 pi)) / ln theta) = 18, high = ceil(64 ln(8192 / (2 pi)) / ln theta) = 35
    assert math.floor(64 * math.log(8192 / (32 * 2 * math.pi)) / math.log(5e5)) == 18
    assert math.ceil(64 * math.log(8192 / (2 * math.pi)) / math.log(5e5)) == 35
    np.testing.assert_allclose(yarn[:19], plain[:19], rtol=1e-6)  # fast dimensions kept
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16.0, rtol=1e-6)  # slow ones interpolated
    i = 26  # halfway up the ramp: (26 - 18) / 17 interpolated
    r = (i - 18) / 17.0
    assert yarn[i] == pytest.approx(plain[i] / 16 * r + plain[i] * (1 - r), rel=1e-6)
    ref_inv, ref_factor = reference.rope_parameters(tiny_lm.tiny_config(), "full_attention")
    assert ref_factor == factor
    small = DecoderConfig(head_dim=16)
    np.testing.assert_array_equal(
        np.asarray(ref_inv), decoder_lib.rope_constants(small, "full_attention")[0])


def test_packer():
    stream = tokens_lib.TokenStreamConfig(median_length=40.0, min_length=8, max_length=100)
    a = list(tokens_lib.packed_token_batches(3, 256, 500, stream, seed=9, steps=4))
    b = list(tokens_lib.packed_token_batches(3, 256, 500, stream, seed=9, steps=2, start_index=2))
    for x, y in zip(a[2:], b):  # same seed, same stream; resumable by index
        for key in x:
            np.testing.assert_array_equal(x[key], y[key])
    other = next(tokens_lib.packed_token_batches(3, 256, 500, stream, seed=10))
    assert not np.array_equal(other["tokens"], a[0]["tokens"])
    for batch in a:
        assert {k: v.shape for k, v in batch.items()} == {
            k: (3, 256) for k in ("tokens", "segment_ids", "positions", "targets")}
        assert batch["tokens"].min() >= 0 and batch["tokens"].max() < 500
        for tok, seg, pos, tgt in zip(*(batch[k] for k in
                                        ("tokens", "segment_ids", "positions", "targets"))):
            assert seg[0] == 0 and np.all(np.diff(seg) >= 0) and np.all(np.diff(seg) <= 1)
            lengths = np.bincount(seg)
            assert np.all(lengths[:-1] >= 8) and np.all(lengths <= 100)  # the last is cut
            starts = np.flatnonzero(np.diff(seg, prepend=-1))
            assert np.all(pos[starts] == 0)  # positions restart
            assert np.all(np.diff(pos)[np.diff(seg) == 0] == 1)
            last = np.append(np.diff(seg) == 1, True)  # a document's last position
            assert np.all(tgt[last] == tokens_lib.NO_TARGET)  # never across a boundary
            assert np.all(tgt[:-1][~last[:-1]] == tok[1:][~last[:-1]])
    counts = np.bincount(np.concatenate([x["tokens"].ravel() for x in a]), minlength=500)
    assert counts[0] > counts[10] > counts[200]  # Zipf: id 0 the commonest


def _tiny_trainer(model_dir, steps_cfg=None):
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    cfg = tiny_lm.tiny_config()
    trainer = ClassifierTrainer(
        str(model_dir), None, _model_config(cfg),
        TrainConfig(optimizer="adam", lr=3e-3, weight_decay=0.1, augmentation="none",
                    train_log_every_steps=5, checkpoint_every_steps=25, n_devices=1,
                    token_stream=STREAM))
    return trainer


def _windows(model_dir):
    with open(os.path.join(model_dir, "telemetry.jsonl"), encoding="utf-8") as f:
        events = [json.loads(line) for line in f if line.strip()]
    return events, [e for e in events if e.get("event") == "step_window"]


def test_fit_trains_the_decoder_and_resumes(tmp_path):
    """Through ClassifierTrainer.fit with make_train_step: the loss falls
    over 50 steps, the windows count tokens and routed pairs with none
    dropped, and a save/restore round-trips the state."""
    result = _tiny_trainer(tmp_path).fit(batch_size=4, steps=50)
    assert result.steps == 50 and np.isfinite(result.final_metrics["loss"])
    events, windows = _windows(tmp_path)
    losses = [w["scalars"]["loss"] for w in windows]
    # untrained, the logits are even: ln(128) = 4.85; the ids are Zipf
    assert len(losses) == 10 and losses[0] > 4.3, losses
    assert np.mean(losses[-3:]) < losses[0] - 0.3 and np.mean(losses[-3:]) < 4.2, losses
    header = next(e for e in events if e.get("event") == "run_header")
    assert header["task"] == "next_token"
    assert header["decoder"] == {"share": [1, 2], "sequence_length": 64,
                                 "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}
    for w in windows:
        assert w["moe_pairs_dropped"] == 0
        assert 0 < w["tokens"] <= w["steps"] * 4 * 64
        assert w["moe_pairs"] == sum(map(sum, w["moe_expert_tokens"]))
        assert len(w["moe_expert_tokens"]) == 4 and len(w["moe_expert_tokens"][0]) == 4
        assert 1.0 <= w["moe_load_max_over_mean"] <= 4.0
        # 4 x 64 tokens x 2 experts a step, under a tile: one segment of them
        # all, or none in a step that routes nothing to this share
        assert all(0 <= r <= 512 for r in w["moe_buffer_rows"]) and max(w["moe_buffer_rows"]) == 512
        assert [round(f * r * w["steps"]) for f, r in zip(
            w["moe_buffer_fill"], w["moe_buffer_rows"])] == [
            sum(row) for row in w["moe_expert_tokens"]]
        keys = w["attn_keys_per_query"]
        assert 1.0 <= keys["sliding_attention"] <= 8.0 <= keys["full_attention"]
        if w.get("images_per_sec"):
            assert w["tokens_per_sec"] == pytest.approx(w["images_per_sec"] * 64, rel=1e-3)
    # a second trainer on the same directory restores step 50 and trains on
    again = _tiny_trainer(tmp_path)
    state = again._checkpointer().restore_latest(again._init_state())
    assert int(state.step) == 50
    more = again.fit(batch_size=4, steps=60)
    assert more.steps == 60
    events, _ = _windows(tmp_path)
    assert any(e.get("event") == "resumed" and e["step"] == 50 for e in events)


def test_the_preset_is_the_published_widths_and_plans():
    from tensorflowdistributedlearning_tpu.configs import get_preset
    from tensorflowdistributedlearning_tpu.parallel import planner

    preset = get_preset("mellum2_12b_a2p5b_share4")
    d = preset.model.decoder
    assert (d.hidden_size, d.head_dim, d.moe_intermediate_size, d.num_experts_per_tok,
            d.sliding_window) == (2304, 128, 896, 8, 1024)
    assert (d.num_attention_heads, d.num_key_value_heads, d.num_experts, d.vocab_size,
            d.share_count) == (8, 1, 16, 24576, 4)
    import dataclasses

    one_chip = dataclasses.replace(preset.train, n_devices=1)
    plan = planner.validate_config(preset.model, one_chip, preset.global_batch)
    assert planner.profile_model(preset.model, preset.train).param_count == 531_452_160
    assert plan.header()


def test_decoder_refuses_what_it_does_not_build(tmp_path):
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    cfg = _model_config(tiny_lm.tiny_config())
    with pytest.raises(ValueError, match="data-parallel only"):
        ClassifierTrainer(str(tmp_path), None, cfg,
                          TrainConfig(expert_parallel=2, n_devices=2, augmentation="none"))
    with pytest.raises(ValueError, match="fed as packed"):
        ClassifierTrainer(str(tmp_path), None, cfg, TrainConfig(n_devices=1))
    with pytest.raises(NotImplementedError, match="yields a"):
        _tiny_trainer(tmp_path).serving_fn()
    with pytest.raises(ValueError, match="go together"):
        ModelConfig(backbone="decoder")
