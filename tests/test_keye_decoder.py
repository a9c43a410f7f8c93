"""The decoder's ``sparse_attention`` layer (the Keye-VL-2.0 family) at a tiny
size on the CPU: program against the plain reference — both losses, every
leaf's gradient, three AdamW steps — the layer against ``full_attention``
where the selection takes everything, what each query reads, the two losses'
gradients kept apart, ties, the kernels in interpret mode against the XLA
path, the share test and the published file's parameter count."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import weights
from perfbench.reference import keye_decoder as reference
from perfbench.tests import tiny_sparse
from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib
from tensorflowdistributedlearning_tpu.models import build_model, decoder as decoder_lib
from tensorflowdistributedlearning_tpu.ops import sparse_attention as sparse_lib

STREAM = tokens_lib.TokenStreamConfig(**tiny_sparse.TINY_STREAM)
# the uncut tiny model: 8 heads on 2 key-value heads, 8 experts, 256 ids
FULL = dict(num_attention_heads=8, num_key_value_heads=2, num_experts=8, vocab_size=256)


def _cfg(n: int, s: int, **sizes) -> dict:
    held = {k: v // n for k, v in FULL.items()}
    held["num_key_value_heads"] = max(held["num_key_value_heads"], 1)  # a head on two shares
    held["num_local_experts"] = held["num_experts"]
    held.update(sizes)
    return tiny_sparse.tiny_config(n, s, **held)


def _model_config(cfg: dict) -> ModelConfig:
    decoder = DecoderConfig.from_published(
        cfg, share_count=cfg["share"]["n"], share_index=cfg["share"]["s"],
        sequence_length=cfg["sequence_length"])
    return ModelConfig(backbone="decoder", dtype="float32", decoder=decoder)


@pytest.fixture(scope="module")
def full_weights():
    spec = reference.param_spec(_cfg(1, 0))
    key = jax.random.key(0)
    return {
        name: (1.0 if kind == "norm_scale" else 0.0) + 0.2 * jax.random.normal(
            jax.random.fold_in(key, i), shape)
        for i, (name, (shape, kind)) in enumerate(sorted(spec.items()))
    }


def _batch(vocab: int, seed: int = 3, length: int = 64, rows: int = 2):
    return {k: jnp.asarray(v) for k, v in next(tokens_lib.packed_token_batches(
        rows, length, vocab, STREAM, seed=seed)).items()}


def _program(cfg, flat):
    model = build_model(_model_config(cfg))
    template = model.init(jax.random.key(1), np.zeros((1, 8), np.int32))["params"]
    return model, weights.unflatten_like(template, flat)


def _losses(model, batch):
    def both(p):
        out = model.apply({"params": p}, batch, train=True)
        return out["loss_sum"] / out["n_targets"], out["align_sum"] / out["n_positions"], out

    return both


@pytest.mark.parametrize("n,s", [(4, 0), (4, 3), (1, 0)])
def test_program_matches_reference(full_weights, n, s):
    """Both losses, routed counts, selection counts and every gradient leaf,
    for two of the four shares and the uncut model, on seeded weights."""
    cfg = _cfg(n, s)
    flat = reference.share_of(full_weights, _cfg(1, 0), n, s)
    model, params = _program(cfg, flat)
    batch = _batch(cfg["vocab_size"])
    both = _losses(model, batch)

    def total(p):
        lm, align, out = both(p)
        return lm + align, (lm, align, out)

    (_, (lm, align, out)), grads = jax.jit(jax.value_and_grad(total, has_aux=True))(params)
    (want_lm, want_align), want_grads, want_counts, want_reads = jax.jit(
        lambda p: reference.batch_loss_and_grad(cfg, p, batch))(flat)
    assert float(lm) == pytest.approx(float(want_lm), rel=1e-5)
    assert float(align) == pytest.approx(float(want_align), rel=1e-4)
    assert float(align) > 1e-3  # a loss, not a zero
    np.testing.assert_array_equal(np.asarray(out["expert_tokens"]), np.asarray(want_counts))
    np.testing.assert_array_equal(np.asarray(out["sparse_key_reads"]), np.asarray(want_reads))
    assert float(out["pairs_dropped"]) == 0.0
    flat_grads = weights.flatten(grads)
    assert set(flat_grads) == set(want_grads)
    for name, g in want_grads.items():
        gap = float(jnp.linalg.norm(flat_grads[name] - g) / (jnp.linalg.norm(g) + 1e-30))
        assert gap < 2e-4, (name, gap)


def test_three_adamw_steps_follow_the_reference(full_weights):
    """The program's own train step (``make_train_step`` with ``SequenceTask``)
    against ``reference.train_steps``: both losses of each step, and the
    parameters after three updates."""
    from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
    from tensorflowdistributedlearning_tpu.train import step as step_lib
    from tensorflowdistributedlearning_tpu.train.state import create_train_state

    cfg = _cfg(4, 1)
    flat = jax.device_get(reference.share_of(full_weights, _cfg(1, 0), 4, 1))  # the reference donates
    mcfg = _model_config(cfg)
    tcfg = TrainConfig(n_devices=1, token_stream=STREAM, **cfg["train"])
    task = step_lib.fit_task(mcfg, tcfg)
    mesh = mesh_lib.make_mesh(devices=jax.devices()[:1])
    state = create_train_state(build_model(mcfg), step_lib.make_optimizer(tcfg),
                               jax.random.key(0), np.zeros((1, 8), np.int32))
    state = state.replace(params=weights.unflatten_like(state.params, flat))
    step = step_lib.make_train_step(mesh, task, donate=False)
    stream = tokens_lib.packed_token_batches(2, 64, cfg["vocab_size"], STREAM, seed=11)
    batches = [next(stream) for _ in range(3)]
    got_lm, got_align = [], []
    for batch in batches:
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in batch.items()})
        got_lm.append(float(metrics["loss"].total / metrics["loss"].count))
        got_align.append(float(metrics["align_loss"].total / metrics["align_loss"].count))
    want = reference.train_steps(cfg, dict(flat), batches)
    np.testing.assert_allclose(got_lm, want["losses"], rtol=2e-5)
    np.testing.assert_allclose(got_align, want["align_losses"], rtol=2e-4)
    after = weights.flatten(state.params)
    for name, w in want["params"].items():
        moved = np.asarray(w) - np.asarray(flat[name])
        gap = np.linalg.norm(np.asarray(after[name]) - np.asarray(w)) / (np.linalg.norm(moved) + 1e-30)
        assert gap < 2e-2, (name, gap)


def test_layer_equals_full_attention_where_topk_covers_the_documents(full_weights):
    """``topk`` no smaller than the longest document: the selection takes every
    visible key and the layer is the ``full_attention`` layer on the same
    weights (same rotary parameters)."""
    sa = dict(tiny_sparse.TINY_SIZES["sa_config"], topk=64)
    cfg = _cfg(4, 0, sa_config=sa)
    flat = reference.share_of(full_weights, _cfg(1, 0), 4, 0)
    dcfg = _model_config(cfg).decoder
    names = {"wq", "wk", "wv", "wo", "q_norm", "k_norm", "indexer"}
    tree = weights.unflatten_like(
        decoder_lib.DecoderAttention(dcfg, "sparse_attention", jnp.float32).init(
            jax.random.key(0), jnp.zeros((1, 8, 64)), jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1, 8), jnp.int32))["params"],
        {k[len("layers_0/attn/"):]: v for k, v in flat.items() if k.startswith("layers_0/attn/")})
    assert set(tree) == names
    batch = _batch(64)
    u = jax.random.normal(jax.random.key(5), (2, 64, 64))
    sparse, extras = decoder_lib.DecoderAttention(dcfg, "sparse_attention", jnp.float32).apply(
        {"params": tree}, u, batch["segment_ids"], batch["positions"])
    full_cfg = DecoderConfig.from_published(
        dict(cfg, layer_types=["full_attention"] * 48), share_count=4, share_index=0,
        sequence_length=64)
    full, none = decoder_lib.DecoderAttention(full_cfg, "full_attention", jnp.float32).apply(
        {"params": {k: v for k, v in tree.items() if k != "indexer"}},
        u, batch["segment_ids"], batch["positions"])
    assert none == {} and set(extras) == {"align", "reads", "searched"}
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(full), atol=2e-5)
    visible = np.asarray(batch["positions"]).astype(np.float64) + 1
    assert float(np.sum(extras["reads"])) == visible.sum()


def _random_indexer(key, t, heads=2, dim=8):
    qi = jax.random.normal(jax.random.fold_in(key, 1), (t, heads, dim))
    ki = jax.random.normal(jax.random.fold_in(key, 2), (t, dim))
    wi = jax.random.normal(jax.random.fold_in(key, 3), (t, heads))
    return qi, ki, wi


def test_each_query_reads_its_best_visible_keys_and_no_other():
    """Exactly ``min(topk, visible)`` keys a query, none later, none of another
    document, and they are the best-scored ones."""
    t, topk = 96, 16
    seg = jnp.asarray(np.repeat([0, 1, 2], [40, 10, 46]), jnp.int32)
    qi, ki, wi = _random_indexer(jax.random.key(7), t)
    scores = sparse_lib._scores_xla(qi, ki, wi, seg)
    tau, tie = sparse_lib.select(scores, topk)
    mask = np.asarray(sparse_lib.selection_mask(scores, tau, tie))
    scores = np.asarray(scores)
    for i in range(t):
        visible = [j for j in range(i + 1) if seg[j] == seg[i]]
        assert np.isfinite(scores[i, visible]).all()
        assert np.isinf(scores[i, [j for j in range(t) if j not in visible]]).all()
        chosen = np.flatnonzero(mask[i])
        assert len(chosen) == min(topk, len(visible)) and set(chosen) <= set(visible)
        left = sorted(set(visible) - set(chosen))
        if left:
            # (>=: two heads of eight dimensions give exact zeros, and so ties)
            assert scores[i, chosen].min() >= scores[i, left].max()


def test_equal_scores_go_to_the_earlier_key():
    """Scores with many exact ties (and -0.0 beside 0.0): among equals the
    earlier positions are taken, as the reference's sort-based selection does."""
    rng = np.random.default_rng(0)
    t, topk = 64, 8
    values = _tied(rng, (t, t))
    seen = np.tril(np.ones((t, t), bool))
    scores = jnp.asarray(np.where(seen, values, -np.inf))
    tau, tie = sparse_lib.select(scores, topk)
    mask = np.asarray(sparse_lib.selection_mask(scores, tau, tie))
    want = np.asarray(reference.select(jnp.asarray(values), jnp.asarray(seen), topk))
    np.testing.assert_array_equal(mask, want)
    for i in range(topk, t):
        order = sorted(range(i + 1), key=lambda j: (-values[i, j], j))  # stable: earlier first
        assert sorted(np.flatnonzero(mask[i])) == sorted(order[:topk])
    # the kernel finds the same thresholds and, on the rows whose ties it
    # searched (those with more keys at the threshold than places left: by
    # hand from the keys around the threshold), the same tie positions
    got, got_tie, _ = sparse_lib._select_pallas(
        scores, jnp.zeros((t,), jnp.int32), topk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(tau))
    value = np.asarray(sparse_lib.threshold_value(tau))[:, None]
    masked = np.where(seen, values, -np.inf)
    need = topk - (masked > value).sum(-1)
    surplus = ((masked == value).sum(-1) > need) & np.isfinite(value[:, 0])
    assert surplus[topk:].any() and not surplus[:topk].any()
    np.testing.assert_array_equal(np.asarray(got_tie) < t - 1, surplus)
    np.testing.assert_array_equal(np.asarray(got_tie)[surplus], np.asarray(tie)[surplus])


def _masked(values, seg):
    """Scores as the indexer's kernel writes them: -inf wherever key s is not
    visible to query t (later, or of another document)."""
    t = len(seg)
    seen = (np.arange(t)[None, :] <= np.arange(t)[:, None]) & (seg[:, None] == seg[None, :])
    return np.where(seen, values, -np.inf).astype(np.float32)


def _tied(rng, shape):
    """Small integers, so a row has many equal scores, -0.0 beside 0.0."""
    values = rng.integers(-2, 3, size=shape).astype(np.float32)
    values[values == 0] = rng.choice([0.0, -0.0], size=int((values == 0).sum()))
    return values


def _selection_case(name):
    """(scores the kernel is given, scores the selection is of, segment ids,
    topk, the kernel's chunk of columns)."""
    rng = np.random.default_rng(5)
    if name in ("documents", "poisoned"):
        # three documents whose ends fall on no multiple of 8, 32 or the chunk;
        # chunks of 128 columns, so a block of rows reads its columns four
        # chunks at a time and then singly
        t, topk, chunk = 1024, 48, 128
        seg = np.repeat([0, 1, 2], [301, 410, 313]).astype(np.int32)
        scores = _masked(rng.standard_normal((t, t)), seg)
        given = scores
        if name == "poisoned":
            # what the kernel must never read: +inf and NaN over every column
            # outside the chunks a block of rows counts over
            rows = math.gcd(t, sparse_lib._SELECT_ROWS)
            first, last = (np.repeat(np.asarray(x), rows)[:, None] * chunk
                           for x in sparse_lib._counted_chunks(jnp.asarray(seg), rows, chunk))
            cols = np.arange(t)[None, :]
            before, after = cols < first, cols >= last
            assert before.any() and after.any() and np.isinf(scores[before | after]).all()
            given = np.where(before | after, np.where(cols % 2 == 0, np.inf, np.nan), scores)
        return given, scores, seg, topk, chunk
    if name == "ties":
        # blocks of rows with ties to leave out (the upper two documents'
        # scores are small integers) and without (the last's are continuous)
        t, topk = 512, 8
        seg = np.repeat([0, 1, 2], [100, 156, 256]).astype(np.int32)
        values = np.where(seg[:, None] < 2, _tied(rng, (t, t)), rng.standard_normal((t, t)))
        scores = _masked(values, seg)
        return scores, scores, seg, topk, None
    if name == "short_rows":
        # documents shorter than topk, one of exactly topk, and a longer one
        t, topk = 256, 48
        seg = np.repeat(np.arange(6), [30, 47, 48, 17, 100, 14]).astype(np.int32)
        scores = _masked(rng.standard_normal((t, t)), seg)
        return scores, scores, seg, topk, None
    if name == "ids_that_fall":
        # a document's id comes back after another's: its later rows see the
        # sequence's first columns again, so nothing bounds a block from below
        t, topk = 512, 16
        seg = np.repeat([1, 0, 1], [150, 200, 162]).astype(np.int32)
        scores = _masked(_tied(rng, (t, t)), seg)
        return scores, scores, seg, topk, None
    raise ValueError(name)


@pytest.mark.parametrize(
    "case", ["documents", "ties", "short_rows", "poisoned", "ids_that_fall"])
def test_selection_kernel_equals_the_xla_selection(case, monkeypatch):
    """``_select_pallas`` (interpreted) against ``select``: the thresholds
    element for element, the tie positions on every row with more ties than
    places (``T - 1`` on the others), the same selection on every row, and its
    two counters against counts by hand."""
    given, scores, seg, topk, chunk = _selection_case(case)
    t = len(seg)
    if chunk is not None:
        monkeypatch.setattr(sparse_lib, "_TILE_K", chunk)
    chunk = math.gcd(t, sparse_lib._TILE_K)
    rows = math.gcd(t, sparse_lib._SELECT_ROWS)
    tau, tie = sparse_lib.select(jnp.asarray(scores), topk)
    got_tau, got_tie, work = sparse_lib._select_pallas(
        jnp.asarray(given), jnp.asarray(seg), topk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_tau), np.asarray(tau))
    # by hand: a row has ties to leave out where more finite scores equal its
    # threshold than it has places left
    value = np.asarray(sparse_lib.threshold_value(tau))[:, None]
    need = topk - (scores > value).sum(-1)
    surplus = ((scores == value).sum(-1) > need) & np.isfinite(value[:, 0])
    got_tie = np.asarray(got_tie)
    np.testing.assert_array_equal(got_tie[surplus], np.asarray(tie)[surplus])
    assert (got_tie[~surplus] == t - 1).all()
    np.testing.assert_array_equal(
        np.asarray(sparse_lib.selection_mask(jnp.asarray(scores), got_tau, jnp.asarray(got_tie))),
        np.asarray(sparse_lib.selection_mask(jnp.asarray(scores), tau, tie)))
    assert float(work["tie_blocks"]) == surplus.reshape(-1, 8).any(axis=1).sum()
    # a block of rows counts from the chunk its first row's document starts in
    # (the sequence's start where ids fall) to the chunk of its last row
    starts = np.concatenate([[0], np.flatnonzero(np.diff(seg)) + 1])
    columns = 0
    for r in range(0, t, rows):
        first = starts[starts <= r].max() // chunk if (np.diff(seg) >= 0).all() else 0
        columns += rows * (-(-(r + rows) // chunk) - first) * chunk
    assert float(work["columns"]) == columns
    if case == "documents":
        assert surplus.sum() == 0 and columns < 0.55 * t * t
    if case == "ties":
        blocks = surplus.reshape(-1, rows).any(axis=1)
        assert blocks.any() and not blocks.all() and 0 < surplus.sum() < t
    if case == "short_rows":
        short = np.isinf(value[:, 0])
        assert short.sum() == 30 + 47 + 47 + 17 + 47 + 14
        assert (np.asarray(got_tau)[short] == sparse_lib._KEY_NEG_INF).all()
    if case == "ids_that_fall":
        assert columns > 0.55 * t * t and surplus.any()


def test_each_loss_reaches_its_own_leaves_only(full_weights):
    """``L_lm``'s gradient is exactly zero on the indexer's leaves and
    ``L_I``'s exactly zero on every other leaf — the head's kernel among them,
    whose gradient is the residual of the head's own pass times ``L_lm``'s
    cotangent alone; both are non-zero on their own."""
    cfg = _cfg(4, 2)
    flat = reference.share_of(full_weights, _cfg(1, 0), 4, 2)
    model, params = _program(cfg, flat)
    both = _losses(model, _batch(cfg["vocab_size"]))
    g_lm = weights.flatten(jax.jit(jax.grad(lambda p: both(p)[0]))(params))
    g_align = weights.flatten(jax.jit(jax.grad(lambda p: both(p)[1]))(params))
    indexer = set(reference.indexer_leaves(cfg))
    assert len(indexer) == 5 * cfg["num_hidden_layers"]
    assert {k.split("/indexer/")[1].split("/")[0] for k in indexer} == {"wq", "wk", "k_norm", "w"}
    assert "head/kernel" in g_lm and "head/kernel" not in indexer
    for name in g_lm:
        own, other = (g_align, g_lm) if name in indexer else (g_lm, g_align)
        assert float(jnp.max(jnp.abs(other[name]))) == 0.0, name
        assert float(jnp.max(jnp.abs(own[name]))) > 0.0, name


def test_kernels_in_interpret_mode_follow_the_xla_path():
    """The Pallas kernels (indexer scores and their gradients, the threshold
    search, the alignment loss) and splash attention under the run's mask,
    interpreted on the CPU, against the XLA path: outputs, the loss, the
    selection counts and every gradient."""
    rng = np.random.default_rng(0)
    b, t, hq, hkv, hd, heads, dim, topk = 1, 512, 4, 1, 128, 2, 64, 48
    arrays = [rng.standard_normal(s).astype(np.float32) for s in (
        (b, t, hq, hd), (b, t, hkv, hd), (b, t, hkv, hd), (b, t, heads, dim), (b, t, dim),
        (b, t, heads))]
    seg = jnp.asarray(np.repeat([0, 1, 2], [200, 250, 62])[None], jnp.int32)

    def run(interpret):
        def loss(*x):
            out, align, reads, searched = sparse_lib.sparse_attention(
                *x, seg, topk=topk, interpret=interpret)
            return jnp.sum(out * jnp.cos(out)) + align, (out, align, reads, searched)

        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True))(
            *map(jnp.asarray, arrays))

    (_, (out, align, reads, searched)), grads = run(None)
    (_, (out_k, align_k, reads_k, searched_k)), grads_k = run(True)
    # the XLA path searches every row whole; the kernel stops a block of rows
    # at the chunk of its last row's own position (one chunk here)
    assert float(searched["columns"]) == t * t == float(searched_k["columns"])
    # ... and searches ties where a row has some to leave out (the ReLU's exact
    # zeros are the threshold of many rows here)
    assert float(searched["tie_blocks"]) == t // 8 > float(searched_k["tie_blocks"]) > 0
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out), atol=2e-5)
    assert float(align_k) == pytest.approx(float(align), rel=1e-5)
    np.testing.assert_array_equal(np.asarray(reads_k), np.asarray(reads))
    for got, want in zip(grads_k, grads):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0 and float(jnp.max(jnp.abs(got - want))) < 2e-5 * max(scale, 1.0)


def test_shares_add_up_to_the_uncut_layer(full_weights):
    """The share test: 4 shares on 2 key-value heads (each held twice). The
    shares' attention outputs, expert outputs and head-summed probabilities
    add up to the uncut reference's layer; the indexer and the router, which
    every share holds whole, count once (all shares select the same keys)."""
    cfg1 = _cfg(1, 0)
    batch = _batch(64)
    seg, pos = batch["segment_ids"][0], batch["positions"][0]
    u = jax.random.normal(jax.random.key(5), (64, cfg1["hidden_size"]))
    for layer in (0, 2):
        whole_attn, whole_reads = reference.attention_part(cfg1, full_weights, layer, u, seg, pos)
        whole_probs = reference.probability_part(cfg1, full_weights, layer, u, seg, pos)
        whole_moe = reference.moe_part(cfg1, full_weights, layer, u)
        attn = moe = probs = 0.0
        for s in range(4):
            cfg = _cfg(4, s)
            assert cfg["num_key_value_heads"] == 1 and cfg["num_attention_heads"] == 2
            flat = reference.share_of(full_weights, cfg1, 4, s)
            # share s holds key-value head s // 2
            kv = full_weights[f"layers_{layer}/attn/wk/kernel"]
            np.testing.assert_array_equal(
                np.asarray(flat[f"layers_{layer}/attn/wk/kernel"]),
                np.asarray(kv[:, (s // 2) * 16:(s // 2 + 1) * 16]))
            for name in reference.indexer_leaves(cfg) + reference.router_leaves(cfg):
                assert flat[name] is full_weights[name]  # whole on every share
            part, reads = reference.attention_part(cfg, flat, layer, u, seg, pos)
            np.testing.assert_array_equal(np.asarray(reads), np.asarray(whole_reads))
            attn = attn + part
            probs = probs + reference.probability_part(cfg, flat, layer, u, seg, pos)
            moe = moe + reference.moe_part(cfg, flat, layer, u)
            # the program's attention gives the same part
            dcfg = _model_config(cfg).decoder
            module = decoder_lib.DecoderAttention(dcfg, "sparse_attention", jnp.float32)
            tree = weights.unflatten_like(
                module.init(jax.random.key(0), u[None], seg[None], pos[None])["params"],
                {k[len(f"layers_{layer}/attn/"):]: v for k, v in flat.items()
                 if k.startswith(f"layers_{layer}/attn/")})
            got, extras = module.apply({"params": tree}, u[None], seg[None], pos[None])
            np.testing.assert_allclose(np.asarray(got[0]), np.asarray(part), atol=1e-4)
            np.testing.assert_array_equal(np.asarray(extras["reads"]), np.asarray(whole_reads))
        np.testing.assert_allclose(np.asarray(attn), np.asarray(whole_attn), atol=1e-4)
        np.testing.assert_allclose(np.asarray(probs), np.asarray(whole_probs), atol=1e-5)
        np.testing.assert_allclose(np.asarray(moe), np.asarray(whole_moe), atol=1e-4)


def test_published_file_counts_its_parameters():
    cfg = tiny_sparse.committed_config()
    spec = reference.param_spec(cfg)
    assert sum(math.prod(shape) for shape, _ in spec.values()) == 400_379_392 == cfg["n_params"]
    layer = sum(math.prod(shape) for name, (shape, _) in spec.items()
                if name.startswith("layers_0/"))
    assert layer == 2_621_696 + 2_261_120 + 4_096 + 262_144 + 16 * 4_718_592 == 80_646_528
    # the program's tree has the same leaves
    from tensorflowdistributedlearning_tpu.configs import get_preset

    model = build_model(get_preset(cfg["preset"]).model)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), np.zeros((1, 8), np.int32)))
    flat = weights.flatten(shapes["params"])
    assert {k: tuple(v.shape) for k, v in flat.items()} == {k: s for k, (s, _) in spec.items()}


def test_config_refuses_a_sparse_layer_without_its_indexer():
    with pytest.raises(ValueError, match="sa_config"):
        DecoderConfig(layer_types=("sparse_attention",) * 28)
    with pytest.raises(ValueError, match="Unknown layer types"):
        DecoderConfig(layer_types=("linear_attention",) * 28)


def test_fit_trains_the_sparse_decoder(tmp_path):
    """``ClassifierTrainer.fit`` on the tiny sparse decoder: the loss falls,
    and every window carries the new fields."""
    import json
    import os

    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    cfg = _cfg(2, 1, num_attention_heads=4, num_key_value_heads=1, num_experts=4,
               num_local_experts=4, vocab_size=128)
    trainer = ClassifierTrainer(
        str(tmp_path), None, _model_config(cfg),
        TrainConfig(optimizer="adam", lr=3e-3, augmentation="none", train_log_every_steps=5,
                    n_devices=1, token_stream=STREAM))
    trainer.fit(batch_size=4, steps=40)
    with open(os.path.join(str(tmp_path), "telemetry.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    windows = [e for e in events if e.get("event") == "step_window"]
    assert len(windows) >= 6
    assert windows[-1]["scalars"]["loss"] < windows[0]["scalars"]["loss"]
    # the indexer chases a target that moves as the attention trains: its
    # loss is reported, finite and positive, not promised to fall in 40 steps
    for w in windows:
        assert set(w["attn_keys_per_query"]) == {"sparse_attention"}
        assert 0 < w["attn_keys_per_query"]["sparse_attention"] <= 16
        assert 0 < w["sparse_pairs_selected"] <= w["sparse_pairs_scored"]
        assert w["moe_pairs_dropped"] == 0 and 0 < w["align_loss"] < 10
        # a sequence's, four layers together; the XLA path (this backend's)
        # searches every row whole: 64 columns and 64 / 8 groups of rows
        assert w["sparse_select_columns"] == 4 * 64 * 64 and w["sparse_tie_blocks"] == 4 * 8
    header = next(e for e in events if e.get("event") == "run_header")
    assert header["decoder"]["layer_types"] == ["sparse_attention"] * 4
