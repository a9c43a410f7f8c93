"""What a recomputed decoder layer keeps (``models/decoder.py:REMAT_POLICY``):
the attention kernels' output and log-sum-exp beside the layer's input, so the
backward pass does not run the forward attention kernel a second time. The
kernel paths in interpret mode at small shapes, a layer type a case: how often
each kernel stands in the gradient's jaxpr, the bits of loss and gradients
against a layer that keeps nothing, and a tiny model of each preset's layer
pattern through a train step on the XLA path, where nothing is named."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.tests import tiny_lm, tiny_mixed, tiny_sparse
from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib
from tensorflowdistributedlearning_tpu.models import build_model, decoder as decoder_lib
from tensorflowdistributedlearning_tpu.ops import blocked_attention as attn_lib
from tensorflowdistributedlearning_tpu.ops import sparse_attention as sparse_lib

T, HEAD, INDEXER_HEAD, TOPK, WINDOW = 512, 128, 64, 48, 64
LAYER_TYPES = ("sliding_attention", "full_attention", "sparse_attention")
# the forward attention kernel of each layer type, and how often every kernel
# stands in the gradient of a layer that keeps what the decoder's policy names
# (``sparse_select`` is left out: its second call is dead code the jaxpr still holds)
FORWARD = {"sliding_attention": "splash_mqa_fwd_segmented_residuals",
           "full_attention": "splash_mqa_fwd_segmented_residuals",
           "sparse_attention": "sparse_attend"}
SPLASH = {"splash_mqa_fwd_segmented_residuals": 1, "splash_mqa_dq_segmented_no_residuals": 1,
          "splash_mqa_dkv_segmented_no_residuals": 1}
KEPT = {"sliding_attention": SPLASH, "full_attention": SPLASH,
        "sparse_attention": {"sparse_attend": 1, "sparse_attend_dq": 1, "sparse_attend_dkv": 1,
                             "sparse_indexer_dq": 1, "sparse_indexer_dk": 1,
                             # their [T, T] products are not kept: computed again
                             "sparse_align": 2, "sparse_indexer_scores": 2}}


def _layer(layer_type):
    """(a layer's attention as ``loss(*operands)`` on the kernel path, its
    operands): something to recompute before the kernel (as the projections
    are), then the kernel, then a loss every output reaches."""
    rng = np.random.default_rng(0)
    shapes = [(1, T, 4, HEAD), (1, T, 1, HEAD), (1, T, 1, HEAD)]
    if layer_type == "sparse_attention":
        shapes += [(1, T, 2, INDEXER_HEAD), (1, T, INDEXER_HEAD), (1, T, 2)]
    operands = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]
    seg = jnp.asarray(np.repeat([0, 1, 2], [200, 250, 62])[None], jnp.int32)

    def loss(q, *rest):
        q = jnp.tanh(q)
        if layer_type == "sparse_attention":
            out, align, _, _ = sparse_lib.sparse_attention(
                q, *rest, seg, topk=TOPK, interpret=True)
            return jnp.sum(jnp.sin(out)) + align
        out = attn_lib.splash_attention(
            q, *rest, seg, window=WINDOW if layer_type == "sliding_attention" else None,
            interpret=True)
        return jnp.sum(jnp.sin(out))

    return loss, operands


def _equations(jaxpr):
    """Every equation, through every jaxpr an equation holds."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner)


def _kernels(jaxpr):
    """``pallas_call``s by name."""
    return collections.Counter(
        eqn.params["name"] for eqn in _equations(jaxpr) if eqn.primitive.name == "pallas_call")


def _recomputed_grad(loss, operands, policy):
    return jax.value_and_grad(jax.checkpoint(loss, policy=policy), tuple(range(len(operands))))


@pytest.mark.parametrize("layer_type", LAYER_TYPES)
def test_the_backward_pass_holds_the_forward_attention_kernel_once(layer_type):
    loss, operands = _layer(layer_type)

    def counts(policy):
        return _kernels(jax.make_jaxpr(_recomputed_grad(loss, operands, policy))(*operands).jaxpr)

    kept = counts(decoder_lib.REMAT_POLICY)
    assert {name: kept[name] for name in KEPT[layer_type]} == KEPT[layer_type]
    # a layer that keeps its input alone runs the forward kernel again for the
    # residuals: the name has to be on the values the backward kernels read
    bare = counts(None)
    assert bare[FORWARD[layer_type]] == 2

    def others(counted):
        return {name: n for name, n in counted.items()
                if name not in (FORWARD[layer_type], "sparse_select")}

    assert others(bare) == others(kept)


@pytest.mark.parametrize("layer_type", LAYER_TYPES)
def test_what_is_kept_is_what_would_be_computed_again(layer_type):
    """Loss and every gradient, bit for bit, between the decoder's policy and
    a layer that keeps nothing."""
    loss, operands = _layer(layer_type)
    got_loss, got = jax.jit(_recomputed_grad(loss, operands, decoder_lib.REMAT_POLICY))(*operands)
    want_loss, want = jax.jit(_recomputed_grad(loss, operands, None))(*operands)
    assert float(got_loss) == float(want_loss) and np.isfinite(float(want_loss))
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(w))) > 0
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_the_two_names_are_one_definition():
    assert attn_lib.ATTENTION_NAME is sparse_lib.ATTENTION_NAME != sparse_lib.SELECT_NAME


PRESETS = {
    "mellum2_12b_a2p5b_share4": (tiny_lm, {"sliding_attention", "full_attention"}),
    "keye_vl2_30b_a3b_share8": (tiny_sparse, {"sparse_attention"}),
    "laguna_xs2_33b_a3b_share8": (tiny_mixed, {"sliding_attention", "full_attention"}),
}


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_a_tiny_model_of_each_layer_pattern_trains_a_recomputed_step(preset, monkeypatch):
    """The preset's layer pattern at toy widths, every layer recomputed under
    the decoder's policy, on the XLA path: nothing there carries the attention's
    name, so nothing more is kept, and the step is the one a model that keeps
    every intermediate takes."""
    from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
    from tensorflowdistributedlearning_tpu.train import step as step_lib
    from tensorflowdistributedlearning_tpu.train.state import create_train_state

    tiny, kinds = PRESETS[preset]
    cfg = tiny.tiny_config()
    decoder = DecoderConfig.from_published(
        cfg, share_count=cfg["share"]["n"], share_index=cfg["share"]["s"],
        sequence_length=cfg["sequence_length"])
    assert set(decoder.layer_types[: decoder.num_hidden_layers]) == kinds
    mcfg = ModelConfig(backbone="decoder", dtype="float32", decoder=decoder)
    stream = tokens_lib.TokenStreamConfig(**tiny.TINY_STREAM)
    tcfg = TrainConfig(n_devices=1, token_stream=stream, **cfg["train"])
    batch = {k: jnp.asarray(v) for k, v in next(tokens_lib.packed_token_batches(
        2, 64, decoder.vocab_size, stream, seed=3)).items()}
    mesh = mesh_lib.make_mesh(devices=jax.devices()[:1])

    def one_step(min_tokens):
        monkeypatch.setattr(decoder_lib, "REMAT_MIN_TOKENS", min_tokens)
        state = create_train_state(build_model(mcfg), step_lib.make_optimizer(tcfg),
                                   jax.random.key(0), np.zeros((1, 8), np.int32))
        # a step of its own, past make_train_step's memo
        step = step_lib._make_train_step_cached.__wrapped__(
            mesh, step_lib.fit_task(mcfg, tcfg), 0.0, False, False, False)
        equations = list(_equations(jax.make_jaxpr(step)(state, batch).jaxpr))
        layers = sum(eqn.primitive.name == "remat2" and eqn.params["differentiated"]
                     and eqn.params["policy"] is decoder_lib.REMAT_POLICY for eqn in equations)
        names = {eqn.params["name"] for eqn in equations if eqn.primitive.name == "name"}
        after, metrics = step(state, batch)
        return state, after, float(metrics["loss"].total / metrics["loss"].count), layers, names

    before, recomputed, loss, layers, names = one_step(1)
    assert layers == decoder.num_hidden_layers
    assert names == ({sparse_lib.SELECT_NAME} if "sparse_attention" in kinds else set())
    _, plain, plain_loss, layers, _ = one_step(10**9)
    assert layers == 0
    assert np.isfinite(loss) and loss == pytest.approx(plain_loss, rel=1e-6)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), before.params,
                         recomputed.params)
    assert min(jax.tree.leaves(moved)) > 0
    for got, want in zip(jax.tree.leaves(recomputed.params), jax.tree.leaves(plain.params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)
