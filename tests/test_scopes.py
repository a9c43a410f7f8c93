"""The scope map of a compiled step program (``obs/scopes.py``): from an
``op_name`` to (chain, pass), from the tiny decoder's compiled train step to a
``program_scopes`` record, the record through ``Telemetry`` into the ledger,
and the capture's reduction by scope. Counts and names only: a CPU run says
nothing about device time."""

import collections
import contextlib
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from perfbench.tests import tiny_lm
from tensorflowdistributedlearning_tpu import obs as obs_lib
from tensorflowdistributedlearning_tpu.config import (
    DecoderConfig, ModelConfig, TokenStreamConfig, TrainConfig,
)
from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib
from tensorflowdistributedlearning_tpu.models import build_model, decoder as decoder_lib
from tensorflowdistributedlearning_tpu.obs import scopes
from tensorflowdistributedlearning_tpu.parallel import make_mesh
from tensorflowdistributedlearning_tpu.train import create_train_state, make_optimizer
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer



@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A program loaded from the persistent cache carries the metadata of
    whoever compiled it first (the suite's cache does not key on metadata):
    what this file reads from compiled programs, it compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# -- op_name -> (chain, pass) -----------------------------------------------------

# the three forms a scope takes in a compiled step (jax 0.9.0): inside
# ``jvp(...)``, under a rematerialised computation, in the backward pass
RECORDED = [
    ("jit(step)/jvp(decoder/head_loss)/closed_call/while/body/closed_call/dot_general",
     ("decoder/head_loss",), "forward"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/decoder/head_loss/"
     "while/body/closed_call/dot_general", ("decoder/head_loss",), "recompute"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/decoder/head_loss/while/body/closed_call/"
     "transpose", ("decoder/head_loss",), "backward"),
]


@pytest.mark.parametrize("op_name,chain,which", RECORDED)
def test_scope_of_the_recorded_forms(op_name, chain, which):
    assert scopes.scope_of(op_name) == (chain, which)


@pytest.mark.parametrize("op_name,chain", [
    # a scope inside a scope whose name it extends: the longer one is the innermost
    ("jit(step)/jvp(MoEDecoder)/layers_0/attn/decoder/attn_sparse/decoder/attn_sparse/indexer/dot",
     ("decoder/attn_sparse", "decoder/attn_sparse/indexer")),
    # a short name inside a longer-named scope is still the innermost: it ends last
    ("jit(step)/jvp(MoEDecoder)/layers_0/attn/decoder/attn_sliding/decoder/attn_gate/head_gate/mul",
     ("decoder/attn_sliding", "decoder/attn_gate")),
    # no registered scope; a name that merely contains one is none either
    ("jit(step)/jvp(MoEDecoder)/layers_0/add", ()),
    ("jit(step)/my_optimizer/mul", ()),
    ("jit(step)/optimizer_state/mul", ()),
    # XLA joins the names of instructions it merged with ";"
    ("jit(step)/mul;jit(step)/optimizer/add", ("optimizer",)),
    ("", ()),
])
def test_scope_of_nested_and_bounded(op_name, chain):
    assert scopes.scope_of(op_name)[0] == chain


def test_only_registered_names_open_a_scope():
    with scopes.scope("optimizer"):
        pass
    with pytest.raises(KeyError, match="SCOPES"):
        scopes.scope("optimiser")
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
    # no scope shares the host spans' ``obs/`` prefix (perfbench/xtrace.py)
    assert not [s for s in scopes.SCOPES if s.startswith("obs/")]


# -- the tiny decoder's compiled step -> record --------------------------------------


def _tiny_step_compiled():
    """The tiny decoder's train step, every layer recomputed, compiled for
    shapes alone (nothing runs)."""
    cfg = tiny_lm.tiny_config()
    decoder = DecoderConfig.from_published(
        cfg, share_count=2, share_index=1, sequence_length=cfg["sequence_length"])
    mcfg = ModelConfig(backbone="decoder", dtype="float32", decoder=decoder)
    tcfg = TrainConfig(optimizer="adam", lr=3e-3, weight_decay=0.1, augmentation="none",
                       n_devices=1, token_stream=TokenStreamConfig(**tiny_lm.TINY_STREAM))
    mesh = make_mesh(1)
    batch = {k: jnp.asarray(v) for k, v in next(tokens_lib.packed_token_batches(
        4, 64, decoder.vocab_size, tcfg.token_stream, seed=3)).items()}
    state = jax.eval_shape(lambda: create_train_state(
        build_model(mcfg), make_optimizer(tcfg), jax.random.key(0), batch["tokens"]))
    # a step of its own, past make_train_step's memo: other tests' steps are
    # not recomputed layer by layer
    step = step_lib._make_train_step_cached.__wrapped__(
        mesh, step_lib.fit_task(mcfg, tcfg), 0.0, False, True, False)
    return step.lower(state, batch).compile()


@pytest.fixture(scope="module")
def compiled():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder_lib, "REMAT_MIN_TOKENS", 1)
        yield _tiny_step_compiled()


@pytest.fixture(scope="module")
def record(compiled):
    return scopes.describe(compiled)


def test_every_instruction_of_the_timeline_has_an_entry(compiled, record):
    module, entry, computations = scopes._parse(compiled.as_text())
    assert record["program"] == module == "jit_step"
    table = scopes.op_table(record)
    timeline = scopes._timeline(entry, computations)
    bodies = [c for name in timeline for inst in computations[name]
              if inst.opcode == "while" for c in inst.called]
    assert entry in timeline and bodies and set(bodies) <= set(timeline)
    wanted = [inst.name for name in timeline for inst in computations[name]
              if inst.opcode not in scopes._NO_OP]
    assert sorted(wanted) == sorted(table) and len(wanted) == record["instructions"]
    # what a fusion holds is no op of its own
    fused = {c for insts in computations.values() for inst in insts
             if inst.opcode == "fusion" for c in inst.called}
    assert fused and not fused & set(timeline)
    assert not {inst.name for c in fused for inst in computations[c]} & set(table)
    # the record is what a ledger line holds
    assert 0 <= record["seconds"] < 30
    assert len(json.dumps(record)) < scopes.INLINE_LIMIT_BYTES


def test_all_three_passes_and_the_named_scopes_occur(record):
    table = scopes.op_table(record)
    by = collections.Counter((chain[-1] if chain else "", which) for chain, which in table.values())
    assert {which for _, which in by} == set(scopes.PASSES)
    for scope in ("optimizer", "decoder/head_loss", "decoder/embed", "decoder/norm",
                  "decoder/attn_proj", "decoder/attn_sliding", "decoder/attn_full",
                  "decoder/moe/route", "decoder/moe/experts", "loss"):
        assert sum(n for (s, _), n in by.items() if s == scope), scope
    # the update is no part of a gradient; every layer is recomputed
    assert {w for (s, w) in by if s == "optimizer"} == {"forward"}
    assert by[("decoder/attn_sliding", "recompute")] and by[("decoder/moe/experts", "recompute")]
    # the projections lie inside their layer's attention scope
    chains = {chain for chain, _ in table.values()}
    assert ("decoder/attn_sliding", "decoder/attn_proj") in chains
    assert ("decoder/attn_full", "decoder/attn_proj") in chains
    # most of the program's ops are named
    unnamed = sum(n for (s, _), n in by.items() if not s)
    assert unnamed < 0.1 * len(table), (unnamed, len(table))


def test_a_fusion_that_mixes_scopes_is_listed(compiled, record):
    _, _, computations = scopes._parse(compiled.as_text())
    assert record["mixed"]
    callers = {inst.name: inst for insts in computations.values() for inst in insts}
    for name, found in record["mixed"].items():
        assert callers[name].opcode == "fusion" and len(found) > 1
        inside = []
        for fused in callers[name].called:
            scopes._fused_chains(fused, computations, inside)
        innermost = {chain[-1] for chain, _ in inside}
        assert {record["scopes"][i] for i in found} <= innermost


_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")


def _code(hlo_text: str) -> str:
    """An optimized module without what only describes it: each
    instruction's metadata, and the tables of files, functions and stack
    frames between the module's first line and its first computation."""
    lines = hlo_text.splitlines()
    first = next(i for i, line in enumerate(lines) if scopes._COMPUTATION.match(line))
    return _METADATA.sub("", "\n".join(lines[:1] + lines[first:]))


def test_scopes_change_nothing_but_metadata(compiled, monkeypatch):
    monkeypatch.setattr(decoder_lib, "REMAT_MIN_TOKENS", 1)
    monkeypatch.setattr(scopes, "scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()  # the attention's and the head's traces keep the names they were made under
    bare = _tiny_step_compiled().as_text()
    with_scopes = compiled.as_text()
    assert "decoder/head_loss" in with_scopes and "optimizer/" in with_scopes
    assert "decoder/" not in bare.replace("MoEDecoder", "") and "optimizer/" not in bare
    assert _code(bare) == _code(with_scopes)
    assert "dot(" in _code(bare) and "metadata" not in _code(bare)


# -- the compiler's own ops take a neighbour's scope -----------------------------------

HLO = """HloModule jit_step, is_scheduled=true

%fused_a (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(M)/decoder/norm/mul"}
}

%fused_b (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %n = f32[8]{0} negate(%p.1), metadata={op_name="jit(step)/jvp(M)/decoder/norm/neg"}
  ROOT %a = f32[8]{0} add(%n, %p.1), metadata={op_name="jit(step)/optimizer/add"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%t), index=1
  %e = f32[8]{0} exponential(%x), metadata={op_name="jit(step)/jvp(decoder/head_loss)/while/body/exp"}
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %r = (s32[], f32[8]{0}) tuple(%i, %e)
}

%cond (t.1: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %fusion.1 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_a
  %kernel.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", backend_config={"x":"{
}"}, metadata={op_name="jit(step)/transpose(jvp(M))/checkpoint/rematted_computation/decoder/attn_full/pallas_call"}
  %copy.2 = f32[8]{0} copy(%kernel.1)
  %z = s32[] constant(0)
  %copy.3 = f32[8]{0} copy(%a)
  %tup = (s32[], f32[8]{0}, f32[8]{0}) tuple(%z, %copy.2, %copy.3)
  %while.1 = (s32[], f32[8]{0}) while(%tup), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(decoder/head_loss)/while"}
  %g = f32[8]{0} get-tuple-element(%while.1), index=1
  %add.9 = f32[8]{0} add(%g, %g), metadata={op_name="jit(step)/jvp(M)/add"}
  ROOT %fusion.2 = f32[8]{0} fusion(%add.9), kind=kLoop, calls=%fused_b, metadata={op_name="jit(step)/optimizer/add"}
}
"""


def test_record_of_a_hand_written_module():
    record = scopes.describe_text(HLO)
    table = scopes.op_table(record)
    norm, attn, head = ("decoder/norm",), ("decoder/attn_full",), ("decoder/head_loss",)
    assert table == {
        # no metadata of their own and no scoped operand: what reads the buffer
        "copy-start.1": (norm, "forward"), "copy-done.1": (norm, "forward"),
        # a fusion with no metadata takes the one scope it holds
        "fusion.1": (norm, "forward"),
        # a kernel's text runs over several lines
        "kernel.1": (attn, "recompute"),
        # the compiler's copy of what the kernel wrote
        "copy.2": (attn, "recompute"),
        # a copy of an argument that only the loop reads, through a tuple that
        # also holds the kernel's buffer: the tuple says nothing, the loop does
        "copy.3": (head, "forward"),
        "while.1": (head, "forward"), "e": (head, "forward"),
        # the program's own op outside every scope stays unnamed
        "add.9": ((), "forward"),
        "fusion.2": (("optimizer",), "forward"),
    }
    assert record["mixed"] == {"fusion.2": sorted(
        record["scopes"].index(s) for s in ("decoder/norm", "optimizer"))}
    assert record["instructions"] == 10 and record["inherited"] == 4
    # the reduction: a container is skipped, an op the record lacks is unnamed
    times = [("fusion.1", 2.0), ("kernel.1", 4.0), ("while.1", 100.0), ("e", 3.0),
             ("add.9", 0.5), ("fusion.2", 1.0), ("fusion.77", 0.5)]
    by = scopes.by_scope([record], times, steps=2)
    assert by["scopes"] == {
        "decoder/attn_full": {"recompute": 2.0}, "decoder/head_loss": {"forward": 1.5},
        "decoder/norm": {"forward": 1.0}, "optimizer": {"forward": 0.5}}
    assert (by["unnamed_ms"], by["mixed_ms"], by["total_ms"]) == (0.5, 0.5, 5.5)
    assert by["per_step"] and by["programs"] == ["jit_step"]
    assert by["unnamed_frac"] == pytest.approx(1.0 / 11.0, abs=1e-4)


# -- through the trainers: one record a compiled program ---------------------------------

TINY_CLASSIFIER = dict(num_classes=5, input_shape=(16, 16), input_channels=3,
                       n_blocks=(1, 1, 1), base_depth=8, width_multiplier=0.125,
                       output_stride=None)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _fit(model_dir, **train_config):
    tcfg = TrainConfig(seed=0, train_log_every_steps=2, checkpoint_every_steps=100,
                       eval_throttle_secs=0, **train_config)
    return ClassifierTrainer(str(model_dir), None, ModelConfig(**TINY_CLASSIFIER), tcfg).fit(
        batch_size=8, steps=6)


def test_one_record_a_compiled_step_and_nothing_lowered_twice(tmp_path, monkeypatch):
    from jax import monitoring

    seconds = collections.Counter()  # by (event, thread): the loader's thread compiles too

    def on_duration(name, took, **_):
        if name in COMPILE_EVENTS:
            seconds[name, threading.get_ident()] += max(took, 1e-9)

    monitoring.register_event_duration_secs_listener(on_duration)
    during_describe = collections.Counter()
    real_records = scopes.Program.records

    def counting_records(self):
        before = collections.Counter(seconds)
        out = real_records(self)
        during_describe.update({name: took for (name, thread), took in (seconds - before).items()
                                if thread == threading.get_ident()})
        return out

    monkeypatch.setattr(scopes.Program, "records", counting_records)
    try:
        _fit(tmp_path / "on")
        assert len({name for name, _ in seconds}) == 3  # the listener hears this jax's compiles
        _fit(tmp_path / "off", telemetry=False)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    events = obs_lib.read_ledger(str(tmp_path / "on"))
    records = [e for e in events if e["event"] == scopes.PROGRAM_SCOPES_EVENT]
    # the classifier's step compiles once; its record follows first_step,
    # which still ends when step one retires
    assert len(records) == 1 and records[0]["program"] == "jit_step"
    kinds = [e["event"] + ":" + e.get("name", "") for e in events]
    assert kinds.index("program_scopes:") == kinds.index("startup_phase:first_step") + 1
    table = scopes.op_table(records[0])
    innermost = {chain[-1] for chain, _ in table.values() if chain}
    assert {"optimizer", "loss"} <= innermost
    # describing hit jit's caches: nothing lowered or compiled, and the trace
    # (whose event fires for a cached one too) took no time
    trace, lowering, backend = COMPILE_EVENTS
    assert lowering not in during_describe and backend not in during_describe, during_describe
    assert during_describe[trace] < 0.05, during_describe
    # the second run found the step compiled: no new program, and with no
    # telemetry no record and no ledger
    assert not os.path.exists(tmp_path / "off" / "telemetry.jsonl")


def test_a_later_run_of_the_process_gets_the_record_again(tmp_path):
    """A step compiled by an earlier run of the process is described once and
    written into each run's ledger."""
    _fit(tmp_path / "a")
    _fit(tmp_path / "b")
    for run in ("a", "b"):
        events = obs_lib.read_ledger(str(tmp_path / run))
        records = [e for e in events if e["event"] == scopes.PROGRAM_SCOPES_EVENT]
        assert len(records) == 1, run


def test_a_record_too_long_for_a_line_goes_beside_the_ledger(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "INLINE_LIMIT_BYTES", 1000)
    _fit(tmp_path)
    events = obs_lib.read_ledger(str(tmp_path))
    (event,) = [e for e in events if e["event"] == scopes.PROGRAM_SCOPES_EVENT]
    assert "ops" not in event and event["program"] == "jit_step" and event["instructions"]
    with open(tmp_path / event["file"], encoding="utf-8") as f:
        whole = json.load(f)
    assert len(scopes.op_table(whole)) == event["instructions"]
