import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) >= 8, "tests expect the 8-device CPU override from root conftest"
    return devices[:8]


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def make_salt_dataset(root, n_images=16, n_test=6, shape=(32, 32), seed=0):
    """Write a tiny TGS-salt-layout dataset: ``{root}/data/images+masks`` and
    ``{root}/test/images`` (uint8 PNGs; every third mask empty — the
    stratification edge case). Shared by the trainer end-to-end suites."""
    import os

    from PIL import Image

    root = str(root)
    data, test = os.path.join(root, "data"), os.path.join(root, "test")
    os.makedirs(os.path.join(data, "images"), exist_ok=True)
    os.makedirs(os.path.join(data, "masks"), exist_ok=True)
    os.makedirs(os.path.join(test, "images"), exist_ok=True)
    rng = np.random.default_rng(seed)
    ids = [f"im{i:02d}" for i in range(n_images)]
    for i, id_ in enumerate(ids):
        img = rng.uniform(0, 255, shape).astype(np.uint8)
        Image.fromarray(img).save(os.path.join(data, "images", f"{id_}.png"))
        mask = (
            np.zeros(shape)
            if i % 3 == 0
            else (rng.uniform(0, 1, shape) > 0.5) * 255
        ).astype(np.uint8)
        Image.fromarray(mask).save(os.path.join(data, "masks", f"{id_}.png"))
    for i in range(n_test):
        img = rng.uniform(0, 255, shape).astype(np.uint8)
        Image.fromarray(img).save(os.path.join(test, "images", f"t{i}.png"))
    return data, test, ids


# the (chain, pass) a made-up ``program_scopes`` record deals out in turn
SCOPE_RECORD_DEAL = [
    (("optimizer",), "forward"),
    (("decoder/attn_full", "decoder/attn_proj"), "recompute"),
    (("decoder/moe/experts",), "backward"),
    (("decoder/head_loss",), "forward"),
    (("loss",), "backward"),
    (("decoder/attn_sparse", "decoder/attn_sparse/indexer"), "forward"),
    ((), "forward"),
]


def make_scope_record(trace, needle="jit_step", leave_out=()):
    """A ``program_scopes`` record (obs/scopes.py) for a recorded trace: the
    op names inside the ``needle`` programs, sorted, take the entries of
    ``SCOPE_RECORD_DEAL`` in turn; ``while``s and ``conditional``s are the
    containers; names in ``leave_out`` get no entry."""
    from perfbench import xtrace
    from tensorflowdistributedlearning_tpu.obs import scopes

    texts = {xtrace.short_name(e[0]).lstrip("%"): e[0] for e in xtrace.ops_inside(trace, needle)}
    chains = [()]
    groups = {}
    for i, name in enumerate(sorted(n for n in texts if n not in leave_out)):
        chain, which = SCOPE_RECORD_DEAL[i % len(SCOPE_RECORD_DEAL)]
        if chain not in chains:
            chains.append(chain)
        groups.setdefault((chains.index(chain), scopes.PASSES.index(which)), []).append(name)
    return {
        "event": scopes.PROGRAM_SCOPES_EVENT, "program": needle,
        "scopes": list(scopes.SCOPES), "passes": list(scopes.PASSES),
        "chains": [[scopes.SCOPES.index(s) for s in chain] for chain in chains],
        "ops": [[c, p, names] for (c, p), names in sorted(groups.items())],
        "mixed": {},
        "containers": [n for n, text in texts.items()
                       if " while(" in text or " conditional(" in text],
        "instructions": sum(len(names) for names in groups.values()),
        "inherited": 0, "seconds": 0.0,
    }
