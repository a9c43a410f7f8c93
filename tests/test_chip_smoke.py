"""chip_smoke.py's contract, as far as a machine without a chip can hold it:
it refuses to run small on a CPU (non-zero exit, no result, nothing trained),
its parent never imports jax or the package — a parent that has touched jax
holds the chip its children need — and its ledger checks reject what they
exist to reject."""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _run(cwd, env_overrides, *args, script=SCRIPT):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_refuses_on_cpu_without_training_anything(tmp_path):
    out_dir = tmp_path / "out"
    res = _run(REPO, {"JAX_PLATFORMS": "cpu"}, "--out", str(out_dir))
    assert res.returncode == chip_smoke.EXIT_NO_ACCELERATOR != 0
    assert res.stdout == ""  # no result, not even a phase line
    assert "does not run small on a CPU" in res.stderr
    assert not out_dir.exists()  # refused before anything was written


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo: the
    probe child cannot import the program, so there is nothing to smoke."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    res = _run(
        str(tmp_path), {"PYTHONPATH": ""}, script=str(tmp_path / "chip_smoke.py")
    )
    assert res.returncode != 0
    assert res.stdout == ""


def test_parent_imports_neither_jax_nor_the_package():
    """Importing the script and parsing its arguments pulls in the standard
    library only: jax and the package load inside the children."""
    probe = (
        "import sys, chip_smoke\n"
        "loaded = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'flax', 'numpy', 'tensorflowdistributedlearning_tpu')]\n"
        "print(loaded)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", probe], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
    # and statically: no module-level import of either
    tree = ast.parse(open(SCRIPT).read())
    top_level = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top_level |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            top_level.add((node.module or "").split(".")[0])
    assert top_level <= set(sys.stdlib_module_names) | {"__future__"}


def test_a_probe_that_finds_no_tpu_ends_the_run_with_no_result(
    monkeypatch, tmp_path, capsys
):
    def no_tpu(out_dir):
        raise chip_smoke.PhaseFailed("probe: exit code 2")

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(chip_smoke, "phase_probe", no_tpu)
    rc = chip_smoke.main(["--out", str(tmp_path / "out")])
    assert rc == chip_smoke.EXIT_NO_ACCELERATOR
    assert capsys.readouterr().out == ""


def test_a_failed_phase_fails_the_run_and_the_last_line_says_so(
    monkeypatch, tmp_path, capsys
):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        chip_smoke, "phase_probe", lambda out_dir: {"device": dict(TPU)}
    )

    def train_fails(out_dir, device):
        raise chip_smoke.PhaseFailed("train: fold 0 loss did not fall")

    monkeypatch.setattr(chip_smoke, "phase_train", train_fails)
    for name in ("placement", "kernels"):
        monkeypatch.setattr(
            chip_smoke, f"phase_{name}", lambda out_dir, device: {}
        )
    rc = chip_smoke.main(["--out", str(tmp_path / "out")])
    assert rc == chip_smoke.EXIT_FAILED
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert lines[-1]["ok"] is False
    # the phases that need the artifact did not run; the others still did
    assert lines[-1]["failed"] == ["train", "serve", "serve_again", "fleet"]
    by_phase = {l["phase"]: l for l in lines if "phase" in l}
    assert by_phase["kernels"]["ok"] and by_phase["placement"]["ok"]
    # every phase line names where it ran
    assert all(
        l["platform"] == "tpu" and l["device_kind"] == "TPU v5 lite"
        and l["n_devices"] == 1
        for l in by_phase.values()
    )


def _header(**fingerprint):
    return {"event": "run_header", "fingerprint": fingerprint}


def test_check_header_rejects_a_ledger_from_another_device():
    good = _header(platform="tpu", device_kind="TPU v5 lite", n_devices=1)
    assert chip_smoke.check_header([good], "train", TPU) is good
    for bad in (
        _header(platform="cpu", device_kind="cpu", n_devices=8),
        _header(platform="tpu", device_kind="TPU v5 lite", n_devices=4),
        {"event": "run_header", "controller": True},
    ):
        with pytest.raises(chip_smoke.PhaseFailed, match="fingerprint"):
            chip_smoke.check_header([bad], "train", TPU)


def test_check_no_recompiles_flags_a_compile_after_warm_up():
    end = {"event": "run_end"}
    warm = {"event": "compile", "post_warmup": False, "duration_s": 60.0}
    loaded = {"event": "compile", "post_warmup": True, "cache_hit": True}
    assert chip_smoke.check_no_recompiles([warm, loaded, end], "serve") is end
    late = {"event": "compile", "post_warmup": True, "duration_s": 2.0}
    with pytest.raises(chip_smoke.PhaseFailed, match="after warm-up"):
        chip_smoke.check_no_recompiles([warm, late, end], "serve")
    with pytest.raises(chip_smoke.PhaseFailed, match="interrupted"):
        chip_smoke.check_no_recompiles(
            [warm, {"event": "run_end", "interrupted": True}], "serve"
        )
    with pytest.raises(chip_smoke.PhaseFailed, match="no run_end"):
        chip_smoke.check_no_recompiles([warm], "serve")
