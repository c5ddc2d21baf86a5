"""Dry-run path (subprocess: 512 fake devices), trainer integration,
crash/restart fault tolerance."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).parent.parent
ENV = dict(os.environ, PYTHONPATH=str(REPO / "src"))


def _run(args, timeout=420):
    return subprocess.run([sys.executable, *args], cwd=REPO, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("arch,shape", [
    ("smollm-360m", "train_4k"),
    ("jamba-1.5-large-398b", "decode_32k"),
])
def test_dryrun_smoke_multipod(arch, shape, tmp_path):
    """Smoke configs on the REAL 512-device multi-pod mesh: proves the
    sharding config lowers+compiles per (arch, shape, mesh)."""
    r = _run(["-m", "repro.launch.dryrun", "--arch", arch, "--shape",
              shape, "--mesh", "multi", "--smoke", "--out",
              str(tmp_path)])
    assert r.returncode == 0, r.stdout + r.stderr
    arts = list(tmp_path.glob("*.json"))
    assert len(arts) == 1
    info = json.loads(arts[0].read_text())
    assert info["n_chips"] == 512
    assert info["flops_per_device"] > 0
    assert info["collectives"]["count"] > 0


def test_trainer_loss_decreases(tmp_path):
    r = _run(["-m", "repro.launch.train", "--arch", "xlstm-125m",
              "--smoke", "--steps", "30", "--batch", "4", "--seq", "48",
              "--lr", "3e-3", "--log-every", "29"])
    assert r.returncode == 0, r.stdout + r.stderr
    lines = [l for l in r.stdout.splitlines() if "loss" in l]
    first = float(lines[0].split("loss")[1].split()[0])
    last = float(lines[-1].split("loss")[1].split()[0])
    assert last < first, f"loss did not decrease: {first} -> {last}"


def test_crash_restart_resumes_bitexact(tmp_path):
    """Kill training mid-run; resumed run must continue from the last
    durable checkpoint and end with the same loss as an uninterrupted run
    (deterministic pipeline + deterministic init)."""
    common = ["-m", "repro.launch.train", "--arch", "xlstm-125m",
              "--smoke", "--steps", "16", "--batch", "2", "--seq", "32",
              "--ckpt-every", "5", "--log-every", "1"]
    # uninterrupted reference
    r_ref = _run(common + ["--ckpt-dir", str(tmp_path / "ref")])
    assert r_ref.returncode == 0, r_ref.stderr
    ref_losses = {l.split()[2]: l.split()[4] for l in
                  r_ref.stdout.splitlines() if l.startswith("[train] step")}
    # crashed run + resume
    r1 = _run(common + ["--ckpt-dir", str(tmp_path / "cr"),
                        "--fail-at-step", "12"])
    assert r1.returncode == 42          # injected crash
    r2 = _run(common + ["--ckpt-dir", str(tmp_path / "cr")])
    assert r2.returncode == 0, r2.stderr
    assert "resuming from checkpoint step 10" in r2.stdout
    res_losses = {l.split()[2]: l.split()[4] for l in
                  r2.stdout.splitlines() if l.startswith("[train] step")}
    for step, loss in res_losses.items():
        assert abs(float(loss) - float(ref_losses[step])) < 5e-4, \
            f"step {step}: resumed {loss} != reference {ref_losses[step]}"


def test_mesh_and_param_shardings_resolve():
    """In-process sanity of the sharding resolution (1-device mesh)."""
    import jax
    from jax.sharding import AxisType
    from repro.configs import get_config
    from repro.launch import mesh as meshlib
    from repro.models.model import build_model
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    for arch in ("smollm_360m", "jamba_15_large", "whisper_base"):
        model = build_model(get_config(arch, smoke=True))
        sh = meshlib.param_shardings(model, mesh)
        n_params = len(jax.tree.leaves(model.abstract_params()))
        assert len(jax.tree.leaves(sh)) == n_params
