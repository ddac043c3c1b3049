"""End-to-end drivers: train loop (with resume), serve loop, dry-run cell."""
import math
import os
import subprocess
import sys
import tempfile

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


def test_train_driver_loss_decreases():
    from repro.launch.train import main

    losses = main([
        "--arch", "olmo-1b", "--smoke", "--steps", "8", "--batch", "4",
        "--seq", "32", "--warmup", "2", "--lr", "3e-3", "--log-every", "4",
    ])
    assert losses[-1] < 6.5  # started ~ ln(512)=6.2+; must have moved down


def test_train_driver_resume_identical():
    from repro.launch.train import main

    with tempfile.TemporaryDirectory() as td:
        full = main([
            "--arch", "olmo-1b", "--smoke", "--steps", "6", "--batch", "2",
            "--seq", "32", "--warmup", "1", "--lr", "1e-3",
            "--checkpoint-dir", os.path.join(td, "a"), "--checkpoint-every", "3",
        ])[-1]
    with tempfile.TemporaryDirectory() as td:
        ckdir = os.path.join(td, "b")
        main([
            "--arch", "olmo-1b", "--smoke", "--steps", "3", "--total-steps", "6",
            "--batch", "2", "--seq", "32", "--warmup", "1", "--lr", "1e-3",
            "--checkpoint-dir", ckdir, "--checkpoint-every", "3",
        ])
        resumed = main([
            "--arch", "olmo-1b", "--smoke", "--steps", "6", "--batch", "2",
            "--seq", "32", "--warmup", "1", "--lr", "1e-3",
            "--checkpoint-dir", ckdir, "--checkpoint-every", "3",
        ])[-1]
    assert resumed == pytest.approx(full, abs=2e-3)


def test_train_main_trains_with_kernels_switched_on():
    """The Pallas kernels have no backward: train.main traces the jnp path
    even inside a caller's use_pallas(True), and hands the setting back."""
    from repro.kernels import pallas_enabled, use_pallas
    from repro.launch.train import main

    with use_pallas(True):
        losses = main([
            "--arch", "llama3.2-1b", "--smoke", "--steps", "2", "--batch", "2",
            "--seq", "32", "--warmup", "1",
        ])
        assert pallas_enabled()
    assert len(losses) == 2 and all(math.isfinite(x) for x in losses)


def test_serve_driver_runs():
    from repro.launch.serve import main

    gen = main([
        "--arch", "llama3.2-1b", "--smoke", "--batch", "2",
        "--prompt-len", "8", "--new-tokens", "4",
    ]).tokens
    assert gen.shape == (2, 4)
    assert (gen >= 0).all()


@pytest.mark.timeout(600)
def test_dryrun_one_cell_512_devices():
    """The 512-virtual-device path end-to-end on the cheapest cell."""
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env.pop("XLA_FLAGS", None)  # dryrun sets its own
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", "rwkv6-1.6b",
             "--shape", "long_500k", "--mesh", "multi", "--out", td],
            env=env, capture_output=True, text=True, timeout=550,
            cwd=os.path.join(HERE, ".."),
        )
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr[-2000:])
        assert proc.returncode == 0
        import json, glob

        rec = json.load(open(glob.glob(os.path.join(td, "*.json"))[0]))
        assert rec["ok"] is True
        assert rec["hlo_cost"]["dot_flops"] > 0
