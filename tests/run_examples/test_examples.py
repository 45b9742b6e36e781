"""Every example must run to completion on the virtual CPU mesh
(SURVEY §2 #51; ref ships examples/imagenet, examples/simple/distributed,
examples/dcgan as its primary user-facing surface)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(script, *args, timeout=420):
    # examples run on whatever backend JAX selects; the tests select the CPU
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@pytest.mark.slow
def test_simple_distributed():
    out = _run("simple_distributed.py")
    assert "DDP grad == global-batch grad: OK" in out
    assert "converged: OK" in out


@pytest.mark.slow
def test_imagenet_resnet50():
    out = _run("imagenet_resnet50.py", "--smoke")
    assert "(decreased)" in out
    assert "val: top1" in out


@pytest.mark.slow
def test_imagenet_resnet50_checkpoint_resume(tmp_path):
    """The ref main_amp.py --resume contract: save, resume from the
    latest epoch, keep training, evaluate-only from the checkpoint."""
    ckpt = str(tmp_path / "ckpt")
    _run("imagenet_resnet50.py", "--smoke", "--checkpoint-dir", ckpt,
         timeout=600)
    out = _run("imagenet_resnet50.py", "--smoke", "--epochs", "2",
               "--resume", "auto", "--checkpoint-dir", ckpt, timeout=600)
    assert "=> resumed from" in out and "epoch   1 " in out
    out = _run("imagenet_resnet50.py", "--smoke", "--evaluate",
               "--resume", "auto", "--checkpoint-dir", ckpt, timeout=600)
    assert "val: top1" in out


@pytest.mark.slow
def test_llama_train():
    out = _run("llama_train.py", "--steps", "4", "--fixed-data")
    assert "(decreased)" in out


@pytest.mark.slow
def test_llama_train_o4_fp8(tmp_path):
    """ISSUE 13 acceptance: --opt-level O4 runs end-to-end on CPU with
    finite loss, and the fp8 scaling state resumes from checkpoints
    (bit-identity is proved in-process by
    tests/run_resilience/test_fp8_roundtrip.py)."""
    ckpt = str(tmp_path / "ck")
    out = _run("llama_train.py", "--steps", "5", "--fixed-data",
               "--opt-level", "O4", "--checkpoint-dir", ckpt)
    assert "opt-level O4" in out
    assert "(decreased)" in out
    out = _run("llama_train.py", "--steps", "8", "--fixed-data",
               "--opt-level", "O4", "--checkpoint-dir", ckpt,
               "--resume")
    assert "=> resumed from step" in out
    assert "(decreased)" in out


@pytest.mark.slow
def test_dcgan():
    out = _run("dcgan.py", "--steps", "4")
    assert "ran to completion: OK" in out


@pytest.mark.slow
def test_bert_train():
    out = _run("bert_train.py", "--steps", "8")
    assert "(decreased)" in out


@pytest.mark.slow
def test_gpt2_train():
    out = _run("gpt2_train.py", "--steps", "8")
    assert "(decreased)" in out


@pytest.mark.slow
def test_moe_train():
    out = _run("moe_train.py", "--steps", "10")
    assert "(decreased)" in out


@pytest.mark.slow
def test_llama_train_checkpoint_resume(tmp_path):
    """Sharded 3D-parallel train state round-trips through orbax and the
    loss trajectory continues from the restored step."""
    ckpt = str(tmp_path / "ck")
    _run("llama_train.py", "--steps", "4", "--fixed-data",
         "--checkpoint-dir", ckpt)
    out = _run("llama_train.py", "--steps", "8", "--fixed-data",
               "--checkpoint-dir", ckpt, "--resume")
    assert "=> resumed from step 3" in out
    assert "(decreased)" in out


@pytest.mark.slow
def test_hf_finetune():
    pytest.importorskip("torch")
    pytest.importorskip("transformers")
    out = _run("hf_finetune.py", "--steps", "12")
    assert "imported llama" in out
    assert "(decreased)" in out
    assert "prompt " in out


@pytest.mark.slow
def test_long_context():
    out = _run("long_context.py", "--cp", "4", "--dp", "2",
               "--seq", "128", "--steps", "6")
    assert "parity: " in out and "OK" in out  # sharded == single-device
    assert "(decreased)" in out
