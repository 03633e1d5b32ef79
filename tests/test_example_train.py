"""The shipped end-to-end example must actually run: train, checkpoint,
resume — as a real subprocess, the way a user would invoke it."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "examples", "train.py")
LM_SCRIPT = os.path.join(REPO, "examples", "train_lm.py")


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT] + args, cwd=cwd,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_example_trains_checkpoints_resumes(tmp_path):
    rng = np.random.default_rng(2)
    data = tmp_path / "d.libsvm"
    with open(data, "w") as f:
        for i in range(1200):
            x0 = rng.uniform(-1, 1)
            feats = " ".join([f"0:{x0:.4f}"] + [
                f"{j}:{rng.uniform(-1, 1):.4f}" for j in range(1, 5)])
            f.write(f"{1 if x0 > 0 else 0} {feats}\n")
    ckpt = str(tmp_path / "ckpt.bin")

    out = _run([str(data), "--epochs", "2", "--batch-rows", "256",
                "--checkpoint", ckpt], cwd=str(tmp_path))
    losses = [float(line.split("mean loss ")[1].split(" ")[0])
              for line in out.splitlines() if "mean loss" in line]
    assert len(losses) == 2 and losses[1] < losses[0], out
    assert os.path.exists(ckpt)
    # every run says where it ran, once, and ends in one summary line
    assert "device: platform=cpu device_kind='cpu' count=8 mesh=data=8" \
        in out
    summary = json.loads(out.splitlines()[-1].split("summary: ", 1)[1])
    assert summary["device"]["platform"] == "cpu"
    assert [e["rows"] for e in summary["epochs"]] == [1200, 1200]
    assert summary["epochs"][1]["new_shapes"] == 0
    assert all(len(ids) == 8 for ids in
               summary["first_batch_devices"].values())
    assert summary["compile"]["cache_dir"]

    # resume continues from epoch 2 (one more epoch only)
    out2 = _run([str(data), "--epochs", "3", "--batch-rows", "256",
                 "--resume", ckpt], cwd=str(tmp_path))
    lines = [line for line in out2.splitlines() if "mean loss" in line]
    assert len(lines) == 1 and lines[0].startswith("epoch 2:"), out2


def test_example_pairwise_over_shuffled_uri(tmp_path):
    rng = np.random.default_rng(3)
    data = tmp_path / "r.libsvm"
    with open(data, "w") as f:
        for q in range(60):
            x = rng.normal(size=(6, 4))
            rank = np.argsort(np.argsort(x[:, 0]))
            for d in range(6):
                feats = " ".join(f"{j}:{x[d, j]:.4f}" for j in range(4))
                f.write(f"{rank[d]} qid:{q} {feats}\n")
    out = _run([str(data) + "?shuffle_parts=4", "--objective", "pairwise",
                "--epochs", "2", "--batch-rows", "128"], cwd=str(tmp_path))
    assert out.count("mean loss") == 2


def test_example_trains_fm_on_libfm(tmp_path):
    """The FM path of the example over the libfm text lane end-to-end."""
    rng = np.random.default_rng(5)
    data = tmp_path / "f.libfm"
    with open(data, "w") as f:
        for i in range(600):
            x = rng.uniform(-1, 1, 4)
            y = 1 if x[0] * x[1] > 0 else 0
            toks = " ".join(f"{j % 2}:{j}:{x[j]:.4f}" for j in range(4))
            f.write(f"{y} {toks}\n")
    out = _run([str(data) + "?format=libfm", "--model", "fm",
                "--fm-rank", "4", "--epochs", "2", "--batch-rows", "128"],
               cwd=str(tmp_path))
    assert out.count("mean loss") == 2


def test_example_trains_on_crec_with_checkpoint(tmp_path):
    """The README quick-start journey: convert text once to CSR device
    planes, then train + checkpoint + resume over the .crec."""
    from dmlc_core_tpu.io.convert import rows_to_csr_recordio
    rng = np.random.default_rng(7)
    src = tmp_path / "j.libsvm"
    with open(src, "w") as f:
        for i in range(900):
            x0 = rng.uniform(-1, 1)
            feats = " ".join([f"0:{x0:.4f}"] + [
                f"{j}:{rng.uniform(-1, 1):.4f}" for j in range(1, 5)])
            f.write(f"{1 if x0 > 0 else 0} {feats}\n")
    crec = tmp_path / "j.crec"
    assert rows_to_csr_recordio(str(src), str(crec)) == 900
    ckpt = str(tmp_path / "c.bin")
    out = _run([str(crec), "--epochs", "2", "--batch-rows", "256",
                "--num-features", "5", "--checkpoint", ckpt],
               cwd=str(tmp_path))
    assert out.count("mean loss") == 2
    out2 = _run([str(crec), "--epochs", "3", "--batch-rows", "256",
                 "--num-features", "5", "--resume", ckpt],
                cwd=str(tmp_path))
    lines = [ln for ln in out2.splitlines() if "mean loss" in ln]
    assert len(lines) == 1 and lines[0].startswith("epoch 2:"), out2


def _run_lm(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, LM_SCRIPT] + args, cwd=cwd,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_lm_example_dp_sp_ring_attention(tmp_path):
    """The LM example's DP x SP lane trains (loss decreases) over an
    8-device virtual mesh with the sequence axis sharded — the runnable
    long-context journey."""
    corpus = tmp_path / "corpus.txt"
    corpus.write_bytes((b"the quick brown fox jumps over the lazy dog. "
                        * 400))
    out = _run_lm([str(corpus), "--mesh", "data=2,seq=4", "--seq", "256",
                   "--steps", "3", "--embed", "32", "--layers", "1"],
                  cwd=str(tmp_path))
    losses = [float(ln.rsplit(" ", 1)[1]) for ln in out.splitlines()
              if ln.startswith("step ")]
    assert len(losses) == 3 and losses[-1] < losses[0], out


def test_lm_example_dp_tp_moe(tmp_path):
    """The LM example's DP x TP + MoE lane trains on a data x model mesh.
    (The corpus must carry structure: uniform bytes sit at the ln(256)
    entropy floor and no model can reduce loss on them.)"""
    corpus = tmp_path / "corpus.txt"
    corpus.write_bytes(b"abcabcabc the rain in spain falls mainly. " * 400)
    out = _run_lm([str(corpus), "--model", "tp", "--mesh", "data=2,model=4",
                   "--seq", "64", "--steps", "3", "--embed", "32",
                   "--layers", "1"], cwd=str(tmp_path))
    losses = [float(ln.rsplit(" ", 1)[1]) for ln in out.splitlines()
              if ln.startswith("step ")]
    assert len(losses) == 3 and losses[-1] < losses[0], out


def test_lm_example_checkpoint_resume_matches_uninterrupted(tmp_path):
    """Checkpoint at step 2, resume, finish: the resumed run's remaining
    losses must equal the uninterrupted run's (params restored onto the
    mesh + the window sampler replayed to the cut point)."""
    corpus = tmp_path / "corpus.txt"
    corpus.write_bytes(b"to be or not to be that is the question. " * 300)
    common = [str(corpus), "--mesh", "data=2,seq=2", "--seq", "128",
              "--embed", "32", "--layers", "1"]

    base = _run_lm(common + ["--steps", "4"], cwd=str(tmp_path))
    base_losses = [ln for ln in base.splitlines() if ln.startswith("step ")]

    ckpt = str(tmp_path / "lm.ckpt")
    _run_lm(common + ["--steps", "2", "--checkpoint", ckpt,
                      "--ckpt-every", "2"], cwd=str(tmp_path))
    out = _run_lm(common + ["--steps", "4", "--resume", ckpt],
                  cwd=str(tmp_path))
    tail = [ln for ln in out.splitlines() if ln.startswith("step ")]
    assert [ln.split()[1] for ln in tail] == ["2:", "3:"], out
    assert tail == base_losses[2:], (tail, base_losses)
