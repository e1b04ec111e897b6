"""End-to-end: SWIRL-planned training loop (smoke config) — loss decreases,
checkpoints resume, compression on/off agree."""

import numpy as np
import pytest

from repro.launch.train import train


@pytest.fixture(scope="module")
def short_run(tmp_path_factory):
    d = tmp_path_factory.mktemp("ckpt")
    out = train(
        "llama3.2-3b", smoke=True, steps=8, n_pods=2,
        global_batch=4, seq_len=32, ckpt_dir=str(d), log_every=100,
    )
    return d, out


def test_loss_decreases(short_run):
    _, out = short_run
    losses = [float(h["loss"]) for h in out["history"]]
    # short smoke run: not monotone step-to-step, but training must make
    # net progress past warmup
    assert min(losses[3:]) < losses[0]


def test_checkpoint_written_and_resumes(short_run):
    d, out = short_run
    from repro.ckpt import latest_step

    assert latest_step(d) is not None
    # resume continues from the saved step
    out2 = train(
        "llama3.2-3b", smoke=True, steps=2, n_pods=2,
        global_batch=4, seq_len=32, ckpt_dir=str(d), log_every=100,
    )
    assert len(out2["history"]) == 2


def test_pods_agree_with_single_pod():
    """2-pod SWIRL plan ≡ 1-pod plan (data-parallel correctness): the
    *parameters* after the same number of steps must match — the logged
    loss is the mean of the pods' losses, whose router aux terms are
    per pod, so it legitimately differs.  Compression disabled (int8 adds
    tiny per-pod noise)."""
    import jax

    a = train(
        "llama3.2-3b", smoke=True, steps=3, n_pods=1,
        global_batch=4, seq_len=32, ckpt_dir=None, log_every=100,
        compress_grads=False,
    )
    b = train(
        "llama3.2-3b", smoke=True, steps=3, n_pods=2,
        global_batch=4, seq_len=32, ckpt_dir=None, log_every=100,
        compress_grads=False,
    )
    diffs = jax.tree.map(
        lambda x, y: float(np.max(np.abs(np.asarray(x, np.float32) - np.asarray(y, np.float32)))),
        a["params"], b["params"],
    )
    assert max(jax.tree.leaves(diffs)) < 1e-5


def test_compressed_training_tracks_uncompressed():
    a = train(
        "llama3.2-3b", smoke=True, steps=6, n_pods=2,
        global_batch=4, seq_len=32, ckpt_dir=None, log_every=100,
        compress_grads=False,
    )
    b = train(
        "llama3.2-3b", smoke=True, steps=6, n_pods=2,
        global_batch=4, seq_len=32, ckpt_dir=None, log_every=100,
        compress_grads=True,
    )
    la = float(a["history"][-1]["loss"])
    lb = float(b["history"][-1]["loss"])
    assert abs(la - lb) / la < 0.05  # int8+EF stays close
