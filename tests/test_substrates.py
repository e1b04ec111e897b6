"""Data pipeline, optimizer, compression, checkpointing."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from conftest import given, settings, st

from repro.ckpt import async_save, latest_step, load_checkpoint, save_checkpoint
from repro.data import ShardedLoader, SyntheticLM
from repro.optim import (
    AdamWConfig,
    allreduce_mean,
    compress,
    compressed_bytes,
    decompress,
    decompress_mean,
)
from repro.optim import adamw
from repro.optim.zero import zero1_specs


class TestData:
    def test_deterministic(self):
        ds = SyntheticLM(vocab=100, seq_len=8, global_batch=4, seed=3)
        assert np.array_equal(
            ds.batch(7)["tokens"], ds.batch(7)["tokens"]
        )
        assert not np.array_equal(
            ds.batch(7)["tokens"], ds.batch(8)["tokens"]
        )

    def test_shards_partition_global_batch(self):
        ds = SyntheticLM(vocab=100, seq_len=8, global_batch=8)
        full = ds.batch(0, 0, 1)["tokens"]
        parts = [ds.batch(0, s, 4)["tokens"] for s in range(4)]
        got = np.concatenate(parts, axis=0)
        assert sorted(map(tuple, got)) == sorted(map(tuple, full))

    def test_labels_shifted(self):
        ds = SyntheticLM(vocab=100, seq_len=8, global_batch=2)
        b = ds.batch(0)
        assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_loader_prefetch(self):
        ds = SyntheticLM(vocab=100, seq_len=8, global_batch=4)
        ld = ShardedLoader(ds, shard=1, n_shards=2, start_step=5)
        s, b = next(ld)
        assert s == 5
        assert np.array_equal(b["tokens"], ds.batch(5, 1, 2)["tokens"])
        ld.close()

    def test_vocab_bounds(self):
        ds = SyntheticLM(vocab=17, seq_len=64, global_batch=4)
        b = ds.batch(0)
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 17


class TestAdamW:
    def test_converges_on_quadratic(self):
        cfg = AdamWConfig(lr=0.1, warmup_steps=5, total_steps=200,
                          weight_decay=0.0)
        params = {"w": jnp.array([5.0, -3.0])}
        state = adamw.init(params)
        loss = lambda p: jnp.sum(jnp.square(p["w"]))  # noqa: E731
        for _ in range(150):
            g = jax.grad(loss)(params)
            params, state, _ = adamw.update(cfg, g, state, params)
        assert float(loss(params)) < 1e-2

    def test_schedule_shape(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
        s = adamw.schedule
        assert float(s(cfg, jnp.array(0))) < 0.2
        assert abs(float(s(cfg, jnp.array(10))) - 1.0) < 1e-6
        assert abs(float(s(cfg, jnp.array(100))) - 0.1) < 1e-6

    def test_clipping(self):
        cfg = AdamWConfig(clip_norm=1.0)
        params = {"w": jnp.zeros(3)}
        state = adamw.init(params)
        g = {"w": jnp.array([100.0, 0, 0])}
        _, _, m = adamw.update(cfg, g, state, params)
        assert float(m["grad_norm"]) == pytest.approx(100.0)

    def test_mixed_precision_dtypes(self):
        params = {"w": jnp.ones((4,), jnp.bfloat16)}
        state = adamw.init(params)
        assert state.m["w"].dtype == jnp.float32
        g = {"w": jnp.ones((4,), jnp.bfloat16)}
        p2, s2, _ = adamw.update(AdamWConfig(), g, state, params)
        assert p2["w"].dtype == jnp.bfloat16


class TestCompression:
    def test_roundtrip_small_error(self):
        g = {"w": jnp.array([[0.5, -0.25, 0.125, 1.0]])}
        c, err = compress(g)
        deq = decompress(c)
        assert float(jnp.max(jnp.abs(deq["w"] - g["w"]))) < 1.0 / 127

    def test_error_feedback_telescopes(self):
        """Σ dequantised ≈ Σ true gradients (bias cancels via feedback)."""
        key = jax.random.key(0)
        true_sum = jnp.zeros(16)
        deq_sum = jnp.zeros(16)
        err = None
        for i in range(50):
            g = {"w": jax.random.normal(jax.random.fold_in(key, i), (16,))}
            c, err = compress(g, err)
            deq_sum = deq_sum + decompress(c)["w"]
            true_sum = true_sum + g["w"]
        # residual bounded by one quantisation step, NOT growing with steps
        assert float(jnp.max(jnp.abs(deq_sum - true_sum))) < 0.2

    def test_compression_ratio(self):
        g = {"w": jnp.zeros((128, 256), jnp.float32)}
        c, _ = compress(g)
        raw = 128 * 256 * 4
        assert compressed_bytes(c) < raw / 3

    def test_allreduce_mean(self):
        a = {"w": jnp.ones(4)}
        b = {"w": jnp.full((4,), 3.0)}
        m = allreduce_mean([a, b])
        assert np.allclose(np.asarray(m["w"]), 2.0)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
    def test_jitted_compress_matches_eager_formula(self, dtype):
        """A vector, a matrix and a stacked 3-D leaf (inside a tuple, as
        parameter trees hold them), with a nonzero error feedback."""
        ks = jax.random.split(jax.random.key(7), 9)
        rows = jnp.exp(jax.random.normal(ks[0], (4, 6, 1)))  # per-row ranges
        grads = {
            "vec": jax.random.normal(ks[1], (40,)),
            "mat": jax.random.normal(ks[2], (6, 24)) * rows[0],
            "blocks": (
                jax.random.normal(ks[3], (4, 6, 24)) * rows,
                jax.random.normal(ks[4], (4, 24)) * 1e-3,
            ),
        }
        grads = jax.tree.map(lambda g: g.astype(dtype), grads)
        error = jax.tree.map(
            lambda g, k: 0.01 * jax.random.normal(k, g.shape), grads,
            jax.tree.unflatten(jax.tree.structure(grads), list(ks[5:])),
        )
        want = [
            np.asarray(g, np.float32) + np.asarray(e)
            for g, e in zip(jax.tree.leaves(grads), jax.tree.leaves(error))
        ]
        c, new_error = compress(grads, error)  # error is donated
        assert all(e.is_deleted() for e in jax.tree.leaves(error))

        for corrected, q, s, ne in zip(
            want, *(jax.tree.leaves(t) for t in (c.q, c.scale, new_error))
        ):
            absmax = np.max(np.abs(corrected), axis=-1, keepdims=True)
            scale = np.maximum(absmax / np.float32(127.0), np.float32(1e-12))
            ratio = corrected / scale
            want_q = np.clip(np.round(ratio), -127, 127).astype(np.int8)
            want_err = corrected - want_q.astype(np.float32) * scale
            q = np.asarray(q)
            assert q.dtype == np.int8 and s.dtype == jnp.float32
            assert ne.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(s), scale, rtol=1e-6)
            # q may differ only where the ratio sits on a rounding tie
            off = q != want_q
            tie = np.abs(np.abs(ratio - np.trunc(ratio)) - 0.5) < 1e-4
            assert np.all(np.abs(q.astype(int) - want_q)[off] == 1)
            assert np.all(tie[off])
            tol = np.broadcast_to(4 * np.finfo(np.float32).eps * absmax, q.shape)
            np.testing.assert_allclose(
                np.asarray(ne)[~off], want_err[~off], rtol=0, atol=tol[~off].max()
            )

        # decompress each part and take the mean, apart and as one program:
        # the eager sum over parts
        parts = [c, compress(grads)[0], compress(grads, new_error)[0]]
        mean = allreduce_mean([decompress(p) for p in parts])
        fused = decompress_mean(allreduce_mean, parts)
        for i, (m, f) in enumerate(zip(jax.tree.leaves(mean), jax.tree.leaves(fused))):
            deq = [
                np.asarray(jax.tree.leaves(p.q)[i], np.float32)
                * np.asarray(jax.tree.leaves(p.scale)[i])
                for p in parts
            ]
            # round-off of a sum is relative to the sum of magnitudes
            bound = 4 * np.finfo(np.float32).eps * sum(np.abs(d) for d in deq) / 3
            for got in (m, f):
                err = np.abs(np.asarray(got) - (deq[0] + deq[1] + deq[2]) / np.float32(3))
                assert np.all(err <= bound), err.max()

    def test_compress_traces_once_per_tree_layout(self):
        from repro.optim.compress import TRACES

        g = {"w": (jnp.ones((3, 40)), jnp.ones(40, jnp.bfloat16))}
        before = TRACES["compress"]
        c, err = compress(g)  # zeros made outside: the same trace as below
        for _ in range(3):
            c, err = compress(g, err)
        assert TRACES["compress"] == before + 1
        d, m = TRACES["decompress"], TRACES["decompress_mean"]
        decompress(c), decompress(c)
        decompress_mean(allreduce_mean, [c, c]), decompress_mean(allreduce_mean, [c, c])
        assert TRACES["decompress"] == d + 1
        assert TRACES["decompress_mean"] == m + 1

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_quantisation_bounded(self, seed):
        g = {"w": jax.random.normal(jax.random.key(seed), (8, 8)) * 10}
        c, err = compress(g)
        step = jnp.max(jnp.abs(g["w"]), axis=-1, keepdims=True) / 127.0
        assert bool(jnp.all(jnp.abs(err["w"]) <= step + 1e-6))


class TestCheckpoint:
    def test_roundtrip_mixed_dtypes(self, tmp_path):
        tree = {
            "a": jnp.ones((3, 4), jnp.bfloat16),
            "b": {"c": jnp.arange(5), "d": (jnp.zeros(2), jnp.ones(2))},
        }
        save_checkpoint(tmp_path, 7, tree)
        back = load_checkpoint(tmp_path, 7, tree)
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert x.dtype == y.dtype
            assert np.allclose(
                np.asarray(x, np.float32), np.asarray(y, np.float32)
            )

    def test_latest_and_gc(self, tmp_path):
        tree = {"w": jnp.ones(2)}
        for s in (1, 2, 3, 4):
            save_checkpoint(tmp_path, s, tree, keep=2)
        assert latest_step(tmp_path) == 4
        kept = sorted(p.name for p in tmp_path.glob("step_*"))
        assert len(kept) == 2

    def test_interrupted_write_ignored(self, tmp_path):
        tree = {"w": jnp.ones(2)}
        save_checkpoint(tmp_path, 1, tree)
        (tmp_path / "step_000000099").mkdir()  # no manifest
        assert latest_step(tmp_path) == 1

    def test_async_save(self, tmp_path):
        tree = {"w": jnp.ones((64, 64))}
        saver = async_save(tmp_path, 3, tree)
        p = saver.wait(10)
        assert p.name == "step_000000003"
        back = load_checkpoint(tmp_path, 3, tree)
        assert np.allclose(np.asarray(back["w"]), 1.0)

    def test_missing_leaf_rejected(self, tmp_path):
        save_checkpoint(tmp_path, 1, {"w": jnp.ones(2)})
        with pytest.raises(KeyError):
            load_checkpoint(tmp_path, 1, {"w": jnp.ones(2), "extra": jnp.ones(1)})


class TestZeRO:
    def test_specs_add_data_axis(self):
        from jax.sharding import PartitionSpec as P

        specs = {"w": P(None, "model"), "b": P("model")}
        shapes = {"w": jax.ShapeDtypeStruct((128, 64), jnp.float32),
                  "b": jax.ShapeDtypeStruct((64,), jnp.float32)}
        z = zero1_specs(specs, shapes, data_axis="data", data_size=16)
        assert z["w"] == P("data", "model")
        assert z["b"] == P("model")  # 64 not divisible by 16 on a free dim? 64%16==0 → first dim taken
