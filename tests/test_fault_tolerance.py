"""Fault tolerance: checkpoint atomicity, crash/restart bit-exactness,
elastic re-mesh restore (subprocess with a different device count), and the
EF-int8 compressed gradient sync."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt.manager import CheckpointManager
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models.model import build
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def tiny_trainer(tmp_path, total=24, ckpt_every=8):
    model = build(configs.reduced("stablelm-1.6b"))
    data = TokenPipeline(DataConfig(
        vocab_size=model.cfg.vocab_size, seq_len=16, global_batch=4,
    ))
    return Trainer(
        model, data,
        TrainerConfig(total_steps=total, ckpt_every=ckpt_every,
                      opt=AdamWConfig(lr=1e-3, warmup_steps=2)),
        str(tmp_path / "ckpt"),
    )


class TestCheckpointManager:
    def test_atomic_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
                "b": [jnp.ones(4), jnp.zeros((2, 2), jnp.int32)]}
        mgr.save(5, tree, {"note": "x"})
        restored, meta = mgr.restore(5, tree)
        assert meta["note"] == "x"
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            assert x.dtype == y.dtype

    def test_keep_last_prunes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        tree = {"a": jnp.zeros(3)}
        for s in (1, 2, 3, 4):
            mgr.save(s, tree)
        assert mgr.all_steps() == [3, 4]

    def test_tmp_dirs_ignored(self, tmp_path):
        """A crash mid-save leaves only a .tmp dir, which restore ignores."""
        mgr = CheckpointManager(str(tmp_path), keep_last=3)
        tree = {"a": jnp.zeros(3)}
        mgr.save(1, tree)
        os.makedirs(str(tmp_path / "step_00000002.tmp"))
        assert mgr.latest_step() == 1

    def test_incompatible_tree_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"a": jnp.zeros(3)})
        with pytest.raises(AssertionError):
            mgr.restore(1, {"a": jnp.zeros(3), "b": jnp.zeros(2)})

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        tree = {"a": jnp.arange(10_000).astype(jnp.float32)}
        mgr.save_async(7, tree)
        mgr.wait()
        restored, _ = mgr.restore(7, tree)
        np.testing.assert_array_equal(
            np.asarray(restored["a"]), np.asarray(tree["a"])
        )


class TestCrashRestart:
    def test_restart_is_bit_exact(self, tmp_path):
        # Uninterrupted reference run.
        ref = tiny_trainer(tmp_path / "ref", total=24)
        ref.init_or_restore()
        ref_losses = ref.fit()

        # Crashing run: dies at step 19 (after the step-16 checkpoint).
        crash = tiny_trainer(tmp_path / "crash", total=24)
        crash.init_or_restore()
        with pytest.raises(RuntimeError, match="injected failure"):
            crash.fit(fail_at_step=19)

        # Restarted run resumes from step 16 and must reproduce the
        # reference losses exactly (deterministic data + arithmetic).
        resumed = tiny_trainer(tmp_path / "crash", total=24)
        start = resumed.init_or_restore()
        assert start == 16
        resumed_losses = resumed.fit()
        np.testing.assert_allclose(
            resumed_losses, ref_losses[16:], rtol=0, atol=0
        )

    def test_restart_without_checkpoint_starts_fresh(self, tmp_path):
        t = tiny_trainer(tmp_path, total=4, ckpt_every=100)
        assert t.init_or_restore() == 0


SUBPROC_ELASTIC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    import sys, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.ckpt.manager import CheckpointManager

    mesh = jax.make_mesh({shape}, {axes})
    mgr = CheckpointManager(sys.argv[1])
    tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
    if sys.argv[2] == "save":
        sharded = jax.device_put(
            tree["w"], NamedSharding(mesh, P({spec})))
        mgr.save(1, {{"w": sharded}})
        print("SAVED")
    else:
        target = {{"w": jnp.zeros((8, 8), jnp.float32)}}
        sh = {{"w": NamedSharding(mesh, P({spec}))}}
        restored, _ = mgr.restore(1, target, shardings=sh)
        w = restored["w"]
        assert len(w.sharding.device_set) == {n}, w.sharding
        np.testing.assert_array_equal(
            np.asarray(w), np.arange(64, dtype=np.float32).reshape(8, 8))
        print("RESTORED_OK")
""")


class TestElasticRemesh:
    @pytest.mark.parametrize("save_n,restore_n", [(4, 8), (8, 2)])
    def test_restore_on_different_mesh(self, tmp_path, save_n, restore_n):
        """Save sharded on an N-device mesh, restore onto an M-device mesh —
        the elastic-scaling path (checkpoints are mesh-agnostic)."""
        env = dict(os.environ, PYTHONPATH=REPO_SRC)
        ck = str(tmp_path / "ck")

        def run(n, mode):
            code = SUBPROC_ELASTIC.format(
                n=n, shape=f"({n},)", axes="('data',)", spec="'data'"
            )
            return subprocess.run(
                [sys.executable, "-c", code, ck, mode],
                env=env, capture_output=True, text=True, timeout=300,
            )

        r = run(save_n, "save")
        assert "SAVED" in r.stdout, r.stderr
        r = run(restore_n, "restore")
        assert "RESTORED_OK" in r.stdout, r.stderr


SUBPROC_COMPRESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train.compression import ef_int8_psum

    mesh = jax.make_mesh((4,), ("pod",))
    gs = np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32)

    def step(g, e):
        return ef_int8_psum(g, e, "pod")

    f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P("pod"), P("pod")),
        out_specs=(P("pod"), P("pod")), check_vma=False))
    g = jax.device_put(jnp.asarray(gs), NamedSharding(mesh, P("pod")))
    err = jnp.zeros_like(g)

    # 1) single shot: compressed mean close to the true mean
    avg, err1 = f(g, err)
    true = gs.mean(0, keepdims=True)
    per_pod = np.asarray(avg).reshape(4, 64)
    for p in range(4):
        np.testing.assert_allclose(per_pod[p], true[0], atol=0.05)

    # 2) error feedback: summed over repeated steps the bias vanishes
    acc = np.zeros((4, 64), np.float32)
    e = err
    for _ in range(200):
        a, e = f(g, e)
        acc += np.asarray(a).reshape(4, 64)
    acc /= 200
    np.testing.assert_allclose(acc[0], true[0], atol=0.005)
    print("COMPRESS_OK")
""")


class TestGradCompression:
    def test_ef_int8_psum(self, tmp_path):
        env = dict(os.environ, PYTHONPATH=REPO_SRC)
        r = subprocess.run(
            [sys.executable, "-c", SUBPROC_COMPRESS],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert "COMPRESS_OK" in r.stdout, r.stderr
