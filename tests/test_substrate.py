"""Substrate tests: data pipeline, checkpointing (+restart +re-mesh),
trainer fault tolerance, optimizer; the serving-engine tests moved to
tests/test_serving.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import Checkpointer
from repro.configs import get_config, reduced_config
from repro.data import DataConfig, Pipeline, for_model
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.training import (StragglerPolicy, Trainer, TrainerConfig,
                            simple_train_step)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced_config(get_config("gemma-2b"))
    m = build_model(cfg)
    params = m.init(KEY)
    return cfg, m, params


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
class TestPipeline:
    def test_deterministic_by_step(self):
        p = Pipeline(DataConfig(vocab=100, batch=4, seq_len=16, seed=7))
        a = p.batch_at(3)
        b = p.batch_at(3)
        np.testing.assert_array_equal(a["inputs"], b["inputs"])
        c = p.batch_at(4)
        assert not np.array_equal(a["inputs"], c["inputs"])

    def test_targets_are_shifted_inputs(self):
        p = Pipeline(DataConfig(vocab=100, batch=2, seq_len=8))
        b = p.batch_at(0)
        assert b["inputs"].shape == (2, 8)
        assert b["targets"].shape == (2, 8)

    def test_frontend_batches(self):
        p = Pipeline(DataConfig(vocab=100, batch=2, seq_len=8,
                                frontend="vision", frontend_len=2,
                                frontend_dim=16, d_model=32))
        b = p.batch_at(0)
        assert b["patch_embeddings"].shape == (2, 2, 16)
        assert b["inputs"].shape == (2, 6)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
class TestCheckpointer:
    def test_save_restore_roundtrip(self, tmp_path, small_model):
        _, m, params = small_model
        ck = Checkpointer(tmp_path, async_writes=False)
        ck.save(10, {"params": params})
        assert ck.latest_step() == 10
        restored = ck.restore(10, {"params": params})
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_async_and_retention(self, tmp_path, small_model):
        _, m, params = small_model
        ck = Checkpointer(tmp_path, keep=2, async_writes=True)
        for s in (1, 2, 3, 4):
            ck.save(s, {"p": params})
        ck.wait()
        assert ck.latest_step() == 4
        steps = sorted(int(p.name.split("_")[1])
                       for p in tmp_path.glob("step_*"))
        assert len(steps) <= 2 + 1  # retention (one in-flight tolerated)

    def test_restore_with_new_sharding(self, tmp_path, small_model):
        """Elastic re-mesh: restore onto explicit (1x1) mesh shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        _, m, params = small_model
        mesh = make_mesh((1,), ("data",))
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
        ck = Checkpointer(tmp_path, async_writes=False)
        ck.save(5, {"params": params})
        restored = ck.restore(5, {"params": params}, {"params": sh})
        leaf = jax.tree.leaves(restored["params"])[0]
        assert leaf.sharding.mesh.shape == {"data": 1}


# ---------------------------------------------------------------------------
# trainer: loss goes down, restart reproduces, stragglers detected
# ---------------------------------------------------------------------------
class TestTrainer:
    def _mk(self, tmp_path, small_model, total=12, hook=None):
        cfg, m, params = small_model
        ocfg = optim.AdamWConfig(learning_rate=3e-3, weight_decay=0.0)
        opt_state = optim.init(ocfg, params)
        step = simple_train_step(m, ocfg)
        pipe = for_model(cfg, batch=4, seq_len=16, seed=1)
        tc = TrainerConfig(total_steps=total, checkpoint_every=5,
                           log_every=4, checkpoint_dir=str(tmp_path),
                           async_checkpoint=False)
        return Trainer(m, step, params, opt_state, pipe, tc,
                       failure_hook=hook)

    def test_loss_decreases(self, tmp_path, small_model):
        tr = self._mk(tmp_path / "a", small_model, total=30)
        out = tr.run()
        first = out["history"][0]["loss"]
        last = out["final_loss"]
        assert last < first, (first, last)

    def test_crash_restart_resumes(self, tmp_path, small_model):
        crashed = {"done": False}

        def bomb(step):
            if step == 8 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("simulated node failure")

        tr = self._mk(tmp_path / "b", small_model, total=12, hook=bomb)
        with pytest.raises(RuntimeError):
            tr.run()
        # relaunch: new trainer restores from step 5 checkpoint
        tr2 = self._mk(tmp_path / "b", small_model, total=12)
        out = tr2.run()
        assert out["final_step"] == 12
        assert tr2.ckpt.latest_step() == 12

    def test_straggler_detection(self):
        pol = StragglerPolicy(warmup=3, k=3.0)
        for s in range(10):
            pol.observe(s, 0.1)
        assert pol.observe(10, 1.0) is True
        assert pol.flagged


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
class TestOptim:
    def test_adamw_converges_quadratic(self):
        ocfg = optim.AdamWConfig(learning_rate=0.1, weight_decay=0.0,
                                 clip_norm=None)
        params = {"w": jnp.array([5.0, -3.0])}
        state = optim.init(ocfg, params)
        upd = optim.update(ocfg)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state, _ = upd(grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 0.05

    def test_moment_dtype_bf16(self):
        ocfg = optim.AdamWConfig(moment_dtype="bfloat16")
        st = optim.init(ocfg, {"w": jnp.ones((4,))})
        assert st["mu"]["w"].dtype == jnp.bfloat16

    def test_int8_grad_compression_roundtrip(self):
        g = {"a": jax.random.normal(KEY, (64, 64)) * 0.01}
        q, s = optim.int8_compress_grads(g)
        back = optim.int8_decompress_grads(q, s)
        err = jnp.max(jnp.abs(back["a"] - g["a"]))
        assert float(err) < 0.01 / 127 * 2

    def test_cosine_schedule(self):
        sched = optim.cosine_schedule(1e-3, warmup=10, total=100)
        assert float(sched(jnp.asarray(5))) < 1e-3
        assert float(sched(jnp.asarray(10))) == pytest.approx(1e-3, rel=0.01)
        assert float(sched(jnp.asarray(100))) < 2e-4
