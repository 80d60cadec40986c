"""Runtime integration: fault-tolerant trainer, checkpoints, data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import CheckpointStore
from repro.data.pipeline import SyntheticLM
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.runtime.train import TrainLoopConfig, Trainer


@pytest.fixture()
def api():
    a = configs.get("granite-8b", reduced=True)
    a.microbatches = 1
    return a


class TestCheckpointStore:
    def test_save_restore_roundtrip(self, tmp_path, key):
        store = CheckpointStore(str(tmp_path))
        tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
                "b": {"c": jnp.ones((4,), jnp.bfloat16)},
                "step": jnp.asarray(7, jnp.int32)}
        store.save(7, tree)
        step, back = store.restore(tree)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
        assert back["b"]["c"].dtype == np.asarray(tree["b"]["c"]).dtype

    def test_atomicity_latest_wins(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        for s in (1, 2, 3):
            store.save(s, {"x": jnp.full((2,), float(s))})
        assert store.latest_step() == 3
        _, back = store.restore({"x": jnp.zeros((2,))})
        np.testing.assert_array_equal(np.asarray(back["x"]), [3.0, 3.0])

    def test_gc_keeps_last_k(self, tmp_path):
        store = CheckpointStore(str(tmp_path), keep=2)
        for s in range(5):
            store.save(s, {"x": jnp.zeros(1)})
        assert store.all_steps() == [3, 4]

    def test_async_save(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        store.save(1, {"x": jnp.ones(8)}, blocking=False)
        store.wait()
        assert store.latest_step() == 1

    def test_shape_mismatch_raises(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        store.save(1, {"x": jnp.zeros((2,))})
        with pytest.raises(ValueError):
            store.restore({"x": jnp.zeros((3,))})

    def test_missing_leaf_raises(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        store.save(1, {"x": jnp.zeros((2,))})
        with pytest.raises(KeyError):
            store.restore({"x": jnp.zeros((2,)), "y": jnp.zeros((1,))})


class TestDataPipeline:
    def test_deterministic_skip_ahead(self):
        """batch_at(step) is pure in step: restart resumes identically."""
        p1 = SyntheticLM(vocab=100, seq_len=8, global_batch=4, seed=1)
        p2 = SyntheticLM(vocab=100, seq_len=8, global_batch=4, seed=1)
        for step in (0, 5, 17):
            b1, b2 = p1.batch_at(step), p2.batch_at(step)
            np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_different_steps_differ(self):
        p = SyntheticLM(vocab=100, seq_len=8, global_batch=4, seed=1)
        assert not np.array_equal(p.batch_at(0)["tokens"],
                                  p.batch_at(1)["tokens"])

    def test_labels_are_shifted_targets(self):
        p = SyntheticLM(vocab=100, seq_len=8, global_batch=4, seed=1)
        b = p.batch_at(0)
        assert b["tokens"].shape == b["labels"].shape
        assert b["labels"].max() < 100


class TestTrainerFaultTolerance:
    def _mk(self, api, tmp_path, total=6, every=2):
        pipe = SyntheticLM(vocab=api.cfg.vocab, seq_len=16, global_batch=4,
                           seed=0)
        mesh = mesh_lib.make_local_mesh()
        cfg = TrainLoopConfig(total_steps=total, ckpt_every=every,
                              ckpt_dir=str(tmp_path), log_every=100,
                              async_ckpt=False, peak_lr=1e-3)
        return Trainer(api, pipe, mesh, cfg)

    def test_run_and_losses_finite(self, api, tmp_path, key):
        trainer = self._mk(api, tmp_path)
        state, history = trainer.run(key)
        assert len(history) == 6
        assert all(np.isfinite(history))
        assert int(state["step"]) == 6

    def test_restart_resumes_from_checkpoint(self, api, tmp_path, key):
        """Kill after 6 steps; a fresh Trainer restores and continues —
        the node-failure / preemption recovery path."""
        t1 = self._mk(api, tmp_path, total=6)
        t1.run(key)
        t2 = self._mk(api, tmp_path, total=10)
        state, history = t2.run(key)
        assert int(state["step"]) == 10
        assert len(history) == 4  # only the remaining steps ran

    def test_restart_equivalence_exact(self, api, tmp_path, key):
        """10 straight steps == 6 steps + restart + 4 steps, bitwise on
        the loss trace (deterministic data + state restore)."""
        t_ab = self._mk(api, tmp_path / "ab", total=6)
        t_ab.run(key)
        t_ab2 = self._mk(api, tmp_path / "ab", total=10)
        _, hist_resumed = t_ab2.run(key)

        t_full = self._mk(api, tmp_path / "full", total=10)
        _, hist_full = t_full.run(key)
        np.testing.assert_allclose(hist_full[6:], hist_resumed, rtol=1e-5)

    def test_straggler_watchdog_fires(self, api, tmp_path, key):
        fired = []
        pipe = SyntheticLM(vocab=api.cfg.vocab, seq_len=16, global_batch=4,
                           seed=0)
        mesh = mesh_lib.make_local_mesh()
        cfg = TrainLoopConfig(total_steps=4, ckpt_every=100,
                              ckpt_dir=str(tmp_path), async_ckpt=False,
                              straggler_factor=0.0)  # every step "straggles"
        tr = Trainer(api, pipe, mesh, cfg,
                     straggler_hook=lambda s, dt: fired.append(s))
        tr.run(key)
        assert fired  # watchdog saw the slow steps


class TestGradAccumulation:
    def test_microbatch_equivalence(self, key):
        """mb=2 grad accumulation == mb=1 on the same global batch."""
        api1 = configs.get("granite-8b", reduced=True); api1.microbatches = 1
        api2 = configs.get("granite-8b", reduced=True); api2.microbatches = 2
        s1 = jax.jit(steps_lib.make_train_step(api1))
        s2 = jax.jit(steps_lib.make_train_step(api2))
        state1 = steps_lib.init_train_state(api1, key)
        state2 = jax.tree.map(lambda x: x, state1)
        batch = {"tokens": jnp.ones((4, 16), jnp.int32),
                 "labels": jnp.ones((4, 16), jnp.int32)}
        n1, m1 = s1(state1, batch)
        n2, m2 = s2(state2, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(n1["params"]),
                        jax.tree.leaves(n2["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-4, atol=2e-6)


class TestOptimizer:
    def test_bf16_moments_still_descend(self, key):
        import jax.numpy as jnp
        api = configs.get("granite-8b", reduced=True)
        api.microbatches = 1
        api.opt_dtype = jnp.bfloat16
        step = jax.jit(steps_lib.make_train_step(api, peak_lr=5e-3))
        state = steps_lib.init_train_state(api, key)
        assert jax.tree.leaves(state["opt"]["m"])[0].dtype == jnp.bfloat16
        b = {"tokens": jnp.ones((4, 16), jnp.int32),
             "labels": jnp.ones((4, 16), jnp.int32)}
        losses = []
        for _ in range(5):
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


class TestGradCompression:
    def test_int8_error_feedback_converges(self, key):
        """Compressed training still descends; residual state is carried."""
        import jax.numpy as jnp
        from repro import configs
        from repro.optim import compress_init
        api = configs.get("granite-8b", reduced=True)
        api.microbatches = 1
        step = jax.jit(steps_lib.make_train_step(api, peak_lr=5e-3,
                                                 grad_compression=True))
        state = steps_lib.init_train_state(api, key)
        state["gc"] = compress_init(state["params"])
        b = {"tokens": jnp.ones((4, 16), jnp.int32),
             "labels": jnp.ones((4, 16), jnp.int32)}
        losses = []
        for _ in range(5):
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_residual_bounds_quant_error(self):
        """|deq - (g + res_in)| <= scale/2 per element (error feedback)."""
        import jax.numpy as jnp
        import numpy as np
        from repro.optim.compress import compress_decompress, compress_init
        g = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1e-3, (64,)),
                              jnp.float32)}
        res = compress_init(g)
        deq, new_res = compress_decompress(g, res)
        scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
        err = np.abs(np.asarray(deq["w"]) - np.asarray(g["w"]))
        assert err.max() <= scale / 2 + 1e-9
        np.testing.assert_allclose(np.asarray(new_res["w"]),
                                   np.asarray(g["w"] - deq["w"]), atol=1e-9)


class TestElasticRestore:
    def test_restore_onto_different_sharding(self, api, tmp_path, key):
        """Elastic re-mesh: checkpoint saved under one sharding restores
        under another (the 512->256 chip restart path, at 1-device scale
        with distinct PartitionSpecs)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        state = steps_lib.init_train_state(api, key)
        store = CheckpointStore(str(tmp_path))
        store.save(3, state)
        mesh = mesh_lib.make_local_mesh()
        template = steps_lib.train_state_specs(api)
        shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), template,
            is_leaf=lambda x: hasattr(x, "shape"))
        step, back = store.restore(template, shardings=shardings)
        assert step == 3
        leaf = jax.tree.leaves(back["params"])[0]
        assert leaf.sharding == NamedSharding(mesh, P())
        a = jax.tree.leaves(state["params"])[0]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(leaf))


class TestCompileCache:
    """``flags.enable_compile_cache``: JAX_COMPILATION_CACHE_DIR wins,
    else one fixed, gitignored directory inside the checkout."""

    @pytest.mark.parametrize("given", [False, True], ids=["unset", "set"])
    def test_cache_directory(self, given, tmp_path, monkeypatch):
        from repro.core import flags
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        was = jax.config.jax_compilation_cache_dir
        if given:
            want = str(tmp_path / "cache")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        else:
            want = os.path.join(root, ".jax_cache")
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            assert flags.enable_compile_cache() == want
            assert flags.enable_compile_cache() == want  # fixed, not per call
            assert jax.config.jax_compilation_cache_dir == (
                was if given else want)
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
