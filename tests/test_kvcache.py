"""Mixed-precision KV cache: packing, plan schema v2, planner descent,
streamed decode, and the serving integration.

The load-bearing invariant everywhere: the packed digit-plane store is
BIT-IDENTICAL to quantize-then-dequantize ('qdq') attention — packing is
a lossless re-encoding of the quantization grid, so correctness is
settled by the quantizer alone and the packed path only changes bytes
moved.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import planner
from repro.core.plan import (KVCachePlan, LayerPlan, PrecisionPlan,
                             kv_cache_token_bytes, plan_footprint_report,
                             resolve_kv_bits, strip_kv)
from repro.nn import attention as attn
from repro.nn import kvcache


def _vals(rng, shape):
    return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)


class TestKVFormat:
    def test_fields(self):
        f = kvcache.KVFormat(4, 4, 64)
        assert (f.planes, f.digits_per_byte, f.packed_d) == (1, 2, 32)
        f = kvcache.KVFormat(8, 4, 64)
        assert (f.planes, f.packed_d) == (2, 32)
        f = kvcache.KVFormat(2, 2, 100)   # ragged head_dim
        assert f.packed_d == 25

    @pytest.mark.parametrize("bad", [(3, 2), (8, 3), (2, 4), (16, 4)])
    def test_invalid_rejected(self, bad):
        with pytest.raises(ValueError):
            kvcache.KVFormat(bad[0], bad[1], 64)

    def test_token_bytes(self):
        # w4k4 @ d=128: 64 packed bytes + 4 scale/zero bytes per head.
        f = kvcache.KVFormat(4, 4, 128)
        assert kvcache.kv_token_bytes(f, heads=8) == 8 * (64 + 4)


class TestPackUnpack:
    @pytest.mark.parametrize("bits,k", [(8, 4), (8, 8), (4, 4), (4, 2),
                                        (2, 2), (2, 1)])
    def test_unpack_equals_qdq(self, rng, bits, k):
        """pack -> unpack must reproduce qdq_kv BITWISE: the packed
        bytes are a re-encoding of the grid, not a second quantizer."""
        f = kvcache.KVFormat(bits, k, 48)
        x = _vals(rng, (2, 9, 3, 48))
        got = kvcache.unpack_kv(kvcache.pack_kv(x, f), f)
        want = kvcache.qdq_kv(x, f)
        assert got.dtype == want.dtype
        assert bool(jnp.all(got == want))

    def test_packed_leaf_layout(self, rng):
        f = kvcache.KVFormat(4, 4, 48)
        p = kvcache.pack_kv(_vals(rng, (2, 9, 3, 48)), f)
        assert p["p"].shape == (1, 2, 9, 3, 24) and p["p"].dtype == jnp.uint8
        assert p["s"].shape == (2, 9, 3) and p["s"].dtype == jnp.bfloat16
        assert p["z"].shape == (2, 9, 3)


class TestPlanSchemaV2:
    def _kv_plan(self, store="packed"):
        return PrecisionPlan(layers=(
            ("k", LayerPlan(w_bits=8, kv_bits=2)),
            ("v", LayerPlan(w_bits=8, kv_bits=4)),
        ), kv=KVCachePlan(k=4, store=store), name="t")

    def test_roundtrip(self, tmp_path):
        plan = self._kv_plan()
        path = tmp_path / "p.json"
        plan.save(path)
        obj = json.loads(path.read_text())
        assert obj["version"] == 2 and obj["kv"]["store"] == "packed"
        back = PrecisionPlan.load(path)
        assert back.kv_bits_for("k") == 2 and back.kv_bits_for("v") == 4
        assert back.kv_store() == "packed"

    def test_v1_with_kv_keys_rejected(self, tmp_path):
        obj = json.loads(json.dumps(self._kv_plan().to_json()))
        obj["version"] = 1
        path = tmp_path / "old.json"
        path.write_text(json.dumps(obj))
        with pytest.raises(ValueError, match="version"):
            PrecisionPlan.load(path)

    def test_default_may_not_carry_kv_bits(self):
        with pytest.raises(ValueError, match="default"):
            PrecisionPlan(default=LayerPlan(w_bits=8, kv_bits=4))

    def test_kv_bits_on_cacheless_arch_rejected(self):
        """Satellite: CNN plans must not claim a decode cache."""
        plan = dataclasses.replace(self._kv_plan(), arch="resnet18")
        api = configs.get("resnet18")
        with pytest.raises(ValueError, match="no decode KV cache"):
            plan.validate_kv(api.kv_layer_names(), arch="resnet18")

    def test_kv_bits_on_wrong_layer_rejected(self):
        plan = PrecisionPlan(layers=(
            ("mlp", LayerPlan(w_bits=8, kv_bits=4)),), name="bad")
        with pytest.raises(ValueError, match="no KV cache"):
            plan.validate_kv(["k", "v"])

    def test_resolve_and_slice(self):
        plan = self._kv_plan()
        assert resolve_kv_bits(plan, "k") == 2
        assert resolve_kv_bits(plan, "mlp") is None
        assert plan.kv_slice(2) == 2 and plan.kv_slice(8) == 4
        assert plan.distinct_kvbits() == (2, 4)

    def test_strip_kv(self):
        s = strip_kv(self._kv_plan())
        assert not s.kv_enabled() and s.kv is None
        # Weight formats untouched: scan grouping must not change.
        assert dict(s.layers)["k"].w_bits == 8

    def test_footprint_kv_math(self):
        plan = self._kv_plan()
        layer_params = {"k": 1000, "v": 1000, "mlp": 4000}
        classes = {n: "inner" for n in layer_params}
        kv_layers = {"k": (8, 128), "v": (8, 128)}
        rep = plan_footprint_report(layer_params, classes, plan,
                                    kv_layers=kv_layers, kv_tokens=1024)
        fp = 1024 * 2 * 8 * 128 * 2.0
        quant = 1024 * (kv_cache_token_bytes(2, 8, 128, slice_k=2)
                        + kv_cache_token_bytes(4, 8, 128, slice_k=4))
        assert rep["kv_fp16_bytes"] == pytest.approx(fp)
        assert rep["kv_quant_bytes"] == pytest.approx(quant)
        assert rep["kv_compression"] == pytest.approx(fp / quant)
        assert rep["total_quant_bytes"] == pytest.approx(
            rep["quant_bytes"] + quant)

    def test_footprint_requires_kv_layers_for_kv_plan(self):
        plan = self._kv_plan()
        with pytest.raises(ValueError):
            plan_footprint_report({"k": 10}, {"k": "inner"}, plan)

    def test_shipped_mixed_plan_compresses_4x(self):
        """The committed granite plan must deliver the headline >=4x
        KV-cache byte reduction at full scale."""
        plan = PrecisionPlan.load("examples/plans/granite_8b_mixed.json")
        api = configs.get("granite-8b")
        plan.validate_kv(api.kv_layer_names(), arch="granite-8b")
        gemms = api.gemm_workload(1)
        rep = plan_footprint_report(
            {g.name: g.k * g.n * g.count for g in gemms},
            {g.name: g.layer_class for g in gemms}, plan,
            kv_layers=api.kv_cache_workload(), kv_tokens=4096)
        assert rep["kv_compression"] >= 4.0


class TestPlannerKVDescent:
    def test_kv_sensitivity_shape(self, rng):
        vals = {"k": np.asarray(rng.normal(size=(64, 8, 16)), np.float32)}
        sens = planner.kv_cache_sensitivity(vals)
        assert set(sens) == {"k"}
        errs = [sens["k"][b] for b in (2, 4, 8, 16)]
        assert errs[-1] == 0.0                      # fp16 = no error
        assert errs[0] >= errs[1] >= errs[2]        # fewer bits, more err

    def test_latency_table_scales_with_bits(self):
        tab = planner.kv_decode_latency_table(
            {"k": (8, 128), "v": (8, 128)}, tokens=4096)
        assert tab["k"][16] > tab["k"][8] > tab["k"][4] > tab["k"][2]

    def test_plan_search_descends_kv(self):
        gemms = [planner.Gemm("a", 256, 144, 16),
                 planner.Gemm("b", 256, 144, 32)]
        sens = {n: {8: 0.0, 4: w, 2: 3 * w, 1: 10 * w}
                for n, w in (("a", 1.0), ("b", 5.0))}
        params = {g.name: g.k * g.n for g in gemms}
        res = planner.plan_search(
            gemms, sens, layer_params=params,
            kv_workload={"k": (8, 128), "v": (8, 128)},
            kv_tokens=4096)
        kv_pts = [p for p in res.points if p.plan.kv_enabled()]
        assert kv_pts, "joint search produced no kv-quantized points"
        deepest = min(kv_pts,
                      key=lambda p: min(p.plan.distinct_kvbits()))
        assert min(deepest.plan.distinct_kvbits()) <= 4
        # kv-quantized points must show the footprint win vs uniform fp-kv
        uni = next(p for p in res.points if p.name == "uniform_w8")
        if uni.footprint_bytes and deepest.footprint_bytes:
            assert deepest.footprint_bytes < uni.footprint_bytes


class TestStreamedDecode:
    def test_streamed_matches_materialized(self, rng):
        b, s, h, d = 2, 48, 4, 32
        q = _vals(rng, (b, 1, h, d))
        k = _vals(rng, (b, s, h, d))
        v = _vals(rng, (b, s, h, d))
        ln = jnp.asarray(37, jnp.int32)
        for window in (None, 9):
            o1 = attn.decode_attention(q, k, v, ln, window=window)
            o2 = attn.decode_attention_streamed(q, k, v, None, None, ln,
                                                window=window, chunk=16)
            np.testing.assert_allclose(np.asarray(o1, np.float32),
                                       np.asarray(o2, np.float32),
                                       rtol=2e-2, atol=2e-2)

    def test_streamed_packed_equals_qdq_bitwise(self, rng):
        b, s, h, kvh, d = 2, 48, 8, 2, 32
        q = _vals(rng, (b, 1, h, d))
        k = _vals(rng, (b, s, kvh, d))
        v = _vals(rng, (b, s, kvh, d))
        fk = kvcache.KVFormat(4, 4, d)
        fv = kvcache.KVFormat(2, 2, d)
        ln = jnp.asarray(37, jnp.int32)
        for window in (None, 9):
            op = attn.decode_attention_streamed(
                q, kvcache.pack_kv(k, fk), kvcache.pack_kv(v, fv),
                fk, fv, ln, window=window, chunk=16)
            oq = attn.decode_attention_streamed(
                q, kvcache.qdq_kv(k, fk), kvcache.qdq_kv(v, fv),
                None, None, ln, window=window, chunk=16)
            assert bool(jnp.all(op == oq))


def _mixed_kv_plan(store):
    return PrecisionPlan(layers=(
        ("k", LayerPlan(w_bits=8, kv_bits=8)),
        ("l1.k", LayerPlan(w_bits=8, kv_bits=2)),
        ("v", LayerPlan(w_bits=8, kv_bits=4)),
    ), kv=KVCachePlan(k=4, store=store), name=f"kv-{store}")


class TestServingIntegration:
    def test_generate_packed_equals_qdq(self, key):
        """THE tentpole invariant end to end: Generator prefill + decode
        over the packed store emits the same tokens as the qdq oracle
        store, on a mixed w8/w4/w2 KV plan with GQA."""
        from repro.runtime.serve import Generator, pack_for_serving
        api = configs.get("granite-8b", reduced=True)
        train = api.init_params(key, "train")
        toks = jnp.asarray(np.random.default_rng(1).integers(
            0, api.cfg.vocab, size=(2, 9)), jnp.int32)
        outs = []
        for store in ("packed", "qdq"):
            api_p = dataclasses.replace(api, policy=_mixed_kv_plan(store))
            gen = Generator(api_p, pack_for_serving(api_p, train),
                            max_len=48)
            outs.append(np.asarray(gen.generate(toks, 8)))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_packed_cache_specs_smaller(self):
        api = configs.get("granite-8b", reduced=True)
        api_p = dataclasses.replace(api, policy=_mixed_kv_plan("packed"))
        bytes_of = lambda specs: sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(specs))
        assert bytes_of(api_p.cache_specs(1, 64)) < \
            bytes_of(api.cache_specs(1, 64))

    def test_scheduler_stats_report_cache_bytes(self, key):
        from repro.runtime.scheduler import GenerateScheduler
        from repro.runtime.serve import Generator, pack_for_serving
        api = configs.get("granite-8b", reduced=True)
        train = api.init_params(key, "train")
        api_p = dataclasses.replace(api, policy=_mixed_kv_plan("packed"))
        gen = Generator(api_p, pack_for_serving(api_p, train))
        sched = GenerateScheduler(gen, max_len=32, slots=2)
        st = sched.stats()
        assert st["cache_bytes_per_slot"] > 0
        assert st["kv_cache_compression"] > 1.5
        assert st["resident_cache_bytes"] == 0  # nothing admitted yet
        # fp plan: packed == fp bytes, ratio exactly 1
        gen_fp = Generator(api, pack_for_serving(api, train))
        sched_fp = GenerateScheduler(gen_fp, max_len=32, slots=2)
        assert sched_fp.stats()["kv_cache_compression"] == pytest.approx(1.0)
