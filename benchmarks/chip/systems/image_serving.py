"""The system under test for an image-classification configuration:
the program's ``ImageServer`` (``impl="auto"``) behind its
``ImageScheduler``, serving a packed mixed-precision CNN.

Weights are the benchmark's: the configuration's reference module draws
them from the seed (one jitted call), and the program packs them under
the configuration's plan (one more jitted call).  After the window the
same jitted draw runs again for the reference, bit for bit the same.
"""
from __future__ import annotations

import gc
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from loadgen import rng

# Reference layer-name suffix -> (program conv key, program BN key).
_CONV_KEYS = {"c1": ("conv1", "bn1"), "c2": ("conv2", "bn2"),
              "c3": ("conv3", "bn3"), "p": ("proj", "bn_proj")}


def prng_key(seed: int):
    """A JAX key from a seed of any size (beyond 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              (seed >> 31) % (2 ** 31))


def program_trees(weights):
    """Reference weights -> the program's (train params, BN state)."""
    params, state = {}, {}
    for name, ent in weights.items():
        q = {"w": ent["w"], "gw": ent["gw"], "ga": ent["ga"]}
        if name in ("stem", "fc"):
            params[name] = q
            conv_key, bn_key, blk = name, "bn_" + name, None
        else:
            blk, part = re.fullmatch(r"(s\d+b\d+)(c\d|p)", name).groups()
            conv_key, bn_key = _CONV_KEYS[part]
            params.setdefault(blk, {})[conv_key] = q
        if "bn" in ent:
            bn = ent["bn"]
            p = {"scale": bn["gamma"], "bias": bn["beta"]}
            s = {"mean": bn["mean"], "var": bn["var"]}
            if blk is None:
                params[bn_key], state[bn_key] = p, s
            else:
                params[blk][bn_key] = p
                state.setdefault(blk, {})[bn_key] = s
    return params, state


def make_pool(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` distinct images from the seed, as the requests carry them:
    float32 (n, H, W, 3) Gaussian pixels at the file's mean and spread,
    drawn on the host (a pool drawn on the device and copied back
    served at half the rate, measured on a v5e host)."""
    img, size = cfg["images"], cfg["img_size"]
    x = rng(seed, 3).standard_normal((n, size, size, 3), dtype=np.float32)
    return x * np.float32(img["std"]) + np.float32(img["mean"])


class System:
    """One configuration served on this process's first device."""

    def __init__(self, cfg: dict, cfg_dir: str, reference, seed: int,
                 traffic: dict, tracer=None):
        from repro import configs
        from repro.core.plan import PrecisionPlan
        from repro.runtime.serve import ImageServer

        self.cfg, self.reference, self.seed = cfg, reference, seed
        plan_path = os.path.join(cfg_dir, cfg["plan"])
        with open(plan_path) as f:
            self.plan_json = json.load(f)
        self.plan = PrecisionPlan.load(plan_path)
        # "smoke": the program's own small preset (tests on the CPU).
        api = configs.get(cfg["model"], policy=self.plan,
                          reduced=cfg.get("smoke", False))
        self._check_sizes(api.cfg)
        mod, pcfg, plan = api.mod, api.cfg, self.plan

        self._init = jax.jit(
            lambda key: reference.init_weights(cfg, self.plan_json, key))

        def pack(weights):
            params, state = program_trees(weights)
            return mod.pack_for_serve(pcfg, params, state, plan)

        packed = jax.jit(pack)(self._init(prng_key(seed)))
        self.server = ImageServer(api=api, params=jax.block_until_ready(packed),
                                  plan=plan, impl="auto",
                                  batch_buckets=tuple(traffic["buckets"]),
                                  tracer=tracer)

    def _check_sizes(self, pcfg) -> None:
        """The program's configuration must be the file's, number for
        number: the benchmark never serves a size it did not state."""
        want = {"depth": self.cfg["depth"], "img_size": self.cfg["img_size"],
                "n_classes": self.cfg["n_classes"], "width": self.cfg["width"],
                "stages": tuple(self.cfg["stages"]), "block": self.cfg["block"]}
        got = {k: getattr(pcfg, k) for k in want}
        if got != want:
            raise SystemExit(f"program config {got} differs from {want}")

    @property
    def image_shape(self):
        s = self.cfg["img_size"]
        return (s, s, 3)

    def warm(self, sizes) -> None:
        """Serve one batch of every size the window will dispatch, so
        every program it runs is compiled (or loaded) before it opens."""
        for n in sizes:
            self.server.predict(np.zeros((n,) + self.image_shape, np.float32))

    def pool(self, n: int) -> np.ndarray:
        return make_pool(self.cfg, self.seed, n)

    def compiled_text(self, bucket: int) -> str:
        """The optimized program the window runs for one bucket."""
        fn = self.server._fn(bucket)
        x = jnp.zeros((bucket,) + self.image_shape, jnp.float32)
        return fn.lower(self.server.params, x).compile().as_text()

    def weights(self):
        """The float weights of this seed, drawn again on the device."""
        return self._init(prng_key(self.seed))

    def free(self) -> None:
        self.server = None
        gc.collect()

    def reference_logits(self, images: np.ndarray, act_dtype=jnp.bfloat16,
                         block: int = 32) -> np.ndarray:
        """The reference's logits for ``images``, in blocks of rows."""
        weights = self.weights()
        fwd = jax.jit(lambda w, x: self.reference.forward(
            self.cfg, self.plan_json, w, x, act_dtype=act_dtype))
        out = []
        for i in range(0, len(images), block):
            x = images[i:i + block]
            n = len(x)
            if n < block:
                x = np.concatenate([x, np.zeros((block - n,) + x.shape[1:],
                                                x.dtype)])
            out.append(np.asarray(fwd(weights, x))[:n])
        return np.concatenate(out)
