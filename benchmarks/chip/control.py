"""Readings that set a cell's correctness limit, and the faults it must
catch.  Not part of a benchmark run.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 2 \\
        --seeds 1 2 3 ... [--put control|altered|half]

Runs the cell once per seed in one process, with a short window at the
cell's own load, and prints each run's checks as one JSON line.  With
no ``--put`` the program serves (the lower reading of each number);
``--put control`` puts the plain reference in the program's place,
computed one precision step below the configuration's (float8 e4m3
activations between layers where it states bfloat16), which gives the
upper reading; ``altered`` and ``half`` break the timed path as the
tests do.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

# The nearest precision below each configured activation dtype.
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def put_control(system) -> None:
    """Serve with the reference, its activations one precision step below
    the configuration's."""
    import jax
    import jax.numpy as jnp

    dtype = getattr(jnp, LOWER[system.cfg["activation_dtype"]])
    weights = system.weights()
    fwd = jax.jit(lambda w, x: system.reference.forward(
        system.cfg, system.plan_json, w, x, act_dtype=dtype))
    system.server.predict = lambda images: np.asarray(
        fwd(weights, np.asarray(images)))


def put_altered(system) -> None:
    """Every answer altered where it is produced: one logit of each row
    moved by that row's spread over classes."""
    predict = system.server.predict

    def altered(images):
        y = np.array(predict(images), np.float32)
        y[:, 0] += y.std(axis=1)
        return y
    system.server.predict = altered


def put_half(system) -> None:
    """Half of each batch left out: the first half is served and its
    answers stand in for the rest."""
    predict = system.server.predict

    def half(images):
        n = len(images)
        y = np.asarray(predict(images[: -(-n // 2)]))
        return np.concatenate([y, y])[:n]
    system.server.predict = half


PUT = {"control": put_control, "altered": put_altered, "half": put_half}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--put", choices=sorted(PUT), default=None)
    args = ap.parse_args(argv)
    hook = PUT[args.put] if args.put else None
    for seed in args.seeds:
        r = harness.run(args.workload, seed, args.seconds, False, time.time(),
                        system_hook=hook)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "put": args.put or "program",
                          "correct": r["correct"], "checks": r["checks"],
                          "attempted": r["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
