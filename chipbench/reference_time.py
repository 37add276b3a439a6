#!/usr/bin/env python3
"""Time a configuration's plain reference at its own widths, as a run's
check drives it.

    python3 chipbench/reference_time.py --config <file> --seed <n>

Makes 4 requests of seeded random tokens (a prompt of 384 and its 128
served tokens each: 512 tokens compared, as a chat cell's check
samples), pads each to the deployment's ``max_len`` as
``check.widest_gap`` does, and runs the reference's ``final_hidden`` and
``head`` over them, in float32 and in the control precision
(``check.CONTROL``), twice each: the first pass compiles, the second
finds every program compiled. Prints one JSON line: the seconds of each
pass, the tokens compared, and the device's peak memory after all four.
Exits 2, with no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def time_reference(config: dict, seed: int, requests: int = 4,
                   prompt: int = 384, output: int = 128) -> dict:
    """Seconds of each reference pass over the requests, by precision."""
    import numpy as np
    from chipbench import check
    length = config["deployment"]["max_len"]
    if prompt + output > length:
        raise ValueError(f"prompt + output {prompt + output} > max_len "
                         f"{length}")
    rng = np.random.default_rng(seed)
    V = config["vocab_size"]
    picked = [(list(rng.integers(1, V, prompt)),
               list(rng.integers(1, V, output))) for _ in range(requests)]
    out = {"tokens_compared": requests * output, "length": length}
    for prec in ("float32", check.CONTROL[config["torch_dtype"]]):
        passes = []
        for _ in range(2):
            t = time.perf_counter()
            model = check.reference(config, seed, prec)
            h, served = check._served_hidden(model, picked, length)
            model.head(h, served, length)         # returns host arrays
            passes.append(time.perf_counter() - t)
            del model, h
        out[f"reference_s.{prec}"] = passes
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"reference_time: needs a TPU; JAX found {device.platform}",
              file=sys.stderr)
        return 2
    from chipbench import loader
    config = loader.read(args.config, ROOT)
    out = time_reference(config, args.seed)
    out["config"] = config["name"]
    out["device"] = {"platform": device.platform,
                     "kind": device.device_kind,
                     "memory_peak_bytes":
                         device.memory_stats()["peak_bytes_in_use"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
