#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --seconds 20

In one process, for each seed: weights from the seed, the cell's traffic
served through a fresh runtime of the cell for its lead-in and
``--seconds`` (the cell's own load and sizes, a replica cell through
its router), and the sample that ``run.py`` would compare; then, with
the program's state freed, the widest reference-logit gap of the served
tokens (the sound reading) and, for the control seeds, the gap of the
tokens that the float8 reference puts first at the same positions (the
control's reading). One JSON line per seed. The limit in the cell's
traffic file is set between the largest sound reading and the smallest
control reading. The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def readings(config: dict, traffic: dict, seeds: list, controls: set,
             seconds: float, device) -> list:
    """One dict per seed: its sound reading, and the control's."""
    import jax
    from chipbench import check, generator, weights
    dep = config["deployment"]
    cfg = run.model_config(config)
    rows = []
    for seed in seeds:
        params = weights.program_params(cfg, seed, device)
        rt, runtimes = run.build(cfg, params, dep)
        run.warm_up(runtimes, traffic, dep, cfg.vocab_size)
        reqs = generator.requests(traffic, cfg.vocab_size, seed, seconds)
        recs = [run.Rec(r.arrival_s, r.prompt, r.max_new) for r in reqs]
        t0 = time.perf_counter()
        t_open = t0 + float(traffic["lead_in_s"])
        run.serve(rt, jax, recs, t0, t_open, t_open + seconds, [])
        done = [r for r in recs if r.finished]
        picked = check.sample([(r.prompt, r.tokens) for r in done], seed,
                              traffic["check"]["tokens"],
                              [runtimes.index(r.handle.runtime)
                               for r in done])
        # the references run with the program's state freed
        del rt, runtimes, params, done, recs
        gc.collect()
        row = {"seed": seed, "requests": len(picked),
               "tokens": sum(len(o) for _, o in picked),
               "gap": check.widest_gap(config, seed, picked, dep["max_len"])}
        if seed in controls:
            row["control_gap"] = check.control_gap(config, seed, picked,
                                                   dep["max_len"])
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(run.ROOT), str(run.ROOT / "src")]
    import jax
    from repro.launch.cache import enable_compile_cache
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    _, config, traffic = run.load_cell(args.workload)
    readings(config, traffic, [int(s) for s in args.seeds.split(",")],
             {int(s) for s in args.control_seeds.split(",") if s},
             args.seconds, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
