#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 1,2,4,...

Builds the cell once (a replica cell as its router over one replica per
chip), then serves its traffic mix at each rate for a lead-in and
``--seconds`` and prints, per rate, what was due, finished and still
waiting at the close, and the tail of the time to first token.
A rate whose backlog at the close stays within the slots is sustained;
the cell's mix then offers about four fifths of the highest such rate.
Run on the chip, once, when a cell is defined; the benchmark's own runs
never call it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(run.ROOT), str(run.ROOT / "src")]
    import jax
    from chipbench import generator, weights
    from repro.launch.cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell, config, traffic = run.load_cell(args.workload)
    dep = config["deployment"]
    cfg = run.model_config(config)
    params = weights.program_params(cfg, args.seed, jax.devices()[0])
    rt, runtimes = run.build(cfg, params, dep)
    run.warm_up(runtimes, traffic, dep, cfg.vocab_size)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(traffic, arrivals=dict(traffic["arrivals"],
                                          rate_per_s=rate))
        reqs = generator.requests(mix, cfg.vocab_size, args.seed,
                                  args.seconds)
        recs = [run.Rec(r.arrival_s, r.prompt, r.max_new) for r in reqs]
        steps = []
        t0 = time.perf_counter()
        t_open = t0 + float(mix["lead_in_s"])
        t_close = t_open + args.seconds
        run.serve(rt, jax, recs, t0, t_open, t_close, steps)
        due = [r for r in recs if t0 + r.due < t_close]
        waiting = sum(1 for r in due if not r.finished)
        unstarted = sum(1 for r in due if not r.stamps)
        ttft = [(r.stamps[0] if r.stamps else t_close) - (t0 + r.due)
                for r in recs if t_open <= t0 + r.due < t_close]
        toks = sum(1 for r in recs for t in r.stamps if t_open <= t < t_close)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "waiting_at_close": waiting,
            "no_token_at_close": unstarted,
            "tokens_per_s": toks / args.seconds,
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "steps": len(steps)}), flush=True)
        rt.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
