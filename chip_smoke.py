#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU.

    python3 chip_smoke.py                # one chip: deepseek-7b-1chip
    python3 chip_smoke.py --four-chips   # Router(replicas=4), one per chip

One chip: builds ``deepseek-7b-1chip`` (published widths, depth and rows
per table cut; configs/deepseek_7b.py) from a seed and serves a few
requests through ``launch.serve.run_once`` -> ``serving.serve()`` with
``pool="CXL"``, a hot-row cache and the Pallas miss-path gather. It then
checks on the chip that

  * every Engram fetcher ran the kernel, and its rows are bit-equal to
    ``jnp.take`` rows for the same ids over the full table;
  * the first decode logits of ``pool="CXL"`` and ``pool=None`` are
    equal to the bit, and a pooled run whose fetcher reads one row's
    neighbour instead (a planted fault) is not;
  * a steady decode wave runs under
    ``jax.transfer_guard_device_to_host("disallow")``.

Four chips: serves the requests from ``Router(replicas=4)``, checks the
four replicas hold their params and decode state on four distinct
devices, and that each replica's token streams equal one replica's
serving the same requests.

Lines starting ``smoke`` are smoke figures (one short run, compiles
included), not benchmark results. The last line is one JSON object,
``{"ok": true, "device": {...}}``. Any failed phase raises: the script
then exits nonzero and prints no result line, as it does when JAX finds
no TPU or the rest of the repo is missing.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "deepseek-7b-1chip"
SEED = 0
REQUESTS, MAX_NEW = 8, 16
MAX_BATCH, MAX_LEN = 16, 1024
CACHE_ROWS = 1 << 16


class SmokeFailure(RuntimeError):
    """A phase's check failed (raised, never caught: the run exits 1)."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _say(key: str, value) -> None:
    print(f"smoke {key}={value}", flush=True)


class CompileClock:
    """Sums the backend compile time JAX reports (cache hits are free)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def _hbm(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    limit = stats.get("bytes_limit", 0)
    return f"{peak / 1e9:.3f}GB/{limit / 1e9:.3f}GB"


def _prompts(cfg, n: int, seed: int) -> list:
    from repro.serving import Workload
    return [list(s.prompt) for s in
            Workload(requests=n, max_new=1, seed=seed).build(cfg.vocab_size)]


# ---------------------------------------------------------------- one chip

def serve_phase(cfg, params, *, gather: str, max_batch: int, max_len: int):
    """The served run: run_once -> serve(pool="CXL") with a hot-row cache."""
    from repro.launch.serve import run_once
    t0 = time.perf_counter()
    frontend, stats = run_once(cfg, requests=REQUESTS, max_new=MAX_NEW,
                               pool="CXL", params=params, seed=SEED,
                               cache_rows=CACHE_ROWS, max_batch=max_batch,
                               max_len=max_len, gather=gather)
    wall = time.perf_counter() - t0
    eng = frontend.engine
    _check(stats.requests_completed == REQUESTS,
           f"{stats.requests_completed} of {REQUESTS} requests completed")
    _check(stats.generated_tokens == REQUESTS * MAX_NEW,
           f"{stats.generated_tokens} tokens generated")
    for req in eng.done.values():
        _check(len(req.out) == MAX_NEW
               and all(0 <= t < cfg.vocab_size for t in req.out),
               f"request {req.rid} emitted {req.out}")
    impls = [f.impl for f in eng._fetchers]
    _check(eng.gather == gather and impls == [gather] * len(impls),
           f"fetchers ran {impls}, asked for {gather}")
    waves = stats.decode_steps + stats.prefill_waves
    _say("serve_wall_s", f"{wall:.3f}")
    _say("tokens_served", stats.generated_tokens)
    _say("d2h_pulls", stats.d2h_pulls)
    _say("waves", f"{waves} ({stats.prefill_waves} admission, "
         f"{stats.decode_steps} decode)")
    _say("d2h_pulls_per_wave", f"{stats.d2h_pulls / waves:.3f}")
    _say("gather", ",".join(f.impl for f in eng._fetchers))
    s = eng.store.stats()
    _say("store", f"{s.tier} hit_rate={s.hit_rate:.3f} waves={s.waves}")
    return frontend


def guard_phase(jax, frontend, cfg):
    """A steady decode wave under the "disallow" device->host guard: the
    wave's one sync goes through Engine._host, which allows it; any other
    pull raises."""
    eng = frontend.engine
    for p in _prompts(cfg, REQUESTS, seed=SEED + 1):
        frontend.submit(p, max_new=4)
    frontend.step()                      # admission + first decode wave
    _check(eng._next_keys is not None, "no steady wave to guard")
    steps, pulls = eng.stats.decode_steps, eng.stats.d2h_pulls
    with jax.transfer_guard_device_to_host("disallow"):
        frontend.step()
    _check(eng.stats.decode_steps == steps + 1, "guarded step ran no wave")
    _say("guarded_wave_d2h_pulls", eng.stats.d2h_pulls - pulls)
    _check(eng.stats.d2h_pulls - pulls == 1, "guarded wave pulled "
           f"{eng.stats.d2h_pulls - pulls} times from the device")
    frontend.drain()


def rows_phase(jax, cfg, params, *, interpret: bool = False):
    """Kernel rows == jnp.take rows, bit for bit, over the full table."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.engram_gather.ops import gather_rows_padded
    e = cfg.engram
    for j, layer in enumerate(params["engram"]["layers"]):
        tables = layer["tables"]
        n_rows = tables.shape[0] * tables.shape[1]
        rng = np.random.RandomState(SEED + j)
        gid = np.concatenate([[0, n_rows - 1],
                              rng.randint(0, n_rows, 4094)]).astype(np.int32)
        got = gather_rows_padded(tables, gid, width=e.head_dim,
                                 interpret=interpret)
        want = jax.jit(lambda t, g: jnp.take(
            t.reshape(n_rows, -1), g, axis=0)[:, :e.head_dim])(tables, gid)
        got, want = np.asarray(got), np.asarray(want)
        _check(got.shape == want.shape == (gid.size, e.head_dim)
               and np.array_equal(got.view(np.uint16), want.view(np.uint16)),
               f"layer {j}: kernel rows differ from jnp.take rows")
    _say("kernel_rows_bit_equal", f"{len(params['engram']['layers'])} "
         f"layers x {gid.size} rows")


def _plant_fault(fetcher) -> None:
    """Make ``fetcher`` read the row after the first row id of each wave
    (slot 0, first table): one mis-addressed row, as an off-by-one in the
    packed key -> row id path would give."""
    gid_for = fetcher.gid_for

    def shifted(keys):
        gid = gid_for(keys).copy()
        gid[0] = (gid[0] + 1) % (fetcher.T * fetcher.V)
        return gid

    fetcher.gid_for = shifted


def first_decode_logits(cfg, params, prompts, *, pool, gather: str,
                        max_batch: int, max_len: int, fault: bool = False):
    """Logits of the first decode wave of a fresh engine, live slots only;
    ``fault`` plants one wrong row in the pool miss-path gather."""
    import numpy as np
    from repro.models.transformer import RunFlags
    from repro.serving import EngramRuntime
    rt = EngramRuntime(cfg, params=params, pool=pool, gather=gather,
                       flags=RunFlags(attn_bf16_scores=True), seed=SEED,
                       max_batch=max_batch, max_len=max_len)
    eng = rt.engine
    if fault:
        _plant_fault(eng._fetchers[0])
    seen = []
    decode = eng._decode_ext

    def spy(*args):
        logits, state = decode(*args)
        seen.append(np.asarray(logits, np.float32))
        return logits, state

    eng._decode_ext = spy
    for p in prompts:
        rt.submit(p, max_new=2)
    rt.drain()
    _check(bool(seen), "no decode wave ran")
    return seen[0][:len(prompts)]


def logits_phase(cfg, params, *, gather: str, max_batch: int, max_len: int):
    """pool="CXL" feeds the decode step rows gathered by packed segment
    key; pool=None gathers them by n-gram index inside the model. The rows
    are bit-equal (rows_phase) and the decode program is the same, so the
    logits must be equal to the bit. The planted-fault run shows that one
    mis-addressed row is visible to this comparison."""
    import numpy as np
    prompts = _prompts(cfg, REQUESTS, seed=SEED + 2)
    kw = dict(gather=gather, max_batch=max_batch, max_len=max_len)
    runs = {}
    for name, pool, fault in (("pooled", "CXL", False), ("local", None, False),
                              ("fault", "CXL", True)):
        runs[name] = first_decode_logits(cfg, params, prompts, pool=pool,
                                         fault=fault, **kw)
        gc.collect()
    _check(all(np.isfinite(v).all() for v in runs.values()),
           "non-finite first-decode logits")
    local = runs["local"]
    diff = float(np.abs(runs["pooled"] - local).max())
    fault = float(np.abs(runs["fault"] - local).max())
    _say("logits_max_abs_diff", f"{diff!r} (max |logit| "
         f"{float(np.abs(local).max())!r}; planted fault {fault!r})")
    _check(diff == 0.0, "pool=CXL and pool=None logits differ")
    _check(fault > 0.0, "a mis-addressed row left the logits unchanged")


def one_chip(jax, cfg=None, *, gather: str = "kernel",
             interpret: bool = False, max_batch: int = MAX_BATCH,
             max_len: int = MAX_LEN) -> None:
    from repro.configs.base import get_config
    from repro.models.model import init_params
    cfg = cfg if cfg is not None else get_config(ARCH)
    device = jax.devices()[0]
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, SEED))
    _say("config", f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
         f"engram_layers={cfg.engram_layers()} "
         f"table_vocab={cfg.engram.table_vocab}")
    _say("init_params_s", f"{time.perf_counter() - t0:.3f} (compile "
         f"{clock.seconds:.3f} s, {clock.count} programs)")
    frontend = serve_phase(cfg, params, gather=gather, max_batch=max_batch,
                           max_len=max_len)
    _say("compile_s", f"{clock.seconds:.3f} ({clock.count} programs, "
         "init included)")
    t0 = time.perf_counter()
    guard_phase(jax, frontend, cfg)
    del frontend
    gc.collect()
    _say("guard_phase_s", f"{time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    rows_phase(jax, cfg, params, interpret=interpret)
    _say("rows_phase_s", f"{time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    logits_phase(cfg, params, gather=gather, max_batch=max_batch,
                 max_len=max_len)
    _say("logits_phase_s", f"{time.perf_counter() - t0:.3f}")
    _say("peak_hbm", _hbm(device))


# -------------------------------------------------------------- four chips

def _devices_of(tree) -> set:
    import jax
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def one_replica_streams(cfg, params, prompts, *, gather: str,
                        max_batch: int, max_len: int) -> list:
    """Token streams of ``prompts`` served, in this order, by one fresh
    replica built as ``run_once`` builds it (same store, flags, seed)."""
    from repro.launch.serve import with_store
    from repro.models.transformer import RunFlags
    from repro.serving import EngramRuntime
    rt = EngramRuntime(with_store(cfg, cache_rows=CACHE_ROWS), params=params,
                       pool="CXL", gather=gather,
                       flags=RunFlags(attn_bf16_scores=True), seed=SEED,
                       max_batch=max_batch, max_len=max_len)
    for p in prompts:
        rt.submit(p, max_new=MAX_NEW)
    rt.drain()
    return [r.out for r in sorted(rt.engine.done.values(),
                                  key=lambda r: r.rid)]


def four_chips(jax, cfg=None, *, gather: str = "kernel",
               max_batch: int = MAX_BATCH, max_len: int = MAX_LEN) -> None:
    """Router(replicas=4), one replica per chip, against one replica.

    Each replica's streams are compared with one replica serving the
    requests that replica was dispatched, in the same order: on a TPU an
    admission group of 8 prompts and one of 2 are different programs
    whose bf16 roundings differ, and random weights leave near-tied
    logits that such roundings flip. Equal groups make the comparison
    exact, so any difference is placement or cross-replica state."""
    from repro.configs.base import get_config
    from repro.launch.serve import run_once
    from repro.models.model import init_params
    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, got {devices}")
    cfg = cfg if cfg is not None else get_config(ARCH)
    params = init_params(cfg, SEED)
    kw = dict(gather=gather, max_batch=max_batch, max_len=max_len)
    t0 = time.perf_counter()
    router, stats = run_once(cfg, replicas=4, requests=REQUESTS,
                             max_new=MAX_NEW, pool="CXL", params=params,
                             seed=SEED, cache_rows=CACHE_ROWS, **kw)
    _say("four_replica_wall_s", f"{time.perf_counter() - t0:.3f}")
    shares = []
    for i, rt in enumerate(router.replicas):
        eng = rt.engine
        held = _devices_of(eng.params) | _devices_of(eng.state) \
            | _devices_of(eng.tokens)
        _check(held == {devices[i]}, f"replica {i} holds arrays on {held}")
        _check(eng.stats.prefills > 0, f"replica {i} served nothing")
        shares.append(sorted(eng.done.values(), key=lambda r: r.rid))
    _say("replica_devices", ",".join(str(d.id) for d in devices))
    _check(sum(map(len, shares)) == REQUESTS
           == stats.generated_tokens // MAX_NEW,
           f"{stats.generated_tokens} tokens for {REQUESTS} requests")
    _say("tokens_served", stats.generated_tokens)
    for d in devices:
        _say(f"peak_hbm_dev{d.id}", _hbm(d))
    del router
    gc.collect()
    t0 = time.perf_counter()
    for i, share in enumerate(shares):
        want = one_replica_streams(cfg, params, [r.prompt for r in share],
                                   **kw)
        _check(want == [r.out for r in share],
               f"replica {i}: token streams differ from one replica")
        gc.collect()
    _say("one_replica_wall_s", f"{time.perf_counter() - t0:.3f}")
    _say("streams_equal", f"{REQUESTS} requests x {MAX_NEW} tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router path")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found: run from a checkout "
              f"of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {device.platform}",
              file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    _say("compile_cache", enable_compile_cache())
    _say("devices", f"{len(jax.devices())} x {device.device_kind}")
    if args.four_chips:
        four_chips(jax)
    else:
        one_chip(jax)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
