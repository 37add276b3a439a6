#!/usr/bin/env python3
"""Chip benchmark of the pooled Engram serving path.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips it is started on: builds
the cell's model (``chipbench/configs/<config>.json``, through
``chipbench/loader.py``) with weights made on the device from the seed,
serves the cell's open-loop traffic (``chipbench/traffic/<cell>.json``)
through one ``repro.serving.EngramRuntime``, or through a
``repro.serving.Router`` over one replica per chip where the deployment
states ``replicas``, for a lead-in and then a measured window of
``--seconds``, and checks what it served against the plain float32
reference (``chipbench/check.py``). ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces a few seconds of the window and
reports its per-layer metrics (``chipbench/metrics/<metric>.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``, each number compared beside its limit
(also the last lines of standard error). Exits 2, with no result, when JAX
finds no TPU, a chip kind without published peaks, fewer chips than the
cell asks for, or no program beside the benchmark.
"""
from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
TRACE_SECONDS = 6.0          # the traced part of a --trace 1 window


class Unavailable(RuntimeError):
    """The run cannot measure here (exit 2, no result line)."""


# ------------------------------------------------------------------ cells

def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of ``name`` in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    from chipbench import loader
    config = loader.read(configs[cell["config"]]["file"], root)
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metric_specs(name: str, kind: str, root: Path = ROOT) -> list[dict]:
    """BENCHMARK.json's ``end_to_end`` or ``per_layer`` metrics of cell
    ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return [m for m in bench[kind] if name in m.get("workloads", [name])]


def reader(metric: str):
    """``read(ctx)`` of ``chipbench/metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file: built by the
    program's loader (``repro.configs.from_file``) where it has one, by
    ``chipbench.loader`` otherwise, and checked against the file's sizes
    before any weight is made."""
    import repro.configs
    from chipbench import loader
    cfg = getattr(repro.configs, "from_file", loader.from_file)(c)
    loader.check(c, cfg)
    return cfg


def build(cfg, params, dep: dict):
    """The serving front the deployment states, and its runtimes: one
    ``EngramRuntime``, or a ``Router`` over ``dep["replicas"]`` of them,
    replica r on chip r, with the deployment's dispatch ``policy`` and
    ``shared_cache``."""
    from repro.serving import EngramRuntime, Router
    kw = dict(params=params, pool=dep["pool"], max_batch=dep["max_batch"],
              max_len=dep["max_len"], prompt_bucket=dep["prompt_bucket"])
    n = dep.get("replicas", 1)
    if n == 1:
        rt = EngramRuntime(cfg, **kw)
        return rt, [rt]
    router = Router(cfg, replicas=n, policy=dep["policy"],
                    shared_cache=dep["shared_cache"], **kw)
    return router, list(router.replicas)


def prompt_buckets(traffic: dict, bucket: int) -> list[int]:
    """Every prompt bucket the mix's lengths fall in (the engine pads a
    prompt to ``max(bucket, ceil(n / bucket) * bucket)``)."""
    lo = max(bucket, -(-traffic["prompt"]["min"] // bucket) * bucket)
    hi = max(bucket, -(-traffic["prompt"]["max"] // bucket) * bucket)
    return list(range(lo, hi + 1, bucket))


def group_sizes(max_batch: int) -> list[int]:
    """Every padded admission group size: powers of two to max_batch."""
    return [1 << k for k in range(max_batch.bit_length())
            if 1 << k <= max_batch]


# ---------------------------------------------------------------- records

@dataclasses.dataclass
class Rec:
    """One request as the client sees it (wall times, perf_counter)."""
    due: float                 # scheduled arrival
    prompt: tuple
    max_new: int
    submitted: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finished: bool = False
    handle: object = None      # the program's RequestHandle


@dataclasses.dataclass
class Step:
    """One ``step()``: what it admitted and what it decoded."""
    n: int
    prefill_lens: list         # prompt lengths admitted
    decode_ctx: list           # keys each decoded token attended


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    config: dict
    peaks: dict
    window: tuple              # (open, close), wall seconds
    requests: list             # Rec of every request submitted
    steps: list                # Step inside the window
    t_traffic: float           # wall time the traffic started
    counters: dict             # EngineStats counters over the window,
                               # summed over replicas
    setup_s: float
    peak_bytes: int
    trace: object = None       # chipbench.trace.Reduced (--trace 1)


class CompileCounter:
    """Counts executables compiled or loaded from the cache, and traces."""

    def __init__(self, jax):
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


def _counters(runtimes) -> dict:
    out = {}
    for rt in runtimes:
        for k, v in dataclasses.asdict(rt.stats).items():
            if isinstance(v, (int, float)):
                out[k] = out.get(k, 0) + v
    return out


# --------------------------------------------------------------- serving

def warm_up(runtimes, traffic: dict, dep: dict, vocab: int) -> int:
    """Run every admission shape the mix can produce (each prompt bucket
    by each padded group size) and the decode, key and sync programs once
    on every runtime (each replica's chip compiles its own). Returns the
    number of shapes."""
    shapes = [(S, n) for S in prompt_buckets(traffic, dep["prompt_bucket"])
              for n in group_sizes(dep["max_batch"])]
    for rt in runtimes:
        # the first wave takes the engine's freshly made state and tokens,
        # which jit keys apart from the state a program returned: served
        # waves all see the latter, so the first shape runs twice
        for S, n in [shapes[0]] + shapes:
            for r in range(n):
                rt.submit([(7919 * i + 104729 * r + S) % vocab
                           for i in range(S)], max_new=2)
            rt.drain()
    return len(shapes)


def serve(rt, jax, recs: list, t_traffic: float, t_open: float,
          t_close: float, steps: list, on_open=None) -> None:
    """The open loop: submit every request whose time has come, step the
    runtime while it is busy, sleep to the next arrival when it is not.
    Tokens are stamped when ``step()`` returns them."""
    ann = jax.profiler.TraceAnnotation
    by_rid = {}
    i, n = 0, len(recs)
    opened = False
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened = True
            if on_open is not None:
                on_open()
        if now >= t_close:
            return
        if i < n and t_traffic + recs[i].due <= now:
            with ann("chipbench.submit"):
                while i < n and t_traffic + recs[i].due <= now:
                    r = recs[i]
                    r.submitted = time.perf_counter()
                    r.handle = rt.submit(list(r.prompt), r.max_new)
                    by_rid[r.handle.rid] = r
                    i += 1
        if rt.busy:
            k = len(steps)
            with ann("chipbench.step", n=k):
                events = rt.step()
            t1 = time.perf_counter()
            pre, dec = [], []
            for ev in events:
                r = by_rid[ev.rid]
                r.stamps.append(t1)
                r.tokens.append(ev.token)
                r.finished = r.finished or ev.finished
                if ev.index == 0:
                    pre.append(len(r.prompt))
                else:
                    dec.append(len(r.prompt) + ev.index)
            steps.append(Step(k, pre, dec))
        else:
            nxt = t_traffic + recs[i].due if i < n else t_close
            if not opened:
                nxt = min(nxt, t_open)
            with ann("chipbench.sleep"):
                time.sleep(max(0.0, min(nxt, t_close) - now))


def run_cell(config: dict, traffic: dict, *, seed: int,
             seconds: float, trace: bool, peaks: dict, device,
             e2e: list, per_layer: list, t_process: float = _T_PROCESS,
             chips: int = 1) -> dict:
    """One run of one cell on the first ``chips`` devices, ``device``
    first: the result line's dict."""
    import jax

    from chipbench import check, generator, weights
    dep = config["deployment"]
    cfg = model_config(config)
    devices = [device] + [d for d in jax.devices() if d != device][
        :chips - 1]
    clock = CompileCounter(jax)
    t0 = time.perf_counter()
    params = jax.block_until_ready(weights.program_params(cfg, seed, device))
    t1 = time.perf_counter()
    rt, runtimes = build(cfg, params, dep)
    t2 = time.perf_counter()
    n_shapes = warm_up(runtimes, traffic, dep, cfg.vocab_size)
    print(f"chipbench weights_s={t1 - t0!r} engine_s={t2 - t1!r} "
          f"warm_up_s={time.perf_counter() - t2!r} "
          f"compiles_in_setup={clock.compiles}", flush=True)
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    reqs = generator.requests(traffic, cfg.vocab_size, seed, window)
    recs = [Rec(r.arrival_s, r.prompt, r.max_new) for r in reqs]
    steps: list = []
    tmp = Path(tempfile.mkdtemp(prefix="chipbench-trace-")) if trace else None
    at_traffic = (clock.compiles, clock.traces)
    t_traffic = time.perf_counter()
    t_open = t_traffic + float(traffic["lead_in_s"])
    t_close = t_open + window
    marks = {}

    def on_open():
        marks["setup_s"] = t_open - t_process
        marks["counters"] = _counters(runtimes)
        marks["compiles"] = (clock.compiles, clock.traces)
        marks["step0"] = len(steps)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp), profiler_options=opts)
            marks["span"] = jax.profiler.TraceAnnotation("chipbench.window")
            marks["span"].__enter__()

    serve(rt, jax, recs, t_traffic, t_open, t_close, steps, on_open)
    red = None
    if trace:
        marks["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = clock.compiles - marks["compiles"][0]
    traces = clock.traces - marks["compiles"][1]
    counters = {k: v - marks["counters"].get(k, 0)
                for k, v in _counters(runtimes).items()}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    if trace:
        from chipbench import trace as tr
        found = sorted(tmp.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        red = tr.load(found[-1], chips)
        shutil.rmtree(tmp, ignore_errors=True)
        if chips > 1:
            print("chipbench idle_share_per_chip="
                  f"{[100.0 * (1.0 - b / red.window_ns) for b in red.busy_ns_per_chip()]!r}",
                  flush=True)
    late = sorted(r.submitted - (t_traffic + r.due) for r in recs
                  if r.submitted)
    print(f"chipbench shapes_warmed={n_shapes} compiles_in_window={compiles} "
          f"traces_in_window={traces} "
          f"compiles_in_traffic={clock.compiles - at_traffic[0]} "
          f"traces_in_traffic={clock.traces - at_traffic[1]} "
          f"requests={len(recs)} "
          f"generator_late_p95_ms={1e3 * late[int(0.95 * (len(late) - 1))] if late else 0.0!r}",
          flush=True)
    ctx = Context(config=config, peaks=peaks,
                  window=(t_open, t_close), requests=recs, t_traffic=t_traffic,
                  steps=[s for s in steps if s.n >= marks["step0"]],
                  counters=counters, setup_s=marks["setup_s"],
                  peak_bytes=peak, trace=red)
    wanted = per_layer if trace else e2e
    metrics = {}
    for m in wanted:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    due = [r for r in recs if t_open <= t_traffic + r.due < t_close]
    done = [r for r in recs if r.finished]
    finished = [(r.prompt, r.tokens) for r in done]
    replica = [runtimes.index(r.handle.runtime) for r in done]
    malformed = sum(1 for r in done if len(r.tokens) != r.max_new
                    or not all(0 <= t < cfg.vocab_size for t in r.tokens))
    picked_at = check.sample_at(finished, seed, traffic["check"]["tokens"],
                                replica)
    picked = [finished[i] for i in picked_at]
    not_compared = len(runtimes) - len({replica[i] for i in picked_at})
    # the reference runs with the program's state freed
    del rt, runtimes, params, done
    for r in recs:
        r.handle = None
    gc.collect()
    t_ref = time.perf_counter()
    gap = check.widest_gap(config, seed, picked, dep["max_len"]) \
        if picked else None
    limit = float(traffic["check"]["max_logit_gap"])
    compared = sum(len(o) for _, o in picked)
    print(f"chipbench reference_s={time.perf_counter() - t_ref!r} "
          f"requests_compared={len(picked)} tokens_compared={compared}",
          flush=True)
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "malformed_outputs": {"value": malformed, "limit": 0}}
    if dep.get("replicas", 1) > 1:
        checks["replicas_not_compared"] = {"value": not_compared, "limit": 0}
    correct = gap is not None and gap <= limit and malformed == 0 \
        and not_compared == 0
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(due), "failed": 0,
           "metrics": metrics, "device": dev}
    if red is not None:
        from chipbench import trace as tr
        busy = red.busy_ns_per_chip()
        dev["busy_s"] = sum(busy) / len(busy) * 1e-9
        dev["window_s"] = red.window_ns * 1e-9
        out["breakdown"] = tr.breakdown(red)
    out["check"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: {ROOT / 'src' / 'repro'} not found: run from a "
              f"checkout of the repo", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    cell, config, traffic = load_cell(args.workload)
    import jax
    from chipbench import peaks as pk
    devices = jax.devices()
    device = devices[0]
    try:
        if device.platform != "tpu":
            raise Unavailable(f"needs a TPU; JAX found {device.platform}")
        if len(devices) < cell["chips"]:
            raise Unavailable(f"cell asks for {cell['chips']} chips, JAX "
                              f"found {len(devices)}")
        peaks = pk.lookup(device.device_kind)
    except (Unavailable, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    print(f"chipbench compile_cache={enable_compile_cache()} "
          f"devices={len(devices)}x{device.device_kind}", flush=True)
    out = run_cell(config, traffic, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace), peaks=peaks,
                   device=device, chips=cell["chips"],
                   e2e=metric_specs(args.workload, "end_to_end"),
                   per_layer=metric_specs(args.workload, "per_layer"))
    for k, v in out["check"].items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
