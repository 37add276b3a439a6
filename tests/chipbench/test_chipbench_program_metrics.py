"""The readers of what the program reports about itself, on a trace
recorded on a TPU v5e with the program's spans and program names
(``ds7b-1chip.chat``, 210 ms: harness steps 244-248, step 246 admitting
one request, cut from a ``--trace 1`` run; the TPU plane's programs and
ops, the host's main thread and the runtime's ``DoEnqueueProgram``
events), and by hand; and the cells each per-layer metric names."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import run
from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
SPANS = DATA / "ds7b-chat-spans.xplane.pb.gz"
UNNAMED = DATA / "ds7b-chat-steps.xplane.pb.gz"
BENCH = json.loads((Path(__file__).resolve().parents[2]
                    / "BENCHMARK.json").read_text())
# device time of the five decode steps in the cut, read from its events
DECODE_NS = [34753341, 34753530, 34747772, 34750058, 34747503]


@pytest.fixture(scope="module")
def red():
    return tr.load(SPANS)


@pytest.fixture(scope="module")
def host():
    """The host events of the cut: (start, end, name, stats, line)."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(
        gzip.decompress(SPANS.read_bytes()))
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name,
             dict(e.stats), line.name)
            for plane in prof.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_programs_carry_their_names(red):
    names = {p.name.split("(")[0] for p in red.programs}
    assert {"jit_decode_step", "jit_engram_row_gather", "jit_decode_keys",
            "jit__admit_wave_fn", "jit__wave_sync_fn"} <= names
    assert not any(n.startswith("jit__lambda") for n in names)
    top, seconds = tr.breakdown(red)["device_ops"][0]
    assert top.startswith("jit_decode_step(")
    assert seconds == pytest.approx(sum(DECODE_NS) * 1e-9)


def test_decode_ms_reads_the_named_decode_step(red):
    got = run.reader("model.decode_ms")(SimpleNamespace(trace=red))
    assert got == pytest.approx(sum(DECODE_NS) / 5 * 1e-6)
    assert got == pytest.approx(34.7504408)


def test_decode_ms_finds_nothing_without_the_name():
    """Before the decode step had a name it lowered as ``jit__lambda``."""
    read = run.reader("model.decode_ms")
    assert read(SimpleNamespace(trace=tr.load(UNNAMED))) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_existing_readers_see_the_named_trace(red):
    steps = [run.Step(n, [], []) for n in range(244, 249)]
    admit = dict((s.n, ns) for s, ns in
                 tr.per_step(red, steps, r"jit__admit_wave_fn"))
    assert admit == {244: 0, 245: 0, 246: 9850230, 247: 0, 248: 0}
    idle = run.reader("device.idle_share")(SimpleNamespace(trace=red))
    assert idle == pytest.approx(100 * (1 - 183742751 / 210267207))


@pytest.mark.parametrize("counters,want", [
    ({"queue_wait_s_sum": 0.5, "prefills": 4}, 125.0),
    ({"queue_wait_s_sum": 0.0, "prefills": 3}, 0.0),
    ({"queue_wait_s_sum": 0.5, "prefills": 0}, None),
    ({"prefills": 4}, None),                 # a program without the counter
])
def test_queue_wait_reads_the_engine_counters(counters, want):
    got = run.reader("engine.queue_wait_ms")(
        SimpleNamespace(counters=counters))
    assert got == (pytest.approx(want) if want is not None else None)


def test_program_spans_nest_in_the_harness_steps(host):
    steps = [e for e in host if e[2] == "chipbench.step"]
    program = [e for e in host if e[2].startswith("repro.")]
    assert {e[2] for e in program} == {
        "repro.step", "repro.admit", "repro.admit.group", "repro.decode",
        "repro.store.charge", "repro.sync"}
    assert {e[4] for e in program} == {"python3"}
    for e in program:
        assert any(s[0] <= e[0] and e[1] <= s[1] for s in steps), e
    (group,) = [e for e in program if e[2] == "repro.admit.group"]
    assert group[3] == {"S": 128, "n": 1, "n_pad": 1, "rids": 139}
    (admitting,) = [s for s in steps if s[0] <= group[0] <= s[1]]
    assert admitting[3] == {"n": 246}


def test_device_clock_runs_ahead_of_the_host(red, host):
    """Each program starts on the device after the host enqueued it; on
    this trace's clocks the device events read up to 1.72 ms early, so a
    reader that sets device idle time against host spans has to align the
    two by ``run_id`` first."""
    enq = {e[3]["run_id"]: e[1] for e in host if e[2] == "DoEnqueueProgram"}
    lead = [enq[p.stats["run_id"]] - p.start for p in red.programs
            if p.stats.get("run_id") in enq]
    assert len(lead) >= 30
    assert 1.6e6 < max(lead) < 1.8e6


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_names_cells_that_report_what_it_moves(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert m["workloads"] and set(m["workloads"]) <= cells
    for cell in m["workloads"]:
        assert m["moves"] in {e["name"] for e in
                              run.metric_specs(cell, "end_to_end")}
    assert (Path(run.HERE) / "metrics" / f"{metric}.py").is_file()
