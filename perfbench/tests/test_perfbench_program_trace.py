"""The program's spans and scopes, and the readers built on them: a CPU
profiler trace of one tiny chunk through the pool, the tick phases in the
compiled program's metadata, synthetic traces for each reader, and the
readers that were there before, unchanged on the recorded chip trace."""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import costs  # noqa: E402
import program_trace as pt  # noqa: E402
import tiny_cells  # noqa: E402
import trace_reduce as tr  # noqa: E402
from harness import View, load_module, reader_path  # noqa: E402

CHUNK_STEPS = ("repro.chunk.inputs", "repro.chunk.dispatch",
               "repro.chunk.wait", "repro.chunk.fetch")


def read(name, view):
    return load_module(reader_path(name), "m").read(view)


def marked(trace, marks, facts=None):
    view = View(trace, 1, facts or {}, "TPU v5 lite")
    view.marks = marks
    return view


@pytest.fixture(scope="module")
def chunk_trace(tmp_path_factory):
    """A profiler trace on the CPU of one tiny chunk through the init pool,
    inside a ``bench.window`` span; the chunk program compiled before."""
    import jax
    from repro.core.prototype import Context
    from repro.evolution import ga
    from repro.launch.explore import make_init_pool

    from entries import streaming_init as entry

    c = tiny_cells.cell("egi_init.chunk64")
    task = ga.make_chunk_task(entry.ga_config(c.config),
                              entry.make_eval(c.config), tiny_cells.SEED)
    pool = make_init_pool(pool_devices=1)
    directory = str(tmp_path_factory.mktemp("trace"))
    try:
        pool.submit(task, Context(chunk=0, size=2))
        jax.profiler.start_trace(directory)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                pool.submit(task, Context(chunk=1, size=2))
        finally:
            jax.profiler.stop_trace()
    finally:
        pool.shutdown()
    return directory, pool.members[0].name


def test_a_traced_chunk_holds_its_attempt_with_the_chunk_steps_nested(
        chunk_trace):
    directory, member = chunk_trace
    trace = tr.Trace.from_dir(directory)
    assert trace.span("bench.window") is not None
    marks = pt.of(View(trace, 1, {}, "TPU v5 lite"), search=directory)
    assert marks is not None
    attempts = [s for s in marks.spans if s.name == "repro.pool.attempt"]
    assert len(attempts) == 1
    (attempt,) = attempts
    for step in CHUNK_STEPS:
        assert len(pt.nested(attempt, marks.spans, step)) == 1, step
    steps = [pt.nested(attempt, marks.spans, s)[0] for s in CHUNK_STEPS]
    assert [s.start_ns for s in steps] == sorted(s.start_ns for s in steps)
    assert {s.line for s in steps} == {attempt.line}
    lo, hi = trace.span("bench.window")
    own = pt.host_ms_per_attempt(marks.spans, lo, hi)
    wait = steps[2].duration_ns / 1e6
    assert own == pytest.approx(attempt.duration_ns / 1e6 - wait)
    assert 0 < own < attempt.duration_ns / 1e6


def test_the_attempt_span_names_its_member_and_round(chunk_trace):
    import glob

    from jax.profiler import ProfileData
    directory, member = chunk_trace
    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)[0]
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    args = [{str(k): str(v) for k, v in e.stats}
            for ln in host.lines for e in ln.events
            if e.name == "repro.pool.attempt"]
    assert args == [{"member": member, "round": "0"}]


def test_a_trace_of_another_window_is_not_taken_for_the_views(chunk_trace):
    directory, _ = chunk_trace
    trace = tr.Trace.from_dir(directory)
    lo, hi = trace.span("bench.window")
    other = tr.Trace({}, [("bench.window", lo + 1.0, hi - lo)])
    assert pt.of(View(other, 1, {}, "TPU v5 lite"),
                 search=directory) is None


def test_the_tapped_program_names_the_tick_phases_and_keeps_its_state():
    import jax
    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import AntsConfig

    import tap
    cfg = AntsConfig(**tiny_cells.TINY_MODEL)
    keys = jax.random.split(jax.random.key(3), 2)
    rates = jax.numpy.full((2,), 50.0)

    def tapped(k, d, e):
        return tap.tapped(simulate_batch, cfg, k, d, e)

    text = jax.jit(tapped).lower(keys, rates, rates).compile().as_text()
    scopes = {pt.scope_of(n) for n in re.findall(r'op_name="([^"]*)"', text)}
    assert {"ants.sense", "ants.deposit", "ants.diffuse"} <= scopes
    obj, state = jax.jit(tapped)(keys, rates, rates)
    assert sorted(state) == sorted(tap.STATE_FIELDS)
    assert obj.shape == (2, 3)
    w = cfg.world_size
    assert state["chem"].shape == state["food"].shape == (2, w, w)


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/closed_call/vmap(ants.sense)/gather", "ants.sense"),
    ("jit(f)/while/body/ants.diffuse/diffuse_evaporate/while/body/add",
     "ants.diffuse"),
    ("jit(f)/ants.rng/vmap(ants.deposit)/scatter-add", "ants.deposit"),
    ("jit(f)/while/body/add", None),
    ("jit(f)/my_ants.sense/add", None),
    ("", None),
])
def test_an_operations_scope_is_its_innermost_ants_component(path, scope):
    assert pt.scope_of(path) == scope


def _sensing_trace():
    # one chip, two ants programs of 4 lanes x 5 ticks; in each a loop op,
    # two sensing ops and one diffusion op; a third program of another name
    ops = [("%while.1", 100.0, 60.0), ("%fusion.1", 100.0, 20.0),
           ("%fusion.2", 120.0, 10.0), ("%diffuse_evaporate.3", 130.0, 30.0),
           ("%while.1", 200.0, 60.0), ("%fusion.1", 200.0, 24.0),
           ("%fusion.2", 224.0, 6.0), ("%diffuse_evaporate.3", 230.0, 30.0),
           ("%fusion.1", 300.0, 50.0)]
    mods = [("jit_ants_evaluation", 100.0, 60.0),
            ("jit_ants_evaluation", 200.0, 60.0), ("jit_other", 300.0, 50.0)]
    trace = tr.Trace({"/device:TPU:0": {"ops": ops, "modules": mods}},
                     [("bench.window", 90.0, 300.0)])
    facts = {"ants_module": "jit_ants_evaluation", "lanes_per_program": 4,
             "ticks": 5}
    return trace, facts


def test_sense_ns_is_the_sensing_scopes_time_per_lane_and_tick():
    trace, facts = _sensing_trace()
    scopes = {"%fusion.1": "ants.sense", "%fusion.2": "ants.sense",
              "%diffuse_evaporate.3": "ants.diffuse",
              "%while.1": "ants.sense"}   # a loop op is never counted
    view = marked(trace, pt.Marks([], {"/device:TPU:0": scopes}), facts)
    assert read("sense_ns.init", view) == pytest.approx(60.0 / (2 * 4 * 5))
    assert read("sense_ns.single", view) == read("sense_ns.init", view)


@pytest.mark.parametrize("scopes", [{}, {"%fusion.1": "ants.rng"}],
                         ids=["no-scope", "no-sensing-op"])
def test_sense_ns_reads_nothing_without_a_sensing_scope(scopes):
    trace, facts = _sensing_trace()
    view = marked(trace, pt.Marks([], {"/device:TPU:0": scopes}), facts)
    assert read("sense_ns.init", view) is None


def _attempt(start, length, wait, line="pool-0"):
    return [pt.Span("repro.pool.attempt", start, length, line),
            pt.Span("repro.chunk.inputs", start + 1, 2, line),
            pt.Span("repro.chunk.wait", start + 5, wait, line)]


def test_chunk_host_ms_is_the_median_attempt_less_its_nested_wait():
    spans = sorted(
        _attempt(100.0, 2e6, 1.5e6) + _attempt(200.0, 3e6, 1e6, "pool-1")
        + _attempt(300.0, 4e6, 3.6e6)
        # a wait on another thread is not the attempt's own
        + [pt.Span("repro.chunk.wait", 210.0, 1e6, "pool-2")]
        # an attempt that started before the window opened
        + _attempt(50.0, 9e6, 0.0), key=lambda s: s.start_ns)
    trace = tr.Trace({}, [("bench.window", 90.0, 1e9)])
    view = marked(trace, pt.Marks(spans, {}))
    # own work: 0.5, 2.0, 0.4 ms
    assert read("chunk_host_ms.init", view) == pytest.approx(0.5)
    assert read("chunk_host_ms.single", view) == pytest.approx(0.5)


def test_chunk_host_ms_reads_nothing_without_an_attempt_in_the_window():
    trace = tr.Trace({}, [("bench.window", 1e9, 1e9)])
    view = marked(trace, pt.Marks(_attempt(100.0, 2e6, 1e6), {}))
    assert read("chunk_host_ms.init", view) is None
    assert read("chunk_host_ms.init", marked(trace, None)) is None


def test_an_idle_gap_is_charged_to_the_innermost_program_span():
    bench = [("bench.window", 90.0, 270.0), ("bench.stream", 95.0, 260.0)]
    program = [pt.Span("repro.pool.attempt", 140.0, 200.0, "pool-0"),
               pt.Span("repro.chunk.inputs", 160.0, 20.0, "pool-0"),
               pt.Span("repro.chunk.fetch", 240.0, 40.0, "pool-0")]
    spans = sorted(bench + [tuple(s[:3]) for s in program],
                   key=lambda e: e[1])
    gaps = [(150.0, 170.0), (180.0, 200.0), (250.0, 260.0), (345.0, 355.0),
            (91.0, 93.0)]
    assert tr.attribute(gaps, spans) == {
        "repro.chunk.inputs": 20.0, "repro.pool.attempt": 20.0,
        "repro.chunk.fetch": 10.0, "bench.stream": 10.0, "bench.window": 2.0}


def _xspace(device_events, host_lines):
    """A serialized XSpace in the layout of a TPU v5e's trace: a device
    plane whose operations carry their op_name path as the ``tf_op`` stat
    of their event metadata (one as a string, one as a reference to an
    interned string), and a host plane of named threads.

    device_events: (line, name, tf_op or None, start_ns, duration_ns);
    host_lines: {thread: [(name, start_ns, duration_ns)]}."""
    from jax.profiler import ProfileData
    metadata, stats, text = {}, [], []

    def meta_id(name, tf_op=None, ref=False):
        key = (name, tf_op, ref)
        if key not in metadata:
            i = len(metadata) + 1
            stat = ""
            if tf_op is not None and ref:
                stats.append(f'stat_metadata {{ key: 9 value {{ id: 9 '
                             f'name: "{tf_op}" }} }}')
                stat = "stats { metadata_id: 1 ref_value: 9 }"
            elif tf_op is not None:
                stat = f'stats {{ metadata_id: 1 str_value: "{tf_op}" }}'
            metadata[key] = (i, f'event_metadata {{ key: {i} value {{ id: {i}'
                                f' name: "{name}" {stat} }} }}')
        return metadata[key][0]

    def line(lid, name, events):
        evs = " ".join(f"events {{ metadata_id: {m} offset_ps: {int(s * 1000)}"
                       f" duration_ps: {int(d * 1000)} }}"
                       for m, s, d in events)
        return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {evs} }}'

    lines = {}
    for n, (ln, name, tf_op, s, d) in enumerate(device_events):
        lines.setdefault(ln, []).append(
            (meta_id(name, tf_op, ref=n == 0), s, d))
    dev = " ".join(line(i + 1, ln, ev) for i, (ln, ev) in
                   enumerate(lines.items()))
    dev_meta = " ".join(m for _, m in metadata.values())
    text.append(f'planes {{ id: 1 name: "/device:TPU:0" {dev} {dev_meta} '
                f'stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} '
                f'{" ".join(stats)} }}')
    metadata.clear()
    host = " ".join(
        line(i + 1, thread.split("#")[0],
             [(meta_id(n), s, d) for n, s, d in events])
        for i, (thread, events) in enumerate(host_lines.items()))
    host_meta = " ".join(m for _, m in metadata.values())
    text.append(f'planes {{ id: 2 name: "/host:CPU" {host} {host_meta} }}')
    return ProfileData.text_proto_to_serialized_xspace(" ".join(text))


def test_both_readers_read_a_tpu_layout_trace_file(tmp_path):
    body = "jit(ants_evaluation)/while/body/closed_call/"
    sense = body + "vmap(ants.sense)/gather:"
    device = [
        ("XLA Modules", "jit_ants_evaluation(1)", None, 1000.0, 400.0),
        ("XLA Modules", "jit_ants_evaluation(1)", None, 1500.0, 400.0),
        ("XLA Ops", "%fusion.111 = f32[4] fusion()", sense, 1000.0, 100.0),
        ("XLA Ops", "%while.3 = (f32[4]) while()", sense, 1100.0, 290.0),
        ("XLA Ops", "%fusion.116 = s32[4] fusion()",
         body + "vmap(ants.move)/add:",
         1100.0, 50.0),
        ("XLA Ops", "%fusion.119 = f32[4] fusion()",
         "jit(ants_evaluation)/while:", 1150.0, 50.0),
        ("XLA Ops", "%fusion.111 = f32[4] fusion()", sense, 1500.0, 140.0),
    ]
    # threads of one name, each an attempt with its own waits: the second
    # wait of the first lies in the time of the second's attempt
    host = {
        "python3#a": [("bench.window", 900.0, 1100.0),
                      ("bench.progress", 1400.0, 5.0)],
        "python3#b": [("repro.pool.attempt", 950.0, 500.0),
                      ("repro.chunk.inputs", 951.0, 20.0),
                      ("repro.chunk.wait", 980.0, 440.0),
                      ("repro.chunk.wait", 1500.0, 100.0)],
        "python3#c": [("repro.pool.attempt", 1450.0, 500.0),
                      ("repro.chunk.wait", 1460.0, 470.0)],
    }
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_xspace(device, host))
    trace = tr.Trace.from_dir(str(tmp_path))
    view = View(trace, 1, {"ants_module": "jit_ants_evaluation",
                           "lanes_per_program": 2, "ticks": 10},
                "TPU v5 lite")
    view.marks = pt.of(view, search=str(tmp_path))
    assert view.marks.scopes["/device:TPU:0"] == {
        "%fusion.111": "ants.sense", "%while.3": "ants.sense",
        "%fusion.116": "ants.move"}
    # the sensing ops of both programs, the loop op left out
    assert read("sense_ns.init", view) == pytest.approx(240.0 / (2 * 2 * 10))
    # own work 60 and 30 ns: the median is 45 ns
    assert read("chunk_host_ms.init", view) == pytest.approx(45e-6)


PROBE = os.path.join(HERE, "data", "probe_trace.json")
# each reader's value on the recorded chip trace, read before the program's
# own spans and scopes existed (two 320-lane, 50-tick programs)
PROBE_VALUES = {
    "lane_tick_ns.init": 25863.5059375,
    "ants_step_mfu.init": 0.20876951533793192,
    "diffusion_roofline.init": 90.92158582715284,
    "chunk_gap_ms.init": None,
    "device_idle.init": 11.439045505947654,
    "sense_ns.init": None,
    "chunk_host_ms.init": None,
}


@pytest.mark.parametrize("name", sorted(PROBE_VALUES))
def test_every_reader_gives_its_value_on_the_recorded_chip_trace(name):
    with open(PROBE) as f:
        data = json.load(f)
    devices = {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
               for p, lines in data["devices"].items()}
    trace = tr.Trace(devices, [tuple(s) for s in data["spans"]])
    model = {"world_size": 72, "population": 125}
    facts = {"ants_module": "jit_tapped", "lanes_per_program": 320,
             "ticks": 50, "tick_bytes": costs.tick_bytes(model),
             "tick_flops": costs.tick_flops(model),
             "kernel": "%diffuse_evaporate",
             "kernel_bytes_per_call": costs.diffusion_bytes(model, 320)}
    # the recorded trace holds no span or scope of the program
    view = marked(trace, pt.Marks([], {p: {} for p in devices}), facts)
    want = PROBE_VALUES[name]
    got = read(name, view)
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))
