"""The reduction of a trace by the program's named scopes, on small
synthetic traces with a TPU plane, and the join of a trace's instruction
names with a program compiled and recorded on the CPU."""
import contextlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, xprof
from repro.core import spans

ADV = "jit(f)/vmap()/while/body/vmap(engine.advance)/while"
TRIP = ADV + "/body/engine.trip/add"
PATHS = {"while.1": ADV, "fusion.1": TRIP, "fusion.2": "jit(f)/step.obs/mul",
         "fusion.3": "jit(f)/transpose(jvp(sac.update))/replay.sample/dot",
         "fusion.4": ADV + "/body/engine.trip/broadcast", "copy.1": ""}


def trace(ops, runs, window=(0, 4000), module="jit_f"):
    """A trace with one TPU plane: ``runs`` [(start, end)] of ``module`` on
    its ``XLA Modules`` line, ``ops`` [(instruction, start, end)] on its
    ``XLA Ops`` line, and the host span ``bench.trace`` over ``window``
    (times in ns)."""
    names = {}

    def events(evs):
        out = []
        for name, s, e in evs:
            i = names.setdefault(name, len(names) + 1)
            out.append(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                       f"duration_ps: {(e - s) * 1000} }}")
        return "\n".join(out)

    tpu_ops = events([(f"%{n} = f32[8]{{0}} op()", s, e) for n, s, e in ops])
    tpu_mods = events([(f"{module}(7)", s, e) for s, e in runs])
    host = events([("bench.trace", *window)])
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for n, i in names.items())
    return jax.profiler.ProfileData.from_text_proto(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {tpu_mods} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {tpu_ops} }}
  {meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 0 {host} }}
  {meta} }}""")


def one_run(t0=100):
    """One run of 1000 ns: a loop of three trips, an observation op after
    it and, after 100 ns idle, a layout copy under no scope."""
    return [("while.1", t0, t0 + 700),
            ("fusion.1", t0 + 100, t0 + 200), ("fusion.1", t0 + 300, t0 + 400),
            ("fusion.1", t0 + 500, t0 + 600),
            ("fusion.4", t0 + 50, t0 + 80),          # hoisted: once a loop
            ("fusion.2", t0 + 700, t0 + 800),
            ("copy.1", t0 + 900, t0 + 1000)]


def test_segments_strip_transform_wrappers():
    segs = scopes.segments("jit(f)/vmap(engine.advance)/while/body/"
                           "transpose(jvp(sac.update))/replay.sample/dot")
    assert segs == ["f", "engine.advance", "while", "body", "sac.update",
                    "replay.sample", "dot"]
    assert scopes.segments("vmap()/x") == ["", "x"]


RAN = """HloModule jit_f, entry_computation_layout={(f32[])->f32[8]{0}}

ENTRY %main.4 (p.1: f32[]) -> f32[8] {
  %p.1 = f32[] parameter(0), metadata={op_name="p"}
  %broadcast_in_dim.3 = f32[8]{0} broadcast(%p.1), dimensions={}
  ROOT %add.2 = f32[8]{0} add(%broadcast_in_dim.3, %broadcast_in_dim.3)
}
"""


def test_paths_come_by_position_from_this_builds_text(capsys):
    # the program that ran, from a build without scopes, and the same
    # program with this build's metadata, which named one instruction
    # differently
    named = (RAN.replace("broadcast_in_dim.3 = f32[8]{0} broadcast(%p.1), "
                         "dimensions={}",
                         "broadcast_in_dim.5 = f32[8]{0} broadcast(%p.1), "
                         'dimensions={}, metadata={op_name="jit(f)/step.obs/'
                         'broadcast_in_dim" source_line=7}')
             .replace("(%broadcast_in_dim.3, %broadcast_in_dim.3)",
                      "(%broadcast_in_dim.5, %broadcast_in_dim.5), "
                      'metadata={op_name="jit(f)/engine.advance/add"}'))
    assert scopes.hlo_paths(RAN, named) == ("jit_f", {
        "p.1": "p", "broadcast_in_dim.3": "jit(f)/step.obs/broadcast_in_dim",
        "add.2": "jit(f)/engine.advance/add"})
    assert scopes.hlo_paths(RAN, RAN) == ("jit_f", {
        "p.1": "p", "broadcast_in_dim.3": "", "add.2": ""})
    # a program that differs in more than names and metadata is refused
    assert scopes.hlo_paths(RAN, named.replace(" add(", " multiply(")) is None
    assert "not the program jit_f that ran" in capsys.readouterr().err


@pytest.fixture
def compile_cache(tmp_path):
    """A persistent compile cache that keeps every program, for one test."""
    from jax.experimental.compilation_cache import compilation_cache
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield
    for k, v in keep.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    jax.clear_caches()


def test_compiled_texts_carry_this_builds_metadata(compile_cache):
    """A cache entry written by a build without scopes serves the run (the
    default key leaves metadata out); the second text is this build's."""
    def build(scope):
        def f(x):
            with jax.named_scope(scope) if scope else contextlib.nullcontext():
                return jnp.tanh(x @ x)
        return jax.jit(f)

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    assert spans.OBS not in build(None).lower(x).compile().as_text()
    ran, named = scopes.compiled_texts(build(spans.OBS), (x,))
    assert spans.OBS not in ran
    assert spans.OBS in named
    module, paths = scopes.hlo_paths(ran, named)
    assert any(spans.OBS in p for p in paths.values())


def test_innermost_counts_each_instant_once():
    evs = [("a", 0, 10), ("b", 2, 4), ("c", 3, 5), ("d", 8, 12)]
    own, outer = scopes.innermost(evs)
    assert own == [2 + 3, 1, 2, 4] and sum(own) == 12
    assert outer == [None, 0, 1, 0]
    # a body op listed before the loop that starts with it, and an op of
    # no duration, which takes no time from the one around it
    evs = [("body", 0, 2), ("loop", 0, 10), ("mark", 5, 5)]
    assert scopes.innermost(evs) == ([2, 8, 0], [1, None, None])


def test_nested_scopes_are_inclusive_and_unscoped_is_kept():
    r = scopes.by_scope(trace(one_run(), [(100, 1100)]), (0, 4000), "jit_f",
                        PATHS, spans)
    assert r["runs"] == 1
    # the loop 100..800, the observation 800..900, the copy 1000..1100
    assert r["busy"] == pytest.approx(0.9e-3)
    # the loop's own time (gaps between trips) stays with the advance
    assert r[spans.ADVANCE] == pytest.approx(0.7e-3)
    assert r[spans.TRIP] == pytest.approx(0.33e-3)
    assert r[spans.OBS] == pytest.approx(0.1e-3)
    assert r["unscoped"] == pytest.approx(0.1e-3)
    assert r[spans.OBS] + r[spans.ADVANCE] + r["unscoped"] == pytest.approx(
        r["busy"])


def test_an_op_without_metadata_takes_the_path_around_it():
    # a copy the compiler added inside the loop's body belongs to the
    # loop; the same copy at the top level of the program to no scope
    ops = one_run() + [("copy.1", 310, 340)]
    r = scopes.by_scope(trace(ops, [(100, 1100)]), (0, 4000), "jit_f",
                        PATHS, spans)
    assert r[spans.ADVANCE] == pytest.approx(0.7e-3)
    assert r[spans.TRIP] == pytest.approx(0.33e-3)
    assert r["unscoped"] == pytest.approx(0.1e-3)


def test_only_whole_runs_count():
    ops = one_run(100) + one_run(1300) + one_run(3500)
    runs = [(100, 1100), (1300, 2300), (3500, 4500)]
    r = scopes.by_scope(trace(ops, runs), (0, 4000), "jit_f", PATHS, spans)
    assert r["runs"] == 2
    assert r[spans.OBS] == pytest.approx(0.1e-3)
    assert r["busy"] == pytest.approx(0.9e-3)
    # another program's runs are not the timed program's
    assert scopes.by_scope(trace(ops, runs, module="jit_g"), (0, 4000),
                           "jit_f", PATHS, spans) is None
    # nor are runs whose instructions join with no scope
    assert scopes.by_scope(trace(ops, runs), (0, 4000), "jit_f",
                           dict.fromkeys(PATHS, ""), spans) is None


def test_an_instruction_missing_from_the_program_text_refuses(capsys):
    # a traced program built from another build's text: its time would
    # go to the wrong scopes, so the reduction gives nothing
    ops = one_run() + [("fusion.99", 820, 850)]
    assert scopes.by_scope(trace(ops, [(100, 1100)]), (0, 4000), "jit_f",
                           PATHS, spans) is None
    assert "1 operations (0.000 ms) of jit_f" in capsys.readouterr().err


def test_the_timed_program_of_an_entry():
    class Declared:
        def timed_program(self):
            return "fn", ("shape",)

    assert scopes.timed_program(Declared(), "serve") == ("fn", ("shape",))
    # an entry that neither declares its timed program nor has the
    # members of the route or train entry is refused by name
    with pytest.raises(TypeError, match="timed_program"):
        scopes.timed_program(object(), "serve")
    with pytest.raises(TypeError, match="'route'"):
        scopes.timed_program(object(), "route")


def test_trips_per_advance():
    ops = one_run(100) + one_run(1300)
    r = scopes.by_scope(trace(ops, [(100, 1100), (1300, 2300)]), (0, 4000),
                        "jit_f", PATHS, spans)
    assert r["trips"] == 3.0
    # with no loop instruction traced (the loop inside one kernel), none
    no_loop = [o for o in ops if o[0] != "while.1"]
    r = scopes.by_scope(trace(no_loop, [(100, 1100), (1300, 2300)]),
                        (0, 4000), "jit_f", PATHS, spans)
    assert r["trips"] is None


def test_idle_within_run_apart_from_idle_between_runs():
    ops = one_run(100) + one_run(1300)
    pd = trace(ops, [(100, 1100), (1300, 2300)], window=(0, 4000))
    r = scopes.by_scope(pd, (0, 4000), "jit_f", PATHS, spans)
    # inside each run: 900..1000 has no op; the rest of the window is
    # between runs
    assert r["idle_within_run"] == pytest.approx(2 * 100 / 4000)
    whole = xprof.reduce(pd, "bench.trace")
    between = whole["idle_share"] - r["idle_within_run"]
    assert between == pytest.approx((4000 - 2000) / 4000)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A program with two scopes, compiled and traced on the CPU."""
    def f(x):
        with jax.named_scope(spans.OBS):
            y = jnp.tanh(x @ x)
        with jax.named_scope(spans.ADVANCE):
            return jax.lax.fori_loop(0, 3, lambda i, v: jnp.sin(v) @ v, y)

    fn = jax.jit(f)
    x = jnp.ones((128, 128))
    fn(x).block_until_ready()
    tmp = str(tmp_path_factory.mktemp("scoped"))
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.trace"):
            for _ in range(3):
                fn(x).block_until_ready()
                time.sleep(0.01)
    return xprof.load(tmp), fn.lower(x).compile().as_text()


def test_a_cpu_trace_gives_none(recorded):
    pd, _ = recorded
    record = {"cell": "paper6.route", "profile": pd}
    # no TPU plane: nothing to reduce, and nothing compiled to join with
    assert scopes.reduce(record) is None
    assert record["scopes"] is None


def test_join_with_the_compiled_program(recorded):
    """The CPU trace's operations, replayed one after another on a TPU
    plane, join with the program's HLO text by instruction name."""
    pd, text = recorded
    module, paths = scopes.hlo_paths(text, text)
    assert module == "jit_f"
    ops = [(xprof.op_name(n), s, e)
           for n, s, e in xprof.device_ops(pd)["/host:CPU"]
           if xprof.op_name(n) in paths]
    assert ops
    t, seq = 0, []
    for name, s, e in ops:
        seq.append((name, t, t + int(e - s) + 1))
        t = seq[-1][2]
    r = scopes.by_scope(trace(seq, [(0, t)], window=(0, t)), (0, t),
                        module, paths, spans)
    assert r[spans.OBS] + r[spans.ADVANCE] > 0
    assert r[spans.OBS] + r[spans.ADVANCE] + r["unscoped"] == pytest.approx(
        r["busy"])
