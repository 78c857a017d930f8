"""Spans and named programs inside the RK3 step: one ``s3`` step and one
``fused`` step of a small uniform Sedov run under the JAX profiler, read
back with the benchmark's reduction (``bench/program_trace.py``)."""
import glob
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs.base import AggregationConfig, HydroConfig
from repro.core import StrategyRunner, UniformSedovScenario
from repro.core.executor import DeviceExecutor
from repro.core.trace import named, program_name, span
from repro.hydro.state import sedov_init
from repro.hydro.stepper import courant_dt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import program_trace  # noqa: E402

CFG = HydroConfig(levels=1)           # 8 sub-grids of 8^3
# s3 at a cap of 4: two bucket-4 launches per stage, so every stage's
# launches nest under repro.submit and repro.staging runs per launch
AGG = {"s3": AggregationConfig(strategy="s3", max_aggregated=4),
       "fused": AggregationConfig(strategy="fused")}
# each span, and the spans one of which must enclose it
PARENTS = {"repro.rk_stage": ("repro.step",),
           "repro.combine": ("repro.step",),
           "repro.populations": ("repro.rk_stage",),
           "repro.assemble": ("repro.rk_stage",),
           "repro.submit": ("repro.rk_stage",),
           "repro.flush": ("repro.rk_stage",),
           "repro.gather": ("repro.rk_stage",),
           "repro.staging": ("repro.submit", "repro.flush"),
           "repro.dispatch": ("repro.submit", "repro.flush",
                              "repro.rk_stage")}
EXECUTOR_SPANS = {"repro.submit", "repro.flush", "repro.staging",
                  "repro.gather"}


def traced_step(runner, u, dt, tmp_path):
    """One RK3 step inside a ``bench_window`` span under the profiler;
    returns the reduced trace and the spans."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with TraceAnnotation("bench_window"):
            jax.block_until_ready(runner.rk3_step(u, dt))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    profile = ProfileData.from_file(path)
    return (program_trace.reduce_program(profile),
            program_trace.host_spans(profile)[1])


@pytest.fixture(scope="module")
def state():
    u = sedov_init(CFG).u
    return u, courant_dt(u, CFG)


@pytest.fixture(scope="module")
def runners(state):
    """One warmed runner per strategy, each past its first (compiling)
    step."""
    u, dt = state
    out = {}
    for strategy, agg in AGG.items():
        runner = out[strategy] = StrategyRunner(UniformSedovScenario(CFG),
                                                agg)
        runner.warmup(wave_only=True)
        jax.block_until_ready(runner.rk3_step(u, dt))
    return out


@pytest.mark.parametrize("strategy", ["s3", "fused"])
def test_step_spans_nest_and_count_launches(strategy, state, runners,
                                            tmp_path):
    u, dt = state
    runner = runners[strategy]
    launches = runner.stats["kernel_launches"]
    staging = runner.stats["staging_s"]
    reduced, spans = traced_step(runner, u, dt, tmp_path)

    counts = reduced["span_count"]
    want = {"repro.step": 1, "repro.rk_stage": 3, "repro.combine": 4,
            "repro.populations": 3, "repro.assemble": 3}
    if strategy == "s3":
        want.update(dict.fromkeys(EXECUTOR_SPANS - {"repro.staging"}, 3))
    assert {k: counts.get(k) for k in want} == want
    assert counts["repro.dispatch"] == \
        runner.stats["kernel_launches"] - launches
    assert counts["repro.dispatch"] == (6 if strategy == "s3" else 3)
    if strategy == "s3":
        assert counts["repro.staging"] == counts["repro.dispatch"]
        assert runner.stats["staging_s"] > staging
    else:
        assert not EXECUTOR_SPANS & set(counts)

    for s, e, name, thread in spans:
        if name in PARENTS:
            assert any(ps <= s and e <= pe and pt == thread
                       and pname in PARENTS[name]
                       for ps, pe, pname, pt in spans), name
    # the step's self times account for the whole step span
    total = reduced["span_total_s"]["repro.step"]
    inside = sum(v for k, v in reduced["span_self_s"].items()
                 if k.startswith("repro."))
    assert inside == pytest.approx(total, rel=1e-6)


def test_bucket_and_wave_programs_are_named(runners):
    """Each bucket program compiles to ``jit_<kernel>_b<bucket>``, the whole
    wave to ``jit_<kernel>``; only the module's name differs from the
    unnamed program's."""
    (region,) = runners["s3"].executor.regions.values()
    texts = [fn.as_text() for fn in region.compiled.values()
             if hasattr(fn, "as_text")]
    assert texts and all("jit_hydro_rhs_b4" in t for t in texts)

    parent = jax.ShapeDtypeStruct((CFG.n_subgrids, CFG.n_fields,
                                   CFG.padded, CFG.padded, CFG.padded),
                                  jnp.float32)
    start = jax.ShapeDtypeStruct((), jnp.int32)
    mine = region.program(4, "prefix").lower(start, parent).as_text()
    plain = jax.jit(partial(region._apply_ring_prefix, 4)).lower(
        start, parent).as_text()
    assert "@jit_hydro_rhs_b4" in mine and "@jit__unknown" in plain
    assert mine.replace("jit_hydro_rhs_b4", "P") == \
        plain.replace("jit__unknown", "P")

    scenario = runners["fused"].scenario
    assert "@jit_hydro_rhs " in scenario.jitted_body("hydro_rhs").lower(
        parent).as_text()
    assert program_name("hydro_rhs+epi", 32) == "hydro_rhs_epi_b32"


def test_span_counter_keeps_time_spent_before_a_raise():
    stats = {"t": 0.0}
    with span("repro.test", stats, "t", kernel="k", bucket=1):
        pass
    first = stats["t"]
    assert first > 0.0
    with pytest.raises(ZeroDivisionError):
        with span("repro.test", stats, "t"):
            1 / 0
    assert stats["t"] > first

    exe = DeviceExecutor(0)
    with pytest.raises(ValueError):
        exe.launch(lambda: (_ for _ in ()).throw(ValueError("bad")),
                   family="k", bucket=2)
    assert exe.dispatch_s > 0.0 and exe.launches == 0
    exe.dispatch_s = 0.0
    assert exe.stats["dispatch_s"] == 0.0
    assert named(lambda x: x + 1, "p")(1) == 2
