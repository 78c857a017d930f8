"""The reduction of the program's own spans and named programs: self
times of nested spans, device time per program, and idle gaps named by the
innermost benchmark or program span."""
import os

import pytest
from jax.profiler import ProfileData

import benchtest_util  # noqa: F401  (puts bench/ on the path)
import program_trace
import trace_reduce
from test_bench_trace_reduce import _plane

NAMES = ["bench_window", "rk3_step", "repro.step", "repro.rk_stage",
         "repro.flush", "repro.dispatch", "repro.combine"]
OPS = ["fusion.1 = f32[2] add(x, y)", "while.1", "jit_hydro_rhs_b32(77)",
       "jit_extract_subgrids(12)"]


def synthetic():
    """One step: ``repro.step`` > ``repro.rk_stage`` > ``repro.flush`` >
    ``repro.dispatch``, then a ``repro.combine``; the bucket program runs
    6-32 and 41-70 us, the ghost extract 75-80 us."""
    host = _plane(1, "/host:CPU", [("python3", [
        ("bench_window", 0, 100), ("rk3_step", 2, 96),
        ("repro.step", 4, 94), ("repro.rk_stage", 10, 60),
        ("repro.flush", 20, 50), ("repro.dispatch", 30, 40),
        ("repro.combine", 70, 90)])], NAMES)
    device = _plane(2, "/device:TPU:0", [
        ("XLA Ops", [(OPS[0], 6, 32), (OPS[0], 41, 70),
                     (OPS[1], 75, 80)]),
        ("XLA Modules", [(OPS[2], 6, 32), (OPS[2], 41, 70),
                         (OPS[3], 75, 80)])], OPS)
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host + device))


def test_nested_spans_self_times_and_programs():
    r = program_trace.reduce_program(synthetic())
    us = pytest.approx
    assert r["window_s"] == us(100e-6)
    assert r["span_self_s"] == {
        "rk3_step": us(4e-6), "repro.step": us(20e-6),
        "repro.rk_stage": us(20e-6), "repro.flush": us(20e-6),
        "repro.dispatch": us(10e-6), "repro.combine": us(20e-6)}
    assert r["span_total_s"]["repro.step"] == us(90e-6)
    assert r["span_count"] == {name: 1 for name in NAMES[1:]}
    assert r["program_device_s"] == {"jit_hydro_rhs_b32": us(55e-6),
                                     "jit_extract_subgrids": us(5e-6)}
    # gaps, each named at its midpoint: 80-100 (90, the end of
    # repro.combine), 32-41 (inside repro.dispatch), 0-6 (3, rk3_step
    # alone), 70-75 (repro.combine)
    assert r["idle_gaps"] == [["repro.combine", us(20e-6)],
                              ["repro.dispatch", us(9e-6)],
                              ["rk3_step", us(6e-6)],
                              ["repro.combine", us(5e-6)]]


def test_spans_on_other_threads_are_not_children():
    host = _plane(1, "/host:CPU", [
        ("main", [("bench_window", 0, 100), ("repro.flush", 10, 60)]),
        ("worker", [("repro.dispatch", 20, 30)])],
        ["bench_window", "repro.flush", "repro.dispatch"])
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host))
    r = program_trace.reduce_program(profile)
    assert r["span_self_s"] == {"repro.flush": pytest.approx(50e-6),
                                "repro.dispatch": pytest.approx(10e-6)}
    assert r["program_device_s"] == {} and r["idle_gaps"] == []
    assert program_trace.reduce_program(profile, window="absent") is None


def test_recorded_chip_trace_matches_trace_reduce():
    """A trace with no program spans names its idle gaps as
    ``trace_reduce`` does, and its programs' device times add up to the
    busy time."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "sedov_t2_s3_cut.xplane.pb")
    profile = ProfileData.from_file(path)
    old = trace_reduce.reduce_trace(profile)
    r = program_trace.reduce_program(profile)
    assert r["idle_gaps"] == old["idle_gaps"]
    assert r["window_s"] == pytest.approx(old["window_s"])
    assert set(r["program_device_s"]) == {"jit_extract_subgrids",
                                          "jit__unknown"}
    # one program at a time on the device: the programs' union of op
    # intervals add up to the busy time, the extract's at least its loop
    assert sum(r["program_device_s"].values()) == \
        pytest.approx(old["busy_s"], rel=1e-9)
    assert r["program_device_s"]["jit_extract_subgrids"] >= \
        dict(old["device_ops"])["jit_extract_subgrids/while.1"]
    assert r["span_count"] == {"courant_dt": 1, "rk3_step": 1}
