"""The trace reduction: device busy time as a union of op intervals inside
the traced window, idle gaps named by the benchmark's host spans, and the
top device operations."""
import os

import pytest
from jax.profiler import ProfileData

import benchtest_util  # noqa: F401  (puts bench/ on the path)
import trace_reduce

US = 1_000_000       # picoseconds per microsecond


def _plane(pid, name, lines, names):
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))
    body = ""
    for lid, (lname, events) in enumerate(lines, 1):
        evs = "".join(f"events {{ metadata_id: {names.index(n) + 1} "
                      f"offset_ps: {s * US} duration_ps: {(e - s) * US} }}\n"
                      for n, s, e in events)
        body += (f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                 f'{evs}}}\n')
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def synthetic():
    host = _plane(1, "/host:CPU", [("python3", [
        ("bench_window", 0, 100), ("rk3_step", 10, 30),
        ("dt_sync", 40, 90)])], ["bench_window", "rk3_step", "dt_sync"])
    device = _plane(2, "/device:TPU:0", [
        ("XLA Ops", [("fusion.a = f32[2] add(x, y)", 5, 20),
                     ("fusion.b", 15, 35),
                     ("fusion.a = f32[2] add(x, y)", 60, 70),
                     ("fusion.a = f32[2] add(x, y)", 95, 130)]),
        ("XLA Modules", [("jit_step(123)", 0, 100)])],
        ["fusion.a = f32[2] add(x, y)", "fusion.b", "jit_step(123)"])
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host + device))


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [(1, 4), (5, 8)]


def test_synthetic_trace():
    r = trace_reduce.reduce_trace(synthetic())
    # busy: [5, 35] + [60, 70] + [95, 100] (clipped to the window)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(45e-6)
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["device_ops"] == [["jit_step/fusion.a", pytest.approx(30e-6)],
                               ["jit_step/fusion.b", pytest.approx(20e-6)]]
    assert r["idle_gaps"] == [["dt_sync", pytest.approx(25e-6)],
                              ["dt_sync", pytest.approx(25e-6)],
                              ["other", pytest.approx(5e-6)]]


def test_recorded_chip_trace():
    """The first 25 ms of a traced ``sedov_t2.s3`` window on a TPU v5 lite
    (cut by ``data/cut_trace.py``)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "sedov_t2_s3_cut.xplane.pb")
    r = trace_reduce.reduce_trace(ProfileData.from_file(path))
    assert r["window_s"] == pytest.approx(0.025)
    assert r["busy_s"] == pytest.approx(0.024791413)
    assert r["idle_share"] == pytest.approx(0.00834348, rel=1e-5)
    assert r["device_ops"][0] == ["jit_extract_subgrids/while.1",
                                  pytest.approx(0.001996196)]
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(" = " not in name for name, _ in r["device_ops"])
    assert r["idle_gaps"][0] == ["courant_dt", pytest.approx(0.000133053)]
    assert {g[0] for g in r["idle_gaps"]} <= {"courant_dt", "rk3_step",
                                              "dt_sync", "other"}


def test_no_window_or_no_device_reads_nothing():
    assert trace_reduce.reduce_trace(synthetic(), window="absent") is None
    host_only = _plane(1, "/host:CPU", [("python3", [
        ("bench_window", 0, 100)])], ["bench_window"])
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host_only))
    assert trace_reduce.reduce_trace(profile) is None
