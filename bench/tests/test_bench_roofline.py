"""``step_mfu``'s least time is a constant of the configuration: the same
for every strategy, and read from files, never from a compiled program."""
import jax
import pytest

import benchtest_util as util
import harness
import roofline

T2_FLOPS = 3 * 512 * 17516040
T2_BYTES = 3 * 512 * 5 * (14 ** 3 + 8 ** 3) * 4


@pytest.fixture
def no_compile(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("the least time must not compile a program")
    monkeypatch.setattr(jax, "jit", refuse)
    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)


def least(cell_name):
    cell = harness.load_cell(util.REPO, cell_name)
    return roofline.least_step_s(cell.config, cell.module("reference"),
                                 roofline.peaks("TPU v5 lite"))


def test_least_time_depends_on_the_configuration_only(no_compile):
    s3, fused = least("sedov_t2.s3"), least("sedov_t2.fused")
    assert s3 == fused
    peak = roofline.peaks("TPU v5 lite")
    assert s3 == max(T2_FLOPS / peak["flops_per_s"],
                     T2_BYTES / peak["hbm_bytes_per_s"])
    cell = harness.load_cell(util.REPO, "sedov_t2.s3")
    l4 = dict(cell.config, hydro=dict(cell.config["hydro"], levels=4))
    assert roofline.least_step_s(l4, cell.module("reference"), peak) == \
        pytest.approx(8 * s3, rel=1e-12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        roofline.peaks("TPU v9 imaginary")


def test_step_mfu_reads_the_measured_step(no_compile):
    cell = harness.load_cell(util.REPO, "sedov_t2.fused")
    run = harness.Run(cell=cell, device_kind="TPU v5 lite",
                      reference=cell.module("reference"), steps=100,
                      window_s=10.0)
    value = harness.read_metrics(run, [{"name": "step_mfu", "unit": "%"}])
    assert value["step_mfu"]["value"] == pytest.approx(
        100.0 * least("sedov_t2.fused") / 0.1)
