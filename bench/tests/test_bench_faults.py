"""A whole run of the harness, with the look for a chip skipped, on the
CPU: sound, it comes out ``correct``; with the timed path broken
underneath in each way a one-chip cell can break, it does not.  Most
faults run on a configuration of 8 sub-grids; one far-field bucket's
fault runs at Table II's size, where most sub-grids lie far from the
blast."""
import time

import jax
import jax.numpy as jnp
import pytest

import benchtest_util as util
import harness
import repro.core.scenario as scenario_mod
from repro.core import StrategyRunner, UniformSedovScenario, xla_task_body
from repro.core.aggregation import AggregationExecutor

SEED = 2 ** 31 + 17


def _wrap_body(monkeypatch, fault):
    """Build every ``UniformSedovScenario`` with ``fault`` applied to the
    batched body's output: each bucket's tasks, where they are produced."""
    init = UniformSedovScenario.__init__

    def patched(self, cfg, bc="outflow", body=None, batched_body=None):
        h = cfg.domain / (cfg.grids_per_edge * cfg.subgrid)
        plain = jax.vmap(xla_task_body(cfg, h))
        init(self, cfg, bc, body, lambda *args: fault(plain(*args)))

    monkeypatch.setattr(UniformSedovScenario, "__init__", patched)


def unchanged_state(monkeypatch):
    monkeypatch.setattr(StrategyRunner, "rk3_step",
                        lambda self, state, dt: state)


def half_batch(monkeypatch):
    def drop(out):
        keep = jnp.arange(out.shape[0]) < (out.shape[0] + 1) // 2
        return jnp.where(keep.reshape((-1,) + (1,) * (out.ndim - 1)),
                         out, 0.0)
    _wrap_body(monkeypatch, drop)


def no_ghost_exchange(monkeypatch):
    """Each sub-grid pads itself from its own edge cells instead of taking
    its neighbours' cells."""
    extract = scenario_mod.extract_subgrids

    def isolated(u, subgrid, ghost, bc="outflow"):
        inner = extract(u, subgrid, 0, bc)
        if ghost == 0:
            return inner
        pads = [(0, 0), (0, 0)] + [(ghost, ghost)] * 3
        return jnp.pad(inner, pads, mode="edge")

    monkeypatch.setattr(scenario_mod, "extract_subgrids", isolated)


def altered_answer(monkeypatch):
    _wrap_body(monkeypatch, lambda out: out.at[0].multiply(1.01))


def far_bucket(monkeypatch):
    """The first bucket of every stage (sub-grids 0-31 of 512: x < 8 and
    y < 32 cells, far from the blast at the centre) has its energy answers
    raised by 1e-3 per unit time where they are produced: over a step its
    cells' energy ends some 8 times the ambient 2.5e-8."""
    launch, dispatch = (AggregationExecutor._launch_tasks,
                        AggregationExecutor._dispatch)
    first = {"on": False}

    def launch_tasks(self, region, tasks, k, mode, degraded=False):
        first["on"] = tasks[0].wave_index == 0
        return launch(self, region, tasks, k, mode, degraded)

    def altered(self, region, fn, call_args, k):
        out = dispatch(self, region, fn, call_args, k)
        return out.at[:, 4].add(1e-3) if first["on"] else out

    monkeypatch.setattr(AggregationExecutor, "_launch_tasks", launch_tasks)
    monkeypatch.setattr(AggregationExecutor, "_dispatch", altered)


def run(root, cell=f"{util.TINY}.s3"):
    return harness.run_cell(root, cell, SEED, 0.3, False,
                            time.perf_counter(), require_chip=False,
                            log=lambda *a: None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = util.copy_bench(str(tmp_path_factory.mktemp("bench")))
    util.add_tiny_cells(root)
    return root


def test_sound_run_is_correct(root):
    result = run(root)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   no_ghost_exchange, altered_answer],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    result = run(root)
    assert not result["correct"], result["compared"]
    assert result["failed"] >= 1


@pytest.mark.parametrize("fault", [None, far_bucket],
                         ids=["sound", "far_bucket"])
def test_table2_size_run(root, monkeypatch, fault):
    """At 512 sub-grids the CPU's program differs from the reference by
    round-off (the same mathematics fused otherwise), which the limit
    admits; one far-field bucket's altered answers it does not."""
    if fault is not None:
        fault(monkeypatch)
    result = run(root, "sedov_t2.s3")
    assert result["correct"] == (fault is None), result["compared"]
