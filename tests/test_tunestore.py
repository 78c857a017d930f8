"""DESIGN.md §13: the persistent tune store + the analytical roofline prior.

Invariants pinned here:

* the store round-trips entries through disk (atomic write, content hash,
  schema + code-salt keying) and ``load -> save -> load`` is a fixed
  point (hypothesis property, stub-compatible);
* a corrupt / truncated / hash-tampered / stale-schema / stale-salt file
  degrades to an EMPTY store with a :class:`TuneStoreWarning` — a warm
  start is an optimization, never a crash or a silently wrong ladder;
* entries are keyed ``backend|device_kind|describe``: a table stored for
  another device kind is invisible, and a malformed entry for THIS key
  warns and leaves the region cold (it measures as if no store existed);
* the executor round trip — a cold process measures and persists, a
  second process against the same directory restores ladder / chunk /
  cost tables / histograms and reaches tuned steady state with
  ``measurement_launches == 0`` and bit-identical results;
* the roofline prior seeds unmeasured regions with a ``validate_ladder``-
  clean ladder (``tuned_by == "prior"``, every table entry tagged
  ``source="prior"``) that a launch-overhead cost model scores within
  1.5x of its own tuned ladder, and a live retune RETIRES the seeds
  wholesale (``tuned_by == "measured"``, prior tables empty);
* families with an explicit (non-"auto") route in ``family_strategies``
  skip the alt-path probes nothing would consult (satellite of §12/§13).
"""
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import AggregationConfig, validate_ladder
from repro.core import AggregationExecutor, derive_ladder
from repro.core.aggregation import (
    BucketCostModel, _backend_key, greedy_decomposition,
)
from repro.core.tunestore import (
    DEVICE_PEAKS, SCHEMA_VERSION, RooflinePrior, TuneStore, TuneStoreWarning,
    code_salt, device_peaks, entry_key,
)

WM = 10 ** 9


def _affine(x):
    return 2.0 * x + 1.0


def _entry(ladder=(1, 16)):
    return {"cost_model": {"s3": {"1": 1e-4, "16": 2e-4}},
            "ladder": list(ladder), "inner_chunk": 0,
            "queue_hist": {"16": 3}, "warmup_wave": 16,
            "tuned_by": "measured"}


def _cfg(tmp_path, **kw):
    base = dict(strategy="s3", max_aggregated=16, launch_watermark=WM,
                autotune=True, autotune_warmup=1, cost_model=True,
                cost_samples=1, tune_store=str(tmp_path))
    base.update(kw)
    return AggregationConfig(**base)


# ---------------------------------------------------------------------------
# store mechanics
# ---------------------------------------------------------------------------

def test_store_roundtrip(tmp_path):
    store = TuneStore(str(tmp_path))
    store.put(("cpu", "cpu0"), "fam[16x2,f32]", _entry())
    store.save()
    again = TuneStore(str(tmp_path))
    assert len(again) == 1
    assert again.get(("cpu", "cpu0"), "fam[16x2,f32]") == _entry()
    assert again.get(("tpu", "v5"), "fam[16x2,f32]") is None  # other device


def test_open_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_TUNE_STORE", raising=False)
    assert TuneStore.open(None) is None           # cold-start default
    store = TuneStore.open(str(tmp_path))
    assert isinstance(store, TuneStore)
    assert TuneStore.open(store) is store         # instance passthrough
    monkeypatch.setenv("REPRO_TUNE_STORE", str(tmp_path))
    via_env = TuneStore.open(None)
    assert via_env is not None and via_env.root == store.root


def test_save_merges_concurrent_entries(tmp_path):
    """Two processes tuning DIFFERENT families must not clobber each
    other: the later save merges over the valid on-disk entries."""
    a, b = TuneStore(str(tmp_path)), TuneStore(str(tmp_path))
    a.put(("cpu", "cpu0"), "fam_a[8x2,f32]", _entry())
    a.save()
    b.put(("cpu", "cpu0"), "fam_b[8x3,f32]", _entry((1, 8)))
    b.save()
    merged = TuneStore(str(tmp_path)).entries()
    assert set(merged) == {entry_key(("cpu", "cpu0"), "fam_a[8x2,f32]"),
                           entry_key(("cpu", "cpu0"), "fam_b[8x3,f32]")}


def _assert_falls_back_empty(root):
    with pytest.warns(TuneStoreWarning):
        assert len(TuneStore(root)) == 0


def test_corrupt_file_warns_and_falls_back(tmp_path):
    path = os.path.join(str(tmp_path), "tunestore.json")
    with open(path, "w") as f:
        f.write("{not json at all")
    _assert_falls_back_empty(str(tmp_path))


def test_truncated_file_warns_and_falls_back(tmp_path):
    store = TuneStore(str(tmp_path))
    store.put(("cpu", "cpu0"), "fam[16x2,f32]", _entry())
    store.save()
    with open(store.path) as f:
        blob = f.read()
    with open(store.path, "w") as f:
        f.write(blob[:len(blob) // 2])            # torn write
    _assert_falls_back_empty(str(tmp_path))


def test_hash_tamper_warns_and_falls_back(tmp_path):
    store = TuneStore(str(tmp_path))
    store.put(("cpu", "cpu0"), "fam[16x2,f32]", _entry())
    store.save()
    with open(store.path) as f:
        payload = json.load(f)
    key = entry_key(("cpu", "cpu0"), "fam[16x2,f32]")
    payload["entries"][key]["ladder"] = [1, 999]  # hand edit, stale hash
    with open(store.path, "w") as f:
        json.dump(payload, f)
    _assert_falls_back_empty(str(tmp_path))


@pytest.mark.parametrize("field,value", [
    ("schema", SCHEMA_VERSION + 1),
    ("salt", "0000000000000000"),
])
def test_stale_schema_or_salt_ignored(tmp_path, field, value):
    store = TuneStore(str(tmp_path))
    store.put(("cpu", "cpu0"), "fam[16x2,f32]", _entry())
    store.save()
    with open(store.path) as f:
        payload = json.load(f)
    payload[field] = value                        # hash still matches
    with open(store.path, "w") as f:
        json.dump(payload, f)
    _assert_falls_back_empty(str(tmp_path))


def test_save_repairs_corrupt_file(tmp_path):
    """A save over a corrupt file must succeed (the repairing write) and
    leave a loadable store behind."""
    path = os.path.join(str(tmp_path), "tunestore.json")
    with open(path, "w") as f:
        f.write("garbage")
    store = TuneStore(str(tmp_path))
    with pytest.warns(TuneStoreWarning):
        store.put(("cpu", "cpu0"), "fam[16x2,f32]", _entry())
    store.save()
    assert len(TuneStore(str(tmp_path))) == 1


@given(n=st.integers(1, 6), seed=st.integers(0, 10 ** 6))
@settings(max_examples=10, deadline=None)
def test_load_save_load_fixed_point(n, seed):
    """Property: one save of arbitrary entries, then load -> save -> load
    reproduces the identical entry table (idempotent persistence)."""
    entries = {}
    for i in range(n):
        fam = f"fam{(seed + i) % 7}[{i + 1}x2,f32]"
        entries[entry_key(("cpu", f"dev{i % 3}"), fam)] = {
            "cost_model": {"s3": {str(1 << i): (seed % 97 + 1) * 1e-5}},
            "ladder": [1, i + 1], "inner_chunk": i % 4,
            "queue_hist": {str(i + 1): seed % 13 + 1},
            "warmup_wave": i + 1, "tuned_by": "measured"}
    root = tempfile.mkdtemp(prefix="tunestore-prop-")
    store = TuneStore(root)
    for key, entry in entries.items():
        backend, device, fam = key.split("|", 2)
        store.put((backend, device), fam, entry)
    store.save()
    first = TuneStore(root)
    snapshot = first.entries()
    assert snapshot == entries
    first.save()                                  # save with zero changes
    assert TuneStore(root).entries() == snapshot


# ---------------------------------------------------------------------------
# executor round trip: cold measures + persists, warm restores
# ---------------------------------------------------------------------------

def _run_wave(exe, parent, n=16):
    fut = exe.submit_range((parent,), 0, n)
    exe.flush()
    return np.asarray(fut.result())


def test_executor_cold_then_warm(tmp_path):
    parent = jnp.arange(32.0).reshape(16, 2)
    cold = AggregationExecutor(jax.vmap(_affine), _cfg(tmp_path))
    cold.warmup(parent_shapes=(parent,))
    for _ in range(3):
        want = _run_wave(cold, parent)
    region = next(iter(cold.regions.values()))
    assert region.stats["tuned_by"] == "measured"
    assert region.stats["measurement_launches"] > 0
    assert cold.save_tuning() == os.path.join(str(tmp_path),
                                              "tunestore.json")

    warm = AggregationExecutor(jax.vmap(_affine), _cfg(tmp_path))
    warm.warmup(parent_shapes=(parent,))
    wregion = next(iter(warm.regions.values()))
    assert wregion.stats["tuned_by"] == "store"
    assert wregion.stats["warm_start"] is True
    assert warm.stats["warm_start"] is True
    assert wregion.buckets == region.buckets      # the tuned ladder
    assert wregion.chunk == region.chunk
    assert wregion.tuned                          # no autotune re-arm due
    got = _run_wave(warm, parent)
    np.testing.assert_array_equal(got, want)      # bit-identical
    np.testing.assert_array_equal(got, np.asarray(2.0 * parent + 1.0))
    # the §13 acceptance counter: a warm process never starts a stopwatch
    assert wregion.stats["measurement_launches"] == 0
    srcs = wregion.stats["cost_sources"]
    assert srcs and all(v == "store" for tbl in srcs.values()
                        for v in tbl.values())


def test_malformed_entry_falls_back_to_measuring(tmp_path):
    """An entry for THIS key with an unusable ladder warns and leaves the
    region cold: it measures exactly as if no store existed."""
    parent = jnp.arange(32.0).reshape(16, 2)
    cold = AggregationExecutor(jax.vmap(_affine), _cfg(tmp_path))
    cold.warmup(parent_shapes=(parent,))
    describe = next(iter(cold.regions.values())).signature.describe()
    store = TuneStore(str(tmp_path))
    bad = _entry()
    bad["ladder"] = ["not", "buckets"]
    store.put(_backend_key(), describe, bad)
    store.save()

    exe = AggregationExecutor(jax.vmap(_affine), _cfg(tmp_path))
    with pytest.warns(TuneStoreWarning, match="unusable"):
        exe.warmup(parent_shapes=(parent,))
    region = next(iter(exe.regions.values()))
    assert region.stats.get("tuned_by") != "store"
    assert not region.stats.get("warm_start")
    assert region.cost.measured()                 # it measured instead
    np.testing.assert_array_equal(_run_wave(exe, parent),
                                  np.asarray(2.0 * parent + 1.0))


def test_stored_entry_for_other_device_is_invisible(tmp_path):
    parent = jnp.arange(32.0).reshape(16, 2)
    probe = AggregationExecutor(jax.vmap(_affine), _cfg(tmp_path))
    probe.warmup(parent_shapes=(parent,))
    describe = next(iter(probe.regions.values())).signature.describe()
    store = TuneStore(str(tmp_path))
    store.put(("tpu", "TPU v5"), describe, _entry((1, 999)))
    store.save()

    exe = AggregationExecutor(jax.vmap(_affine), _cfg(tmp_path))
    exe.warmup(parent_shapes=(parent,))           # no warning: just a miss
    region = next(iter(exe.regions.values()))
    assert region.stats.get("tuned_by") != "store"
    assert 999 not in region.buckets


# ---------------------------------------------------------------------------
# roofline prior
# ---------------------------------------------------------------------------

def test_device_peaks_and_prior_shape():
    bw, flops, launch = device_peaks(("cpu", "cpu"))
    assert (bw, flops, launch) == DEVICE_PEAKS["cpu"]     # table, no calibration
    assert bw > 0 and flops > 0 and launch > 0
    prior = RooflinePrior(("cpu", "cpu"))
    specs = (jax.ShapeDtypeStruct((2,), jnp.float32),)
    fn = jax.vmap(_affine)
    t1, t8, t16 = (prior.predict(fn, specs, b) for b in (1, 8, 16))
    assert 0 < t1 < t8 < t16                      # monotone in bucket
    assert t8 - t1 == pytest.approx((t16 - t1) * 7 / 15)  # linear slope


def test_prior_seeds_sane_ladder_without_measuring(tmp_path):
    parent = jnp.arange(32.0).reshape(16, 2)
    exe = AggregationExecutor(jax.vmap(_affine),
                              _cfg(tmp_path, prior="roofline"))
    exe.warmup(parent_shapes=(parent,))
    region = next(iter(exe.regions.values()))
    assert region.stats["tuned_by"] == "prior"
    assert region.stats["measurement_launches"] == 0   # no stopwatch ran
    assert not region.cost.measured()
    assert region.cost.seeded() and region.cost.seeded("s2") \
        and region.cost.seeded("fused")
    assert validate_ladder(region.buckets, 16) == region.buckets
    srcs = region.stats["cost_sources"]
    assert all(v == "prior" for tbl in srcs.values() for v in tbl.values())
    assert not region.tuned                       # seeds never pin tuning


def test_prior_ladder_within_1p5x_of_tuned(tmp_path):
    """Acceptance: score the prior-seeded ladder under a launch-overhead
    measured model — it must cost at most 1.5x that model's OWN tuned
    ladder for the observed wave (the prior also charges per launch, so
    both converge on wave-covering buckets)."""
    parent = jnp.arange(32.0).reshape(16, 2)
    exe = AggregationExecutor(jax.vmap(_affine),
                              _cfg(tmp_path, prior="roofline"))
    exe.warmup(parent_shapes=(parent,))
    prior_ladder = next(iter(exe.regions.values())).buckets

    measured = BucketCostModel()
    for b in range(1, 17):
        measured.record(b, 1.0 + 0.01 * b)        # overhead-dominated
    tuned = derive_ladder({16: 1}, cap=16, budget=4, cost_model=measured)
    cost_prior = measured.predict_seq(greedy_decomposition(16, prior_ladder))
    cost_tuned = measured.predict_seq(greedy_decomposition(16, tuned))
    assert cost_prior <= 1.5 * cost_tuned


def test_retune_retires_prior_seeds(tmp_path):
    parent = jnp.arange(32.0).reshape(16, 2)
    exe = AggregationExecutor(jax.vmap(_affine),
                              _cfg(tmp_path, prior="roofline"))
    exe.warmup(parent_shapes=(parent,))
    region = next(iter(exe.regions.values()))
    assert region.stats["tuned_by"] == "prior"
    for _ in range(3):                            # real waves -> retune
        got = _run_wave(exe, parent)
    assert region.stats["tuned_by"] == "measured"
    assert not region.cost.priors                 # seeds retired wholesale
    srcs = region.stats["cost_sources"]
    assert all(v == "measured" for tbl in srcs.values()
               for v in tbl.values())
    np.testing.assert_array_equal(got, np.asarray(2.0 * parent + 1.0))


def test_bad_prior_mode_fails_fast(tmp_path):
    with pytest.raises(ValueError, match="prior"):
        AggregationExecutor(jax.vmap(_affine),
                            _cfg(tmp_path, prior="bogus"))


# ---------------------------------------------------------------------------
# explicit routes skip the probes nothing would consult (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route,want_s2,want_fused", [
    (None, True, True),                           # "auto": measure all
    ("s2", True, False),                          # s2 needs its width table
    ("s3", False, False),                         # nothing consults probes
])
def test_explicit_route_skips_alt_probes(tmp_path, route, want_s2,
                                         want_fused):
    parent = jnp.arange(16.0).reshape(8, 2)
    strategies = None if route is None else {"region": route}
    cfg = _cfg(tmp_path, max_aggregated=8, family_strategies=strategies,
               tune_store=None)
    exe = AggregationExecutor(jax.vmap(_affine), cfg)
    exe.warmup(parent_shapes=(parent,))
    region = next(iter(exe.regions.values()))
    assert region.cost.measured()                 # s3 always measured
    assert region.cost.measured("s2") is want_s2
    assert region.cost.measured("fused") is want_fused
