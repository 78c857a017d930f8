"""Persistent warm-start subsystem (DESIGN.md §13).

Two halves, both feeding the same :class:`~repro.core.aggregation.
BucketCostModel` currency:

* :class:`TuneStore` — the on-disk table of everything a tuned process
  knows (cost tables, ladders, inner chunks, strategy selections); a
  configured store also turns on the persistent compilation cache
  (``repro.core.compile_cache``), so process two measures nothing and
  recompiles nothing;
* :class:`RooflinePrior` — the analytical fallback for process ONE, so
  an empty store still yields a sane ladder without zero-fill timing.
"""
from repro.core.tunestore.prior import (
    DEVICE_PEAKS, RooflinePrior, device_peaks,
)
from repro.core.tunestore.store import (
    SCHEMA_VERSION, STORE_ENV_VAR, TuneStore, TuneStoreWarning, code_salt,
    entry_key,
)

__all__ = [
    "DEVICE_PEAKS", "RooflinePrior", "device_peaks",
    "SCHEMA_VERSION", "STORE_ENV_VAR", "TuneStore", "TuneStoreWarning",
    "code_salt", "entry_key",
]
