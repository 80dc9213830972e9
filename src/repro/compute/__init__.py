"""Device-side verify pipeline (ROADMAP "device-side staging").

Extends the I/O pipeline one hop past the host cache: bucket slabs cross
H2D once per cache residency (``DeviceSlabPool``), verify batches are
dispatched double-buffered, and the kernel returns compacted
(row, col, distance) triples instead of full (E, cap, cap) masks
(``DeviceVerifyEngine``). ``HostVerifyEngine`` is the reference host
path; both produce byte-identical results and are selected by
``JoinConfig.compute_mode``. See README.md for the staging pipeline and
slab-pool lifecycle.
"""
from repro.compute.engine import (PAIR_CAP_INIT, DeviceQueryVerify,
                                  DeviceVerifyEngine, HostVerifyEngine,
                                  QueryLaneState, RoutedVerifyEngine,
                                  compact_batch, compact_pairs,
                                  make_verify_engine, next_pow2)
from repro.compute.slab_pool import DeviceSlabPool

__all__ = ["DeviceQueryVerify", "DeviceSlabPool", "DeviceVerifyEngine",
           "HostVerifyEngine", "PAIR_CAP_INIT", "QueryLaneState",
           "RoutedVerifyEngine", "compact_batch", "compact_pairs",
           "make_verify_engine", "next_pow2"]
